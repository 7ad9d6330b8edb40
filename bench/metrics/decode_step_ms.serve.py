"""decode_step_ms.serve: median host-clock time of one engine decode step.

Each step ends in the engine's own sync (the next tokens reach the host).
"""
import statistics


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["step_s"]:
        return None
    return 1e3 * statistics.median(ctx["step_s"])

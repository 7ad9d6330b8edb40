"""prefill_ms.serve: median host-clock time of one admission (adapter load,
prefill, page write, first token on the host)."""
import statistics


def read(ctx):
    if ctx["kind"] != "serve" or not ctx["prefill_s"]:
        return None
    return 1e3 * statistics.median(ctx["prefill_s"])

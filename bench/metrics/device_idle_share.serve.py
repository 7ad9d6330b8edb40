"""device_idle_share.serve: share of the traced serving window with no device op."""


def read(ctx):
    s = ctx.get("trace")
    if ctx["kind"] != "serve" or s is None:
        return None
    return 100.0 * s.idle_share

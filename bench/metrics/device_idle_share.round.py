"""device_idle_share.round: share of the traced round window with no device op."""


def read(ctx):
    s = ctx.get("trace")
    if ctx["kind"] != "round" or s is None:
        return None
    return 100.0 * s.idle_share

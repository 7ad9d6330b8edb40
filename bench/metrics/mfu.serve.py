"""mfu.serve: the serving window's share of the chip's roofline.

For every prefill and decode step of the window, the least time the chip
needs is the larger of its needed FLOPs over the bf16 peak and its needed
bytes over HBM bandwidth (the family module's ``prefill_cost`` and
``decode_cost``: real prompt positions, live slots' KV up to their
positions). Their sum over the window's length.
"""


def read(ctx):
    if ctx["kind"] != "serve" or ctx["window_s"] <= 0:
        return None
    return 100.0 * ctx["roofline_bound_s"] / ctx["window_s"]

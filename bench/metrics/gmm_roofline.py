"""gmm_roofline: the grouped expert matmul's share of its roofline.

The kernel is ``kernels/moe_gmm``: three launches per expert layer and
pass forward (gate, up, down over the rows sorted by held expert) and
three in its input-gradient backward, all through one launcher,
``_gmm_jit``, whose name the trace gives each launch's custom call. Every
launch in the traced window counts on both sides: its device time, and its
least time, the larger of its FLOPs over the bf16 peak and its bytes over
HBM bandwidth, as the family module counts the launch (``gmm_launch_cost``
of its output's dims). The trace does not carry the routing, so the rows
counted are the held experts' expected share of the launch's token copies
(top-k x held / routed experts); how far one run's real rows lie from it
is in PERF.md. A configuration whose family has no grouped matmul, or a
trace without its launches, reads nothing.
"""
import re

from bench import peaks

LAUNCHER = "_gmm_jit"
OUT_DIMS = re.compile(r"=\s*\(?\s*\w+\[([\d,]+)\]")


def launches(summary):
    """(seconds, output dims) of each launch of the kernel in the window."""
    out = []
    for e in summary.ops:
        if not (e.name.lstrip("%").startswith(LAUNCHER) and "custom-call(" in e.name):
            continue
        m = OUT_DIMS.search(e.name)
        if m:
            out.append((e.dur_ns * 1e-9, [int(x) for x in m.group(1).split(",")]))
    return out


def read(ctx):
    s, model = ctx.get("trace"), ctx["model"]
    if ctx["kind"] != "round" or s is None or not hasattr(model, "gmm_launch_cost"):
        return None
    calls = launches(s)
    t = sum(sec for sec, _ in calls)
    if t <= 0:
        return None
    pk = peaks.peaks(ctx["device_kind"])
    least = 0.0
    for _, dims in calls:
        f, b = model.gmm_launch_cost(ctx["sz"], dims)
        least += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / t

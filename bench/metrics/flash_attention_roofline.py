"""flash_attention_roofline: the Pallas flash-attention kernel's share of its roofline.

The kernel is the forward (``kernels/flash_attention``), one launch per
layer and pass; its custom VJP's backward is plain XLA operations that the
trace cannot tell from the rest of the layer, so it counts on neither side.
Every launch in the traced window counts on both sides: its device time,
and its least time, the larger of its FLOPs over the bf16 peak and its
bytes over HBM bandwidth, as the configuration's family module counts the
launch (``flash_launch_cost``: at the batch and heads of its output shape
and the cell's real sequence length, which the kernel pads to its block).
So the roofline counts the same work whatever layer launches the kernel.
Under remat the forward runs twice per pass, and both launches count. A
launch is a custom call that the trace names after the kernel's launcher,
``_fa_jit``.
"""
import re

from bench import peaks

LAUNCHER = "_fa_jit"
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
    s = ctx.get("trace")
    if ctx["kind"] != "round" or s is None:
        return None
    calls = launches(s)
    t = sum(sec for sec, _ in calls)
    if t <= 0:
        return None
    model, sz, tr = ctx["model"], ctx["sz"], ctx["traffic"]
    seq = tr["text_len"] + (sz.image_patches if sz.frontend else 0)
    pk = peaks.peaks(ctx["device_kind"])
    least = 0.0
    for _, dims in calls:
        f, b = model.flash_launch_cost(sz, dims, seq)
        least += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    return 100.0 * least / t

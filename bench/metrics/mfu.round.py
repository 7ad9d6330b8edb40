"""mfu.round: the round's needed FLOPs per second over the chips' bf16 peak.

Needed FLOPs are counted from shapes by the family module's
``round_flops`` (the rules of ``bench/flops.py``: frozen backbone, input
gradients only, causal attention, the head where the loss reads); the rate
is over the whole traced window on the host clock.
"""
from bench import peaks


def read(ctx):
    if ctx["kind"] != "round" or ctx["window_s"] <= 0:
        return None
    peak = peaks.peaks(ctx["device_kind"])["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["need_flops"] / ctx["window_s"] / peak

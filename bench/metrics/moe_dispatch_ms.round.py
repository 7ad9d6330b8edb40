"""moe_dispatch_ms.round: device milliseconds a round in the expert layer's dispatch.

The dispatch is what the expert layer does besides its grouped matmuls:
routing (router scores, top-k, the pick weights), the sort of the token
copies by held expert, the permutation of the rows, their unpermutation and
weighing, forward and backward. The program runs it under the
``jax.named_scope``s ``fednano.moe.route`` and ``fednano.moe.dispatch``.
Where the trace's op events name those scopes, the ops that do count; the
v5e's events carry only the HLO text, so otherwise the ops are found by
their output shape: a flat token axis leading it, of the step's T tokens
(the cohort's rows times their positions) or of their T x top-k copies,
which only the routed part of the expert layer folds out of the clients'
batch axes. The grouped-matmul launches and the expert activation between
them (its last dim the expert width) are the experts' work and do not
count. Device seconds (mean over devices) over the window, per round.
"""
import re

SCOPES = ("fednano.moe.route", "fednano.moe.dispatch")
LAUNCHER = "_gmm_jit"
OUT_DIMS = re.compile(r"=\s*\(?\s*\w+\[([\d,]+)\]")


def dispatch_seconds(summary, tokens: int, copies: int, expert_width: int) -> float:
    """Device seconds of the dispatch's ops, by scope or else by shape."""
    sec = summary.seconds_matching(SCOPES)
    if sec > 0:
        return sec
    total = 0.0
    for e in summary.ops:
        if e.name.lstrip("%").startswith(LAUNCHER):
            continue
        m = OUT_DIMS.search(e.name)
        if not m:
            continue
        dims = [int(x) for x in m.group(1).split(",")]
        if dims[0] in (tokens, copies) and not (len(dims) > 1 and dims[-1] == expert_width):
            total += e.dur_ns * 1e-9
    return total / max(summary.n_devices, 1)


def read(ctx):
    s, sz, tr = ctx.get("trace"), ctx["sz"], ctx["traffic"]
    if ctx["kind"] != "round" or s is None or not hasattr(sz, "top_k") or ctx["rounds"] <= 0:
        return None
    image = sz.image_patches if sz.frontend else 0
    tokens = ctx["cohort"] * tr["batch"] * (tr["text_len"] + image)
    sec = dispatch_seconds(s, tokens, tokens * sz.top_k, sz.ff_expert)
    if sec <= 0:
        return None
    return 1000.0 * sec / ctx["rounds"]

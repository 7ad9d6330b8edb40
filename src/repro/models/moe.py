"""Mixture-of-experts layer — dropless, over the experts this chip holds.

Routing (``MoEConfig``): the router scores every one of ``n_experts`` in
float32 (softmax over them, or a sigmoid each), picks the ``top_k`` by
score (plus the correction bias where ``score_bias``: DeepSeek-V3's
``noaux_tc``, which chooses by s + b and weighs by s), renormalises the
chosen scores and scales them (``routed_scale``).

Expert parallelism: the chip holds experts ``[expert_first, expert_first +
held)``. Of each token's picks it keeps those that fall on a held expert,
sorts the token copies by expert, and runs the held experts' MLPs as
grouped matmuls over the sorted rows (``kernels/moe_gmm`` with
``use_pallas``, else ``jax.lax.ragged_dot``): each expert takes exactly
the rows routed to it, with no capacity and no dropped token, so one
token's output never depends on which other tokens share its batch. The
rows are unsorted, weighed and summed per token. What the absent experts
would add is left out; no code stands in for the other chips. The shared
experts (one MLP of width ``shared_d_ff``) are added for every token.

The routed part is token-wise, so under ``jax.vmap`` (the round engine's
vmap over clients) its batching rule folds the mapped axis into the
tokens: one sort and one grouped matmul per expert see the whole cohort's
rows, not one dispatch per client. Its backward pass gives input
gradients only (the backbone is frozen): through the experts by the same
grouped matmul on transposed weights, and through the routing weights to
the router's input.

Device scopes: ``fednano.moe.route``, ``fednano.moe.dispatch`` (sort,
permute, unpermute), ``fednano.moe.experts`` and ``fednano.moe.shared``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import custom_batching

from repro.models.layers import dense_init, init_mlp, mlp


def init_moe(key, cfg, dtype=jnp.float32):
    mcfg = cfg.moe
    d, f = cfg.d_model, cfg.d_ff
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(kr, (d, mcfg.n_experts), dtype=jnp.float32),
        "w_gate": dense_init(kg, (mcfg.held, d, f), dtype),
        "w_up": dense_init(ku, (mcfg.held, d, f), dtype),
        "w_down": dense_init(kd, (mcfg.held, f, d), dtype, scale=f**-0.5),
    }
    if mcfg.score_bias:
        p["bias"] = jnp.zeros((mcfg.n_experts,), jnp.float32)
    if mcfg.shared_d_ff:
        p["shared"] = init_mlp(ks, cfg, d_ff=mcfg.shared_d_ff, dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# routing (float32)
# ---------------------------------------------------------------------------

def route_scores(mcfg, router, x):
    """x (T, D) -> scores (T, E) float32."""
    logits = jnp.dot(x.astype(jnp.float32), router, preferred_element_type=jnp.float32)
    if mcfg.scoring == "sigmoid":
        return jax.nn.sigmoid(logits)
    if mcfg.scoring == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    raise ValueError(f"unknown MoE scoring {mcfg.scoring!r}")


def choose(mcfg, scores, bias):
    """Indices (T, K) of the top-k experts by score (+ correction bias)."""
    pick = scores if bias is None else scores + bias
    return jax.lax.top_k(pick, mcfg.top_k)[1]


def pick_weights(mcfg, scores, idx):
    """Weights (T, K) of the chosen experts: their scores, renormalised
    over the chosen ones, times the routed scale."""
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * mcfg.routed_scale


def route(mcfg, params, x):
    """x (T, D) -> (indices (T, K), weights (T, K) float32, scores (T, E))."""
    scores = route_scores(mcfg, params["router"], x)
    idx = choose(mcfg, scores, params.get("bias"))
    return idx, pick_weights(mcfg, scores, idx), scores


# ---------------------------------------------------------------------------
# the routed part: token-wise, on flat (T, D) tokens
# ---------------------------------------------------------------------------

def _grouped(use_pallas, lhs, rhs, sizes, transpose_rhs=False):
    """Rows sorted by held expert -> each row times its expert's matrix;
    rows past the held experts' (the last entry of ``sizes``) give zeros."""
    if use_pallas:
        from repro.kernels.moe_gmm import ops as gmm_ops

        return gmm_ops.gmm(lhs, rhs, sizes, transpose_rhs=transpose_rhs)
    w = jnp.swapaxes(rhs, 1, 2) if transpose_rhs else rhs
    return jax.lax.ragged_dot(lhs, w, sizes[:-1]).astype(lhs.dtype)


def _act(act, gate, up):
    """The experts' activation: SwiGLU, GeGLU, or GELU of ``up`` alone."""
    if act == "swiglu":
        return jax.nn.silu(gate) * up
    if act == "geglu":
        return jax.nn.gelu(gate) * up
    return jax.nn.gelu(up)


def _routed_fwd(mcfg, act, use_pallas, params, x):
    """Routed experts' output of x (T, D), and what the backward pass reads."""
    T, D = x.shape
    K, E_h = mcfg.top_k, mcfg.held
    with jax.named_scope("fednano.moe.route"):
        idx, w, _ = route(mcfg, params, x)
    with jax.named_scope("fednano.moe.dispatch"):
        local = idx - mcfg.expert_first
        held = (local >= 0) & (local < E_h)
        key = jnp.where(held, local, E_h).reshape(-1)             # (T*K,)
        order = jnp.argsort(key, stable=True)
        inv = jnp.argsort(order)
        sizes = jnp.bincount(key, length=E_h + 1).astype(jnp.int32)
        rows = x[order // K]                                        # (T*K, D)
    with jax.named_scope("fednano.moe.experts"):
        up = _grouped(use_pallas, rows, params["w_up"], sizes)
        gate = up if act == "gelu" else _grouped(use_pallas, rows, params["w_gate"], sizes)
        out = _grouped(use_pallas, _act(act, gate, up), params["w_down"], sizes)
    with jax.named_scope("fednano.moe.dispatch"):
        yk = out[inv].reshape(T, K, D)
        wk = jnp.where(held, w, 0.0)
        y = jnp.einsum("tk,tkd->td", wk, yk.astype(jnp.float32)).astype(x.dtype)
        counts = sizes[:E_h]
    return (y, counts), (idx, held, order, inv, sizes, gate, up, yk)


def _routed_bwd(mcfg, act, use_pallas, params, x, res, dy):
    """Input gradient of the routed part: through the held experts and
    through the routing weights of their picks."""
    idx, held, order, inv, sizes, gate, up, yk = res
    T, D = x.shape
    K = mcfg.top_k
    dy32 = dy.astype(jnp.float32)
    with jax.named_scope("fednano.moe.route"):
        scores = route_scores(mcfg, params["router"], x)
        wk = jnp.where(held, pick_weights(mcfg, scores, idx), 0.0)
        dw = jnp.where(held, jnp.einsum("td,tkd->tk", dy32, yk.astype(jnp.float32)), 0.0)
    with jax.named_scope("fednano.moe.dispatch"):
        dout = (dy32[:, None, :] * wk[..., None]).astype(x.dtype).reshape(T * K, D)[order]
    with jax.named_scope("fednano.moe.experts"):
        dh = _grouped(use_pallas, dout, params["w_down"], sizes, transpose_rhs=True)
        dgate, dup = jax.vjp(functools.partial(_act, act), gate, up)[1](dh)
        drows = _grouped(use_pallas, dup, params["w_up"], sizes, transpose_rhs=True)
        if act != "gelu":
            drows = drows + _grouped(use_pallas, dgate, params["w_gate"], sizes,
                                     transpose_rhs=True)
    with jax.named_scope("fednano.moe.dispatch"):
        dx = jnp.sum(drows[inv].reshape(T, K, D).astype(jnp.float32), axis=1)
    with jax.named_scope("fednano.moe.route"):
        _, vjp = jax.vjp(lambda xx: pick_weights(
            mcfg, route_scores(mcfg, params["router"], xx), idx), x)
        dx = dx + vjp(dw)[0].astype(jnp.float32)
    return dx.astype(x.dtype)


def _folded(fn):
    """``fn(params, glob, *tok) -> (tok_outs, glob_outs)``, token-wise in the
    leading axis of every array of ``tok`` and ``tok_outs``, as a
    ``custom_vmap``. Its batching rule folds the mapped axis into the
    tokens, calls ``fn`` once on the whole batch's tokens, and splits
    ``tok_outs`` along it again; ``glob`` and ``glob_outs`` (the groups'
    sizes, counted over every token) are not mapped. Parameters must not be."""
    cv = custom_batching.custom_vmap(fn)

    @cv.def_vmap
    def rule(axis_size, in_batched, params, glob, *tok):
        if any(jax.tree.leaves((in_batched[0], in_batched[1]))):
            raise NotImplementedError("MoE parameters or counts mapped over a batch axis")
        flat = [a.reshape((-1,) + a.shape[2:]) if b else
                jnp.broadcast_to(a, (axis_size,) + a.shape).reshape((-1,) + a.shape[1:])
                for a, b in zip(tok, in_batched[2:])]
        tok_outs, glob_outs = cv(params, glob, *flat)
        tok_outs = jax.tree.map(lambda o: o.reshape((axis_size, -1) + o.shape[1:]), tok_outs)
        return ((tok_outs, glob_outs),
                (jax.tree.map(lambda _: True, tok_outs), jax.tree.map(lambda _: False, glob_outs)))

    return cv


@functools.lru_cache(maxsize=None)
def _routed_call(mcfg, act: str, use_pallas: bool):
    """The routed part of one MoE config as a function of (params, x (T, D))
    returning (y (T, D), tokens per held expert (E_held,)), differentiable
    in x."""

    def fwd_fn(params, glob, x):
        (y, counts), (idx, held, order, inv, sizes, gate, up, yk) = _routed_fwd(
            mcfg, act, use_pallas, params, x)
        return (y, idx, held, order, inv, gate, up, yk), (counts, sizes)

    def bwd_fn(params, glob, x, dy, idx, held, order, inv, gate, up, yk):
        (sizes,) = glob
        res = (idx, held, order, inv, sizes, gate, up, yk)
        return (_routed_bwd(mcfg, act, use_pallas, params, x, res, dy),), ()

    fwd, bwd = _folded(fwd_fn), _folded(bwd_fn)

    @jax.custom_vjp
    def routed(params, x):
        (y, *_), (counts, _) = fwd(params, (), x)
        return y, counts

    def routed_f(params, x):
        (y, *res), (counts, sizes) = fwd(params, (), x)
        return (y, counts), (params, x, sizes, res)

    def routed_b(saved, g):
        params, x, sizes, res = saved
        (dx,), _ = bwd(params, (sizes,), x, g[0], *res)
        return None, dx

    routed.defvjp(routed_f, routed_b)
    return routed


def moe_apply(cfg, params, x):
    """x (B, S, D) -> (y (B, S, D), aux).

    aux: ``lb_loss`` (GShard load-balance term over every expert's top-1
    share, reported only: the backbone is frozen) and ``tokens`` (E_held,),
    the token copies each held expert computed (the whole batch's under
    vmap).
    """
    mcfg = cfg.moe
    B, S, D = x.shape
    routed = _routed_call(mcfg, cfg.act, bool(cfg.use_pallas))
    y, counts = routed({k: v for k, v in params.items() if k != "shared"},
                       x.reshape(B * S, D))
    y = y.reshape(B, S, D)
    if "shared" in params:
        with jax.named_scope("fednano.moe.shared"):
            y = y + mlp(cfg, params["shared"], x)
    with jax.named_scope("fednano.moe.route"):
        scores = jax.lax.stop_gradient(
            route_scores(mcfg, params["router"], x.reshape(B * S, D)))
        top1 = jax.nn.one_hot(jnp.argmax(scores, axis=-1), mcfg.n_experts)
        lb = mcfg.n_experts * jnp.sum(jnp.mean(top1, 0) * jnp.mean(scores, 0))
    return y, {"lb_loss": lb, "tokens": counts}

"""Decoder-only transformer stacks: dense / moe / ssm / hybrid.

Layers are **scanned** (``lax.scan`` over stacked per-layer params) so HLO
size is O(1) in depth — 80-layer dry-runs stay tractable — with optional
``jax.checkpoint`` (remat) on the scanned body for training.

Layer bodies by family:
    dense/vlm : x += attn(norm(x));  x += mlp(norm(x))
    moe       : x += attn(norm(x));  x += moe(norm(x))   (+ shared expert),
                after ``n_dense_layers`` leading dense layers (their own
                stack, ``lead``, with MLP width ``dense_d_ff``)
    ssm       : x += mamba2(norm(x))
    hybrid    : 12 × (rec, rec, attn) triples + 2 trailing rec layers,
                every sub-layer followed by its own MLP (Griffin residual
                pattern); attn sub-layers use the local window.

All execution modes share layer params:
    forward_stack      — full sequence, no state (training loss path)
    prefill_stack      — full sequence, returns stacked decode state
    decode_stack       — one token, consumes/produces stacked decode state
    decode_pages_stack — one token per page of the serving engine's pool,
                         which it writes one K/V row per page per layer
"""
from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import rglru as rglru_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import init_mlp, init_norm, mlp, norm
from repro.models.moe import init_moe, moe_apply
from repro.sharding import constrain, residual_spec


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _attn_cfg(cfg):
    """Attention-sublayer view of the config (hybrid uses the local window)."""
    if cfg.family == "hybrid":
        return cfg.with_(sliding_window=cfg.rglru.local_window)
    return cfg


def init_dense_layer(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": init_norm(cfg, cfg.d_model, dtype),
        "attn": attn_lib.init_attention(k1, cfg, dtype=dtype),
        "norm2": init_norm(cfg, cfg.d_model, dtype),
        "mlp": init_mlp(k2, cfg, dtype=dtype),
    }


def _lead_cfg(cfg):
    """The leading dense layers' view of an MoE config: their MLP width."""
    return cfg.with_(d_ff=cfg.dense_d_ff or cfg.d_ff)


def init_moe_layer(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": init_norm(cfg, cfg.d_model, dtype),
        "attn": attn_lib.init_attention(k1, cfg, dtype=dtype),
        "norm2": init_norm(cfg, cfg.d_model, dtype),
        "moe": init_moe(k2, cfg, dtype=dtype),
    }


def init_ssm_layer(key, cfg, dtype):
    return {
        "norm1": init_norm(cfg, cfg.d_model, dtype),
        "ssm": ssm_lib.init_ssm(key, cfg, dtype=dtype),
    }


def init_rec_layer(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": init_norm(cfg, cfg.d_model, dtype),
        "rgl": rglru_lib.init_rglru(k1, cfg, dtype=dtype),
        "norm2": init_norm(cfg, cfg.d_model, dtype),
        "mlp": init_mlp(k2, cfg, dtype=dtype),
    }


def init_attn_mix_layer(key, cfg, dtype):
    """Hybrid attention sub-layer (same structure as dense)."""
    return init_dense_layer(key, _attn_cfg(cfg), dtype)


def hybrid_split(cfg) -> Tuple[int, int]:
    """(n_triples, n_extra_rec) — 38 = 12×3 + 2 for recurrentgemma-9b."""
    n_triples = cfg.n_layers // 3
    n_extra = cfg.n_layers - 3 * n_triples
    return n_triples, n_extra


def init_stack(key, cfg, dtype):
    """Stacked per-layer params for the decoder stack."""
    if cfg.family == "hybrid":
        n_t, n_e = hybrid_split(cfg)
        kt, ke = jax.random.split(key)

        def init_triple(k):
            k0, k1, k2 = jax.random.split(k, 3)
            return {
                "rec0": init_rec_layer(k0, cfg, dtype),
                "rec1": init_rec_layer(k1, cfg, dtype),
                "attn": init_attn_mix_layer(k2, cfg, dtype),
            }

        triples = jax.vmap(init_triple)(jax.random.split(kt, n_t))
        extras = (
            jax.vmap(lambda k: init_rec_layer(k, cfg, dtype))(jax.random.split(ke, n_e))
            if n_e
            else None
        )
        return {"triples": triples, "extras": extras}

    init_one = {
        "dense": init_dense_layer,
        "vlm": init_dense_layer,
        "audio": init_dense_layer,  # used for the whisper *encoder* stack
        "moe": init_moe_layer,
        "ssm": init_ssm_layer,
    }[cfg.family]
    n_lead = cfg.n_dense_layers if cfg.family == "moe" else 0
    if n_lead:
        key, kl = jax.random.split(key)
    layers = jax.vmap(lambda k: init_one(k, cfg, dtype))(
        jax.random.split(key, cfg.n_layers - n_lead)
    )
    if not n_lead:
        return {"layers": layers}
    lead = jax.vmap(lambda k: init_dense_layer(k, _lead_cfg(cfg), dtype))(
        jax.random.split(kl, n_lead))
    return {"lead": lead, "layers": layers}


# ---------------------------------------------------------------------------
# layer bodies (single layer, full sequence)
# ---------------------------------------------------------------------------

def dense_body(cfg, lp, x, angles):
    # seq_parallel: residual lives sequence-sharded; the block input is
    # all-gathered exactly at the norm output (Megatron-SP AG point) so the
    # attention/MLP interior keeps its tensor-parallel layout. With
    # seq_parallel off NO constraint is inserted at all — even identity
    # constraints perturb XLA fusion (EXPERIMENTS.md §Perf, glm4 iter 3).
    sp = getattr(cfg, "seq_parallel", False)
    if sp:
        x = constrain(x, residual_spec(cfg))
    h = norm(cfg, lp["norm1"], x)
    if sp:
        h = constrain(h, ("data", None, None))
    x = x + attn_lib.full_attention(cfg, lp["attn"], h, angles)
    if sp:
        x = constrain(x, residual_spec(cfg))
    h = norm(cfg, lp["norm2"], x)
    if sp:
        h = constrain(h, ("data", None, None))
    x = x + mlp(cfg, lp["mlp"], h)
    return x, jnp.float32(0.0)


def moe_body(cfg, lp, x, angles):
    sp = getattr(cfg, "seq_parallel", False)
    if sp:
        x = constrain(x, residual_spec(cfg))
    h = norm(cfg, lp["norm1"], x)
    if sp:
        h = constrain(h, ("data", None, None))
    x = x + attn_lib.full_attention(cfg, lp["attn"], h, angles)
    if sp:
        x = constrain(x, residual_spec(cfg))
    h = norm(cfg, lp["norm2"], x)
    if sp:
        h = constrain(h, ("data", None, None))
    y, aux = moe_apply(cfg, lp["moe"], h)
    return x + y, aux["lb_loss"]


def ssm_body(cfg, lp, x, angles):
    x = x + ssm_lib.ssm_apply(cfg, lp["ssm"], norm(cfg, lp["norm1"], x),
                              use_pallas=cfg.use_pallas)
    return x, jnp.float32(0.0)


def rec_body(cfg, lp, x, angles):
    x = x + rglru_lib.rglru_block(cfg, lp["rgl"], norm(cfg, lp["norm1"], x))
    x = x + mlp(cfg, lp["mlp"], norm(cfg, lp["norm2"], x))
    return x, jnp.float32(0.0)


def hybrid_triple_body(cfg, lp, x, angles):
    x, _ = rec_body(cfg, lp["rec0"], x, angles)
    x, _ = rec_body(cfg, lp["rec1"], x, angles)
    x, _ = dense_body(_attn_cfg(cfg), lp["attn"], x, angles)
    return x, jnp.float32(0.0)


_BODY = {
    "dense": dense_body,
    "vlm": dense_body,
    "audio": dense_body,
    "moe": moe_body,
    "ssm": ssm_body,
}


# ---------------------------------------------------------------------------
# full-sequence forward (training loss path)
# ---------------------------------------------------------------------------

def _unstack(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


def _n_stacked(stacked) -> int:
    return jax.tree.leaves(stacked)[0].shape[0]


def _scan_layers(body, x, stacked, remat: bool, scan: bool = True):
    """Run ``body`` over stacked layer params.

    scan=True: lax.scan (HLO size O(1) in depth — production path).
    scan=False: unrolled python loop (dry-run roofline path: XLA's
    cost_analysis counts while-loop bodies ONCE, so the roofline lowering
    unrolls to get true per-step FLOPs/bytes/collectives).
    """

    def f(carry, lp):
        y, aux = body(carry, lp)
        return y, aux

    if remat:
        f = jax.checkpoint(f, prevent_cse=False)
    if scan:
        x, auxs = jax.lax.scan(f, x, stacked)
        return x, jnp.sum(auxs)
    aux_total = jnp.float32(0.0)
    for i in range(_n_stacked(stacked)):
        x, aux = f(x, _unstack(stacked, i))
        aux_total = aux_total + aux
    return x, aux_total


def forward_stack(cfg, stack, x, angles):
    """x (B, S, D) -> (hidden (B, S, D), aux_loss scalar)."""
    if cfg.family == "hybrid":
        body = functools.partial(hybrid_triple_body, cfg)
        x, aux = _scan_layers(lambda c, lp: body(lp, c, angles), x,
                              stack["triples"], cfg.remat, cfg.scan_layers)
        if stack["extras"] is not None:
            body_e = functools.partial(rec_body, cfg)
            x, aux2 = _scan_layers(lambda c, lp: body_e(lp, c, angles), x,
                                   stack["extras"], cfg.remat, cfg.scan_layers)
            aux = aux + aux2
        return x, aux
    if "lead" in stack:
        lead = functools.partial(dense_body, cfg)
        x, _ = _scan_layers(lambda c, lp: lead(lp, c, angles), x, stack["lead"],
                            cfg.remat, cfg.scan_layers)
    body = functools.partial(_BODY[cfg.family], cfg)
    return _scan_layers(lambda c, lp: body(lp, c, angles), x, stack["layers"],
                        cfg.remat, cfg.scan_layers)


def _scan_emit(f, x, xs, scan: bool):
    """lax.scan or unrolled loop for carry+emit bodies (prefill/decode)."""
    if scan:
        return jax.lax.scan(f, x, xs)
    n = _n_stacked(xs)
    ys = []
    for i in range(n):
        x, y = f(x, _unstack(xs, i))
        ys.append(y)
    stacked = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    return x, stacked


# ---------------------------------------------------------------------------
# prefill: full sequence + decode state
# ---------------------------------------------------------------------------

def _attn_prefill(cfg, lp, x, angles, capacity: int):
    h = norm(cfg, lp["norm1"], x)
    out, (k, v) = attn_lib.full_attention(cfg, lp["attn"], h, angles, return_kv=True)
    x = x + out
    x = x + mlp(cfg, lp["mlp"], norm(cfg, lp["norm2"], x))
    cache = attn_lib.init_cache(cfg, x.shape[0], capacity, x.dtype)
    cache = attn_lib.seed_cache(cfg, cache, k, v, start=0)
    return x, cache


def _rec_prefill(cfg, lp, x, angles, length=None):
    h = norm(cfg, lp["norm1"], x)
    out, state = rglru_lib.rglru_block_prefill(cfg, lp["rgl"], h, length=length)
    x = x + out
    x = x + mlp(cfg, lp["mlp"], norm(cfg, lp["norm2"], x))
    return x, state


def _ssm_prefill(cfg, lp, x, length=None):
    h = norm(cfg, lp["norm1"], x)
    out, state = ssm_lib.ssm_prefill(cfg, lp["ssm"], h, length=length)
    return x + out, state


def _moe_prefill(cfg, lp, x, angles, capacity: int):
    h = norm(cfg, lp["norm1"], x)
    out, (k, v) = attn_lib.full_attention(cfg, lp["attn"], h, angles, return_kv=True)
    x = x + out
    y, _ = moe_apply(cfg, lp["moe"], norm(cfg, lp["norm2"], x))
    x = x + y
    cache = attn_lib.init_cache(cfg, x.shape[0], capacity, x.dtype)
    cache = attn_lib.seed_cache(cfg, cache, k, v, start=0)
    return x, cache


def prefill_stack(cfg, stack, x, angles, capacity: int, length=None):
    """Returns (hidden, stacked decode state).

    ``length`` (scalar int32, optional) marks only the first ``length``
    positions as real — recurrent sub-layers (ssm / rg-lru) gate their state
    updates so right-padding never leaks into the terminal decode state.
    Attention caches need no masking: pad KV is position-invalidated and
    overwritten before it becomes reachable (see serving.engine docstring).
    Latent attention has no cache yet (ROADMAP R2).
    """
    if "lead" in stack:
        raise NotImplementedError("a stack with leading dense layers has no "
                                  "decode state yet (ROADMAP R2)")
    if cfg.family == "hybrid":
        acfg = _attn_cfg(cfg)
        acap = attn_lib.cache_capacity(acfg, capacity)

        def f(c, lp):
            c, s0 = _rec_prefill(cfg, lp["rec0"], c, angles, length)
            c, s1 = _rec_prefill(cfg, lp["rec1"], c, angles, length)
            c, kv = _attn_prefill(acfg, lp["attn"], c, angles, acap)
            return c, {"rec0": s0, "rec1": s1, "attn": kv}

        x, st_t = _scan_emit(f, x, stack["triples"], cfg.scan_layers)
        state = {"triples": st_t, "extras": None}
        if stack["extras"] is not None:
            def fe(c, lp):
                return _rec_prefill(cfg, lp, c, angles, length)
            x, st_e = _scan_emit(fe, x, stack["extras"], cfg.scan_layers)
            state["extras"] = st_e
        return x, state

    if cfg.family == "ssm":
        def f(c, lp):
            return _ssm_prefill(cfg, lp, c, length)
        x, states = _scan_emit(f, x, stack["layers"], cfg.scan_layers)
        return x, {"layers": states}

    cap = attn_lib.cache_capacity(cfg, capacity)
    pre = _moe_prefill if cfg.family == "moe" else _attn_prefill

    def f(c, lp):
        return pre(cfg, lp, c, angles, cap)

    x, caches = _scan_emit(f, x, stack["layers"], cfg.scan_layers)
    return x, {"layers": caches}


# ---------------------------------------------------------------------------
# decode: one token
# ---------------------------------------------------------------------------

def _attn_step(cfg, lp, x, attend, cache):
    """Attention then MLP (or MoE) residual block of one decode token.

    ``attend(cfg, attn_params, h, cache) -> (out, kv)`` is the attention
    sub-layer and what it emits for the cache. The expert layer is dropless,
    so rows decoded together never compete for an expert.
    """
    out, kv = attend(cfg, lp["attn"], norm(cfg, lp["norm1"], x), cache)
    x = x + out
    h = norm(cfg, lp["norm2"], x)
    if "moe" not in lp:
        return x + mlp(cfg, lp["mlp"], h), kv
    y, _ = moe_apply(cfg, lp["moe"], h)
    return x + y, kv


def _rec_step(cfg, lp, x, state):
    h = norm(cfg, lp["norm1"], x)
    out, state = rglru_lib.rglru_block_step(cfg, lp["rgl"], h, state)
    x = x + out
    x = x + mlp(cfg, lp["mlp"], norm(cfg, lp["norm2"], x))
    return x, state


def _ssm_step(cfg, lp, x, state):
    h = norm(cfg, lp["norm1"], x)
    out, state = ssm_lib.ssm_decode_step(cfg, lp["ssm"], h, state)
    return x + out, state


def _decode_layers(cfg, stack, x, state, attend):
    """Layer loop of one decode token over the stacked state.

    Each attention sub-layer's cache is replaced by what ``attend`` emits
    for it (see ``_attn_step``); recurrent states by their new values.
    Returns (hidden, emitted state).
    """
    if cfg.family == "hybrid":
        acfg = _attn_cfg(cfg)

        def f(c, inp):
            lp, st = inp
            c, s0 = _rec_step(cfg, lp["rec0"], c, st["rec0"])
            c, s1 = _rec_step(cfg, lp["rec1"], c, st["rec1"])
            c, kv = _attn_step(acfg, lp["attn"], c, attend, st["attn"])
            return c, {"rec0": s0, "rec1": s1, "attn": kv}

        x, st_t = _scan_emit(f, x, (stack["triples"], state["triples"]), cfg.scan_layers)
        st_e = None
        if stack["extras"] is not None:
            def fe(c, inp):
                lp, st = inp
                return _rec_step(cfg, lp, c, st)
            x, st_e = _scan_emit(fe, x, (stack["extras"], state["extras"]), cfg.scan_layers)
        return x, {"triples": st_t, "extras": st_e}

    if cfg.family == "ssm":
        def f(c, inp):
            lp, st = inp
            return _ssm_step(cfg, lp, c, st)
        x, states = _scan_emit(f, x, (stack["layers"], state["layers"]), cfg.scan_layers)
        return x, {"layers": states}

    def f(c, inp):
        lp, st = inp
        return _attn_step(cfg, lp, c, attend, st)

    x, caches = _scan_emit(f, x, (stack["layers"], state["layers"]), cfg.scan_layers)
    return x, {"layers": caches}


def decode_stack(cfg, stack, x, angles, state, pos):
    """x (B, 1, D), stacked state -> (hidden (B, 1, D), new state)."""
    def attend(acfg, p, h, cache):
        return attn_lib.decode_attention(acfg, p, h, angles, cache, pos)

    return _decode_layers(cfg, stack, x, state, attend)


def decode_pages_stack(cfg, stack, x, angles, pool, pos):
    """The serving engine's decode: one token for each page of a pool.

    x (P, 1, D); ``pool`` is the stacked decode state of P pages; pos (P,)
    int32 is each page's position, -1 on a free page. Rows never mix: each
    page decodes as it would in a batch of one. The layer loop only reads
    the KV caches, where they lie, and emits each layer's new K/V rows;
    after the loop they are written into the pool, one row per live page
    per layer (``attention.write_rows``), so under a donated pool no
    layer's pages are copied. Recurrent states, O(1) a page, are emitted
    whole, a free page's with them: its next page write replaces them.
    Returns (hidden (P, 1, D), pool).
    """
    def attend(acfg, p, h, cache):
        return attn_lib.page_decode_attention(acfg, p, h, angles, cache, pos)

    x, emitted = _decode_layers(cfg, stack, x, pool, attend)

    def merge(old, new):
        if isinstance(old, attn_lib.KVCache):
            return attn_lib.write_rows(old, new, pos)
        return new

    is_kv = lambda a: isinstance(a, attn_lib.KVCache)
    return x, jax.tree.map(merge, pool, emitted, is_leaf=is_kv)


def init_decode_state(cfg, batch: int, capacity: int, dtype):
    """Zero decode state with the right stacked structure (for dry-run specs)."""
    if cfg.family == "hybrid":
        n_t, n_e = hybrid_split(cfg)
        acfg = _attn_cfg(cfg)
        acap = attn_lib.cache_capacity(acfg, capacity)

        def one_triple(_):
            return {
                "rec0": rglru_lib.init_rglru_state(cfg, batch, dtype),
                "rec1": rglru_lib.init_rglru_state(cfg, batch, dtype),
                "attn": attn_lib.init_cache(acfg, batch, acap, dtype),
            }

        triples = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[one_triple(i) for i in range(n_t)]
        )
        extras = None
        if n_e:
            extras = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[rglru_lib.init_rglru_state(cfg, batch, dtype) for _ in range(n_e)],
            )
        return {"triples": triples, "extras": extras}

    if cfg.family == "ssm":
        states = [ssm_lib.init_ssm_state(cfg, batch, dtype) for _ in range(cfg.n_layers)]
        return {"layers": jax.tree.map(lambda *xs: jnp.stack(xs), *states)}

    cap = attn_lib.cache_capacity(cfg, capacity)
    caches = [attn_lib.init_cache(cfg, batch, cap, dtype) for _ in range(cfg.n_layers)]
    return {"layers": jax.tree.map(lambda *xs: jnp.stack(xs), *caches)}

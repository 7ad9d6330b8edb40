"""Attention: GQA / MHA, sliding-window, logit softcap, cross-attention,
KV-cache decode.

Layout conventions:
    x           (B, S, D)
    q           (B, S, n_heads, head_dim)
    k, v        (B, S, n_kv,   head_dim)
    cache k/v   (B, C, n_kv,   head_dim)   C = cache capacity
RoPE is applied *before* caching (keys are stored rotated), so decode never
re-rotates history. Sliding-window decode uses a ring buffer of capacity
``window`` — the mask only needs slot validity, never slot age.

Sharding: q heads over the ``model`` axis, kv heads over ``model`` when
divisible (fallback: replicated — glm4 kv=2, recurrentgemma kv=1, qwen1.5 /
whisper head counts; see DESIGN.md §5).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init
from repro.models.rotary import apply_rotary
from repro.sharding import constrain, residual_spec

NEG_INF = -2.0e38


class KVCache(NamedTuple):
    k: jax.Array  # (B, C, n_kv, head_dim)
    v: jax.Array  # (B, C, n_kv, head_dim)


def init_attention(key, cfg, cross: bool = False, dtype=jnp.float32):
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, (d, nh * hd), dtype),
        "wk": dense_init(kk, (d, nkv * hd), dtype),
        "wv": dense_init(kv, (d, nkv * hd), dtype),
        "wo": dense_init(ko, (nh * hd, d), dtype, scale=(nh * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh * hd,), dtype)
        p["bk"] = jnp.zeros((nkv * hd,), dtype)
        p["bv"] = jnp.zeros((nkv * hd,), dtype)
    return p


def _project_q(cfg, params, x):
    B, S, _ = x.shape
    q = x @ params["wq"]
    if "bq" in params:
        q = q + params["bq"].astype(q.dtype)
    q = q.reshape(B, S, cfg.n_heads, cfg.resolved_head_dim)
    return constrain(q, ("data", None, "model", None))


def _project_kv(cfg, params, x):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bk" in params:
        k = k + params["bk"].astype(k.dtype)
        v = v + params["bv"].astype(v.dtype)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    k = constrain(k, ("data", None, "model", None))
    v = constrain(v, ("data", None, "model", None))
    return k, v


def repeat_kv(cfg, kv):
    """(B, S, n_kv, hd) -> (B, S, n_heads, hd) by repeating head groups."""
    if cfg.n_kv_heads == cfg.n_heads:
        return kv
    return jnp.repeat(kv, cfg.q_per_kv, axis=2)


def _softcap(logits, cap: float):
    if cap and cap > 0.0:
        return jnp.tanh(logits / cap) * cap
    return logits


def sdpa(cfg, q, k, v, mask, *, window: Optional[int] = None):
    """Grouped-GQA scaled-dot-product attention (pure jnp path).

    q (B,Sq,nh,hd); k,v (B,Sk,n_kv,hd) UNREPEATED — the einsums carry the
    (kv, group) factorization so repeated K/V are never materialized (the
    naive repeat costs gigabytes per layer at decode shapes).
    mask (Sq, Sk) boolean (True = attend), or None.
    """
    B, Sq, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    qg = q.reshape(B, Sq, nkv, g, hd)
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)
    ) * (hd**-0.5)
    logits = _softcap(logits, cfg.logit_softcap)
    if mask is not None:
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, nh, hd)


def chunked_sdpa(cfg, q, k, v, *, chunk: int):
    """Blockwise-softmax attention over query chunks (memory-bounded jnp path).

    Live logits shrink from (B, H, S, S) to (B, H, chunk, S) — the reason
    prefill_32k fits HBM without the Pallas kernel. Semantically identical to
    :func:`sdpa` with a causal(+window) mask. Chunks iterate under lax.scan,
    so HLO stays small; the Pallas flash kernel is the TPU production path.
    """
    B, S, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    pad = (-S) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = q.shape[1]
    nc = Sp // chunk
    qs = jnp.moveaxis(q.reshape(B, nc, chunk, nkv, g, hd), 1, 0)
    kpos = jnp.arange(S)

    def f(_, inp):
        qc, ci = inp  # (B, chunk, nkv, g, hd), scalar chunk index
        logits = jnp.einsum(
            "bqkgd,bskd->bkgqs", qc.astype(jnp.float32), k.astype(jnp.float32)
        ) * (hd**-0.5)
        logits = _softcap(logits, cfg.logit_softcap)
        qpos = ci * chunk + jnp.arange(chunk)
        m = kpos[None, :] <= qpos[:, None]
        if cfg.sliding_window is not None:
            m = m & (qpos[:, None] - kpos[None, :] < cfg.sliding_window)
        logits = jnp.where(m[None, None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        return None, jnp.einsum("bkgqs,bskd->bqkgd", probs, v)

    _, outs = jax.lax.scan(f, None, (qs, jnp.arange(nc)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sp, nh, hd)
    return out[:, :S] if pad else out


def causal_mask(sq: int, sk: int, *, q_offset: int = 0, window: Optional[int] = None):
    """(Sq, Sk) boolean mask. Query i has absolute position q_offset + i."""
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    m = kpos[None, :] <= qpos[:, None]
    if window is not None:
        m = m & (qpos[:, None] - kpos[None, :] < window)
    return m


def full_attention(cfg, params, x, angles, *, causal: bool = True,
                   memory=None, return_kv: bool = False):
    """Full-sequence attention for train/prefill.

    memory: (B, M, D) for cross-attention (no mask, keys from memory).
    Returns (out, (k, v)) when return_kv (pre-repeat KV for cache seeding).
    """
    q = _project_q(cfg, params, x)
    kv_src = memory if memory is not None else x
    k, v = _project_kv(cfg, params, kv_src)
    if angles is not None and memory is None:
        q = apply_rotary(q, angles)
        k = apply_rotary(k, angles)
    # Context-parallel queries for head counts that don't divide the model
    # axis (qwen1.5: 20 heads vs 16): instead of replicating the whole
    # attention block (16x wasted FLOPs), shard the QUERY sequence over
    # `model` and replicate K/V — compute balances, k/v are all-gathered
    # once per layer (EXPERIMENTS.md §Perf, qwen1.5/prefill).
    from repro.sharding import current_mesh

    mesh = current_mesh()
    if (
        mesh is not None
        and causal
        and memory is None
        and getattr(cfg, "ctx_parallel_attn", False)
        and cfg.n_heads % mesh.shape.get("model", 1) != 0
    ):
        q = constrain(q, ("data", "model", None, None))
        k = constrain(k, ("data", None, None, None))
        v = constrain(v, ("data", None, None, None))
    mask = None
    if causal and memory is None:
        mask = causal_mask(x.shape[1], x.shape[1], window=cfg.sliding_window)
    if cfg.use_pallas and memory is None and causal:
        from repro.kernels.flash_attention import ops as flash_ops

        out = flash_ops.flash_attention(
            q, k, v, causal=True, window=cfg.sliding_window,
            softcap=cfg.logit_softcap,
        )
    elif (
        causal
        and memory is None
        and cfg.attn_chunk is not None
        and x.shape[1] > cfg.attn_chunk
    ):
        out = chunked_sdpa(cfg, q, k, v, chunk=cfg.attn_chunk)
    else:
        out = sdpa(cfg, q, k, v, mask)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.n_heads * cfg.resolved_head_dim)
    out = out @ params["wo"]
    out = constrain(out, residual_spec(cfg))
    if return_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------

def cache_capacity(cfg, seq_len: int) -> int:
    """SWA archs bound the live KV by the window (ring buffer)."""
    if cfg.sliding_window is not None:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def init_cache(cfg, batch: int, capacity: int, dtype) -> KVCache:
    hd = cfg.resolved_head_dim
    shape = (batch, capacity, cfg.n_kv_heads, hd)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def seed_cache(cfg, cache: KVCache, k, v, *, start: int = 0) -> KVCache:
    """Write prefill KV (already rotated) into the cache at [start, start+S)."""
    C = cache.k.shape[1]
    S = k.shape[1]
    if S > C:
        # Sliding-window ring: only the last C positions survive, and position
        # p must land at slot p % C so later decode writes (slot = pos % C)
        # overwrite the oldest entry. roll by S % C achieves exactly that.
        k = jnp.roll(k[:, -C:], S % C, axis=1)
        v = jnp.roll(v[:, -C:], S % C, axis=1)
        start = 0
    ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, start, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, start, 0, 0))
    return KVCache(ck, cv)


def decode_qkv(cfg, params, x, angles):
    """One token's rotated query and new K/V rows: x (B, 1, D) ->
    q (B, 1, n_heads, hd), k and v (B, 1, n_kv, hd)."""
    q = _project_q(cfg, params, x)
    k, v = _project_kv(cfg, params, x)
    if angles is not None:
        q = apply_rotary(q, angles)
        k = apply_rotary(k, angles)
    return q, k, v


def attend_cache(cfg, params, q, ck, cv, valid):
    """One query per row against that row's cache: q (B, 1, n_heads, hd),
    ck/cv (B, C, n_kv, hd), valid (B or 1, C) boolean. Returns (B, 1, D)."""
    B = q.shape[0]
    hd = cfg.resolved_head_dim
    nkv = cfg.n_kv_heads
    g = cfg.n_heads // nkv
    qg = q.reshape(B, 1, nkv, g, hd)
    # Align q's sharding with the KV-cache layout (EXPERIMENTS.md §Perf,
    # grok/decode): when kv-heads don't divide the model axis the cache is
    # head_dim-sharded; constraining q the same way replaces the per-layer
    # "involuntary full rematerialization" cache copies with one small
    # fp32 logits all-reduce (contraction over the sharded head_dim).
    from repro.sharding import current_mesh

    mesh = current_mesh()
    if mesh is not None:
        msize = mesh.shape.get("model", 1)
        if nkv % msize == 0:
            qg = constrain(qg, ("data", None, "model", None, None))
        elif hd % msize == 0:
            qg = constrain(qg, ("data", None, None, None, "model"))
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, ck, preferred_element_type=jnp.float32
    ) * (hd**-0.5)
    logits = _softcap(logits, cfg.logit_softcap)
    logits = jnp.where(valid[:, None, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, cv)
    out = out.reshape(B, 1, cfg.n_heads * hd) @ params["wo"]
    return constrain(out, ("data", None, None))


def decode_attention(cfg, params, x, angles, cache: KVCache, pos):
    """One-token decode: x (B, 1, D), pos scalar int32 (absolute position).

    Writes the new KV at slot ``pos % C`` (ring semantics — for full caches
    C == seq_len so the slot is just ``pos``) and attends over valid slots.
    Returns (out (B,1,D), new_cache).
    """
    C = cache.k.shape[1]
    q, k, v = decode_qkv(cfg, params, x, angles)
    slot = jnp.mod(pos, C)
    ck = jax.lax.dynamic_update_slice(cache.k, k.astype(cache.k.dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache.v, v.astype(cache.v.dtype), (0, slot, 0, 0))
    # slot j valid iff it has been written: j <= pos (ring: pos >= C -> all valid)
    valid = (jnp.arange(C) <= pos)[None]  # covers both ring and linear cases
    return attend_cache(cfg, params, q, ck, cv, valid), KVCache(ck, cv)


def page_decode_attention(cfg, params, x, angles, cache: KVCache, pos):
    """One token per page against this layer's pages, which it only reads.

    x (P, 1, D); cache k/v (P, C, n_kv, hd), this layer's pages where they
    lie in the pool; pos (P,) int32, each page's position, -1 on a free
    page. Each page's new K/V row takes the place of slot ``pos % C`` in
    the attention, so the numbers are those of ``decode_attention``
    writing it there first. Returns (out (P, 1, D), the KVCache of the new
    rows (P, n_kv, hd)) for :func:`write_rows`.
    """
    C = cache.k.shape[1]
    q, k, v = decode_qkv(cfg, params, x, angles)
    k, v = k.astype(cache.k.dtype), v.astype(cache.v.dtype)
    here = (jnp.arange(C)[None, :] == jnp.mod(pos, C)[:, None]) & (pos >= 0)[:, None]
    ck = jnp.where(here[:, :, None, None], k, cache.k)
    cv = jnp.where(here[:, :, None, None], v, cache.v)
    valid = jnp.arange(C)[None, :] <= pos[:, None]
    return attend_cache(cfg, params, q, ck, cv, valid), KVCache(k[:, 0], v[:, 0])


def write_rows(pool: KVCache, rows: KVCache, pos) -> KVCache:
    """Write every layer's new K/V row of each live page into the pool.

    pool k/v (L, P, C, n_kv, hd); rows (L, P, n_kv, hd); pos (P,), -1 on a
    free page, which is left as it is. One dynamic-update-slice of L rows
    a page: in place when the pool's buffer is donated, and in the pool's
    own layout. (A scatter, or a write that reads the pool at the rows it
    writes, makes the TPU compiler lay the whole pool out anew, twice a
    call.) A free page's slot 0 is read before any write and put back.
    """
    C = pool.k.shape[2]
    slot = jnp.mod(jnp.maximum(pos, 0), C)
    live = (pos >= 0)[None, :, None, None]

    def put(a, r):
        r = jnp.where(live, r.astype(a.dtype), a[:, :, 0])
        for p in range(a.shape[1]):
            a = jax.lax.dynamic_update_slice(a, r[:, p, None, None], (0, p, slot[p], 0, 0))
        return a

    return KVCache(put(pool.k, rows.k), put(pool.v, rows.v))


def cross_decode_attention(cfg, params, x, mem_kv: KVCache):
    """Decoder cross-attention against a fixed (precomputed) encoder memory."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    q = _project_q(cfg, params, x)
    nkv = cfg.n_kv_heads
    g = cfg.n_heads // nkv
    qg = q.reshape(B, 1, nkv, g, hd)
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, mem_kv.k, preferred_element_type=jnp.float32
    ) * (hd**-0.5)
    probs = jax.nn.softmax(logits, axis=-1).astype(mem_kv.v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, mem_kv.v)
    out = out.reshape(B, 1, cfg.n_heads * hd) @ params["wo"]
    return constrain(out, ("data", None, None))

"""Blockwise flash attention — Pallas TPU kernel.

TPU-native online-softmax attention (the SDPA replacement, DESIGN.md §3):

  * grid (B, H, nQ, nK) — the nK axis is innermost and sequential on a TPU
    core, so the running max/denominator/accumulator live in VMEM scratch
    and carry across k-steps; they are initialized at k==0 and the output
    tile is written once at the final k-step (classic two-pass-free form).
  * GQA-aware: K/V BlockSpecs index-map head h -> h // (H // Hkv), so a KV
    head group is loaded into VMEM ONCE per Q-head — on real hardware this
    is the bandwidth win over head-repeated SDPA.
  * causal + sliding-window masks are applied per tile from 2D iotas;
    grok-style tanh softcap optionally applied pre-mask.
  * block sizes default to (128, 512) — MXU-aligned (multiples of 8×128
    lanes) and small enough that q, k, v, acc tiles fit VMEM at head_dim 256.
  * head-major layout inside the kernel: q/k/v are (B, H, S, D) with
    (1, 1, block, D) blocks and the logsumexp is (B, H, S, 1), so the last
    two dims of every block are (block, D) or (block, 1) — the TPU's tiling
    rule (divisible by (8, 128) or equal to the array dims). The public
    wrapper keeps the model's (B, S, H, D) layout and transposes.
  * v (and so the output) may have a head width Dv of its own: latent
    attention scores at the q/k width and mixes values of another.

Numerics: all softmax state in fp32 scratch regardless of input dtype.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int], softcap: float,
            block_q: int, block_k: int, q_offset: int, n_k: int, kv_len: int):
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, Dv)

    # q·kᵀ contracting the head dim of both (no explicit transpose)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap and softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap

    qb = pl.program_id(2)
    qpos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    # padded keys (kpos >= kv_len) must never reach the softmax denominator;
    # the causal mask happens to cover them when Sq == Sk, but bidirectional
    # or cross-attention shapes need the explicit bound
    mask = kpos < kv_len
    if causal:
        mask = mask & (kpos <= qpos)
    if window is not None:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                           # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (exp(NEG_INF - NEG_INF) would be exp(0)=1)
    p = jnp.exp(jnp.where(m_new <= NEG_INF / 2, NEG_INF, s - m_new))
    alpha = jnp.exp(
        jnp.where(m_prev <= NEG_INF / 2, NEG_INF, m_prev - m_new)
    )                                             # (bq, 1)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(kb == n_k - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)
        # per-row logsumexp (flash residual for the backward pass); rows
        # that never saw an unmasked key keep m == NEG_INF as the marker
        lse_ref[0, 0] = m_scr[...] + jnp.log(denom)


def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 512,
    interpret: bool = False,
    return_lse: bool = False,
):
    """q, k (B, S, H or Hkv, D); v (B, Sk, Hkv, Dv), H % Hkv == 0. Returns
    (B, Sq, H, Dv).

    Query i has absolute position (Sk - Sq) + i (decode/prefill alignment).
    With ``return_lse`` also returns the per-row logsumexp (B, H, Sq) — the
    flash residual the custom VJP in ``ops.py`` rebuilds probabilities from.
    """
    B, Sq, H, D = q.shape
    Bk, Sk, Hkv, Dk = k.shape
    Dv = v.shape[-1]
    assert (B, D) == (Bk, Dk) and v.shape[:3] == k.shape[:3] and H % Hkv == 0
    group = H // Hkv

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    # head-major (B, H, S, D), sequence padded to whole blocks
    q = jnp.pad(jnp.swapaxes(q, 1, 2), ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    k = jnp.pad(jnp.swapaxes(k, 1, 2), ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    v = jnp.pad(jnp.swapaxes(v, 1, 2), ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sqp, Skp = q.shape[2], k.shape[2]
    n_q, n_k = Sqp // bq, Skp // bk
    q_offset = Sk - Sq

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=D**-0.5,
            causal=causal,
            window=window,
            softcap=softcap,
            block_q=bq,
            block_k=bk,
            q_offset=q_offset,
            n_k=n_k,
            kv_len=Sk,
        ),
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sqp, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    out, lse = out
    out = jnp.swapaxes(out[:, :, :Sq], 1, 2)
    lse = lse[:, :, :Sq, 0]
    return (out, lse) if return_lse else out

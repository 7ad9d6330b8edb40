"""Jitted public wrapper for the flash-attention kernel, with a custom VJP.

Pallas calls are not differentiable in this JAX build, so the backward pass
is the standard flash-attention recomputation: the forward kernel saves the
per-row logsumexp L, and the backward rebuilds the probabilities blockwise
from p = exp(s − L) instead of differentiating through a softmax —

    dv = pᵀ·do
    ds = p ∘ (do·vᵀ − rowsum(do ∘ o))        (the "D-trick": no p saved)
    dq = scale · ds·k,   dk = scale · dsᵀ·q

with the softcap chain factor (1 − tanh²) folded into ds and GQA K/V grads
summed over each head group. v may be narrower or wider than q and k
(latent attention): the scale is the q/k width's, dv has v's width. This is an independent implementation of the
gradient (saved-LSE + D-trick vs autodiff-through-softmax), so the parity
check against ``jax.grad`` of the jnp ref in tests/kernel_harness.py is a
real differential test of both the kernel's LSE and the backward math.

Block sizes: ``block_q=None`` / ``block_k=None`` consult the tuning table
(``repro.kernels.tuning``); explicit values pass through untouched.
``interpret=None`` follows the platform (``repro.kernels.platform``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.flash_attention.flash_attention import NEG_INF, flash_attention as _fa
from repro.kernels.platform import interpret_mode


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fa_vjp(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    return _fa(q, k, v, causal=causal, window=window, softcap=softcap,
               block_q=block_q, block_k=block_k, interpret=interpret)


def _fa_fwd(q, k, v, causal, window, softcap, block_q, block_k, interpret):
    out, lse = _fa(q, k, v, causal=causal, window=window, softcap=softcap,
                   block_q=block_q, block_k=block_k, interpret=interpret,
                   return_lse=True)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, softcap, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    Hkv = k.shape[2]
    Sk = k.shape[1]
    Dv = v.shape[-1]
    group = H // Hkv

    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k, group, axis=2).astype(jnp.float32)
    vf = jnp.repeat(v, group, axis=2).astype(jnp.float32)
    gf = g.astype(jnp.float32)
    of = out.astype(jnp.float32)
    scale = D**-0.5

    u = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * scale
    if softcap and softcap > 0.0:
        t = jnp.tanh(u / softcap)
        s = t * softcap
        dfac = 1.0 - t * t
    else:
        s = u
        dfac = None

    qpos = jnp.arange(Sq) + (Sk - Sq)
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask = mask & (kpos[None, :] <= qpos[:, None])
    if window is not None:
        mask = mask & (qpos[:, None] - kpos[None, :] < window)

    # p from the kernel's saved LSE (B, H, Sq); fully-masked rows carry
    # lse ~ NEG_INF
    live = (lse > NEG_INF / 2)[..., None]                     # (B, H, Sq, 1)
    p = jnp.where(mask[None, None] & live, jnp.exp(s - lse[..., None]), 0.0)

    dv_h = jnp.einsum("bhqk,bqhd->bkhd", p, gf)
    dp = jnp.einsum("bqhd,bkhd->bhqk", gf, vf)
    drow = jnp.moveaxis(jnp.sum(gf * of, axis=-1), 1, 2)      # (B, H, Sq)
    ds = p * (dp - drow[..., None])
    if dfac is not None:
        ds = ds * dfac
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, kf) * scale
    dk_h = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale

    if group > 1:
        dk_h = dk_h.reshape(B, Sk, Hkv, group, D).sum(axis=3)
        dv_h = dv_h.reshape(B, Sk, Hkv, group, Dv).sum(axis=3)
    return dq.astype(q.dtype), dk_h.astype(k.dtype), dv_h.astype(v.dtype)


_fa_vjp.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k", "interpret"),
)
def _fa_jit(q, k, v, *, causal, window, softcap, block_q, block_k, interpret):
    return _fa_vjp(q, k, v, causal, window, softcap, block_q, block_k, interpret)


def flash_attention(q, k, v, *, causal=True, window: Optional[int] = None,
                    softcap: float = 0.0, block_q: int = None,
                    block_k: int = None, interpret: Optional[bool] = None):
    """q (B, Sq, H, D); k (B, Sk, Hkv, D); v (B, Sk, Hkv, Dv). Returns
    (B, Sq, H, Dv). Differentiable in (q, k, v).

    ``block_q``/``block_k`` = None → tuning table (clamped to the sequence
    lengths inside the kernel, so small shapes match the historical
    (128, 512) defaults exactly).
    """
    if block_q is None or block_k is None:
        bq, bk = tuning.flash_blocks(q.shape[1], k.shape[1], q.shape[-1], v.shape[-1])
        block_q = bq if block_q is None else block_q
        block_k = bk if block_k is None else block_k
    return _fa_jit(q, k, v, causal=causal, window=window, softcap=softcap,
                   block_q=block_q, block_k=block_k,
                   interpret=interpret_mode(interpret))

"""Jitted public wrappers for the fused LoRA kernels, with a custom VJP.

Pallas calls are not differentiable in this JAX build, so ``lora_residual``
carries a hand-written backward: for y = x + s·(x·A)·B,

    dx = g + s·(g·Bᵀ)·Aᵀ        — the forward kernel with transposed adapters
    dA = s · xᵀ·(g·Bᵀ)
    dB = s · (x·A)ᵀ·g

dx reuses the Pallas kernel (it IS a LoRA residual over g with the adapter
pair (Bᵀ, Aᵀ)); the adapter grads are adapter-sized f32 matmuls, too small
to be worth a kernel. Gradient parity vs ``jax.grad`` of the jnp ref is
pinned by the kernel harness (tests/kernel_harness.py).

Block sizes: ``block_t=None`` consults the tuning table
(``repro.kernels.tuning``); explicit values pass through untouched. Token
blocking tiles independent rows, so every block size is bit-identical.
``interpret=None`` follows the platform (``repro.kernels.platform``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.lora.lora import grouped_lora_residual_2d, lora_residual_2d
from repro.kernels.platform import interpret_mode


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _lora_2d(x, down, up, scale, block_t, interpret):
    return lora_residual_2d(x, down, up, scale=scale, block_t=block_t,
                            interpret=interpret)


def _lora_2d_fwd(x, down, up, scale, block_t, interpret):
    out = lora_residual_2d(x, down, up, scale=scale, block_t=block_t,
                           interpret=interpret)
    return out, (x, down, up)


def _lora_2d_bwd(scale, block_t, interpret, res, g):
    x, down, up = res
    # dx through the same kernel: g + s·(g·Bᵀ)·Aᵀ.
    dx = lora_residual_2d(g, jnp.transpose(up), jnp.transpose(down),
                          scale=scale, block_t=block_t, interpret=interpret)
    gf = g.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    gb = gf @ jnp.transpose(up).astype(jnp.float32)          # (T, r)
    d_down = scale * (jnp.transpose(xf) @ gb)                # (D, r)
    h = xf @ down.astype(jnp.float32)                        # (T, r)
    d_up = scale * (jnp.transpose(h) @ gf)                   # (r, D)
    return dx.astype(x.dtype), d_down.astype(down.dtype), d_up.astype(up.dtype)


_lora_2d.defvjp(_lora_2d_fwd, _lora_2d_bwd)


@functools.partial(jax.jit, static_argnames=("scale", "block_t", "interpret"))
def _lora_residual_jit(x, down, up, *, scale, block_t, interpret):
    lead = x.shape[:-1]
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    out = _lora_2d(flat, down, up, scale, block_t, interpret)
    return out.reshape(*lead, d)


def lora_residual(x, down, up, *, scale: float, block_t: int = None,
                  interpret: Optional[bool] = None):
    """y = x + scale·(x·down)·up for x of any leading shape (..., D).

    Differentiable in (x, down, up). ``block_t=None`` → tuning table.
    """
    if block_t is None:
        t = 1
        for s in x.shape[:-1]:
            t *= int(s)
        block_t = tuning.lora_block_t(t, x.shape[-1], down.shape[-1])
    return _lora_residual_jit(x, down, up, scale=scale, block_t=block_t,
                              interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("scale", "block_t", "interpret"))
def _grouped_jit(x, down, up, idx, *, scale, block_t, interpret):
    lead = x.shape[:-1]
    d = x.shape[-1]
    flat = x.reshape(-1, d)
    fidx = idx.reshape(-1)
    out = grouped_lora_residual_2d(flat, down, up, fidx, scale=scale,
                                   block_t=block_t, interpret=interpret)
    return out.reshape(*lead, d)


def grouped_lora_residual(x, down, up, idx, *, scale: float, block_t: int = None,
                          interpret: Optional[bool] = None):
    """Multi-tenant LoRA: per-row adapter ids into a stacked bank.

    x (..., D); down (N, D, r); up (N, r, D); idx (...) int32 aligned with
    x's leading shape (idx < 0 = identity row). ``block_t=None`` → tuning
    table (numerics-free either way: rows are tiled independently).
    """
    if block_t is None:
        t = 1
        for s in x.shape[:-1]:
            t *= int(s)
        block_t = tuning.lora_block_t(t, x.shape[-1], down.shape[-1])
    return _grouped_jit(x, down, up, idx, scale=scale, block_t=block_t,
                        interpret=interpret_mode(interpret))

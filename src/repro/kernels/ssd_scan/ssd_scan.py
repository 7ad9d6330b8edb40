"""Mamba2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the CUDA selective scan: the state-space
duality lets each Q-length chunk be computed as two MXU matmuls (intra-chunk
"attention" C·Bᵀ⊙decay and the state contraction) plus an O(1)-per-chunk
recurrence. The kernel runs grid (B, H, n_chunks) with the chunk axis
innermost/sequential; the carried state h (N × P) lives in fp32 VMEM scratch
across chunk steps (initialized at c==0), so the recurrence never touches
HBM.

Per grid step the VMEM working set is
    x (Q, P) + B, C (Q, N) + att (Q, Q) + h (N, P)
≈ 1.3 MiB at Q=256, P=64, N=128 (fp32) — comfortably VMEM-resident with
room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, lc_ref, b_ref, c_ref, o_ref, h_scr, *, chunk: int):
    cb = pl.program_id(2)

    @pl.when(cb == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)           # (Q, P)
    dt = dt_ref[0, 0]                             # (Q, 1)
    L = lc_ref[0, 0]                              # (Q, 1) chunk-local cumsum of dt·A
    Bm = b_ref[0].astype(jnp.float32)             # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)             # (Q, N)

    # segment decay matrix: seg[i, j] = L_i - L_j for j <= i
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    seg = jnp.where(jj <= ii, L - L.T, -jnp.inf)

    xdt = x * dt                                   # (Q, P)
    cb_mat = jax.lax.dot_general(                  # C·Bᵀ (Q, Q)
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    att = cb_mat * jnp.exp(seg)
    y_intra = jnp.dot(att, xdt, preferred_element_type=jnp.float32)  # (Q, P)

    # inter-chunk: y_i += exp(L_i) * C_i · h      (h: (N, P))
    y_inter = jnp.exp(L) * jnp.dot(
        Cm, h_scr[...], preferred_element_type=jnp.float32
    )

    o_ref[0, 0] = (y_intra + y_inter).astype(o_ref.dtype)

    # state update: h' = exp(L_last) h + Σ_j exp(L_last - L_j) B_j ⊗ xdt_j
    L_last = L[chunk - 1:, :]                      # (1, 1)
    dec_last = jnp.exp(L_last - L)                 # (Q, 1)
    # (1, 1) -> (1, P) -> (N, P): Mosaic broadcasts lanes or sublanes, not
    # both in one step
    dec_chunk = jnp.exp(jnp.broadcast_to(L_last, (1, h_scr.shape[1])))
    h_scr[...] = dec_chunk * h_scr[...] + jax.lax.dot_general(
        Bm * dec_last, xdt, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def ssd_chunked_pallas(x, dt, A, B, C, *, chunk: int = 256, interpret: bool = False):
    """x (Bt, S, H, P); dt (Bt, S, H); A (H,); B, C (Bt, S, N) -> y like x.

    The kernel works head-major: x as (Bt, H, S, P) with (1, 1, Q, P)
    blocks, and the per-step dt and chunk-local log-decay cumsum L as
    (Bt, H, S, 1) columns, so the last two dims of every block meet the
    TPU's tiling rule. L is an O(S·H) cumsum done here in jnp (the TPU
    kernel compiler has no cumsum); the kernel keeps the O(S·Q) work.
    """
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    Sp = x.shape[1]
    nc = Sp // Q

    xh = jnp.swapaxes(x, 1, 2)                                   # (Bt, H, Sp, P)
    dth = jnp.swapaxes(dt, 1, 2).astype(jnp.float32)             # (Bt, H, Sp)
    la = (dth * A.astype(jnp.float32)[None, :, None]).reshape(Bt, H, nc, Q)
    L = jnp.cumsum(la, axis=-1).reshape(Bt, H, Sp)

    out = pl.pallas_call(
        functools.partial(_kernel, chunk=Q),
        grid=(Bt, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bt, H, Sp, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xh, dth[..., None], L[..., None], B, C)
    out = jnp.swapaxes(out, 1, 2)
    return out[:, :S] if pad else out

"""Mamba2 block (SSD — state space duality), TPU-adapted.

Block structure (arXiv:2405.21060, "parallel" Mamba2 block):

    u -> in_proj -> [z | xBC | dt]
         xBC -> causal depthwise conv1d -> silu -> [x | B | C]
         x:(B,S,H,P)  dt:(B,S,H) -> softplus(dt + dt_bias)
         y = SSD(x·dt, exp(dt·A) decay, B, C) + D ⊙ x
         y -> gated RMSNorm(y, z) -> out_proj

Train/prefill uses the chunked-matmul SSD (Pallas kernel or jnp oracle);
decode carries (conv_state, ssm_state) and does the O(1) recurrence step.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.ssd_scan import ref as ssd_ref
from repro.models.layers import dense_init
from repro.sharding import constrain


class SSMState(NamedTuple):
    conv: jax.Array  # (B, d_conv-1, conv_dim) — trailing conv window
    h: jax.Array     # (B, H, P, N) — SSM state


def _dims(cfg):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.d_state
    return d_inner, n_heads, conv_dim


def init_ssm(key, cfg, dtype=jnp.float32):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, H, conv_dim = _dims(cfg)
    d_in_proj = 2 * d_inner + 2 * s.d_state + H
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = jnp.exp(
        jax.random.uniform(k3, (H,)) * (jnp.log(s.dt_max) - jnp.log(s.dt_min))
        + jnp.log(s.dt_min)
    )
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    return {
        "in_proj": dense_init(k1, (d, d_in_proj), dtype),
        "conv_w": (jax.random.normal(k2, (s.d_conv, conv_dim)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm_scale": jnp.ones((d_inner,), dtype),
        "out_proj": dense_init(k4, (d_inner, d), dtype, scale=d_inner**-0.5),
    }


def _gated_rmsnorm(scale, y, z, eps=1e-6):
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    yf = y.astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(y.dtype)


def _split_proj(cfg, zxbcdt):
    s = cfg.ssm
    d_inner, H, _ = _dims(cfg)
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner : 2 * d_inner + 2 * s.d_state]
    dt = zxbcdt[..., 2 * d_inner + 2 * s.d_state :]
    return z, xBC, dt


def _causal_conv(params, xBC, cfg):
    """Depthwise causal conv over time. xBC (B, S, conv_dim)."""
    w = params["conv_w"].astype(xBC.dtype)  # (d_conv, conv_dim)
    d_conv = w.shape[0]
    pads = jnp.pad(xBC, ((0, 0), (d_conv - 1, 0), (0, 0)))
    out = jnp.zeros_like(xBC)
    for i in range(d_conv):  # d_conv == 4: tiny unrolled loop
        out = out + pads[:, i : i + xBC.shape[1]] * w[i]
    return out + params["conv_b"].astype(xBC.dtype)


def ssm_apply(cfg, params, u, *, use_pallas: bool = False):
    """Full-sequence Mamba2 block. u (B, S, D) -> (B, S, D)."""
    s = cfg.ssm
    B_, S, D = u.shape
    d_inner, H, conv_dim = _dims(cfg)

    zxbcdt = u @ params["in_proj"]
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    xBC = jax.nn.silu(_causal_conv(params, xBC, cfg))
    x = xBC[..., :d_inner].reshape(B_, S, H, s.head_dim)
    x = constrain(x, ("data", None, "model", None))
    Bm = xBC[..., d_inner : d_inner + s.d_state]
    Cm = xBC[..., d_inner + s.d_state :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])

    if use_pallas:
        from repro.kernels.ssd_scan import ops as ssd_ops

        y = ssd_ops.ssd(x, dt.astype(x.dtype), A, Bm, Cm, chunk=s.chunk_size)
    else:
        y = ssd_ref.ssd_chunked(x, dt.astype(x.dtype), A, Bm, Cm, chunk=s.chunk_size)
    y = y + x * params["D"][:, None].astype(x.dtype)
    y = y.reshape(B_, S, d_inner)
    y = _gated_rmsnorm(params["norm_scale"], y, z)
    out = y @ params["out_proj"]
    return constrain(out, ("data", None, None))


def init_ssm_state(cfg, batch: int, dtype) -> SSMState:
    s = cfg.ssm
    d_inner, H, conv_dim = _dims(cfg)
    return SSMState(
        conv=jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
        h=jnp.zeros((batch, H, s.head_dim, s.d_state), jnp.float32),
    )


def ssm_prefill(cfg, params, u, length=None):
    """Run full sequence AND return the terminal SSMState for decoding.

    ``length`` (scalar int32, optional) marks only the first ``length``
    positions as real: ``dt`` is zeroed on the tail, which makes the decay
    ``exp(0·A) = 1`` and the input contribution ``0·x = 0`` — pad steps pass
    the recurrent state through *exactly*, so the terminal state equals the
    unpadded run's bit-for-bit (the chunked machinery already relies on this
    identity for its internal chunk padding). The conv tail is sliced at the
    valid length. Serving uses this to prefill right-padded prompts without
    contaminating the SSM state.
    """
    s = cfg.ssm
    B_, S, D = u.shape
    d_inner, H, conv_dim = _dims(cfg)
    zxbcdt = u @ params["in_proj"]
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    # last (d_conv-1) *valid* inputs; the window before t=0 is zero by the
    # causal-conv convention, so left-extend with zeros — this also keeps
    # prompts shorter than d_conv-1 from yielding a truncated conv window
    zext = jnp.concatenate(
        [jnp.zeros((B_, s.d_conv - 1, conv_dim), xBC.dtype), xBC], axis=1)
    if length is None:
        conv_tail = zext[:, -(s.d_conv - 1) :, :]
    else:
        conv_tail = jax.lax.dynamic_slice_in_dim(
            zext, jnp.asarray(length, jnp.int32), s.d_conv - 1, axis=1)
    xBCc = jax.nn.silu(_causal_conv(params, xBC, cfg))
    x = xBCc[..., :d_inner].reshape(B_, S, H, s.head_dim)
    Bm = xBCc[..., d_inner : d_inner + s.d_state]
    Cm = xBCc[..., d_inner + s.d_state :]
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    if length is not None:
        valid = jnp.arange(S) < jnp.asarray(length, jnp.int32)
        dtp = jnp.where(valid[None, :, None], dtp, 0.0)
    A = -jnp.exp(params["A_log"])
    y = ssd_ref.ssd_chunked(x, dtp.astype(x.dtype), A, Bm, Cm, chunk=s.chunk_size)
    y = y + x * params["D"][:, None].astype(x.dtype)
    y = _gated_rmsnorm(params["norm_scale"], y.reshape(B_, S, d_inner), z)
    out = y @ params["out_proj"]

    # terminal state: replay the recurrence per-chunk is equivalent to running
    # the sequential reference once over the last state; we compute it exactly
    # with the chunked machinery's final carry.
    h_final = _final_state(x, dtp, A, Bm, Cm, cfg.ssm.chunk_size)
    state = SSMState(conv=conv_tail, h=h_final)
    return out, state


def _final_state(x, dt, A, Bm, Cm, chunk: int):
    """Exact terminal SSM state h_S (B, H, P, N) via the chunked recurrence."""
    Bt, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = chunk
    pad = (-S) % Q
    if pad:
        zf = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        # pad with dt = 0 -> decay 1, update 0: state passes through unchanged
        x, Bm, Cm = zf(x), zf(Bm), zf(Cm)
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    Sp = x.shape[1]
    nc = Sp // Q
    xf = x.reshape(Bt, nc, Q, H, P).astype(jnp.float32)
    dtf = dt.reshape(Bt, nc, Q, H).astype(jnp.float32)
    Bf = Bm.reshape(Bt, nc, Q, N).astype(jnp.float32)
    la = jnp.moveaxis(dtf * A, -1, 2)  # (Bt, nc, H, Q)
    L = jnp.cumsum(la, axis=-1)
    dec_last = jnp.exp(L[..., -1:] - L)
    xdt = xf * dtf[..., None]
    states = jnp.einsum("bchj,bcjn,bcjhp->bchnp", dec_last, Bf, xdt)
    chunk_decay = jnp.exp(L[..., -1])

    def step(h, inp):
        st, dec = inp
        return h * dec[..., None, None] + st, None

    h0 = jnp.zeros((Bt, H, N, P), jnp.float32)
    h, _ = jax.lax.scan(
        step, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    )
    return jnp.swapaxes(h, -1, -2)  # (Bt, H, P, N)


def ssm_decode_step(cfg, params, u, state: SSMState):
    """One-token decode. u (B, 1, D) -> (out (B, 1, D), new state)."""
    s = cfg.ssm
    B_, _, D = u.shape
    d_inner, H, conv_dim = _dims(cfg)
    zxbcdt = u[:, 0] @ params["in_proj"]  # (B, d_in_proj)
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    window = jnp.concatenate([state.conv, xBC[:, None, :]], axis=1)  # (B, d_conv, conv_dim)
    w = params["conv_w"].astype(xBC.dtype)
    conv_out = jnp.sum(window * w[None], axis=1) + params["conv_b"].astype(xBC.dtype)
    xBCc = jax.nn.silu(conv_out)
    x = xBCc[..., :d_inner].reshape(B_, H, s.head_dim)
    Bm = xBCc[..., d_inner : d_inner + s.d_state]
    Cm = xBCc[..., d_inner + s.d_state :]
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B, H)
    A = -jnp.exp(params["A_log"])
    y, h_new = ssd_ref.ssd_decode_step(state.h, x, dtp, A, Bm, Cm)
    y = y + x * params["D"][:, None].astype(x.dtype)
    y = _gated_rmsnorm(params["norm_scale"], y.reshape(B_, d_inner), z)
    out = (y @ params["out_proj"])[:, None, :]
    new_state = SSMState(conv=window[:, 1:], h=h_new)
    return constrain(out, ("data", None, None)), new_state

"""Whisper-style encoder-decoder backbone (audio family).

The conv/mel frontend is STUBBED per the assignment carve-out — the encoder
consumes precomputed frame embeddings (B, enc_seq, frontend_dim) through the
frozen connector. Everything downstream is real: bidirectional encoder,
causal decoder with self-KV cache + precomputed cross-KV, learned positions.

NanoEdge attachment (see repro.core.adapters): 𝒜_I adapts the frame
embeddings before the encoder; 𝒜_T adapts decoder token embeddings.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models.layers import init_mlp, init_norm, mlp, norm
from repro.models.attention import KVCache


class DecLayerState(NamedTuple):
    self_kv: KVCache
    cross_kv: KVCache  # fixed after prefill


def init_dec_layer(key, cfg, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm1": init_norm(cfg, cfg.d_model, dtype),
        "self_attn": attn_lib.init_attention(k1, cfg, dtype=dtype),
        "norm_x": init_norm(cfg, cfg.d_model, dtype),
        "cross_attn": attn_lib.init_attention(k2, cfg, cross=True, dtype=dtype),
        "norm2": init_norm(cfg, cfg.d_model, dtype),
        "mlp": init_mlp(k3, cfg, dtype=dtype),
    }


def init_enc_layer(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": init_norm(cfg, cfg.d_model, dtype),
        "attn": attn_lib.init_attention(k1, cfg, dtype=dtype),
        "norm2": init_norm(cfg, cfg.d_model, dtype),
        "mlp": init_mlp(k2, cfg, dtype=dtype),
    }


def init_encdec_stacks(key, cfg, dtype):
    ke, kd = jax.random.split(key)
    enc = jax.vmap(lambda k: init_enc_layer(k, cfg, dtype))(
        jax.random.split(ke, cfg.n_enc_layers)
    )
    dec = jax.vmap(lambda k: init_dec_layer(k, cfg, dtype))(
        jax.random.split(kd, cfg.n_layers)
    )
    return {"enc_layers": enc, "dec_layers": dec}


def encode(cfg, stacks, x):
    """Bidirectional encoder. x (B, M, D) frame embeddings (+pos added upstream)."""

    def body(c, lp):
        h = norm(cfg, lp["norm1"], c)
        c = c + attn_lib.full_attention(cfg, lp["attn"], h, None, causal=False)
        c = c + mlp(cfg, lp["mlp"], norm(cfg, lp["norm2"], c))
        return c, None

    f = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
    x, _ = jax.lax.scan(f, x, stacks["enc_layers"])
    return x


def _dec_body(cfg, lp, x, memory):
    h = norm(cfg, lp["norm1"], x)
    x = x + attn_lib.full_attention(cfg, lp["self_attn"], h, None, causal=True)
    h = norm(cfg, lp["norm_x"], x)
    x = x + attn_lib.full_attention(cfg, lp["cross_attn"], h, None, memory=memory)
    x = x + mlp(cfg, lp["mlp"], norm(cfg, lp["norm2"], x))
    return x


def decode_forward(cfg, stacks, x, memory):
    """Teacher-forced decoder over the full target sequence."""

    def body(c, lp):
        return _dec_body(cfg, lp, c, memory), None

    f = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
    x, _ = jax.lax.scan(f, x, stacks["dec_layers"])
    return x, jnp.float32(0.0)


def dec_prefill(cfg, stacks, x, memory, capacity: int):
    """Teacher-forced pass that also builds decode state (self KV + cross KV)."""

    def body(c, lp):
        h = norm(cfg, lp["norm1"], c)
        out, (k, v) = attn_lib.full_attention(
            cfg, lp["self_attn"], h, None, causal=True, return_kv=True
        )
        c = c + out
        self_kv = attn_lib.init_cache(cfg, c.shape[0], capacity, c.dtype)
        self_kv = attn_lib.seed_cache(cfg, self_kv, k, v, start=0)
        h = norm(cfg, lp["norm_x"], c)
        out, (ck, cv) = attn_lib.full_attention(
            cfg, lp["cross_attn"], h, None, memory=memory, return_kv=True
        )
        c = c + out
        c = c + mlp(cfg, lp["mlp"], norm(cfg, lp["norm2"], c))
        return c, DecLayerState(self_kv=self_kv, cross_kv=KVCache(ck, cv))

    x, states = jax.lax.scan(body, x, stacks["dec_layers"])
    return x, {"layers": states}


def dec_step(cfg, stacks, x, state, pos):
    """One-token decode. x (B, 1, D)."""

    def body(c, inp):
        lp, st = inp
        h = norm(cfg, lp["norm1"], c)
        out, self_kv = attn_lib.decode_attention(cfg, lp["self_attn"], h, None, st.self_kv, pos)
        c = c + out
        h = norm(cfg, lp["norm_x"], c)
        c = c + attn_lib.cross_decode_attention(cfg, lp["cross_attn"], h, st.cross_kv)
        c = c + mlp(cfg, lp["mlp"], norm(cfg, lp["norm2"], c))
        return c, DecLayerState(self_kv=self_kv, cross_kv=st.cross_kv)

    x, states = jax.lax.scan(body, x, (stacks["dec_layers"], state["layers"]))
    return x, {"layers": states}


def dec_pages_step(cfg, stacks, x, pool, pos):
    """The serving engine's decode: one token for each page of a pool.

    x (P, 1, D); pos (P,) int32, -1 on a free page. The layer loop reads
    the self-KV pages where they lie and emits each layer's new rows, which
    are then written one per live page per layer
    (``attention.write_rows``); the cross-KV is read-only and passes
    through. Returns (x, pool).
    """
    layers = pool["layers"]

    def body(c, inp):
        lp, st = inp
        h = norm(cfg, lp["norm1"], c)
        out, rows = attn_lib.page_decode_attention(cfg, lp["self_attn"], h, None,
                                                   st.self_kv, pos)
        c = c + out
        h = norm(cfg, lp["norm_x"], c)
        c = c + attn_lib.cross_decode_attention(cfg, lp["cross_attn"], h, st.cross_kv)
        c = c + mlp(cfg, lp["mlp"], norm(cfg, lp["norm2"], c))
        return c, rows

    x, rows = jax.lax.scan(body, x, (stacks["dec_layers"], layers))
    self_kv = attn_lib.write_rows(layers.self_kv, rows, pos)
    return x, {"layers": DecLayerState(self_kv=self_kv, cross_kv=layers.cross_kv)}


def init_dec_state(cfg, batch: int, capacity: int, dtype):
    layers = []
    for _ in range(cfg.n_layers):
        layers.append(
            DecLayerState(
                self_kv=attn_lib.init_cache(cfg, batch, capacity, dtype),
                cross_kv=attn_lib.init_cache(cfg, batch, cfg.enc_seq_len, dtype),
            )
        )
    return {"layers": jax.tree.map(lambda *xs: jnp.stack(xs), *layers)}

"""Core neural-net layers (pure JAX, pytree params).

Every layer is a pair of functions:

    init_<layer>(key, cfg, ...) -> params (nested dict of jnp arrays)
    <layer>(params, x, ...)     -> output

Parameters are plain dicts so the federated/aggregation/checkpoint layers can
treat everything uniformly as pytrees.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.sharding import constrain, residual_spec


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype=jnp.float32, scale: float | None = None):
    """Truncated-normal fan-in init (the default for all projections)."""
    fan_in = shape[0] if len(shape) >= 2 else max(shape[-1], 1)
    std = scale if scale is not None else fan_in**-0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


def init_layernorm(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    out = x * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return out.astype(dtype)


def init_norm(cfg, d: int, dtype=jnp.float32):
    return init_layernorm(d, dtype) if cfg.norm == "layernorm" else init_rmsnorm(d, dtype)


def norm(cfg, params, x):
    if cfg.norm == "layernorm":
        return layernorm(params, x)
    return rmsnorm(params, x, cfg.norm_eps)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeGLU / GELU)
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_model: int | None = None, d_ff: int | None = None, dtype=jnp.float32):
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.act in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(k1, (d, f), dtype),
            "w_up": dense_init(k2, (d, f), dtype),
            "w_down": dense_init(k3, (f, d), dtype),
        }
    return {
        "w_up": dense_init(k2, (d, f), dtype),
        "w_down": dense_init(k3, (f, d), dtype),
    }


def mlp(cfg, params, x):
    """Position-wise MLP. Hidden activations sharded over the model axis."""
    if cfg.act in ("swiglu", "geglu"):
        gate = x @ params["w_gate"]
        up = x @ params["w_up"]
        gate = constrain(gate, ("data", None, "model"))
        act = jax.nn.silu(gate) if cfg.act == "swiglu" else jax.nn.gelu(gate)
        h = act * up
    else:
        h = jax.nn.gelu(x @ params["w_up"])
        h = constrain(h, ("data", None, "model"))
    out = h @ params["w_down"]
    return constrain(out, residual_spec(cfg))


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype=jnp.float32):
    return {"table": embed_init(key, (vocab, d), dtype)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x, table=None):
    """Project back to vocab. ``table`` overrides for tied embeddings."""
    t = table if table is not None else params["table"]
    return x @ t.T.astype(x.dtype)


def init_learned_pos(key, max_len: int, d: int, dtype=jnp.float32):
    return {"pos": embed_init(key, (max_len, d), dtype)}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def lm_loss(logits, labels, mask):
    """Masked next-token cross entropy.

    logits: (B, S, V) — already shifted (logits[t] predicts labels[t]).
    labels: (B, S) int32.
    mask:   (B, S) {0,1} — 1 on supervised (answer) positions.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_lm_loss(hidden, table, labels, mask, *, chunk: int):
    """Blockwise fused unembed + masked CE (never materializes (B, S, V)).

    hidden (B, S, D); table (V, D); labels/mask (B, S). Scans over sequence
    chunks with a rematerialized body, so the live working set is
    (B, chunk, V) in fp32 — the memory-term optimization for the train
    shapes (see EXPERIMENTS.md §Perf).
    """
    B, S, D = hidden.shape
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    Sp = hidden.shape[1]
    nc = Sp // chunk
    hs = jnp.moveaxis(hidden.reshape(B, nc, chunk, D), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
    ms = jnp.moveaxis(mask.reshape(B, nc, chunk), 1, 0)

    @jax.checkpoint
    def body(carry, inp):
        h, lab, m = inp
        lg = (h @ table.T.astype(h.dtype)).astype(jnp.float32)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lab[..., None], axis=-1)[..., 0]
        nll = jnp.sum((logz - gold) * m)
        return (carry[0] + nll, carry[1] + jnp.sum(m)), None

    (total, denom), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ls, ms))
    return total / jnp.maximum(denom, 1.0)


def token_accuracy(logits, labels, mask):
    pred = jnp.argmax(logits, axis=-1)
    correct = (pred == labels) * mask
    return jnp.sum(correct) / jnp.maximum(jnp.sum(mask), 1.0)

"""Backbone facade — one uniform API over all six architecture families.

The *backbone* is the server-side frozen model of FedNano: token embedding,
connector, layer stack, final norm, unembedding. NanoEdge (client-side
encoders + adapters) lives in ``repro.core`` and feeds this module
**embeddings**, never raw tokens — mirroring the split-learning interface.

API (module-level functions, ``cfg`` first):
    init_backbone(key, cfg)                      -> params
    embed_tokens(cfg, params, tokens)            -> (B, S, D)
    connect(cfg, params, feats)                  -> (B, M, D)   connector
    forward(cfg, params, embeds, positions, enc_embeds=None) -> (hidden, aux)
    logits(cfg, params, hidden)                  -> (B, S, V)
    prefill(cfg, params, embeds, positions, capacity, enc_embeds=None)
    decode_step(cfg, params, embed, state, pos)  -> (logits, state)
    decode_pages(cfg, params, embed, pool, pos)  -> (logits, pool)
    init_state(cfg, batch, capacity, dtype)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models.layers import (
    dense_init,
    init_embedding,
    init_learned_pos,
    init_norm,
    norm,
    unembed,
)
from repro.models.rotary import make_angles
from repro.sharding import constrain


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def init_backbone(key, cfg, sharding=None):
    """Random backbone weights from ``key``, built under ``jax.jit``.

    Eager init would materialize each stacked weight's float32 draw (and
    the truncated-normal intermediates) before the cast: at llava-1.5-7b
    width one MLP stack is a (32, 4096, 11008) f32 temporary of 5.4 GiB.
    Jitted, only the cast weights are written. ``sharding`` places the
    output (e.g. replicated over a client mesh) without a second copy.
    """
    return jax.jit(_init_backbone, static_argnums=1,
                   out_shardings=sharding)(key, cfg)


def _init_backbone(key, cfg):
    dtype = param_dtype(cfg)
    keys = jax.random.split(key, 8)
    params = {
        "embed": init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_norm(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = init_embedding(keys[1], cfg.vocab_size, cfg.d_model, dtype)
    if cfg.pos_type == "learned":
        params["pos"] = init_learned_pos(keys[2], cfg.max_seq_len, cfg.d_model, dtype)
    if cfg.frontend_dim:
        params["connector"] = {
            "w": dense_init(keys[3], (cfg.frontend_dim, cfg.d_model), dtype),
            "b": jnp.zeros((cfg.d_model,), dtype),
        }
    if cfg.family == "audio":
        params.update(encdec.init_encdec_stacks(keys[4], cfg, dtype))
        params["enc_pos"] = init_learned_pos(keys[5], cfg.enc_seq_len, cfg.d_model, dtype)
        params["enc_final_norm"] = init_norm(cfg, cfg.d_model, dtype)
    else:
        params.update(transformer.init_stack(keys[4], cfg, dtype))
    return params


def embed_tokens(cfg, params, tokens):
    emb = jnp.take(params["embed"]["table"], tokens, axis=0)
    return constrain(emb, ("data", None, None))


def connect(cfg, params, feats):
    """Frozen modality connector: (B, M, frontend_dim) -> (B, M, D)."""
    c = params["connector"]
    return feats.astype(c["w"].dtype) @ c["w"] + c["b"]


def _add_learned_pos(cfg, params, x, positions):
    if cfg.pos_type != "learned":
        return x
    pos_emb = jnp.take(params["pos"]["pos"], positions, axis=0)  # (B, S, D)
    return x + pos_emb.astype(x.dtype)


def _encode_memory(cfg, params, enc_embeds):
    """Whisper encoder over connected frame embeddings (B, M, D)."""
    m = enc_embeds.shape[1]
    pos = jnp.arange(m)
    mem = enc_embeds + params["enc_pos"]["pos"][pos][None].astype(enc_embeds.dtype)
    mem = encdec.encode(cfg, params, mem)
    return norm(cfg, params["enc_final_norm"], mem)


def forward(cfg, params, embeds, positions, enc_embeds: Optional[jax.Array] = None):
    """Full-sequence causal forward.

    embeds (B, S, D) — adapter-processed input embeddings.
    positions (B, S) int32 (or (3, B, S) for mrope).
    enc_embeds (B, M, D) — connected frame embeddings (audio family only).
    Returns (hidden (B, S, D), aux scalar).
    """
    x = _add_learned_pos(cfg, params, embeds, positions if positions.ndim == 2 else positions[0])
    angles = make_angles(cfg, positions)
    if cfg.family == "audio":
        memory = _encode_memory(cfg, params, enc_embeds)
        x, aux = encdec.decode_forward(cfg, params, x, memory)
    else:
        x, aux = transformer.forward_stack(cfg, params, x, angles)
    return norm(cfg, params["final_norm"], x), aux


def logits(cfg, params, hidden):
    table = params["embed"]["table"] if cfg.tie_embeddings else params["unembed"]["table"]
    out = unembed({"table": table}, hidden)
    return constrain(out, ("data", None, "model"))


def loss_fn(cfg, params, embeds, positions, labels, mask, enc_embeds=None):
    from repro.models.layers import chunked_lm_loss, lm_loss

    hidden, aux = forward(cfg, params, embeds, positions, enc_embeds)
    if cfg.loss_chunk is not None and hidden.shape[1] > cfg.loss_chunk:
        table = params["embed"]["table"] if cfg.tie_embeddings else params["unembed"]["table"]
        return chunked_lm_loss(hidden, table, labels, mask, chunk=cfg.loss_chunk), aux
    lg = logits(cfg, params, hidden)
    return lm_loss(lg, labels, mask), aux


def prefill(cfg, params, embeds, positions, capacity: int, enc_embeds=None,
            length=None):
    """Returns (state, hidden) — state is the stacked decode state.

    ``length`` (scalar int32, optional): number of real positions when the
    sequence is right-padded; only recurrent families consume it (their
    terminal state must not integrate pad steps). Attention/enc-dec caches
    are position-masked and ignore it.
    """
    x = _add_learned_pos(cfg, params, embeds, positions if positions.ndim == 2 else positions[0])
    angles = make_angles(cfg, positions)
    if cfg.family == "audio":
        memory = _encode_memory(cfg, params, enc_embeds)
        x, state = encdec.dec_prefill(cfg, params, x, memory, capacity)
    else:
        x, state = transformer.prefill_stack(cfg, params, x, angles, capacity,
                                             length=length)
    return state, norm(cfg, params["final_norm"], x)


def decode_step(cfg, params, embed, state, pos):
    """One-token decode. embed (B, 1, D); pos scalar int32.

    Returns (logits (B, 1, V), new state).
    """
    b = embed.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    x = _add_learned_pos(cfg, params, embed, positions)
    angles = make_angles(cfg, positions)
    if cfg.family == "audio":
        x, state = encdec.dec_step(cfg, params, x, state, pos)
    else:
        x, state = transformer.decode_stack(cfg, params, x, angles, state, pos)
    hidden = norm(cfg, params["final_norm"], x)
    return logits(cfg, params, hidden), state


def decode_pages(cfg, params, embed, pool, pos):
    """One-token decode of every page of the serving engine's pool.

    embed (P, 1, D), one token per page; pool: the stacked decode state of
    P pages (``init_state(cfg, P, ...)``); pos (P,) int32, each page's
    position, -1 on a free page. Each page decodes as ``decode_step`` would
    decode it alone; the pool's KV is written one row per live page per
    layer, and a free page's KV is left as it is.

    Returns (logits (P, 1, V), pool).
    """
    positions = jnp.maximum(pos, 0)[:, None].astype(jnp.int32)
    x = _add_learned_pos(cfg, params, embed, positions)
    angles = make_angles(cfg, positions)
    if cfg.family == "audio":
        x, pool = encdec.dec_pages_step(cfg, params, x, pool, pos)
    else:
        x, pool = transformer.decode_pages_stack(cfg, params, x, angles, pool, pos)
    hidden = norm(cfg, params["final_norm"], x)
    return logits(cfg, params, hidden), pool


def init_state(cfg, batch: int, capacity: int, dtype):
    if cfg.family == "audio":
        return encdec.init_dec_state(cfg, batch, capacity, dtype)
    return transformer.init_decode_state(cfg, batch, capacity, dtype)

"""NanoEdge & NanoAdapters — the paper's client-side module (§3.3).

A *NanoAdapter* is a low-rank residual map at the connector→LLM interface:

    y = x + (alpha / rank) · (x · W_down) · W_up

with ``W_up`` zero-initialized (LoRA convention: the adapter is an exact
identity at round 0, preserving the pretrained multimodal alignment). One
adapter per modality: 𝒜_T on text token embeddings, 𝒜_I on connected
image/frame embeddings. They attach **outside** the backbone — the client
never executes or introspects the LLM (DESIGN.md §1).

*NanoEdge* = frozen modality encoder (stub) + frozen connector + frozen token
embedder + trainable NanoAdapters. Only the adapters are trainable/uploaded.

``nanoedge_forward`` assembles backbone-ready embeddings from a Batch — this
is the client half of the split execution; the returned arrays are exactly
the activations that cross the client→server wire in a real deployment.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.types import Batch
from repro.models import model as model_lib
from repro.models.layers import dense_init
from repro.sharding import constrain


# ---------------------------------------------------------------------------
# NanoAdapter
# ---------------------------------------------------------------------------

def init_nano_adapter(key, d_model: int, rank: int, dtype=jnp.float32):
    """LoRA-style pair; up-projection zero-init => identity at init."""
    return {
        "down": dense_init(key, (d_model, rank), dtype),
        "up": jnp.zeros((rank, d_model), dtype),
    }


def nano_adapter_apply(params, x, *, rank: int, alpha: float, use_pallas: bool = False):
    """y = x + (alpha/rank) · (x·down)·up."""
    scale = alpha / rank
    if use_pallas:
        from repro.kernels.lora import ops as lora_ops

        return lora_ops.lora_residual(x, params["down"], params["up"], scale=scale)
    # compute in the activation dtype (bf16 on the mesh): fp32 master weights
    # are cast at use so no fp32 activation ever crosses a collective
    # (EXPERIMENTS.md §Perf glm4/train iteration 3); grads still flow to the
    # fp32 masters through the cast.
    h = x @ params["down"].astype(x.dtype)
    h = constrain(h, ("data", None, None))
    return x + (h @ params["up"].astype(x.dtype)) * scale


# ---------------------------------------------------------------------------
# NanoEdge (trainable part: the adapter dict)
# ---------------------------------------------------------------------------

def init_nanoedge(key, cfg) -> Dict:
    """Trainable NanoAdapter params, one entry per configured modality."""
    acfg = cfg.adapter
    dtype = jnp.dtype(acfg.dtype)
    keys = jax.random.split(key, len(acfg.modalities))
    return {
        mod: init_nano_adapter(k, cfg.d_model, acfg.rank, dtype)
        for mod, k in zip(acfg.modalities, keys)
    }


def adapter_param_count(cfg) -> int:
    return len(cfg.adapter.modalities) * 2 * cfg.d_model * cfg.adapter.rank


def nanoedge_forward(
    cfg, backbone, adapters, batch: Batch
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, Optional[jax.Array]]:
    """Client-side compute: embed + connect + adapt.

    Returns (embeds, positions, labels, mask, enc_embeds):
      embeds     (B, S_total, D) — what the client ships to the server
      positions  (B, S_total) int32
      labels/mask aligned with embeds (image prefix unsupervised)
      enc_embeds (B, M, D) or None — audio-family encoder stream
    """
    acfg = cfg.adapter
    kw = dict(rank=acfg.rank, alpha=acfg.alpha, use_pallas=cfg.use_pallas)

    tok_emb = model_lib.embed_tokens(cfg, backbone, batch.tokens)
    if "text" in adapters:
        tok_emb = nano_adapter_apply(adapters["text"], tok_emb, **kw)

    B, S = batch.tokens.shape

    if cfg.family == "audio":
        enc = model_lib.connect(cfg, backbone, batch.patches)
        if "image" in adapters:
            enc = nano_adapter_apply(adapters["image"], enc, **kw)
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        return tok_emb, positions, batch.labels, batch.mask, enc

    if cfg.frontend_dim and batch.patches is not None:
        img = model_lib.connect(cfg, backbone, batch.patches)
        if "image" in adapters:
            img = nano_adapter_apply(adapters["image"], img, **kw)
        M = img.shape[1]
        embeds = jnp.concatenate([img.astype(tok_emb.dtype), tok_emb], axis=1)
        positions = jnp.broadcast_to(jnp.arange(M + S, dtype=jnp.int32), (B, M + S))
        pad_lab = jnp.zeros((B, M), batch.labels.dtype)
        pad_mask = jnp.zeros((B, M), batch.mask.dtype)
        labels = jnp.concatenate([pad_lab, batch.labels], axis=1)
        mask = jnp.concatenate([pad_mask, batch.mask], axis=1)
        return embeds, positions, labels, mask, None

    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return tok_emb, positions, batch.labels, batch.mask, None


def fednano_loss(cfg, backbone, adapters, batch: Batch):
    """End-to-end FedNano loss: client NanoEdge -> frozen server backbone.

    Differentiate w.r.t. ``adapters`` only — the backbone is frozen by
    construction (it is a closed-over constant for the gradient).
    """
    embeds, positions, labels, mask, enc = nanoedge_forward(cfg, backbone, adapters, batch)
    loss, aux = model_lib.loss_fn(cfg, backbone, embeds, positions, labels, mask, enc)
    return loss, aux

"""Rotary position embeddings: standard RoPE and qwen2-vl M-RoPE.

M-RoPE (arXiv:2409.12191 §2.1): the head_dim/2 frequency slots are split into
``sections = (t, h, w)`` groups; each group reads a different component of a
3-component position id. Text tokens carry identical (t, h, w) components, so
M-RoPE degenerates to RoPE on text — which our stubbed-frontend dry-run uses —
but the section plumbing is real and exercised by tests with distinct (t,h,w).
"""
from __future__ import annotations

import jax.numpy as jnp


def rope_freqs(head_dim: int, theta: float):
    """(head_dim/2,) inverse frequencies."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def rope_angles(positions, head_dim: int, theta: float):
    """positions (..., S) int32 -> angles (..., S, head_dim/2) f32."""
    inv = rope_freqs(head_dim, theta)
    return positions[..., None].astype(jnp.float32) * inv


def mrope_angles(positions3, sections, head_dim: int, theta: float):
    """positions3 (3, B, S) -> angles (B, S, head_dim/2).

    Frequency slot i uses position component c(i) given by ``sections``:
    the first ``sections[0]`` slots read the temporal component, the next
    ``sections[1]`` the height component, the last ``sections[2]`` the width.
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    inv = rope_freqs(head_dim, theta)  # (half,)
    sel = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=head_dim // 2
    )  # (half,) in {0,1,2}
    # gather the right component per slot: (B, S, half)
    pos = jnp.take(positions3, sel, axis=0)          # (half, B, S) -> wrong order
    pos = jnp.moveaxis(pos, 0, -1)                    # (B, S, half)
    return pos.astype(jnp.float32) * inv


def apply_rotary(x, angles):
    """x (..., S, H, D), angles (..., S, D/2) -> rotated x (same dtype).

    Uses the "rotate halves" convention (llama-style): the first D/2 dims
    pair with the last D/2. cos/sin are computed in fp32 then cast to the
    activation dtype: the rotation itself runs in bf16 (standard practice —
    orthogonal map, error ~1 ulp) so no fp32 activations leak into the
    attention dgrad collectives (EXPERIMENTS.md §Perf).
    """
    half = x.shape[-1] // 2
    cos = jnp.cos(angles)[..., None, :].astype(x.dtype)  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :].astype(x.dtype)
    x1 = x[..., :half]
    x2 = x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def make_angles(cfg, positions):
    """Dispatch on cfg.pos_type.

    positions: (B, S) int32 for rope; (3, B, S) for mrope. Returns
    (B, S, rotary_dim/2) angles, or None for learned/none position types.
    The rotary dims are the head's, or latent attention's rope dims.
    """
    hd = cfg.mla.qk_rope_head_dim if cfg.mla is not None else cfg.resolved_head_dim
    if cfg.pos_type == "rope":
        return rope_angles(positions, hd, cfg.rope_theta)
    if cfg.pos_type == "mrope":
        if positions.ndim == 2:  # text-only stream: broadcast to 3 equal components
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return mrope_angles(positions, cfg.mrope_sections, hd, cfg.rope_theta)
    return None

"""Prefill + decode_step must reproduce the teacher-forced forward pass.

Covers every sequence-mixing mechanism: SWA ring buffer (danube), GQA cache,
SSM state (mamba2), RG-LRU + local attention (recurrentgemma), enc-dec cross
attention (whisper), M-RoPE (qwen2-vl).
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.models import vision_stub

ARCHS = [
    "h2o-danube-1.8b",
    "qwen1.5-4b",
    "recurrentgemma-9b",
    "qwen2-vl-72b",
    "mamba2-130m",
    "glm4-9b",
    "whisper-base",
    "internlm2-20b",
]


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch, rng):
    cfg = get_smoke_config(arch)
    params = M.init_backbone(rng, cfg)
    B, S = 2, 16
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    emb = M.embed_tokens(cfg, params, toks)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    enc = None
    if cfg.family == "audio":
        feats = vision_stub.patch_embeddings(rng, cfg, B)
        enc = M.connect(cfg, params, feats)
    hidden, _ = M.forward(cfg, params, emb, pos, enc)
    want = M.logits(cfg, params, hidden)

    # prefill on the first half, then decode the second half token by token
    half = S // 2
    state, _ = M.prefill(cfg, params, emb[:, :half], pos[:, :half], capacity=S, enc_embeds=enc)
    for t in range(half, S):
        got, state = M.decode_step(cfg, params, emb[:, t : t + 1], state, jnp.int32(t))
        err = float(jnp.max(jnp.abs(got[:, 0] - want[:, t])))
        assert err < 5e-4, f"{arch}: step {t} logits diverge by {err}"


@pytest.mark.parametrize("arch", ["grok-1-314b", "llama4-scout-17b-a16e"])
def test_moe_decode_matches_forward_without_drops(arch, rng):
    """The expert layer is dropless, so a token's experts do not depend on
    the batch it is decoded in: decode must agree with teacher forcing."""
    cfg = get_smoke_config(arch)
    params = M.init_backbone(rng, cfg)
    B, S = 2, 12
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    emb = M.embed_tokens(cfg, params, toks)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    hidden, _ = M.forward(cfg, params, emb, pos)
    want = M.logits(cfg, params, hidden)
    state, _ = M.prefill(cfg, params, emb[:, : S - 1], pos[:, : S - 1], capacity=S)
    got, _ = M.decode_step(cfg, params, emb[:, S - 1 : S], state, jnp.int32(S - 1))
    err = float(jnp.max(jnp.abs(got[:, 0] - want[:, -1])))
    assert err < 5e-4, err


def test_swa_ring_buffer_long_decode(rng):
    """Decode far past the window: ring cache must equal full-cache attention."""
    cfg = get_smoke_config("h2o-danube-1.8b")  # window 64 in smoke
    w = cfg.sliding_window
    params = M.init_backbone(rng, cfg)
    B, S = 1, 2 * w + 8  # well past one wrap
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    emb = M.embed_tokens(cfg, params, toks)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    hidden, _ = M.forward(cfg, params, emb, pos)
    want = M.logits(cfg, params, hidden)

    half = w + 4  # prefill longer than the window: seeds must roll correctly
    state, _ = M.prefill(cfg, params, emb[:, :half], pos[:, :half], capacity=S)
    for t in range(half, S):
        got, state = M.decode_step(cfg, params, emb[:, t : t + 1], state, jnp.int32(t))
        err = float(jnp.max(jnp.abs(got[:, 0] - want[:, t])))
        assert err < 5e-4, f"ring decode diverges at t={t}: {err}"


def test_mrope_distinct_positions(rng):
    """M-RoPE with distinct (t, h, w) components must differ from plain RoPE
    and preserve shapes (exercises the section plumbing)."""
    from repro.models.rotary import make_angles

    cfg = get_smoke_config("qwen2-vl-72b")
    B, S = 2, 8
    text_pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    a_text = make_angles(cfg, text_pos)
    pos3 = jnp.stack([text_pos, text_pos * 2, text_pos * 3])
    a_img = make_angles(cfg, pos3)
    assert a_text.shape == a_img.shape
    assert float(jnp.max(jnp.abs(a_text - a_img))) > 1e-3

"""Latent attention, the dropless expert layer and the layer stack of an
MLA + MoE config (kimi-vl-a3b's smoke cut), against plain float32 code.

Every comparison runs at ``highest`` matmul precision on seeded random
weights, with the program in float32: the program and the plain code must
agree to float32 rounding.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import attention, moe, transformer
from repro.models import model as model_lib
from repro.models.layers import mlp

ARCH = "kimi-vl-a3b"


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _cfg(**kw):
    return get_smoke_config(ARCH).with_(**kw)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * max(1.0, np.abs(want).max()))


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------

def _plain_mla(cfg, p, x):
    """MLA written out: up-projected per-head k/v, full scores, softmax."""
    m, (b, s, _), nh = cfg.mla, x.shape, cfg.n_heads
    nope, rope = m.qk_nope_head_dim, m.qk_rope_head_dim
    half = rope // 2
    inv = 1.0 / cfg.rope_theta ** (np.arange(half) / half)
    ang = np.arange(s)[:, None] * inv

    def rot(t):
        c, sn = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        return jnp.concatenate([t[..., :half] * c - t[..., half:] * sn,
                                t[..., half:] * c + t[..., :half] * sn], -1)

    q = (x @ p["wq"]).reshape(b, s, nh, nope + rope)
    kv = x @ p["wkv_a"]
    c = kv[..., :m.kv_lora_rank]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + cfg.norm_eps) * p["kv_norm"]["scale"]
    up = (c @ p["wkv_b"]).reshape(b, s, nh, nope + m.v_head_dim)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(rot(kv[..., None, m.kv_lora_rank:]),
                                          (b, s, nh, rope))], -1)
    q = jnp.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + rope)
    sc = jnp.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), up[..., nope:])
    return o.reshape(b, s, -1) @ p["wo"]


@pytest.mark.parametrize("use_pallas", [False, True])
def test_mla_layer_matches_the_plain_reference(use_pallas):
    cfg = _cfg(use_pallas=use_pallas)
    key = jax.random.PRNGKey(1)
    p = attention.init_attention(key, cfg)
    p["kv_norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(key, p["kv_norm"]["scale"].shape)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 19, cfg.d_model))
    angles = model_lib.make_angles(cfg, jnp.broadcast_to(jnp.arange(19), (2, 19)))
    got = attention.full_attention(cfg, p, x, angles)
    _close(got, _plain_mla(cfg, p, x))
    g = jax.grad(lambda y: jnp.sum(attention.full_attention(cfg, p, y, angles) ** 2))(x)
    gr = jax.grad(lambda y: jnp.sum(_plain_mla(cfg, p, y) ** 2))(x)
    _close(g, gr, 1e-4)


def test_mla_has_no_cache_yet():
    cfg = _cfg()
    p = attention.init_attention(jax.random.PRNGKey(0), cfg)
    x = jnp.ones((1, 4, cfg.d_model))
    with pytest.raises(NotImplementedError, match="R2"):
        attention.full_attention(cfg, p, x, None, return_kv=True)


def test_the_serving_engine_refuses_a_latent_attention_config():
    from repro.serving import ServingEngine

    with pytest.raises(ValueError, match="latent page pool|compressed-latent page pool"):
        ServingEngine(_cfg(), None, max_slots=1)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def _params(cfg, key, bias=None):
    p = moe.init_moe(key, cfg)
    p["bias"] = (0.2 * jax.random.normal(jax.random.fold_in(key, 9), p["bias"].shape)
                 if bias is None else jnp.asarray(bias, jnp.float32))
    return p


def _plain_route(mcfg, p, t):
    s = jax.nn.sigmoid(t @ p["router"])
    idx = np.argsort(-(np.asarray(s) + np.asarray(p["bias"])), axis=-1, kind="stable")
    idx = idx[:, :mcfg.top_k]
    w = np.take_along_axis(np.asarray(s), idx, -1)
    return idx, w / w.sum(-1, keepdims=True) * mcfg.routed_scale


def _per_expert_loop(cfg, p, x, first=None, held=None):
    """The routed part over experts [first, first + held), one expert at a
    time on every token, masked by its picks."""
    mcfg = cfg.moe
    first = mcfg.expert_first if first is None else first
    held = mcfg.held if held is None else held
    t = x.reshape(-1, x.shape[-1])
    idx, w = _plain_route(mcfg, p, t)
    y = jnp.zeros_like(t)
    for j in range(held):
        e = first + j
        sel = jnp.asarray(np.where(idx == e, w, 0.0).sum(-1))
        h = jax.nn.silu(t @ p["w_gate"][j]) * (t @ p["w_up"][j])
        y = y + sel[:, None] * (h @ p["w_down"][j])
    return y.reshape(x.shape)


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("routing", ["mixed", "one-takes-all-one-gets-none"])
def test_dropless_layer_matches_a_per_expert_loop(use_pallas, routing):
    """Forward and input gradient; with a bias that sends every token to
    expert 0 and none to expert 3, and with mixed routing."""
    cfg = _cfg(use_pallas=use_pallas)
    n_e = cfg.moe.n_experts
    bias = None if routing == "mixed" else [10.0] + [0.0] * (n_e - 2) + [-10.0]
    key = jax.random.PRNGKey(2)
    p = _params(cfg, key, bias)
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, 7, cfg.d_model))
    y, aux = moe.moe_apply(cfg, p, x)
    shared = mlp(cfg, p["shared"], x)
    _close(y - shared, _per_expert_loop(cfg, p, x))
    counts = np.asarray(aux["tokens"])
    if routing != "mixed":
        assert counts[0] == 14 and counts[-1] == 0
    assert counts.sum() == 14 * cfg.moe.top_k
    loss = lambda f: (lambda z: jnp.sum(jnp.sin(f(z))))
    g = jax.grad(loss(lambda z: moe.moe_apply(cfg, p, z)[0] - mlp(cfg, p["shared"], z)))(x)
    gr = jax.grad(loss(lambda z: _per_expert_loop_differentiable(cfg, p, z)))(x)
    _close(g, gr, 1e-4)


def _per_expert_loop_differentiable(cfg, p, x):
    """The loop with the routing weights differentiable in x (the picks
    fixed by the plain routing)."""
    mcfg = cfg.moe
    t = x.reshape(-1, x.shape[-1])
    idx, _ = _plain_route(mcfg, p, jax.lax.stop_gradient(t))
    s = jax.nn.sigmoid(t @ p["router"])
    w = jnp.take_along_axis(s, jnp.asarray(idx), -1)
    w = w / jnp.sum(w, -1, keepdims=True) * mcfg.routed_scale
    y = jnp.zeros_like(t)
    for e in range(mcfg.held):
        sel = jnp.sum(jnp.where(jnp.asarray(idx) == e, w, 0.0), -1)
        h = jax.nn.silu(t @ p["w_gate"][e]) * (t @ p["w_up"][e])
        y = y + sel[:, None] * (h @ p["w_down"][e])
    return y.reshape(x.shape)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips, each holding a quarter of the experts: their routed parts,
    with the shared experts counted once, give the uncut layer."""
    base = _cfg()
    cfg = base.with_(moe=dataclasses.replace(base.moe, n_experts=8, top_k=3))
    key = jax.random.PRNGKey(4)
    whole = _params(cfg, key)
    x = jax.random.normal(jax.random.fold_in(key, 5), (3, 5, cfg.d_model))
    uncut, _ = moe.moe_apply(cfg, whole, x)
    shared = mlp(cfg, whole["shared"], x)
    parts = []
    for first in range(0, 8, 2):
        share = cfg.with_(moe={"expert_first": first, "experts_held": 2})
        p = dict(whole, **{k: whole[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")})
        parts.append(moe.moe_apply(share, p, x)[0] - shared)
    _close(sum(parts) + shared, uncut)
    _close(uncut - shared, _per_expert_loop(cfg, whole, x, first=0, held=8))


def test_sigmoid_bias_routing_picks_by_biased_score_and_weighs_by_score():
    cfg = _cfg()
    mcfg = cfg.moe
    key = jax.random.PRNGKey(6)
    p = _params(cfg, key)
    t = jax.random.normal(key, (11, cfg.d_model))
    idx, w, scores = moe.route(mcfg, p, t)
    s = np.asarray(jax.nn.sigmoid(t @ p["router"]))
    np.testing.assert_allclose(np.asarray(scores), s, rtol=1e-6)
    b = np.asarray(p["bias"])
    for i in range(11):
        want = set(np.argsort(-(s[i] + b))[:mcfg.top_k])
        assert set(np.asarray(idx[i])) == want
        sel = s[i][np.asarray(idx[i])]
        np.testing.assert_allclose(np.asarray(w[i]), sel / sel.sum() * mcfg.routed_scale,
                                   rtol=1e-5)
    # the bias moves the choice, not the weights: without it another set wins
    idx0, _, _ = moe.route(mcfg, dict(p, bias=jnp.zeros_like(p["bias"])), t)
    assert not np.array_equal(np.sort(np.asarray(idx0)), np.sort(np.asarray(idx)))


def test_vmap_folds_the_cohort_into_one_dispatch():
    """Under vmap over clients the grouped matmuls and the sort see every
    client's token copies at once: no mapped axis is left on them."""
    cfg = _cfg(use_pallas=True)
    p = _params(cfg, jax.random.PRNGKey(7))
    x = jnp.ones((5, 2, 3, cfg.d_model))
    jaxpr = jax.make_jaxpr(jax.vmap(lambda z: moe.moe_apply(cfg, p, z)[0]))(x)
    rows = 5 * 2 * 3 * cfg.moe.top_k
    text = str(jaxpr)
    assert "pallas_call" in text
    assert f"[{rows},{cfg.d_model}]" in text.replace(" ", "")
    got = jax.vmap(lambda z: moe.moe_apply(cfg, p, z)[0])(x)
    _close(got, jnp.stack([moe.moe_apply(cfg, p, z)[0] for z in x]))


# ---------------------------------------------------------------------------
# the layer stack
# ---------------------------------------------------------------------------

def test_leading_dense_layer_then_scanned_moe_stack_matches_layer_by_layer():
    cfg = _cfg(remat=True)
    params = model_lib.init_backbone(jax.random.PRNGKey(8), cfg)
    assert jax.tree.leaves(params["lead"])[0].shape[0] == cfg.n_dense_layers == 1
    assert jax.tree.leaves(params["layers"])[0].shape[0] == cfg.n_layers - 1
    assert params["lead"]["mlp"]["w_gate"].shape[-1] == cfg.dense_d_ff
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 9, cfg.d_model))
    angles = model_lib.make_angles(cfg, jnp.broadcast_to(jnp.arange(9), (2, 9)))
    got, _ = transformer.forward_stack(cfg, params, x, angles)
    want = transformer.dense_body(cfg, jax.tree.map(lambda a: a[0], params["lead"]),
                                  x, angles)[0]
    for i in range(cfg.n_layers - 1):
        lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        want = transformer.moe_body(cfg, lp, want, angles)[0]
    _close(got, want)


def test_a_stack_without_leading_layers_has_no_lead():
    cfg = get_smoke_config("llama4-scout-17b-a16e")
    params = jax.eval_shape(lambda k: model_lib._init_backbone(k, cfg), jax.random.PRNGKey(0))
    assert "lead" not in params
    assert jax.tree.leaves(params["layers"])[0].shape[0] == cfg.n_layers

"""Serving engine: grouped adapters, paged KV slots, continuous batching.

The engine's contract is EXACTNESS under batching: for any mix of tenants,
prompt lengths, and token budgets it must emit byte-identical token streams
to the naive one-request-at-a-time loop (``generate_naive`` — the shape of
the pre-engine ``launch/serve.py``, un-jitted per-token adapter apply and
all). Goldens in tests/golden/serve_tokens.json pin the streams themselves
against silent drift of both paths.
"""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import adapters as nano
from repro.models import model as model_lib
from repro.models.attention import KVCache
from repro.models.vision_stub import num_patches
from repro.serving import (
    AdapterBank,
    AdapterCache,
    AdapterCacheMiss,
    KVSlotManager,
    Request,
    ServingEngine,
    checkpoint_adapter_loader,
    generate_naive,
)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "serve_tokens.json")

# vlm / sliding-window dense / ssm / hybrid (rg-lru + local attn) / enc-dec
ARCHS = ["llava-1.5-7b", "h2o-danube-1.8b", "mamba2-130m",
         "recurrentgemma-9b", "whisper-base"]


@functools.lru_cache(maxsize=8)
def _setup(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    backbone = model_lib.init_backbone(key, cfg)
    tenants = {}
    for i, t in enumerate(["alpha", "beta"]):
        ad = nano.init_nanoedge(jax.random.fold_in(key, 100 + i), cfg)
        ad = jax.tree.map(
            lambda a, j=i: jax.random.normal(
                jax.random.fold_in(key, 200 + 17 * j + a.size % 91),
                a.shape, a.dtype) * 0.05,
            ad)
        tenants[t] = ad
    return cfg, backbone, tenants


def _requests(cfg, spec):
    """spec: [(tenant, prompt_len, max_new_tokens), ...] — deterministic."""
    rng = np.random.default_rng(7)
    m = num_patches(cfg) if cfg.frontend_dim else 0
    reqs = []
    for i, (tn, L, mnt) in enumerate(spec):
        patches = (rng.standard_normal((m, cfg.frontend_dim)).astype(np.float32)
                   if cfg.frontend_dim else None)
        reqs.append(Request(
            rid=i, tenant=tn,
            prompt=rng.integers(0, cfg.vocab_size, L).astype(np.int32),
            patches=patches, max_new_tokens=mnt))
    return reqs


MIXED_SPEC = [("alpha", 5, 6), ("beta", 9, 4), (None, 3, 5),
              ("alpha", 12, 3), ("beta", 7, 6)]


# ---------------------------------------------------------------------------
# exactness: engine == naive loop, token for token
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS)
def test_engine_matches_naive(arch):
    cfg, backbone, tenants = _setup(arch)
    reqs = _requests(cfg, MIXED_SPEC)
    eng = ServingEngine(cfg, backbone, max_slots=3, prefill_len=12,
                        max_new_tokens=8, adapter_loader=tenants.__getitem__)
    got = eng.run(reqs)
    ref = generate_naive(cfg, backbone, reqs, tenants)
    for r in reqs:
        assert got[r.rid].tokens == ref[r.rid].tokens, (
            f"{arch} rid={r.rid}: engine {got[r.rid].tokens} != "
            f"naive {ref[r.rid].tokens}")
    # the batching actually batched: >1 request per decode step on average
    assert eng.mean_occupancy() > 1.0
    # mixed-length traffic compiled exactly one prefill + one decode shape
    assert eng.stats["prefills"] == len(reqs)


@pytest.mark.parametrize("arch", ["mamba2-130m", "recurrentgemma-9b"])
def test_short_prompt_below_conv_window(arch):
    """Prompts shorter than the causal-conv window (d_conv-1 / cw-1) must
    still produce a full zero-left-extended conv state — regression for the
    truncated-tail crash in the unpadded (naive) prefill path."""
    cfg, backbone, tenants = _setup(arch)
    reqs = _requests(cfg, [("alpha", 1, 4), ("beta", 2, 4), (None, 2, 4)])
    eng = ServingEngine(cfg, backbone, max_slots=3, prefill_len=8,
                        max_new_tokens=4, adapter_loader=tenants.__getitem__)
    got = eng.run(reqs)
    ref = generate_naive(cfg, backbone, reqs, tenants)
    for r in reqs:
        assert got[r.rid].tokens == ref[r.rid].tokens


def test_engine_tokens_golden():
    """Pin the llava token streams — catches any drift of engine OR naive."""
    cfg, backbone, tenants = _setup("llava-1.5-7b")
    reqs = _requests(cfg, MIXED_SPEC)
    eng = ServingEngine(cfg, backbone, max_slots=3, prefill_len=12,
                        max_new_tokens=8, adapter_loader=tenants.__getitem__)
    got = eng.run(reqs)
    with open(GOLDEN) as f:
        want = json.load(f)["llava-1.5-7b"]
    assert {str(r.rid): got[r.rid].tokens for r in reqs} == want


def test_engine_pallas_grouped_matches_ref_path():
    """The Pallas grouped kernel inside the jitted decode step (interpret
    mode) produces the same streams as the jnp reference path."""
    cfg, backbone, tenants = _setup("h2o-danube-1.8b")
    reqs = _requests(cfg, [("alpha", 4, 4), ("beta", 6, 4), (None, 5, 4)])
    runs = {}
    for use_pallas in (False, True):
        eng = ServingEngine(cfg, backbone, max_slots=3, prefill_len=8,
                            max_new_tokens=4,
                            adapter_loader=tenants.__getitem__,
                            use_pallas_grouped=use_pallas)
        runs[use_pallas] = eng.run(reqs)
    for r in reqs:
        assert runs[True][r.rid].tokens == runs[False][r.rid].tokens


# ---------------------------------------------------------------------------
# the pool is updated in place: donated, and written one row per page
# ---------------------------------------------------------------------------

def _aliased_outputs(compiled):
    """Output indices the compiled program writes into a donated input."""
    head = compiled.as_text().split("\n", 1)[0]
    return {int(o) for o, _ in re.findall(r"\{(\d+)\}: \((\d+), \{\}", head)}


@pytest.mark.parametrize("arch", ARCHS)
def test_pool_donated_to_page_write_and_decode(arch):
    from repro.serving.kv_cache import _write_page

    cfg, backbone, tenants = _setup(arch)
    eng = ServingEngine(cfg, backbone, max_slots=2, prefill_len=8,
                        max_new_tokens=4, adapter_loader=tenants.__getitem__)
    done = {}
    for r in _requests(cfg, [("alpha", 5, 4), ("beta", 3, 4)]):
        old = eng.slots.state
        eng.submit(r)
        eng._admit(done)
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(old))
        old = eng.slots.state
        eng._step(done)
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(old))
    st = eng.stats
    assert st["pool_donations"] == st["decode_steps"] + st["prefills"] == 4

    # every pool leaf of the output is written into the donated input
    pool = eng.slots.state
    n = len(jax.tree.leaves(pool))
    page = jax.tree.map(lambda a: a[:, :1], pool)
    wp = _write_page.lower(pool, page, jnp.int32(0)).compile()
    assert _aliased_outputs(wp) == set(range(n))
    ints = jnp.zeros((2,), jnp.int32)
    dec = eng._decode_fn.lower(eng.backbone, eng.bank.data, pool, ints, ints,
                               ints - 1).compile()
    assert _aliased_outputs(dec) == set(range(1, 1 + n))   # output 0: tokens


def _kv_leaves(pool):
    """(path, k or v leaf, read-only?) of every KV cache in the pool."""
    out = []
    flat = jax.tree_util.tree_flatten_with_path(
        pool, is_leaf=lambda a: isinstance(a, KVCache))[0]
    for path, node in flat:
        if isinstance(node, KVCache):
            cross = "cross_kv" in jax.tree_util.keystr(path)
            out += [(jax.tree_util.keystr(path) + name, leaf, cross)
                    for name, leaf in (("k", node.k), ("v", node.v))]
    return out


@pytest.mark.parametrize("arch", ["llava-1.5-7b", "h2o-danube-1.8b",
                                  "recurrentgemma-9b", "whisper-base"])
def test_decode_step_writes_one_row_per_live_page_per_layer(arch):
    """Live pages at a young position, at the last slot, and (sliding-window
    ring) past the window, beside a free page: each layer's KV changes in
    exactly the row of each live page's position, to what ``decode_step``
    writes for that page decoded alone; every other row, the free page
    and the read-only cross-KV stay bit-equal."""
    cfg, backbone, _ = _setup(arch)
    eng = ServingEngine(cfg, backbone, max_slots=4, prefill_len=8,
                        max_new_tokens=64)
    key = jax.random.PRNGKey(5)
    leaves, treedef = jax.tree.flatten(eng.slots.state)
    pool = treedef.unflatten([
        jax.random.normal(jax.random.fold_in(key, i), a.shape, a.dtype)
        for i, a in enumerate(leaves)])
    kv = _kv_leaves(pool)
    C = next(leaf for _, leaf, cross in kv if not cross).shape[2]
    ring = C < eng.capacity
    assert ring == (arch in ("h2o-danube-1.8b", "recurrentgemma-9b"))
    pos = np.array([3, -1, C - 1, C + 5 if ring else 7], np.int32)
    live = [p for p in range(4) if pos[p] >= 0]
    toks = np.array([11, 12, 13, 14], np.int32)
    before = jax.tree.map(np.asarray, pool)
    pages = {p: jax.tree.map(lambda a, p=p: a[:, p:p + 1], pool) for p in live}

    nxt, after = eng._decode_fn(backbone, eng.bank.data, pool, jnp.asarray(toks),
                                jnp.asarray(pos), jnp.full((4,), -1, jnp.int32))
    refs = {}
    for p in live:
        emb = model_lib.embed_tokens(cfg, backbone, jnp.asarray(toks[p:p + 1, None]))
        lg, refs[p] = model_lib.decode_step(cfg, backbone, emb, pages[p], jnp.int32(pos[p]))
        assert int(jnp.argmax(lg[0, 0])) == int(nxt[p])
    for (name, old, cross), (_, new, _) in zip(_kv_leaves(before), _kv_leaves(after)):
        new = np.asarray(new)
        if cross:
            np.testing.assert_array_equal(new, old, err_msg=name)
            continue
        changed = np.any(new != old, axis=(3, 4))            # (L, P, C)
        want = np.zeros_like(changed)
        for p in live:
            want[:, p, pos[p] % C] = True
        np.testing.assert_array_equal(changed, want, err_msg=name)
        for p in live:
            ref = dict((n, leaf) for n, leaf, _ in _kv_leaves(refs[p]))[name]
            np.testing.assert_allclose(new[:, p, pos[p] % C],
                                       np.asarray(ref)[:, 0, pos[p] % C],
                                       rtol=1e-5, atol=1e-5, err_msg=name)


def test_moe_pages_do_not_share_expert_capacity():
    """Eight pages on the same token pick the same expert. Tokens of one
    batch share an expert's capacity, so decoded as one batch half of them
    would be dropped; each page must decode as its request would alone."""
    cfg, backbone, _ = _setup("llama4-scout-17b-a16e")
    assert cfg.family == "moe"
    pool = model_lib.init_state(cfg, 8, 16, model_lib.param_dtype(cfg))
    emb = model_lib.embed_tokens(cfg, backbone, jnp.full((8, 1), 7, jnp.int32))
    lg, _ = model_lib.decode_pages(cfg, backbone, emb, pool,
                                   jnp.full((8,), 3, jnp.int32))
    alone, _ = model_lib.decode_step(cfg, backbone, emb[:1],
                                     jax.tree.map(lambda a: a[:, :1], pool),
                                     jnp.int32(3))
    for p in range(8):
        np.testing.assert_allclose(np.asarray(lg[p]), np.asarray(alone[0]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.smoke
def test_two_tenants_distinct_adapters_distinct_streams():
    """Two tenants, same prompt, different adapters: the streams differ from
    each other AND each matches its single-tenant (isolated) run."""
    cfg, backbone, tenants = _setup("h2o-danube-1.8b")
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    reqs = [Request(rid=0, tenant="alpha", prompt=prompt, max_new_tokens=6),
            Request(rid=1, tenant="beta", prompt=prompt, max_new_tokens=6)]

    def fresh():
        return ServingEngine(cfg, backbone, max_slots=2, prefill_len=8,
                             max_new_tokens=8,
                             adapter_loader=tenants.__getitem__)

    both = fresh().run(reqs)
    assert both[0].tokens != both[1].tokens, (
        "distinct adapters must steer distinct streams")
    solo_a = fresh().run([reqs[0]])
    solo_b = fresh().run([reqs[1]])
    assert both[0].tokens == solo_a[0].tokens
    assert both[1].tokens == solo_b[1].tokens


def test_engine_stop_token_and_budget():
    cfg, backbone, tenants = _setup("h2o-danube-1.8b")
    reqs = _requests(cfg, [("alpha", 5, 6)])
    free_run = ServingEngine(cfg, backbone, max_slots=2, prefill_len=8,
                             max_new_tokens=8,
                             adapter_loader=tenants.__getitem__).run(reqs)
    toks = free_run[0].tokens
    # stop on the first token (past the first) not seen earlier in the
    # stream, so the run must end exactly there
    cut = next(i for i in range(1, len(toks)) if toks[i] not in toks[:i])
    eng = ServingEngine(cfg, backbone, max_slots=2, prefill_len=8,
                        max_new_tokens=8, stop_token=toks[cut],
                        adapter_loader=tenants.__getitem__)
    stopped = eng.run(_requests(cfg, [("alpha", 5, 6)]))
    assert stopped[0].tokens == toks[:cut + 1]
    assert len(free_run[0].tokens) == 6  # budget respected


def test_submit_rejects_overlong_prompt():
    cfg, backbone, _ = _setup("h2o-danube-1.8b")
    eng = ServingEngine(cfg, backbone, max_slots=1, prefill_len=4,
                        max_new_tokens=4)
    with pytest.raises(ValueError, match="prefill_len"):
        eng.submit(Request(rid=0, tenant=None,
                           prompt=np.zeros(9, np.int32), max_new_tokens=2))


def test_window_guard_rejects_pad_overflow():
    """Padded prefill longer than the attention window would let pad KV evict
    live ring entries — the engine must refuse to build."""
    cfg, backbone, _ = _setup("h2o-danube-1.8b")
    assert cfg.sliding_window is not None
    with pytest.raises(ValueError, match="window"):
        ServingEngine(cfg, backbone, max_slots=1,
                      prefill_len=cfg.sliding_window + 1, max_new_tokens=4)


# ---------------------------------------------------------------------------
# adapter bank / cache units
# ---------------------------------------------------------------------------

def _bank(n_slots):
    cfg = get_smoke_config("h2o-danube-1.8b")
    return cfg, AdapterBank(cfg, n_slots)


def _adapters(cfg, seed):
    ad = nano.init_nanoedge(jax.random.PRNGKey(seed), cfg)
    return jax.tree.map(lambda a: a + 0.01 * seed, ad)


def test_adapter_cache_lru_eviction_order():
    cfg, bank = _bank(2)
    loads = []

    def loader(t):
        loads.append(t)
        return _adapters(cfg, len(loads))

    cache = AdapterCache(bank, loader=loader)
    sa = cache.acquire("a"); cache.release("a")
    sb = cache.acquire("b"); cache.release("b")
    assert {sa, sb} == {0, 1}
    assert cache.acquire("a") == sa          # hit, no load
    cache.release("a")
    assert loads == ["a", "b"]
    cache.acquire("c"); cache.release("c")   # evicts b (a was touched later)
    assert "b" not in cache and "a" in cache
    assert cache.stats() == {"hits": 1, "misses": 3, "evictions": 1,
                             "resident": 2}


def test_adapter_cache_pinned_slots_never_evicted():
    cfg, bank = _bank(1)
    cache = AdapterCache(bank, loader=lambda t: _adapters(cfg, 1))
    cache.acquire("a")  # pinned (no release)
    with pytest.raises(AdapterCacheMiss, match="pinned"):
        cache.acquire("b")
    cache.release("a")
    assert cache.acquire("b") == 0  # now evictable


def test_adapter_cache_none_tenant_is_identity():
    cfg, bank = _bank(1)
    cache = AdapterCache(bank)
    assert cache.acquire(None) == -1
    cache.release(None)  # no-op


def test_adapter_cache_miss_without_loader():
    cfg, bank = _bank(1)
    with pytest.raises(AdapterCacheMiss, match="no loader"):
        AdapterCache(bank).acquire("ghost")


def test_adapter_bank_set_slot_validates():
    cfg, bank = _bank(2)
    with pytest.raises(IndexError):
        bank.set_slot(5, _adapters(cfg, 1))
    bad = {"text": {"down": np.zeros((3, 3)), "up": np.zeros((3, 3))}}
    with pytest.raises(ValueError, match="shape"):
        bank.set_slot(0, bad)


def test_checkpoint_adapter_loader_roundtrip(tmp_path):
    from repro.checkpoint import save_pytree

    cfg = get_smoke_config("h2o-danube-1.8b")
    ad = _adapters(cfg, 3)
    save_pytree(str(tmp_path / "tenant7.npz"), ad)
    loader = checkpoint_adapter_loader(cfg, str(tmp_path))
    got = loader("tenant7")
    for mod in ad:
        for k in ("down", "up"):
            np.testing.assert_array_equal(np.asarray(got[mod][k]),
                                          np.asarray(ad[mod][k]))


# ---------------------------------------------------------------------------
# kv slot manager units
# ---------------------------------------------------------------------------

def test_kv_slot_manager_alloc_free():
    cfg = get_smoke_config("h2o-danube-1.8b")
    mgr = KVSlotManager(cfg, n_slots=3, capacity=16, dtype=jnp.float32)
    assert [mgr.alloc(), mgr.alloc(), mgr.alloc()] == [0, 1, 2]
    assert mgr.alloc() is None
    mgr.free(1)
    with pytest.raises(ValueError, match="double free"):
        mgr.free(1)
    assert mgr.alloc() == 1  # deterministic lowest-first reuse
    assert mgr.n_free == 0
    assert mgr.pool_bytes() == 3 * mgr.page_bytes()


def test_kv_slot_manager_write_installs_page():
    cfg = get_smoke_config("h2o-danube-1.8b")
    mgr = KVSlotManager(cfg, n_slots=2, capacity=16, dtype=jnp.float32)
    page = jax.tree.map(
        lambda a: jnp.ones((1,) + a.shape[1:] if a.ndim == 1 else
                           a.shape[:1] + (1,) + a.shape[2:], a.dtype),
        jax.tree.map(lambda a: a[:, :1], mgr.state))
    mgr.write(1, page, start_pos=5)
    assert mgr.pos[1] == 5 and mgr.pos[0] == 0
    for leaf in jax.tree.leaves(mgr.state):
        assert np.all(np.asarray(leaf)[:, 1] == 1.0)
        assert np.all(np.asarray(leaf)[:, 0] == 0.0)

"""The ``mla_moe`` family: a tiny Kimi-style round cell against the plain
reference on this host's CPU, its weights in the program's layout, and its
counts pinned at the configuration's full size.

The tiny cell keeps every mechanism of ``round.kimi-vl-a3b.ep4.silo-hires``
(latent attention with q/k and v widths apart, a leading dense layer, a
scanned stack of expert layers with sigmoid + bias routing, top-k,
renormalised and scaled weights, shared experts, a share of the experts
that does not start at expert 0, both NanoAdapters, the Pallas kernels) at
sizes a test can hold. A router of 8 experts flips far more of its top-k
picks on bf16 rounding than one of 64 (the gaps between its scores are
wider), and a flipped pick moves a token's output by a whole expert; so
the tiny cell draws its correction bias 5 times wider (std 0.5), which
spreads the biased scores. Its limits are set the way the cells' are
(readings on this host's CPU, program max / float8 control min, three
seeds): loss 6.4e-5 / 2.0e-4, global_delta 8.1e-3 / 1.2e-2, fisher
7.8e-2 / 1.3e-1, merge_last at most 1.0e-7, where a merged update applied
twice reads 1.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, traffic_gen
from bench.kinds import round as rk
from bench.models import common
from bench.tests.cells import run_tiny

CELL = "round.kimi-vl-a3b.ep4.silo-hires"
SEED = 2**33 + 77
TINY_LIMITS = {"loss": 1.2e-4, "global_delta": 0.01, "merge_last": 1e-5, "fisher": 0.1}


def tiny_config():
    with open(os.path.join(spec.BENCH_DIR, "configs", "kimi-vl-a3b.ep4.json")) as f:
        cfg = json.load(f)
    cfg["run"].update(
        hidden_size=256, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=512, moe_intermediate_size=64, n_routed_experts=4,
        router_experts=8, expert_first=2, num_experts_per_tok=3, vocab_size=512,
        frontend_dim=128, image_patches=16, correction_bias_std=0.5)
    cfg["overrides"] = dict(
        n_layers=3, d_model=256, n_heads=4, n_kv_heads=4, head_dim=24, d_ff=64,
        dense_d_ff=512, vocab_size=512, frontend_dim=128,
        mla={"kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16},
        moe={"n_experts": 8, "top_k": 3, "shared_d_ff": 128, "expert_first": 2,
             "experts_held": 4})
    cfg["adapter"] = dict(cfg["adapter"], rank=8, alpha=16.0)
    return cfg


def tiny_cell() -> spec.Cell:
    bench = spec.load_benchmark()
    w = {c["name"]: c for c in bench["workloads"]}[CELL]
    with open(os.path.join(spec.BENCH_DIR, "traffic", w["traffic"] + ".json")) as f:
        tr = json.load(f)
    tr.update(clients=3, text_len=32, image_patches=16)
    return spec.Cell(name=CELL, chips=1, config=tiny_config(), traffic=tr,
                     limits=dict(TINY_LIMITS),
                     end_to_end=[m for m in bench["end_to_end"]
                                 if CELL in m.get("workloads", [CELL])],
                     per_layer=[])


@pytest.fixture(autouse=True)
def fresh_programs():
    from repro.core import client

    def clear():
        jax.clear_caches()
        for make in (client.make_many_update, client.make_train_step,
                     client.make_fisher_grad, client.make_local_adapter_step):
            make.cache_clear()

    clear()
    yield
    clear()


def test_the_cell_resolves_to_the_mla_moe_module():
    c = spec.load_cell(CELL)
    assert c.model.__file__ == os.path.join(spec.BENCH_DIR, "models", "mla_moe.py")
    assert {m["name"] for m in c.per_layer} >= {"gmm_roofline", "moe_dispatch_ms.round",
                                                 "flash_attention_roofline", "mfu.round"}


def test_the_config_file_matches_the_program_config():
    c = spec.load_cell(CELL)
    sz, m = c.model.sizes(c.config), c.model_config()
    assert m.mla is not None and m.moe is not None
    assert (m.n_heads, m.mla.qk_nope_head_dim, m.mla.qk_rope_head_dim, m.mla.v_head_dim,
            m.mla.kv_lora_rank) == (sz.heads, sz.qk_nope, sz.qk_rope, sz.v_dim, sz.kv_rank)
    assert (m.moe.n_experts, m.moe.held, m.moe.expert_first, m.moe.top_k) == (
        sz.router_experts, sz.held, sz.expert_first, sz.top_k)
    assert (m.d_ff, m.dense_d_ff, m.moe.shared_d_ff, m.n_dense_layers) == (
        sz.ff_expert, sz.ff_dense, sz.shared_ff, sz.dense_layers)
    assert m.moe.scoring == "sigmoid" and m.moe.score_bias and c.config["norm_topk_prob"]
    assert m.moe.routed_scale == sz.routed_scale and m.rope_theta == sz.rope_theta
    assert m.norm_eps == sz.rms_eps and not m.tie_embeddings
    # the catalog's numbers at the top level: the run's, cut only where reduced
    cut = {k: v["run"] for k, v in c.config["reduced"].items()}
    for k, v in c.config["published"].items():
        assert c.config[k] == cut.get(k, v), k


def test_weights_have_the_program_layout():
    from repro.models import model as model_lib

    cell = tiny_cell()
    sz, cfg = cell.model.sizes(cell.config), cell.model_config()
    ours = cell.model.backbone_shapes(sz, "bfloat16")
    theirs = jax.eval_shape(lambda k: model_lib._init_backbone(k, cfg), jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_one_layer_made_again_equals_the_stacked_one():
    cell = tiny_cell()
    model = cell.model
    sz = model.sizes(cell.config)
    full = model.backbone_weights(SEED, sz)
    for i, (stack, j) in enumerate([("lead", 0), ("layers", 0), ("layers", 1)]):
        again = model.ref_layer(SEED, sz, i)
        for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(full[stack])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b[j], np.float32))


def test_a_share_holds_the_uncut_layers_experts():
    """Experts are drawn one key each: a share starting at expert 2 holds
    experts 2.. of the layer that holds them all."""
    cell = tiny_cell()
    model = cell.model
    sz = model.sizes(cell.config)
    import dataclasses

    whole = dataclasses.replace(sz, held=sz.router_experts, expert_first=0)
    a = model.backbone_weights(SEED, sz)["layers"]["moe"]["w_up"]
    b = model.backbone_weights(SEED, whole)["layers"]["moe"]["w_up"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b[:, 2:6]))


def test_loss_and_adapter_gradients_match_the_program():
    from repro.core import adapters as nano
    from repro.core.types import Batch

    cell = tiny_cell()
    model = cell.model
    sz = model.sizes(cell.config)
    cfg = cell.model_config(dtype="float32", use_pallas=True)
    tr = dict(cell.traffic, clients=2, batches_per_client=[2, 2], text_len=24)
    rows = traffic_gen.round_population(SEED, sz.vocab, sz.frontend, tr)[0]
    backbone = jax.tree.map(lambda x: x.astype(jnp.float32), model.backbone_weights(SEED, sz))
    adp = common.adapter_set(SEED, sz, "global")
    batch = Batch(*(jnp.asarray(a[0]) for a in (rows.tokens, rows.labels, rows.mask,
                                                rows.patches)))
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda a: nano.fednano_loss(cfg, backbone, a, batch), has_aux=True)(adp)
        ref = model.Reference(SEED, sz)
        rl, rg = ref.loss_and_grads(
            jax.tree.map(lambda x: x[None], adp), rows.tokens[None, 0],
            rows.labels[None, 0], rows.mask[None, 0], jnp.asarray(rows.patches[None, 0]))
    np.testing.assert_allclose(float(rl[0]), float(loss), rtol=1e-5)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(rg)):
        np.testing.assert_allclose(np.asarray(r[0]), np.asarray(g), rtol=2e-3,
                                   atol=2e-3 * float(jnp.max(jnp.abs(g))))


def test_sound_round_is_correct():
    result, checks = run_tiny(tiny_cell(), SEED)
    assert result["correct"], checks
    assert result["failed"] == 0


@pytest.mark.parametrize("fault", ["no_correction_bias", "capacity_dispatch"])
def test_routing_faults_are_caught(fault, monkeypatch):
    """Routing without the correction bias, and the old capacity dispatch
    (tokens past 1.25 x the even share of an expert dropped)."""
    from repro.models import moe

    if fault == "no_correction_bias":
        choose = moe.choose
        monkeypatch.setattr(moe, "choose", lambda mcfg, scores, bias: choose(mcfg, scores, None))
    else:
        fwd = moe._routed_fwd

        def capped(mcfg, act, use_pallas, params, x):
            (y, counts), res = fwd(mcfg, act, use_pallas, params, x)
            idx, held, order, inv, sizes, gate, up, yk = res
            cap = int(1.25 * x.shape[0] * mcfg.top_k / mcfg.n_experts + 0.999)
            flat = jnp.where(held, idx, -1).reshape(-1)
            rank = jnp.cumsum(jax.nn.one_hot(flat, mcfg.n_experts), axis=0)
            kept = (jnp.take_along_axis(rank, jnp.maximum(flat, 0)[:, None], 1)[:, 0] <= cap)
            drop = (~kept).reshape(held.shape) & held
            w = moe.route(mcfg, params, x)[1]
            y = y - jnp.einsum("tk,tkd->td", jnp.where(drop, w, 0.0),
                               yk.astype(jnp.float32)).astype(y.dtype)
            return (y, counts), res

        monkeypatch.setattr(moe, "_routed_fwd", capped)
        moe._routed_call.cache_clear()
    result, checks = run_tiny(tiny_cell(), SEED)
    moe._routed_call.cache_clear()
    assert not result["correct"], checks


def test_the_reference_rounds_are_the_cells():
    """The family's reference drives the round kind's reference unchanged."""
    cell = tiny_cell()
    model, tr = cell.model, cell.traffic
    sz = model.sizes(cell.config)
    pop = traffic_gen.round_population(SEED, sz.vocab, sz.frontend, tr)
    sampler = rk._sampler(tr, SEED)
    cohorts = [list(sampler.select(r, sorted(pop))) for r in range(rk.CHECKED_ROUNDS)]
    start = rk._host(common.adapter_set(SEED, sz, "global"))
    got = rk.reference_rounds(model, SEED, sz, tr, pop, cohorts, start)
    assert all(np.isfinite(got["loss"])) and len(got["keep"]) == 4


# ---------------------------------------------------------------------------
# the counts at full size
# ---------------------------------------------------------------------------

def _full():
    c = spec.load_cell(CELL)
    return c.model, c.model.sizes(c.config)


def test_counts_at_full_size():
    model, sz = _full()
    # parameters this chip holds: 26 x 169.6M + 83.0M + 167.8M (+ connector)
    assert model.layer_weight_params(sz, True) == 169_611_840
    assert model.layer_weight_params(sz, False) == 82_973_184
    assert model.mla_params(sz) == 13_762_560
    assert model.weight_bytes(sz) == 9_153_538_304
    assert model.kv_bytes_per_position(sz) == 27 * 576 * 2
    # a position, forward: the dense layer, then 26 x (MLA, router, shared,
    # 1.5 held-expert evaluations)
    assert model.active_expert_rows(sz) == 1.5
    assert model.position_matmul_params(sz) == 82_968_576 + 26 * 44_171_264
    assert model.round_flops(sz, sequences=10, text_len=256, image_len=1024,
                             loss_positions=200) == 70_124_065_259_520
    f, b = model.flash_launch_cost(sz, [5, 2, 16, 1280, 128], 1280)
    assert f == 2 * 10 * 16 * 1280 * 1280 / 2 * 320
    assert b == 10 * 16 * 1280 * (2 * 192 + 2 * 128) * 2 + 10 * 16 * 1280 * 4
    f, b = model.gmm_launch_cost(sz, [76800, 1408])
    assert f == 2 * 19200 * 2048 * 1408
    assert b == (16 * 2048 * 1408 + 19200 * (2048 + 1408)) * 2
    f, _ = model.gmm_launch_cost(sz, [76800, 2048])
    assert f == 2 * 19200 * 1408 * 2048


# ---------------------------------------------------------------------------
# the readers of the new per-layer metrics, on events made by hand
# ---------------------------------------------------------------------------

def _ctx(events, rounds=2):
    from bench import trace as trace_lib

    model, sz = _full()
    c = spec.load_cell(CELL)
    s = trace_lib.reduce(events)
    return {"kind": "round", "model": model, "sz": sz, "traffic": c.traffic, "trace": s,
            "device_kind": "TPU v5 lite", "rounds": rounds, "cohort": 5}


def _op(name, start, dur):
    from bench import trace as trace_lib

    return trace_lib.Event("/device:TPU:0", "XLA Ops", name, start, dur, {})


def test_gmm_roofline_reads_each_launch_against_its_count():
    from bench import peaks

    ops = [_op("%_gmm_jit.3 = bf16[76800,1408]{1,0} custom-call(%a, %b), "
               'custom_call_target="tpu_custom_call"', 0, 1e6),
           _op("%_gmm_jit.4 = bf16[76800,2048]{1,0} custom-call(%a, %b), "
               'custom_call_target="tpu_custom_call"', 2e6, 1e6),
           _op("%fusion.1 = bf16[76800,1408]{1,0} fusion(%a)", 4e6, 5e5)]
    ctx = _ctx(ops)
    got = spec.metric_reader("gmm_roofline")(ctx)
    model, sz = ctx["model"], ctx["sz"]
    pk = peaks.peaks("TPU v5 lite")
    least = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
                for f, b in (model.gmm_launch_cost(sz, [76800, 1408]),
                             model.gmm_launch_cost(sz, [76800, 2048])))
    assert got == pytest.approx(100 * least / 2e-3)
    assert 0 < got < 100


def test_moe_dispatch_reads_the_flat_token_ops_but_not_the_experts():
    ops = [_op("%sort.1 = (s32[76800]{0}, s32[76800]{0}) sort(%a, %b)", 0, 1e5),
           _op("%fusion.2 = bf16[76800,2048]{1,0} fusion(%a, %b)", 2e5, 2e5),
           _op("%fusion.3 = f32[12800,64]{1,0} fusion(%a)", 5e5, 1e5),
           _op("%fusion.4 = bf16[76800,1408]{1,0} fusion(%a, %b)", 7e5, 3e5),
           _op("%_gmm_jit.5 = bf16[76800,2048]{1,0} custom-call(%a, %b)", 1.1e6, 4e5),
           _op("%fusion.6 = bf16[5,2,1280,2048]{3,2,1,0} fusion(%a)", 1.6e6, 1e5)]
    got = spec.metric_reader("moe_dispatch_ms.round")(_ctx(ops, rounds=2))
    assert got == pytest.approx(1000 * (1e5 + 2e5 + 1e5) * 1e-9 / 2)
    scoped = [_op("%fusion.7 = f32[8]{0} fusion(%a)", 0, 3e5)]
    scoped[0].stats["source"] = "jit(step)/fednano.moe.dispatch/gather"
    assert spec.metric_reader("moe_dispatch_ms.round")(_ctx(scoped, rounds=1)) == (
        pytest.approx(0.3))

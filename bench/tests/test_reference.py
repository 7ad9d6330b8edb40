"""The plain reference against the program, in float32 at test size.

With the program's backbone computing in float32 on the same (bf16-valued)
weights, the reference and the program must agree to float32 rounding:
loss, adapter gradients (through M-RoPE or RoPE, GQA or MHA, QKV bias, the
connector and both NanoAdapters) and the logits of a prefill.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import traffic_gen
from bench.models import common
from bench.tests.cells import tiny_cell

CELLS = ["round.qwen2-vl-72b.silo-vqa", "serve.qwen1.5-4b.chat-zipf"]
SEED = 2**31 + 12345          # more than 32 signed bits


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


@pytest.mark.parametrize("name", CELLS)
def test_weights_have_the_program_layout(name):
    from repro.models import model as model_lib

    cell = tiny_cell(name)
    sz, cfg = cell.model.sizes(cell.config), cell.model_config()
    ours = cell.model.backbone_shapes(sz, "bfloat16")
    theirs = jax.eval_shape(lambda k: model_lib._init_backbone(k, cfg),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_one_layer_made_again_equals_the_stacked_one():
    cell = tiny_cell(CELLS[0])
    model = cell.model
    sz = model.sizes(cell.config)
    stacked = model.backbone_weights(SEED, sz)["layers"]
    again = model.ref_layer(SEED, sz, 1)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(stacked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b[1], np.float32))
    top = model.ref_top(SEED, sz)
    full = model.backbone_weights(SEED, sz)
    np.testing.assert_array_equal(np.asarray(top["unembed"]["table"]),
                                  np.asarray(full["unembed"]["table"], np.float32))


@pytest.mark.parametrize("name", CELLS)
def test_loss_and_adapter_gradients_match_the_program(name):
    from repro.core import adapters as nano
    from repro.core.types import Batch

    cell = tiny_cell(name)
    model = cell.model
    sz = model.sizes(cell.config)
    cfg = cell.model_config(dtype="float32")
    tr = dict(cell.traffic, kind="round", clients=2, topics=4, dirichlet_alpha=0.5,
              batches_per_client=[2, 2], batch=2, text_len=24, image_patches=64,
              answer_len=[3, 6], local_steps=2, fisher_batches=2)
    rows = traffic_gen.round_population(SEED, sz.vocab, sz.frontend, tr)[0]
    backbone = _f32(model.backbone_weights(SEED, sz))
    adp = common.adapter_set(SEED, sz, "global")
    patches = None if rows.patches is None else jnp.asarray(rows.patches[0])
    batch = Batch(jnp.asarray(rows.tokens[0]), jnp.asarray(rows.labels[0]),
                  jnp.asarray(rows.mask[0]), patches)
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = jax.value_and_grad(
            lambda a: nano.fednano_loss(cfg, backbone, a, batch), has_aux=True)(adp)
        ref = model.Reference(SEED, sz)
        rl, rg = ref.loss_and_grads(
            jax.tree.map(lambda x: x[None], adp), rows.tokens[None, 0],
            rows.labels[None, 0], rows.mask[None, 0],
            None if rows.patches is None else jnp.asarray(rows.patches[None, 0]))
    np.testing.assert_allclose(float(rl[0]), float(loss), rtol=1e-5)
    for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(rg)):
        np.testing.assert_allclose(np.asarray(r[0]), np.asarray(g), rtol=2e-3,
                                   atol=2e-3 * float(jnp.max(jnp.abs(g))))


def test_prefill_logits_match_the_program():
    from repro.core import adapters as nano
    from repro.core.types import Batch
    from repro.models import model as model_lib

    cell = tiny_cell(CELLS[1])
    model = cell.model
    sz = model.sizes(cell.config)
    cfg = cell.model_config(dtype="float32")
    backbone = _f32(model.backbone_weights(SEED, sz))
    adp = common.adapter_set(SEED, sz, "tenant003")
    toks = np.random.default_rng(0).integers(0, sz.vocab, (1, 20)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        batch = Batch(jnp.asarray(toks), jnp.zeros_like(toks),
                      jnp.zeros(toks.shape, jnp.float32), None)
        emb, pos, _, _, _ = nano.nanoedge_forward(cfg, backbone, adp, batch)
        _, hidden = model_lib.prefill(cfg, backbone, emb, pos, 32)
        got = model_lib.logits(cfg, backbone, hidden)[0]
        ref = model.Reference(SEED, sz)
        stacked = jax.tree.map(lambda x: x[None], adp)
        h = ref.hidden(ref.embed(stacked, jnp.asarray(toks[None]), None))
        want = ref.logits_at(h, jnp.arange(toks.shape[1])[None])[0]
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), rtol=1e-4, atol=1e-4)

"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode (every other kernel test) checks numerics but not what the
chip's compiler accepts: block tiling, fast-memory limits, Mosaic lowering.
These tests lower each kernel at real widths (llava-1.5-7b; SSD at
mamba2-130m; latent attention and the grouped expert matmul at kimi-vl-a3b) against a described ``v5e:2x2`` topology — no chip needed —
and assert the compiled program holds the kernel (``tpu_custom_call``), so
a refusal shows up here instead of on the chip.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under several test workers
only the worker that runs this file should. ``interpret=False`` is passed
explicitly because the platform decision sees this host's CPU.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fisher_merge import ops as fm_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.lora import ops as lora_ops
from repro.kernels.moe_gmm import ops as gmm_ops
from repro.kernels.ssd_scan import ops as ssd_ops

# llava-1.5-7b: d_model 4096, 32 heads of 128, rank-64 NanoAdapters; a train
# batch of 4 x (128 text + 64 image) positions
D_MODEL, HEADS, HEAD_DIM, RANK = 4096, 32, 128, 64
BATCH, SEQ = 4, 192


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one; keep the cache out of it
        prev_cache = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev_cache)


def _compile_has_kernel(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash_loss(q, k, v):
    y = fa_ops.flash_attention(q, k, v, causal=True, interpret=False)
    return jnp.sum(y.astype(jnp.float32) ** 2)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_compiles(one_chip, grad):
    qkv = _spec(one_chip, (BATCH, SEQ, HEADS, HEAD_DIM))
    fn = (jax.grad(_flash_loss, argnums=(0, 1, 2)) if grad
          else lambda q, k, v: fa_ops.flash_attention(q, k, v, causal=True,
                                                      interpret=False))
    _compile_has_kernel(fn, qkv, qkv, qkv)


def _lora_loss(x, down, up):
    y = lora_ops.lora_residual(x, down, up, scale=2.0, interpret=False)
    return jnp.sum(y.astype(jnp.float32) ** 2)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_lora_compiles(one_chip, grad):
    # bf16 activations through the f32 adapter masters, as on the train path
    x = _spec(one_chip, (BATCH, SEQ, D_MODEL))
    down = _spec(one_chip, (D_MODEL, RANK), jnp.float32)
    up = _spec(one_chip, (RANK, D_MODEL), jnp.float32)
    fn = (jax.grad(_lora_loss, argnums=(0, 1, 2)) if grad
          else lambda x, d, u: lora_ops.lora_residual(x, d, u, scale=2.0,
                                                      interpret=False))
    _compile_has_kernel(fn, x, down, up)


def test_grouped_lora_compiles(one_chip):
    n_adapters, rows = 8, 64
    fn = lambda x, d, u, i: lora_ops.grouped_lora_residual(  # noqa: E731
        x, d, u, i, scale=2.0, interpret=False)
    _compile_has_kernel(
        fn,
        _spec(one_chip, (rows, D_MODEL)),
        _spec(one_chip, (n_adapters, D_MODEL, RANK), jnp.float32),
        _spec(one_chip, (n_adapters, RANK, D_MODEL), jnp.float32),
        _spec(one_chip, (rows,), jnp.int32))


def test_fisher_merge_and_fold_compile(one_chip):
    # one NanoAdapter leaf: (4096, 64) = 262k elements, four clients
    k = 4
    leaf = (D_MODEL, RANK)
    theta = _spec(one_chip, (k, *leaf), jnp.float32)
    weights = _spec(one_chip, (k,), jnp.float32)
    _compile_has_kernel(
        lambda t, f, w: fm_ops.fisher_merge(t, f, w, interpret=False),
        theta, theta, weights)
    one = _spec(one_chip, leaf, jnp.float32)
    _compile_has_kernel(
        lambda n, d, t, f, w: fm_ops.fisher_fold(n, d, t, f, w, interpret=False),
        one, one, one, one, _spec(one_chip, (), jnp.float32))


def test_ssd_scan_compiles(one_chip):
    # mamba2-130m: d_inner 1536 = 24 SSD heads of 64, d_state 128, chunk 256
    b, s, h, p, n = 2, 512, 24, 64, 128
    fn = lambda x, dt, A, B, C: ssd_ops.ssd(  # noqa: E731
        x, dt, A, B, C, chunk=256, interpret=False)
    _compile_has_kernel(
        fn,
        _spec(one_chip, (b, s, h, p)),
        _spec(one_chip, (b, s, h)),
        _spec(one_chip, (h,), jnp.float32),
        _spec(one_chip, (b, s, n)),
        _spec(one_chip, (b, s, n)))


# kimi-vl-a3b: 16 latent-attention heads, q/k 192 wide, v 128; 16 held
# experts of 1408 over d_model 2048, one client's 2 x 1280 positions x top-6
MLA_HEADS, QK_DIM, V_DIM, MLA_SEQ = 16, 192, 128, 1280
EXPERTS, D_EXPERT, D_KIMI, COPIES = 16, 1408, 2048, 2 * 1280 * 6


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_attention_with_its_own_v_width_compiles(one_chip, grad):
    qk = _spec(one_chip, (2, MLA_SEQ, MLA_HEADS, QK_DIM))
    v = _spec(one_chip, (2, MLA_SEQ, MLA_HEADS, V_DIM))
    fn = (jax.grad(_flash_loss, argnums=(0, 1, 2)) if grad
          else lambda q, k, v: fa_ops.flash_attention(q, k, v, causal=True,
                                                      interpret=False))
    _compile_has_kernel(fn, qk, qk, v)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_grouped_expert_matmul_compiles(one_chip, grad):
    """Forward, and the input-gradient VJP alone: no weight-gradient kernel."""
    rows = _spec(one_chip, (COPIES, D_KIMI))
    w = _spec(one_chip, (EXPERTS, D_KIMI, D_EXPERT))
    sizes = _spec(one_chip, (EXPERTS + 1,), jnp.int32)
    fwd = lambda x, w, g: gmm_ops.gmm(x, w, g, interpret=False)  # noqa: E731
    fn = (jax.grad(lambda x, w, g: jnp.sum(fwd(x, w, g).astype(jnp.float32) ** 2))
          if grad else fwd)
    compiled = _compile_has_kernel(fn, rows, w, sizes)
    assert compiled.as_text().count("tpu_custom_call") == (2 if grad else 1)

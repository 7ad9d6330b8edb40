"""Launch layer: step factories lower+compile on a debug mesh; sharding specs
resolve for every arch; roofline HLO parsing extracts collective bytes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import InputShape
from repro.launch import sharding_rules as rules
from repro.launch import steps as steps_lib
from repro.launch.roofline import collective_bytes_from_hlo, model_flops_estimate
from repro.sharding import use_mesh


def _mesh():
    devs = np.array(jax.devices()).reshape(1, 1)
    return jax.sharding.Mesh(devs, ("data", "model"))


SHAPES = {
    "train": InputShape("t", "train", 32, 2),
    "prefill": InputShape("p", "prefill", 32, 2),
    "decode": InputShape("d", "decode", 32, 2),
}


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "mamba2-130m", "grok-1-314b",
                                  "recurrentgemma-9b", "whisper-base", "qwen2-vl-72b"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_steps_lower_and_compile(arch, kind):
    from repro.launch.dryrun import build_lowerable

    cfg = get_smoke_config(arch)
    mesh = _mesh()
    with use_mesh(mesh):
        jitted, args = build_lowerable(cfg, SHAPES[kind], mesh)
        compiled = jitted.lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    assert float(cost.get("flops", 0.0)) > 0


def test_param_shardings_cover_all_archs():
    mesh = _mesh()
    for arch in ("glm4-9b", "llama4-scout-17b-a16e", "internlm2-20b"):
        cfg = get_smoke_config(arch)
        backbone = steps_lib.backbone_specs(cfg)
        sh = rules.make_param_shardings(mesh, backbone)
        assert jax.tree_util.tree_structure(sh) == jax.tree_util.tree_structure(backbone)


@pytest.mark.smoke
def test_collective_parse():
    hlo = """
  %ag = bf16[16,512]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[1024]{0} all-reduce(%y), to_apply=%sum
  %noise = f32[8]{0} add(%a, %b)
  %a2a = bf16[4,4]{1,0} all-to-all(%z)
"""
    out = collective_bytes_from_hlo(hlo)
    assert out["all-gather"] == 16 * 512 * 2
    assert out["all-reduce"] == 1024 * 4
    assert out["all-to-all"] == 16 * 2
    assert out["count"] == 3


def test_model_flops_estimate_moe_counts_active_only():
    from repro.configs import get_config

    cfg = get_config("grok-1-314b")
    sh = InputShape("t", "train", 4096, 256)
    est = model_flops_estimate(cfg, sh)
    # active params ~ 314B*(2/8 experts)+attn ≈ 90B; 6*N*D with D=1.05M tokens
    n_active = est / (6 * 4096 * 256)
    assert 5e10 < n_active < 1.5e11, n_active


def test_input_specs_decode_state_structure():
    cfg = get_smoke_config("recurrentgemma-9b")
    ins = steps_lib.input_specs(cfg, SHAPES["decode"])
    assert "state" in ins and "token" in ins and "pos" in ins
    leaves = jax.tree.leaves(ins["state"])
    assert all(hasattr(l, "shape") for l in leaves)


@pytest.mark.smoke
def test_exec_config_modes():
    cfg = get_smoke_config("glm4-9b")
    full = steps_lib.exec_config(cfg, SHAPES["prefill"], "full")
    assert full.attn_chunk == 1024 and full.scan_layers
    roof = steps_lib.exec_config(cfg, SHAPES["prefill"], "roofline")
    assert roof.attn_chunk is None and not roof.scan_layers
    over = steps_lib.exec_config(cfg, SHAPES["train"], "roofline", {"loss_chunk": 512})
    assert over.loss_chunk == 512


@pytest.mark.parametrize("full", [False, True])
def test_driver_model_config(full):
    """One option picks the published config or its CPU smoke cut, for the
    train and serve drivers alike."""
    import argparse

    from repro.configs import get_config
    from repro.launch.common import add_model_args, model_config

    ap = argparse.ArgumentParser()
    add_model_args(ap)
    args = ap.parse_args(["--arch", "llava-1.5-7b"] + (["--full-width"] if full else []))
    cfg = model_config(args)
    want = get_config("llava-1.5-7b") if full else get_smoke_config("llava-1.5-7b")
    assert cfg == want
    if full:
        assert (cfg.n_layers, cfg.d_model, cfg.dtype) == (32, 4096, "bfloat16")


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_dir(env_dir, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise one fixed
    directory at the checkout root, which git ignores."""
    import os

    from repro.launch.common import enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    root = os.path.normpath(os.path.join(os.path.dirname(__file__), os.pardir))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = enable_compile_cache()
        if env_dir:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            assert path == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            with open(os.path.join(root, ".gitignore")) as f:
                assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)

"""Checkpoint IO: strict restore semantics, versioning, and the golden
RunState layout.

The restore contract is *strict by default*: missing keys, extra keys,
shape drift, and dtype drift are all errors — never silent casts or
half-restores. A checkpoint saved at a different precision (or by a
different format version) must be converted deliberately; loading it
through an implicit cast corrupts optimizer moments without a single
visible symptom.

The golden fixture under ``tests/golden/run_state/`` (regenerate with
``scripts/gen_runstate_golden.py``) pins the on-disk layout: npz key paths,
meta.json fields, and leaf values. If this file's tests fail after a format
change, bump ``RUN_STATE_VERSION`` and regenerate — loudly, on purpose.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointError,
    CheckpointVersionError,
    RUN_STATE_VERSION,
    SERVER_CHECKPOINT_VERSION,
    load_pytree,
    load_run_state,
    load_server_checkpoint,
    read_run_meta,
    resolve_run_state_dir,
    save_pytree,
    save_run_state,
    save_server_checkpoint,
)
from repro.utils import tree_allclose

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden", "run_state")


# ---------------------------------------------------------------------------
# pytree <-> npz edge cases
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_empty_pytree_roundtrip(tmp_path):
    p = str(tmp_path / "empty.npz")
    save_pytree(p, {})
    assert load_pytree(p, {}) == {}


@pytest.mark.smoke
def test_scalar_leaves_roundtrip(tmp_path):
    tree = {"a": jnp.float32(1.5), "b": jnp.int32(3),
            "nested": {"c": jnp.zeros(())}}
    p = str(tmp_path / "scalars.npz")
    save_pytree(p, tree)
    back = load_pytree(p, jax.tree.map(jnp.zeros_like, tree))
    assert float(back["a"]) == 1.5
    assert int(back["b"]) == 3
    assert back["b"].dtype == jnp.int32
    assert back["nested"]["c"].shape == ()


def test_missing_key_errors(tmp_path):
    p = str(tmp_path / "t.npz")
    save_pytree(p, {"a": jnp.ones(3)})
    with pytest.raises(CheckpointError, match="missing key"):
        load_pytree(p, {"a": jnp.ones(3), "b": jnp.ones(2)})


def test_extra_key_errors_unless_lenient(tmp_path):
    p = str(tmp_path / "t.npz")
    save_pytree(p, {"a": jnp.ones(3), "b": jnp.ones(2)})
    with pytest.raises(CheckpointError, match="keys not in the reference"):
        load_pytree(p, {"a": jnp.ones(3)})
    back = load_pytree(p, {"a": jnp.zeros(3)}, strict=False)
    assert tree_allclose(back, {"a": jnp.ones(3)})


def test_shape_mismatch_errors(tmp_path):
    p = str(tmp_path / "t.npz")
    save_pytree(p, {"a": jnp.ones((2, 3))})
    with pytest.raises(CheckpointError, match="shape mismatch"):
        load_pytree(p, {"a": jnp.ones((3, 2))})


@pytest.mark.smoke
def test_dtype_mismatch_errors_not_casts(tmp_path):
    # the satellite fix: a float32 checkpoint restored into a float16
    # reference used to cast silently — now it refuses
    p = str(tmp_path / "t.npz")
    save_pytree(p, {"a": jnp.ones(4, dtype=jnp.float32)})
    with pytest.raises(CheckpointError, match="dtype mismatch"):
        load_pytree(p, {"a": jnp.ones(4, dtype=jnp.float16)})


# ---------------------------------------------------------------------------
# server checkpoints: v2 carries what v1 dropped
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_server():
    from repro.configs import get_smoke_config
    from repro.core import server as server_lib

    cfg = get_smoke_config("llava-1.5-7b").with_(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=64, frontend_dim=16,
    )
    return server_lib.init_server(jax.random.PRNGKey(0), cfg)


def test_server_checkpoint_preserves_opt_moments_and_rng(tmp_path, tiny_server):
    from repro.strategies.server_opt import FedAdamOpt

    opt = FedAdamOpt()
    moments = jax.tree.map(lambda x: jnp.full_like(x, 0.5),
                           opt.init(tiny_server.global_adapters))
    key = jax.random.PRNGKey(42)
    d = str(tmp_path / "ckpt")
    save_server_checkpoint(d, tiny_server, round_idx=3,
                           server_opt_state=moments, rng_key=key)
    restored, meta = load_server_checkpoint(
        d, tiny_server, server_opt_state=opt.init(tiny_server.global_adapters))
    assert meta["round_idx"] == 3
    assert tree_allclose(meta["server_opt_state"], moments)
    assert np.array_equal(meta["rng_key"], np.asarray(key))
    assert tree_allclose(restored.global_adapters, tiny_server.global_adapters)


def test_server_checkpoint_without_backbone(tmp_path, tiny_server):
    """A snapshot may leave out the frozen backbone; restoring keeps the
    caller's (rebuilt from the seed) and still restores the adapters."""
    d = str(tmp_path / "ckpt")
    save_server_checkpoint(d, tiny_server, round_idx=2, backbone=False)
    assert not os.path.exists(os.path.join(d, "backbone.npz"))
    other = jax.tree.map(jnp.zeros_like, tiny_server.global_adapters)
    restored, meta = load_server_checkpoint(
        d, dataclasses.replace(tiny_server, global_adapters=other))
    assert meta["has_backbone"] is False
    assert restored.backbone is tiny_server.backbone
    assert tree_allclose(restored.global_adapters, tiny_server.global_adapters)


def test_server_checkpoint_refuses_to_drop_moments(tmp_path, tiny_server):
    moments = {"m": jax.tree.map(jnp.zeros_like, tiny_server.global_adapters)}
    d = str(tmp_path / "ckpt")
    save_server_checkpoint(d, tiny_server, round_idx=1,
                           server_opt_state=moments)
    with pytest.raises(CheckpointError, match="ServerOpt moments"):
        load_server_checkpoint(d, tiny_server)


@pytest.mark.smoke
def test_server_checkpoint_version_mismatch(tmp_path, tiny_server):
    d = str(tmp_path / "ckpt")
    save_server_checkpoint(d, tiny_server, round_idx=1)
    meta_path = os.path.join(d, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["format_version"] = SERVER_CHECKPOINT_VERSION - 1
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(CheckpointVersionError, match="format_version"):
        load_server_checkpoint(d, tiny_server)


# ---------------------------------------------------------------------------
# RunState: torn writes, version checks, LATEST resolution, golden layout
# ---------------------------------------------------------------------------

def _golden_refs():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gen_runstate_golden",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "gen_runstate_golden.py"))
    gen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen)

    import dataclasses

    def zeroed(c):  # ClientState is not a pytree node; zero per field
        return dataclasses.replace(
            c,
            adapters=jax.tree.map(jnp.zeros_like, c.adapters),
            opt_state=jax.tree.map(jnp.zeros_like, c.opt_state),
            fisher=(jax.tree.map(jnp.zeros_like, c.fisher)
                    if c.fisher is not None else None),
        )

    rs = gen.build()
    return rs, {
        "clients_ref": [zeroed(c) for c in rs.clients],
        "global_ref": jax.tree.map(jnp.zeros_like, rs.global_adapters),
        "transform_templates": [jax.tree.map(jnp.zeros_like,
                                             rs.global_adapters)],
    }


def test_golden_run_state_layout_pinned():
    # the committed fixture must load with today's code and carry exactly
    # the documented npz paths — renames/additions are format changes
    want_keys = {
        "__nonce__", "rng_key",
        "global/layer0/A", "global/layer0/B",
        "client/0/adapters/layer0/A", "client/0/adapters/layer0/B",
        "client/0/opt/mu/layer0/A", "client/0/opt/mu/layer0/B",
        "client/0/opt/nu/layer0/A", "client/0/opt/nu/layer0/B",
        "client/0/opt/step",
        "client/0/fisher/layer0/A", "client/0/fisher/layer0/B",
        "client/1/adapters/layer0/A", "client/1/adapters/layer0/B",
        "client/1/opt/mu/layer0/A", "client/1/opt/mu/layer0/B",
        "client/1/opt/nu/layer0/A", "client/1/opt/nu/layer0/B",
        "client/1/opt/step",
        "tstate/0/0/layer0/A", "tstate/0/0/layer0/B",
    }
    data = np.load(os.path.join(GOLDEN_DIR, "run_state.npz"))
    assert set(data.files) == want_keys

    meta = read_run_meta(GOLDEN_DIR)
    assert meta["format_version"] == RUN_STATE_VERSION
    assert meta["engine"] == "sequential"
    assert meta["strategy"] == "fedavg"
    assert meta["round_idx"] == 2
    assert meta["cfg_name"] == "golden-fixture"
    assert meta["tstate_present"] == [[True], [False]]

    want, refs = _golden_refs()
    rs = load_run_state(GOLDEN_DIR, **refs)
    assert tree_allclose(rs.global_adapters, want.global_adapters)
    for got, exp in zip(rs.clients, want.clients):
        assert got.cid == exp.cid
        assert got.rounds_participated == exp.rounds_participated
        assert tree_allclose(got.adapters, exp.adapters)
        assert tree_allclose(got.opt_state.mu, exp.opt_state.mu)
    assert rs.clients[0].fisher is not None
    assert rs.clients[1].fisher is None
    assert rs.comm_rounds == want.comm_rounds
    assert rs.round_metrics == want.round_metrics


def test_run_state_torn_write_detected(tmp_path):
    want, refs = _golden_refs()
    d = str(tmp_path / "rs")
    save_run_state(d, want)
    meta_path = os.path.join(d, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    # simulate a crash between the npz and meta.json of DIFFERENT saves
    meta["nonce"] = "sequential:99:99:0"
    meta["round_idx"] = 99
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(CheckpointError, match="torn checkpoint"):
        load_run_state(d, **refs)


def test_run_state_version_mismatch(tmp_path):
    want, _ = _golden_refs()
    d = str(tmp_path / "rs")
    save_run_state(d, want)
    meta_path = os.path.join(d, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["format_version"] = RUN_STATE_VERSION + 1
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(CheckpointVersionError):
        read_run_meta(d)


@pytest.mark.smoke
def test_resolve_run_state_dir(tmp_path):
    want, _ = _golden_refs()
    root = str(tmp_path / "ckpts")
    sub = os.path.join(root, "round_000002")
    save_run_state(sub, want)
    with open(os.path.join(root, "LATEST"), "w") as f:
        f.write("round_000002")
    assert resolve_run_state_dir(root) == sub       # via LATEST
    assert resolve_run_state_dir(sub) == sub        # direct
    with pytest.raises(CheckpointError, match="no run-state checkpoint"):
        resolve_run_state_dir(str(tmp_path / "nowhere"))


def test_run_state_client_count_mismatch(tmp_path):
    want, refs = _golden_refs()
    d = str(tmp_path / "rs")
    save_run_state(d, want)
    refs["clients_ref"] = refs["clients_ref"][:1]
    with pytest.raises(CheckpointError, match="clients"):
        load_run_state(d, **refs)

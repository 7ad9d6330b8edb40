"""The sharded round engine (`engine="sharded"`) and its satellites.

Two layers of coverage:

* In-process tests on a 1-device mesh — the mesh/shard_map/padding/pipeline
  machinery all runs (a 1-device mesh is a degenerate but complete mesh),
  so parity here is bitwise and fast. This is where the padding-inertness,
  batched-init, resume, and validation cases live.
* One subprocess test that forces an 8-device CPU topology via XLA_FLAGS
  (must be set before jax initializes, so it can't run in this process —
  tests/conftest.py pins the real 1-CPU topology) and checks all six paper
  strategies against the committed golden, an uneven K=5 cohort, and
  checkpoint/resume. See tests/_sharded_subproc.py.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import HyperParams, run_federated
from repro.core import client as client_lib
from repro.core.aggregation import _norm_weights, fedavg
from repro.data import make_federated_data
from repro.sharding import CLIENT_AXIS, client_mesh, pad_to_multiple
from repro.strategies.base import Strategy, get_strategy
from repro.utils import tree_sq_norm

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("llava-1.5-7b").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, frontend_dim=32,
    )
    train, evald, _ = make_federated_data(
        cfg, n_clients=4, examples_per_client=16, alpha=1.0, batch_size=4,
        seq_len=16,
    )
    return cfg, train, evald


def _run(cfg, train, evald, strategy, *, rounds=2, **kw):
    hp = HyperParams(lr=5e-3, local_steps=2, fisher_batches=2)
    return run_federated(jax.random.PRNGKey(0), cfg, train, evald,
                         strategy=strategy, rounds=rounds, hp=hp, **kw)


def _tree_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------

def test_client_mesh_shape():
    mesh = client_mesh()
    assert mesh.axis_names == (CLIENT_AXIS,)
    assert mesh.size == jax.device_count()


def test_client_mesh_too_many_devices():
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        client_mesh(jax.device_count() + 1)


def test_pad_to_multiple():
    assert pad_to_multiple(5, 8) == 8
    assert pad_to_multiple(8, 8) == 8
    assert pad_to_multiple(9, 8) == 16
    assert pad_to_multiple(0, 8) == 0
    with pytest.raises(ValueError):
        pad_to_multiple(3, 0)


# ---------------------------------------------------------------------------
# sharded engine, 1-device mesh: bitwise parity with vmap
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_sharded_matches_vmap_one_device():
    cfg = get_smoke_config("llava-1.5-7b").with_(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
        d_ff=64, frontend_dim=16,
    )
    train, evald, _ = make_federated_data(
        cfg, n_clients=2, examples_per_client=4, alpha=1.0, batch_size=2,
        seq_len=8,
    )
    hp = HyperParams(lr=5e-3, local_steps=1, fisher_batches=1)
    a = run_federated(jax.random.PRNGKey(0), cfg, train, evald,
                      strategy="fednano", rounds=2, hp=hp, engine="vmap")
    b = run_federated(jax.random.PRNGKey(0), cfg, train, evald,
                      strategy="fednano", rounds=2, hp=hp, engine="sharded")
    # on a 1-device mesh the shard_map body IS the vmap body, so compute is
    # bitwise identical; the device-side stacked aggregation reorders the
    # f32 merge sums (tensordot over the client axis vs per-client folds),
    # so everything downstream of the first merge agrees to float
    # tolerance, not bitwise
    np.testing.assert_allclose(
        [m["mean_loss"] for m in a.round_metrics],
        [m["mean_loss"] for m in b.round_metrics], rtol=1e-6)
    assert a.comm_totals == b.comm_totals
    assert all(m["cohort_devices"] == [1] for m in b.round_metrics)
    assert all("cohort_devices" not in m for m in a.round_metrics)
    np.testing.assert_allclose(a.avg_accuracy, b.avg_accuracy, rtol=1e-6)
    for x, y in zip(jax.tree.leaves(a.server.global_adapters),
                    jax.tree.leaves(b.server.global_adapters)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-7)


def test_sharded_overlap_off_identical(setup):
    cfg, train, evald = setup
    a = _run(cfg, train, evald, "fednano", engine="sharded", overlap=True)
    b = _run(cfg, train, evald, "fednano", engine="sharded", overlap=False)
    # the double buffer changes only WHEN results are collected, never what
    # is computed or the order offers reach aggregation
    assert [m["mean_loss"] for m in a.round_metrics] == \
           [m["mean_loss"] for m in b.round_metrics]
    assert a.comm_totals == b.comm_totals
    assert _tree_equal(a.server.global_adapters, b.server.global_adapters)


def test_devices_arg_rejected_on_other_engines(setup):
    cfg, train, evald = setup
    with pytest.raises(ValueError, match="devices"):
        _run(cfg, train, evald, "fednano", engine="vmap", devices=1)


# ---------------------------------------------------------------------------
# padding rows: provably inert
# ---------------------------------------------------------------------------

def test_padding_rows_inert_in_states_and_metrics(setup):
    """local_update_many(pad_to=N) must return exactly the unpadded result:
    the duplicated tail rows compute but never escape collect_cohort."""
    cfg, train, _ = setup
    hp = HyperParams(lr=5e-3, local_steps=2, fisher_batches=2)
    strat = get_strategy("fednano")
    mesh = client_mesh(1)
    k_server, k_clients = jax.random.split(jax.random.PRNGKey(0))
    from repro.core import server as server_lib

    server = server_lib.init_server(k_server, cfg)
    cids = sorted(train)[:3]  # 3 clients, padded to 4
    ckeys = jax.random.split(k_clients, len(cids))
    states = [strat.init_client(ck, cfg, cid, n_examples=len(train[cid]))
              for ck, cid in zip(ckeys, cids)]
    blists = [train[c] for c in cids]

    plain, pm = client_lib.local_update_many(
        cfg, server.backbone, states, blists, hp, strat,
        server.global_adapters, mesh=mesh)
    padded, qm = client_lib.local_update_many(
        cfg, server.backbone, states, blists, hp, strat,
        server.global_adapters, mesh=mesh, pad_to=4)
    assert len(padded) == len(plain) == 3
    assert pm == qm
    for s_plain, s_pad in zip(plain, padded):
        assert _tree_equal(s_plain.adapters, s_pad.adapters)
        assert _tree_equal(s_plain.fisher, s_pad.fisher)
        assert s_plain.rounds_participated == s_pad.rounds_participated


def test_pad_to_validation(setup):
    cfg, train, _ = setup
    hp = HyperParams(lr=5e-3, local_steps=2, fisher_batches=2)
    strat = get_strategy("fedavg")
    mesh = client_mesh(1)
    cids = sorted(train)[:3]
    ckeys = jax.random.split(jax.random.PRNGKey(1), len(cids))
    states = [strat.init_client(ck, cfg, cid, n_examples=len(train[cid]))
              for ck, cid in zip(ckeys, cids)]
    with pytest.raises(ValueError, match="smaller than the cohort"):
        client_lib.prepare_cohort(
            cfg, states, [train[c] for c in cids], hp, strat,
            mesh=mesh, pad_to=2)


def test_zero_weight_rows_inert_in_aggregation():
    """A zero-weight row contributes exactly nothing to the weighted merge
    (x + 0.0*y == x bitwise for finite y), and an all-zero weight vector
    falls back to uniform instead of emitting NaN."""
    key = jax.random.PRNGKey(7)
    thetas = [{"w": jax.random.normal(jax.random.fold_in(key, i), (4, 3))}
              for i in range(3)]
    merged = fedavg(thetas[:2], [2.0, 3.0])
    with_zero = fedavg(thetas, [2.0, 3.0, 0.0])
    assert np.array_equal(np.asarray(merged["w"]), np.asarray(with_zero["w"]))

    w = _norm_weights([0.0, 0.0], 2)
    assert np.all(np.isfinite(np.asarray(w)))
    assert np.asarray(w) == pytest.approx([0.5, 0.5])


# ---------------------------------------------------------------------------
# checkpoint / resume on the sharded engine
# ---------------------------------------------------------------------------

def test_sharded_checkpoint_resume(setup, tmp_path):
    cfg, train, evald = setup
    full = _run(cfg, train, evald, "fednano", engine="sharded", rounds=3)
    ck = str(tmp_path / "state")
    _run(cfg, train, evald, "fednano", engine="sharded", rounds=2,
         checkpoint_dir=ck, checkpoint_every=1)
    resumed = _run(cfg, train, evald, "fednano", engine="sharded", rounds=3,
                   resume=ck)
    lf = [m["mean_loss"] for m in full.round_metrics]
    lr_ = [m["mean_loss"] for m in resumed.round_metrics]
    assert lf == pytest.approx(lr_, rel=1e-6)
    assert full.comm_totals == resumed.comm_totals
    assert float(tree_sq_norm(full.server.global_adapters)) == pytest.approx(
        float(tree_sq_norm(resumed.server.global_adapters)), rel=1e-6)


# ---------------------------------------------------------------------------
# batched client init (satellite: vmapped init_clients fast path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["fednano", "feddpa_f"])
def test_batched_init_bitwise_matches_loop(setup, name):
    """The stacked fast path must be bit-identical to K init_client calls —
    jax.random is counter-based, so vmapped draws equal sequential draws."""
    cfg, train, _ = setup
    strat = get_strategy(name)
    cids = sorted(train)
    keys = jax.random.split(jax.random.PRNGKey(3), len(cids))
    n_ex = [len(train[c]) for c in cids]
    fast = strat.init_clients(keys, cfg, cids, n_ex)
    slow = [strat.init_client(k, cfg, c, n)
            for k, c, n in zip(keys, cids, n_ex)]
    for f, s in zip(fast, slow):
        assert f.cid == s.cid and f.n_examples == s.n_examples
        assert _tree_equal(f.adapters, s.adapters)
        assert _tree_equal(f.opt_state, s.opt_state)
        if strat.dual_adapters:
            assert _tree_equal(f.local_adapters, s.local_adapters)
        else:
            assert f.local_adapters is None and s.local_adapters is None


def test_batched_init_falls_back_for_custom_strategies(setup):
    """A strategy overriding init_client (ragged/custom state) must take the
    per-client loop, not the stacked fast path."""
    cfg, train, _ = setup
    calls = []

    class Ragged(Strategy):
        def init_client(self, key, cfg, cid, n_examples):
            calls.append(cid)
            return Strategy.init_client(self, key, cfg, cid, n_examples)

    strat = Ragged()
    cids = sorted(train)
    keys = jax.random.split(jax.random.PRNGKey(3), len(cids))
    out = strat.init_clients(keys, cfg, cids, [len(train[c]) for c in cids])
    assert calls == cids  # fallback loop hit every client
    assert [s.cid for s in out] == cids


# ---------------------------------------------------------------------------
# buffered engine: seeded failure draws (satellite)
# ---------------------------------------------------------------------------

def test_buffered_failure_counters_deterministic(setup):
    from repro.core.failures import FailureModel

    cfg, train, evald = setup
    fm = FailureModel(dropout_prob=0.4, crash_prob=0.2, straggler_prob=0.3,
                      seed=11)
    kw = dict(engine="buffered", buffer_size=2, failures=fm, rounds=3)
    a = _run(cfg, train, evald, "fednano", **kw)
    b = _run(cfg, train, evald, "fednano", **kw)
    assert a.round_metrics == b.round_metrics  # seeded draws: exact replay
    for m in a.round_metrics:
        for key in ("dropped", "crashed", "straggled"):
            assert key in m and m[key] >= 0
    # with these probabilities at least one failure of each kind must show
    # up across 3 merges of 4 clients — otherwise the wiring is dead
    assert sum(m["dropped"] for m in a.round_metrics) > 0
    assert sum(m["crashed"] for m in a.round_metrics) > 0
    assert sum(m["straggled"] for m in a.round_metrics) > 0


def test_buffered_failure_resume_replay(setup, tmp_path):
    from repro.core.failures import FailureModel

    cfg, train, evald = setup
    fm = FailureModel(dropout_prob=0.3, crash_prob=0.2, straggler_prob=0.3,
                      seed=5)
    kw = dict(engine="buffered", buffer_size=2, failures=fm)
    full = _run(cfg, train, evald, "fednano", rounds=3, **kw)
    ck = str(tmp_path / "state")
    _run(cfg, train, evald, "fednano", rounds=2, checkpoint_dir=ck,
         checkpoint_every=1, **kw)
    resumed = _run(cfg, train, evald, "fednano", rounds=3, resume=ck, **kw)
    assert full.round_metrics == resumed.round_metrics
    assert full.comm_totals == resumed.comm_totals


# ---------------------------------------------------------------------------
# 8-device subprocess: six-strategy golden parity, uneven cohorts, resume
# ---------------------------------------------------------------------------

def test_sharded_eight_devices_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (os.path.join(HERE, "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_sharded_subproc.py")],
        env=env, capture_output=True, text=True, timeout=1200)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0, \
        f"8-device sharded checks failed:\n{proc.stdout}\n{proc.stderr}"

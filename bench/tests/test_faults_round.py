"""Round cells: a whole run at test size, past the look for a chip, with
the timed path broken underneath: ``correct`` must come out false for
every fault a round cell can have, and true for the sound program.

Faults of a round cell: a local step that returns its state unchanged;
half of the batch left out and the mean taken over the rest (for batches
of one row, half of the cohort left out of the merge); the round's answer
(the merged global adapters) altered where it is produced, its update
applied twice. The exchange between chips is not a fault these one-chip
cells can have. (The serve cell's faults are in test_faults_serve.py.)
"""
import jax
import pytest

from bench.tests.cells import run_tiny, tiny_cell

ROUND_CELLS = ["round.qwen2-vl-72b.silo-vqa", "round.qwen1.5-4b.xdevice"]
SEED = 3 * 2**31 + 7


@pytest.fixture(autouse=True)
def fresh_programs():
    """Programs traced before a fault was planted must not be reused."""
    from repro.core import client

    def clear():
        jax.clear_caches()
        for make in (client.make_many_update, client.make_train_step,
                     client.make_fisher_grad, client.make_local_adapter_step):
            make.cache_clear()

    clear()
    yield
    clear()


@pytest.mark.parametrize("name", ROUND_CELLS)
def test_sound_run_is_correct(name):
    result, checks = run_tiny(tiny_cell(name), SEED)
    assert result["correct"], checks
    assert result["failed"] == 0


def _state_unchanged(mp):
    from repro.core import client

    mp.setattr(client, "adamw_update",
               lambda grads, state, params, **kw: (params, state))


def _half_batch(mp, cell):
    from repro.core import adapters, aggregation
    from repro.core.types import Batch

    if cell.traffic["batch"] >= 2:
        loss = adapters.fednano_loss

        def half(cfg, backbone, adp, batch):
            n = batch.tokens.shape[0] // 2
            return loss(cfg, backbone, adp, Batch(*(None if x is None else x[:n]
                                                    for x in batch)))

        mp.setattr(adapters, "fednano_loss", half)
    else:
        merge = aggregation.fisher_merge

        def half(thetas, fishers, sizes=None, **kw):
            n = max(1, len(thetas) // 2)
            return merge(thetas[:n], fishers[:n], None if sizes is None else sizes[:n], **kw)

        mp.setattr(aggregation, "fisher_merge", half)


def _answer_altered(mp):
    from repro.core import server

    aggregate = server.server_aggregate

    def twice(srv, *a, **kw):
        before = srv.global_adapters
        out = aggregate(srv, *a, **kw)
        doubled = jax.tree.map(lambda n, o: 2 * n - o, out.global_adapters, before)
        out.global_adapters = doubled
        return out

    mp.setattr(server, "server_aggregate", twice)


@pytest.mark.parametrize("name", ROUND_CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "answer_altered"])
def test_round_faults_are_caught(name, fault, monkeypatch):
    cell = tiny_cell(name)
    if fault == "state_unchanged":
        _state_unchanged(monkeypatch)
    elif fault == "half_batch":
        _half_batch(monkeypatch, cell)
    else:
        _answer_altered(monkeypatch)
    result, checks = run_tiny(cell, SEED)
    assert not result["correct"], checks


def test_readings_take_the_worst_leaf_and_client():
    import numpy as np

    from bench.kinds import round as rk

    def tree(vals):
        names = [("text", "down"), ("text", "up"), ("image", "down"), ("image", "up")]
        out = {}
        for (mod, leaf), v in zip(names, vals):
            out.setdefault(mod, {})[leaf] = np.array([v])
        return out

    keep = ["text.down", "text.up", "image.down", "image.up"]
    zero = tree([0.0] * 4)
    ref = {"loss": [10.0, 8.0], "start": [zero, zero],
           "global": [tree([1.0] * 4), tree([1.0] * 4)], "keep": keep}
    got = {"loss": [10.05, 8.0], "start": [zero, zero],
           "global": [tree([1.01, 1.002, 1.003, 1.0]), tree([1.02, 1.0, 1.0, 1.0])]}
    r = rk.compare(got, ref)
    assert r["loss"] == pytest.approx(0.005)
    assert r["global_delta"] == pytest.approx(0.02)
    # two clients: gaps 0.01, 0.002, 0.003, 0 and 0.02, 0.004, 0.001, 0.001
    prog = [tree([1.01, 1.002, 1.003, 1.0]), tree([2.04, 2.008, 1.998, 2.002])]
    want = [tree([1.0] * 4), tree([2.0] * 4)]
    assert rk.fisher_gap(prog, want, keep) == pytest.approx(0.02)
    assert rk.fisher_gap(want, want, keep) == 0.0

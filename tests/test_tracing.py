"""Program spans: the round engine's and the serving engine's host spans.

A tiny run of each under ``jax.profiler`` on the CPU, read back from the
profiler's own trace: every span is there, children lie inside their
parents, and the spans' counts (bytes between host and device, adapter
cache hits) equal what the shapes and the cache's own counters say.
"""
import glob
import os
from dataclasses import dataclass
from typing import Dict, List

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import HyperParams, run_federated
from repro.core import adapters as nano
from repro.data import make_federated_data
from repro.models import model as model_lib
from repro.serving import Request, ServingEngine
from repro.utils import device_bytes, host_bytes, tree_bytes


@dataclass
class Span:
    name: str
    start: float
    end: float
    args: Dict

    def inside(self, other: "Span") -> bool:
        return other.start <= self.start and self.end <= other.end


def traced(fn, trace_dir) -> List[Span]:
    """Run ``fn`` under the profiler; the ``fednano.*`` host spans it left."""
    with jax.profiler.trace(str(trace_dir)):
        fn()
    path = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("fednano."):
                    out.append(Span(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return sorted(out, key=lambda s: s.start)


def named(spans, name):
    return [s for s in spans if s.name == name]


def parent(span, spans, name):
    """The one ``name`` span that holds ``span``."""
    holders = [p for p in named(spans, name) if span.inside(p)]
    assert len(holders) == 1, (span, holders)
    return holders[0]


# ---------------------------------------------------------------------------
# round engine
# ---------------------------------------------------------------------------

K, STEPS, FISHER, ROUNDS = 4, 2, 2, 2


@pytest.fixture(scope="module")
def fed():
    cfg = get_smoke_config("llava-1.5-7b").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, frontend_dim=32)
    train, _, _ = make_federated_data(
        cfg, n_clients=K, examples_per_client=16, alpha=1.0, batch_size=4,
        seq_len=16)
    hp = HyperParams(local_steps=STEPS, fisher_batches=FISHER)
    return cfg, train, hp


def _run(fed, tmp_path, engine):
    cfg, train, hp = fed
    box = {}

    def go():
        box["res"] = run_federated(
            jax.random.PRNGKey(0), cfg, train, {}, strategy="fednano",
            rounds=ROUNDS, hp=hp, engine=engine, final_eval=False,
            checkpoint_dir=str(tmp_path / "ckpt"), checkpoint_every=1)

    return traced(go, tmp_path / "trace"), box["res"]


def test_vmap_round_spans_nest_and_count_the_bytes_they_move(fed, tmp_path):
    cfg, train, hp = fed
    spans, res = _run(fed, tmp_path, "vmap")
    rounds = named(spans, "fednano.round")
    assert [s.args for s in rounds] == [{"round": r, "clients": K}
                                        for r in range(ROUNDS)]
    phases = ["prepare", "launch", "wait", "unstack", "offer", "merge",
              "checkpoint"]
    assert {s.name for s in spans} == {"fednano.round"} | {
        f"fednano.round.{p}" for p in phases}
    for s in spans:
        if s.name != "fednano.round":
            parent(s, spans, "fednano.round")
    for r in rounds:
        inner = [s.name.rsplit(".", 1)[1] for s in spans
                 if s is not r and s.inside(r)]
        assert inner == phases        # one cohort chunk: each phase once, in order

    # what crosses, reckoned from the shapes of what each phase stacks
    batches = [b for cid in sorted(train)
               for b in [train[cid][t % len(train[cid])] for t in range(STEPS)]
               + train[cid][:FISHER]]
    state = res.clients[0]
    adapter_b, opt_b = tree_bytes(state.adapters), tree_bytes(state.opt_state)
    assert tree_bytes(state.fisher) == adapter_b
    want = {
        "fednano.round.prepare": {"clients": K,
                                  "bytes_to_device": tree_bytes(batches) + K * opt_b,
                                  "bytes_to_host": device_bytes(batches)},
        # adapters, AdamW state, Fisher diagonals, and the (K, T) f32 losses
        "fednano.round.unstack": {"bytes_to_host": K * (2 * adapter_b + opt_b)
                                  + K * STEPS * 4},
        # each client's adapters and Fisher diagonals go to the merge
        "fednano.round.merge": {"bytes_to_device": 2 * K * adapter_b},
        "fednano.round.offer": {"clients": K},
    }
    assert device_bytes(batches) == tree_bytes(batches) > 0
    for name, args in want.items():
        assert [s.args for s in named(spans, name)] == [args] * ROUNDS, name


def test_sharded_round_keeps_state_on_the_device(fed, tmp_path):
    spans, _ = _run(fed, tmp_path, "sharded")
    assert {s.name for s in spans} == {
        "fednano.round", "fednano.round.prepare", "fednano.round.launch",
        "fednano.round.wait", "fednano.round.merge", "fednano.round.checkpoint"}
    for s in spans:
        if s.name != "fednano.round":
            parent(s, spans, "fednano.round")
    prep = named(spans, "fednano.round.prepare")
    assert prep[0].args["bytes_to_device"] > 0
    # the checkpoint pulled the resident rows back, so round 1 stacks again
    assert prep[1].args["bytes_to_device"] > 0
    # the round-end loss gather: one (K, T) f32 array, and the device-side
    # merge moves nothing
    assert [s.args for s in named(spans, "fednano.round.wait")] == [
        {"bytes_to_host": K * STEPS * 4}] * ROUNDS
    assert [s.args for s in named(spans, "fednano.round.merge")] == [
        {"bytes_to_device": 0}] * ROUNDS


def test_host_and_device_bytes_split_a_tree_by_where_its_leaves_live():
    tree = {"a": np.zeros((3, 4), np.float32), "b": jax.numpy.ones(5, jax.numpy.int32),
            "c": np.float32(1.0), "d": None}
    assert host_bytes(tree) == 48 + 4
    assert device_bytes(tree) == 20
    assert host_bytes(tree) + device_bytes(tree) == tree_bytes(tree)


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

def test_serving_spans_nest_and_carry_the_cache_counters(tmp_path):
    cfg = get_smoke_config("h2o-danube-1.8b")
    key = jax.random.PRNGKey(0)
    backbone = model_lib.init_backbone(key, cfg)
    tenants = {t: nano.init_nanoedge(jax.random.fold_in(key, i), cfg)
               for i, t in enumerate("abc")}
    eng = ServingEngine(cfg, backbone, max_slots=2, prefill_len=8,
                        max_new_tokens=6, adapter_slots=2,
                        adapter_loader=tenants.__getitem__)
    rng = np.random.default_rng(0)
    order = ["a", "b", "a", "c", None, "b", "a"]
    reqs = [Request(rid=i, tenant=t, max_new_tokens=3 + i % 3,
                    prompt=rng.integers(0, cfg.vocab_size, 2 + i).astype(np.int32))
            for i, t in enumerate(order)]
    spans = traced(lambda: eng.run(reqs), tmp_path)

    admits = named(spans, "fednano.serve.admit")
    assert [s.args for s in admits] == [
        {"rid": r.rid, "tenant": str(r.tenant), "prompt_len": len(r.prompt)}
        for r in reqs]
    for child in ("adapter", "prefill", "prefill.wait", "page_write"):
        for s in named(spans, f"fednano.serve.{child}"):
            parent(s, spans, "fednano.serve.admit")
    for a in admits:
        inner = [s.name for s in spans if s is not a and s.inside(a)]
        assert inner == ["fednano.serve.adapter", "fednano.serve.prefill",
                         "fednano.serve.prefill.wait", "fednano.serve.page_write"]

    # the adapter spans' arguments are the cache's own counts, event by event
    acq = [s.args for s in named(spans, "fednano.serve.adapter")]
    c = eng.cache
    assert sum(a["hit"] for a in acq) == c.hits
    assert sum(a["miss"] for a in acq) == c.misses
    assert sum(a["evicted"] for a in acq) == c.evictions
    assert c.hits > 0 and c.misses > 0 and c.evictions > 0
    assert acq[4] == {"hit": 0, "miss": 0, "evicted": 0}   # no tenant: identity

    decodes = named(spans, "fednano.serve.decode")
    assert [s.args["step"] for s in decodes] == list(range(eng.stats["decode_steps"]))
    # every decode step and page write consumed the pool it was given
    writes = named(spans, "fednano.serve.page_write")
    assert all(s.args["donated"] == 1 for s in decodes + writes)
    assert len(decodes + writes) == eng.stats["pool_donations"]
    assert sum(s.args["live"] for s in decodes) == eng.stats["occupancy_sum"]
    for d in decodes:
        inner = [s.name for s in spans if s is not d and s.inside(d)]
        assert inner == ["fednano.serve.decode.dispatch", "fednano.serve.decode.wait",
                         "fednano.serve.decode.bookkeep"]

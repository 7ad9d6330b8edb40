"""Subprocess body for the 8-device sharded-engine tests.

The main test session runs on the real 1-CPU topology (tests/conftest.py),
and a forced multi-device topology must be set via XLA_FLAGS *before* jax
first initializes — so tests/test_sharded.py runs this file in a fresh
interpreter with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.

Prints one line per check: ``OK <name>`` or ``FAIL <name>: <detail>``, and
exits non-zero if anything failed. Checks:

  * all six paper strategies on ``engine="sharded"`` (D=8) against the
    committed golden (rel 1e-6 on losses / accuracy / comm bytes; adapter
    sq-norms at 2e-5 — squaring near-zero adapters doubles the relative
    error of the per-device XLA fusion differences)
  * an uneven cohort (K=5 on D=8 → padded rows) against ``engine="vmap"``:
    identical comm byte counts prove the padding rows move zero bytes and
    never enter aggregation
  * checkpoint/resume replay parity on the sharded engine
"""
import json
import os
import sys
import tempfile

import jax

from repro.configs import get_smoke_config
from repro.core import HyperParams, run_federated
from repro.data import make_federated_data
from repro.utils import tree_sq_norm

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "strategy_parity.json")
STRATEGIES = ("fednano", "fednano_ef", "fedavg", "fedprox", "feddpa_f", "locft")

failures = []


def check(name, cond, detail=""):
    if cond:
        print(f"OK {name}")
    else:
        failures.append(name)
        print(f"FAIL {name}: {detail}")


def rel(a, b):
    return abs(a - b) / max(abs(a), abs(b), 1e-12)


def make(n_clients):
    cfg = get_smoke_config("llava-1.5-7b").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, frontend_dim=32)
    train, evald, _ = make_federated_data(
        cfg, n_clients=n_clients, examples_per_client=16, alpha=1.0,
        batch_size=4, seq_len=16)
    return cfg, train, evald


def run(cfg, train, evald, strategy, rounds=2, **kw):
    hp = HyperParams(lr=5e-3, local_steps=2, fisher_batches=2)
    return run_federated(jax.random.PRNGKey(0), cfg, train, evald,
                         strategy=strategy, rounds=rounds, hp=hp, **kw)


def main():
    check("device_count", jax.device_count() == 8,
          f"got {jax.device_count()} devices")

    with open(GOLDEN) as f:
        golden = json.load(f)

    # --- six-strategy golden parity, even cohort (K=4 on D=8: padded) ------
    cfg, train, evald = make(4)
    for strategy in STRATEGIES:
        res = run(cfg, train, evald, strategy, engine="sharded")
        want = golden[strategy]
        got_losses = [m["mean_loss"] for m in res.round_metrics]
        bad = []
        if any(rel(g, w) > 1e-6 for g, w in zip(got_losses, want["round_losses"])):
            bad.append(f"losses {got_losses} vs {want['round_losses']}")
        if rel(res.avg_accuracy, want["avg_accuracy"]) > 1e-6:
            bad.append(f"acc {res.avg_accuracy} vs {want['avg_accuracy']}")
        spans = [m["cohort_devices"] for m in res.round_metrics]
        if any(s != [8] for s in spans):
            bad.append(f"cohorts span {spans} devices, not [8] each round")
        if {str(k): v for k, v in res.comm_totals.items()} != \
                {k: v for k, v in want["comm_totals"].items()}:
            bad.append(f"comm {res.comm_totals} vs {want['comm_totals']}")
        if rel(float(tree_sq_norm(res.server.global_adapters)),
               want["global_sq_norm"]) > 2e-5:
            bad.append("global_sq_norm")
        if rel(float(tree_sq_norm(res.clients[0].adapters)),
               want["client0_sq_norm"]) > 2e-5:
            bad.append("client0_sq_norm")
        check(f"golden:{strategy}", not bad, "; ".join(bad))

    # --- uneven cohort (K=5 on D=8): padding inert vs vmap ------------------
    cfg5, train5, evald5 = make(5)
    for strategy in ("fednano", "feddpa_f"):
        a = run(cfg5, train5, evald5, strategy, engine="vmap")
        b = run(cfg5, train5, evald5, strategy, engine="sharded")
        bad = []
        if a.comm_totals != b.comm_totals:
            bad.append(f"comm {a.comm_totals} vs {b.comm_totals} — padding "
                       "rows leaked into byte accounting")
        la = [m["mean_loss"] for m in a.round_metrics]
        lb = [m["mean_loss"] for m in b.round_metrics]
        if any(rel(x, y) > 1e-6 for x, y in zip(la, lb)):
            bad.append(f"losses {la} vs {lb}")
        if any(x["participants"] != y["participants"]
               for x, y in zip(a.round_metrics, b.round_metrics)):
            bad.append("participant counts differ — padding rows counted")
        if rel(a.avg_accuracy, b.avg_accuracy) > 1e-6:
            bad.append("accuracy")
        check(f"uneven:{strategy}", not bad, "; ".join(bad))

    # --- checkpoint/resume on the sharded engine ----------------------------
    with tempfile.TemporaryDirectory() as td:
        full = run(cfg, train, evald, "fednano", engine="sharded")
        ck = os.path.join(td, "state")
        run(cfg, train, evald, "fednano", engine="sharded",
            checkpoint_dir=ck, checkpoint_every=1, rounds=1)
        resumed = run_federated(
            jax.random.PRNGKey(0), cfg, train, evald, strategy="fednano",
            rounds=2, hp=HyperParams(lr=5e-3, local_steps=2, fisher_batches=2),
            engine="sharded", resume=ck)
        lf = [m["mean_loss"] for m in full.round_metrics]
        lr_ = [m["mean_loss"] for m in resumed.round_metrics]
        bad = []
        if any(rel(x, y) > 1e-6 for x, y in zip(lf, lr_)):
            bad.append(f"losses {lf} vs {lr_}")
        if rel(float(tree_sq_norm(full.server.global_adapters)),
               float(tree_sq_norm(resumed.server.global_adapters))) > 2e-5:
            bad.append("global_sq_norm")
        check("resume:sharded", not bad, "; ".join(bad))

    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

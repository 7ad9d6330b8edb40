"""Serving benchmark: continuous-batching engine vs naive per-request loop.

Measures mixed-tenant decode throughput (tokens/s) at growing tenant
counts. The model is smoke-scale (h2o-danube, d=256, 2 layers), so
absolute tok/s is meaningless — what the sweep shows is the
*orchestration* win: the naive loop runs one B=1 jitted decode step per
token with a host-Python adapter apply between steps, while the engine
amortizes one fixed-shape batched step over all occupied slots and folds
the per-tenant adapter math into the same jit (grouped LoRA).

Timing protocol (per tenant count):

  1. warmup run of the FULL workload for both paths — pays every
     compilation (the naive loop compiles one prefill per distinct prompt
     length; the engine exactly one prefill + one decode shape), discarded
  2. timed fresh run of the identical workload; throughput = total
     generated tokens / wall

Token parity between the two paths is asserted on every run — a bench
that drifts from the exactness contract is a bug, not a result.

Usage:
    PYTHONPATH=src python benchmarks/serve_bench.py           # full sweep
    PYTHONPATH=src python benchmarks/serve_bench.py --quick   # wiring check
    PYTHONPATH=src python benchmarks/serve_bench.py --tenants 8

Full runs merge results into BENCH_serve.json at the repo root (existing
entries for re-run tenant counts are replaced).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src"))

import jax

from repro.configs import get_smoke_config
from repro.launch.serve import make_requests, synth_tenant_adapters
from repro.models import model as model_lib
from repro.serving import ServingEngine, generate_naive

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
OUT = os.path.join(ROOT, "BENCH_serve.json")

ARCH = "h2o-danube-1.8b"
SLOTS = 8
PREFILL_LEN = 16
GEN_TOKENS = 16


def bench_tenants(cfg, backbone, n_tenants, n_requests, gen_tokens):
    tenants = [f"tenant{i}" for i in range(n_tenants)]
    adapters = synth_tenant_adapters(jax.random.PRNGKey(0), cfg, tenants)
    reqs = make_requests(cfg, tenants, n_requests, PREFILL_LEN, gen_tokens,
                         seed=0)

    engine = ServingEngine(
        cfg, backbone, max_slots=SLOTS, prefill_len=PREFILL_LEN,
        max_new_tokens=gen_tokens, adapter_slots=max(SLOTS, 8),
        adapter_loader=adapters.__getitem__)
    engine.run(reqs)                       # warmup: compiles, discarded
    engine.stats = dict.fromkeys(engine.stats, 0)
    t0 = time.time()
    got = engine.run(reqs)
    t_engine = time.time() - t0

    generate_naive(cfg, backbone, reqs, adapters)   # warmup (per-length jits)
    t0 = time.time()
    ref = generate_naive(cfg, backbone, reqs, adapters)
    t_naive = time.time() - t0

    mismatch = [r.rid for r in reqs if got[r.rid].tokens != ref[r.rid].tokens]
    if mismatch:
        raise SystemExit(f"token mismatch engine vs naive: rids {mismatch}")

    n_tok = sum(len(c.tokens) for c in got.values())
    row = {
        "tenants": n_tenants,
        "requests": n_requests,
        "gen_tokens": gen_tokens,
        "total_tokens": n_tok,
        "engine_s": round(t_engine, 4),
        "naive_s": round(t_naive, 4),
        "engine_tok_s": round(n_tok / t_engine, 2),
        "naive_tok_s": round(n_tok / t_naive, 2),
        "speedup": round(t_naive / t_engine, 2),
        "mean_occupancy": round(engine.mean_occupancy(), 2),
    }
    print(f"  tenants={n_tenants:>3}  reqs={n_requests:>4}  "
          f"engine={row['engine_tok_s']:8.1f} tok/s  "
          f"naive={row['naive_tok_s']:8.1f} tok/s  "
          f"speedup={row['speedup']:.2f}x  occ={row['mean_occupancy']}")
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tenants", default=None,
                    help="comma-separated tenant counts (default 1,8,64)")
    ap.add_argument("--quick", action="store_true",
                    help="tiny workload, no JSON written — wiring check")
    ap.add_argument("--out", default=None,
                    help=f"output JSON (default {OUT}; --quick skips writing)")
    args = ap.parse_args(argv)

    if args.tenants:
        sizes = [int(s) for s in args.tenants.split(",")]
    elif args.quick:
        sizes = [2]
    else:
        sizes = [1, 8, 64]

    cfg = get_smoke_config(ARCH)
    backbone = model_lib.init_backbone(jax.random.PRNGKey(0), cfg)
    gen_tokens = 4 if args.quick else GEN_TOKENS
    print(f"### serve bench: {ARCH}, slots={SLOTS}, "
          f"prefill_len={PREFILL_LEN}, gen_tokens={gen_tokens}, "
          "token parity asserted per row")
    rows = []
    for n in sizes:
        n_requests = 8 if args.quick else max(2 * n, 32)
        rows.append(bench_tenants(cfg, backbone, n, n_requests, gen_tokens))

    out_path = args.out or (None if args.quick else OUT)
    if out_path:
        doc = {"config": {
            "arch": f"{ARCH} (smoke scale)", "slots": SLOTS,
            "prefill_len": PREFILL_LEN, "gen_tokens": gen_tokens,
            "timing": "fresh full-workload run after a warmup run that pays "
                      "all compilation; throughput = generated tokens / wall",
        }, "results": []}
        if os.path.exists(out_path):
            try:
                with open(out_path) as f:
                    doc["results"] = json.load(f).get("results", [])
            except (json.JSONDecodeError, OSError):
                pass
        done = {r["tenants"] for r in rows}
        doc["results"] = sorted(
            [r for r in doc["results"] if r["tenants"] not in done] + rows,
            key=lambda r: r["tenants"])
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {out_path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

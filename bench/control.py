"""Readings of the control and of planted faults, for setting a cell's limits.

    python3 bench/control.py --workload <cell> --seeds a,b,c [--seconds s]

Not part of a benchmark run. For each seed it prints, as JSON rows, the
numbers that decide ``correct`` when something else stands in the
program's place:

* ``control``: the plain reference computed with float8 (e4m3) weights per
  output channel, the step below the configuration's bf16 that would tempt
  a later change. For a round cell it follows the window's first two
  rounds in the program's place and is compared with the float32
  reference exactly as the program is (the reference starting round 1 from
  the control's own merge of round 0); for a serve cell, at each position
  of the served tokens, the reference's best logit minus its logit of the
  token the float8 pass puts first (widest over positions), beside the
  program's own reading on the same requests.
* ``half`` (round cells): the fault "half of the batch left out, the mean
  taken over the rest", planted in the reference put in the program's
  place. (The fault "a step returns its state unchanged" reads 1 on
  ``global_delta`` by construction, and "the merged update applied twice"
  reads 1 on ``global_delta`` and ``merge_last``; neither needs a run.)
* ``fisher`` (round cells, both stand-ins): their Fisher pass over the
  last checked cohort's rows against the reference's, both at the
  window's starting adapters (in a run, the clients' own last adapters).

The round cohorts are the window's: the mix's sampler at rounds 2 and 3,
as ``bench/kinds/round.py`` draws them after its two rounds of set-up;
round 0 starts from the seed's global adapters.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def round_rows(cell, seed):
    from bench import traffic_gen
    from bench.kinds import round as rk
    from bench.models import common

    tr, model = cell.traffic, cell.model
    sz = model.sizes(cell.config)
    pop = traffic_gen.round_population(seed, sz.vocab, sz.frontend, tr)
    start = rk._host(common.adapter_set(seed, sz, "global"))
    sampler = rk._sampler(tr, seed)
    cohorts = [list(sampler.select(2 + r, sorted(pop)))
               for r in range(rk.CHECKED_ROUNDS)]
    last, round0 = cohorts[-1], None
    thetas = [start] * len(last)
    fisher = rk.reference_fisher(model, seed, sz, tr, pop, last, thetas)
    for name, kw in (("control_fp8", {"quant": "fp8"}), ("half", {"half": True})):
        other = rk.reference_rounds(model, seed, sz, tr, pop, cohorts, start, **kw)
        ref = rk.reference_rounds(model, seed, sz, tr, pop, cohorts, start,
                                  g1=other["global"][0], round0=round0)
        round0 = ref["round0"]
        f = rk.reference_fisher(model, seed, sz, tr, pop, last, thetas, **kw)
        yield {"seed": seed, "stand_in": name, **rk.compare(other, ref),
               "fisher": rk.fisher_gap(f, fisher, ref["keep"])}


def serve_rows(cell, seed, seconds):
    import numpy as np

    from bench import harness, traffic_gen
    from bench.kinds import serve

    tr = cell.traffic
    sz = cell.model.sizes(cell.config)
    engine = serve.build(cell, seed)
    reqs = traffic_gen.serve_requests(seed, sz.vocab, tr, seconds)
    o = serve.offer(engine, reqs, seconds, harness.Tracer(False))
    served = {r.rid: list(o.done[r.rid].tokens) for r in o.finished}
    del engine
    harness.free_device_memory()
    sample = serve.check_sample(seed, tr, o.finished, served)
    gaps = serve.reference_gaps(cell.model, seed, sz, tr, sample, served, quant="fp8")
    yield {"seed": seed, "stand_in": "program",
           "logit_gap": float(max(np.max(g) for g, _ in gaps)),
           "tokens": int(sum(len(g) for g, _ in gaps))}
    yield {"seed": seed, "stand_in": "control_fp8",
           "logit_gap": float(max(np.max(c) for _, c in gaps)),
           "tokens": int(sum(len(c) for _, c in gaps))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro.launch.common import enable_compile_cache

    from bench import spec

    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    for seed in (int(x) for x in args.seeds.split(",")):
        rows = (round_rows(cell, seed) if cell.kind == "round"
                else serve_rows(cell, seed, args.seconds))
        for row in rows:
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

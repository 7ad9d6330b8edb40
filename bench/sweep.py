"""Find the serving knee: offer a serve cell's mix at several fixed rates.

    python3 bench/sweep.py --workload <serve cell> --seed <n> --seconds 15 --rates 3,4,5,6

One process, one engine (built and warmed once); for each rate the mix is
offered open-loop for ``--seconds`` and then drained. Each rate prints one
JSON row: TTFT and inter-token percentiles, the backlog (requests due but
not yet admitted) when the offer closed, how long the drain took, and the
tokens completed per second. The knee is the highest rate whose backlog
stays near zero; a cell's rate is set once from it and written into the
traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from repro.launch.common import enable_compile_cache

    from bench import harness, spec, traffic_gen
    from bench.kinds import serve

    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU visible", file=sys.stderr)
        return 1
    sz = cell.model.sizes(cell.config)
    engine = serve.build(cell, args.seed)
    null = harness.Tracer(False)
    for i, rate in enumerate(float(x) for x in args.rates.split(",")):
        tr = dict(cell.traffic, rate_per_s=rate)
        reqs = traffic_gen.serve_requests(args.seed + i, sz.vocab, tr, args.seconds)
        o = serve.offer(engine, reqs, args.seconds, null)
        ttft = o.ttft()
        toks = sum(len(o.done[r.rid].tokens) for r in o.finished)
        row = {"rate_per_s": rate, "requests": len(reqs), "finished": len(o.finished),
               "ttft_p50_ms": 1e3 * harness.percentile(ttft, 50),
               "ttft_p95_ms": 1e3 * harness.percentile(ttft, 95),
               "itl_p50_ms": 1e3 * harness.percentile(o.rec.itl, 50),
               "itl_p95_ms": 1e3 * harness.percentile(o.rec.itl, 95),
               "decode_step_p50_ms": 1e3 * harness.percentile(o.rec.step_s, 50),
               "prefill_p50_ms": 1e3 * harness.percentile(o.rec.prefill_s, 50),
               "backlog_at_close": o.backlog_at_close,
               "drain_s": (o.t_end - o.t0) - args.seconds,
               "tokens_per_s": toks / (o.t_end - o.t0),
               "compiles": o.compiles}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

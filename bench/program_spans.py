"""The program's own spans in a cell's traced window: where the idle time goes.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s> [--excerpt PATH]

Runs one cell as ``bench/run.py --trace 1`` does, in one process, and
prints one JSON line: the run's end-to-end metrics and per-layer metrics
as the harness reads them, and under ``program`` what the program's host
spans (``fednano.*``, ``src/repro/tracing.py``) say about the same window.
``--excerpt`` writes a few hundred of the window's events (device
operations and host spans around its longest idle gap) in the form of
``bench/tests/data/trace_excerpt.json``.

``bench/trace.py`` keeps only the harness's ``bench.*`` spans, without
their arguments, and puts each idle gap down to the last span that
started before it. The reduction here reads the program's spans as well,
with their arguments, and nests: each gap goes to the span that started
last among those that hold the gap's midpoint, so a gap after a child
span ends goes to its parent. Four readings of the program's spans, each
over the window's spans:

* ``round_host_ms``: median over ``fednano.round`` spans of the round's
  duration less its ``fednano.round.wait`` spans, in ms.
* ``host_transfer_mb``: mean over rounds of the ``bytes_to_device`` and
  ``bytes_to_host`` arguments of the round's spans, in MB (1e6 B).
* ``decode_host_ms``: median over ``fednano.serve.decode`` spans of the
  duration less the step's ``.wait`` child, in ms.
* ``adapter_miss_share``: misses over acquisitions (hits and misses) among
  the ``fednano.serve.adapter`` spans, in %.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import importlib
import json
import math
import os
import statistics
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "fednano."


def load_program_spans(trace_dir: str) -> List[trace.Event]:
    """Host spans named ``fednano.*`` of the newest trace, arguments as stats."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    out = []
    for plane in jax.profiler.ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.append(trace.Event(plane.name, line.name, ev.name,
                                           float(ev.start_ns), float(ev.duration_ns),
                                           {str(k): v for k, v in ev.stats}))
    return out


def _host(events):
    return [e for e in events if not e.plane.startswith(trace.DEVICE_PREFIX)]


def window(events) -> Tuple[float, float]:
    """The ``bench.window`` span, else first to last device operation."""
    w = [e for e in events if e.name == trace.WINDOW_SPAN]
    if w:
        return w[0].start_ns, w[0].end_ns
    dev = [e for e in events if e.plane.startswith(trace.DEVICE_PREFIX)]
    return min(e.start_ns for e in dev), max(e.end_ns for e in dev)


def idle_by_span(events: Sequence[trace.Event]) -> Dict:
    """Busy and window seconds, and idle seconds by the innermost host span.

    Busy, the window and the gaps are ``trace.reduce``'s (mean over
    devices); a gap goes to the span that started last among the host
    spans, harness's and program's, that hold its midpoint.
    """
    lo, hi = window(events)
    spans = sorted((s for s in _host(events) if s.name != trace.WINDOW_SPAN),
                   key=lambda s: s.start_ns)
    dev = [e for e in events if e.plane.startswith(trace.DEVICE_PREFIX)
           and e.opcode not in trace.CONTROL_OPS]
    planes = sorted({e.plane for e in dev})
    busy_total, idle = 0.0, defaultdict(float)
    for p in planes:
        busy = trace._clip(trace._union(
            (e.start_ns, e.end_ns) for e in dev
            if e.plane == p and e.end_ns > lo and e.start_ns < hi), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        # sweep the gaps in time order; ``live`` holds the spans begun and
        # not yet ended, in order of start
        nxt, live = 0, []
        for a, b in gaps:
            t = (a + b) / 2
            while nxt < len(spans) and spans[nxt].start_ns <= t:
                live.append(spans[nxt])
                nxt += 1
            live = [s for s in live if s.end_ns >= t]
            idle[live[-1].name if live else trace.WINDOW_SPAN] += (b - a) * 1e-9
    n = max(len(planes), 1)
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_total * 1e-9 / n,
            "idle_by_span": {k: v / n for k, v in
                             sorted(idle.items(), key=lambda kv: -kv[1])}}


def program_idle_share(idle: Dict[str, float]) -> Optional[float]:
    """Share (%) of the idle seconds put down to a program span."""
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * sum(v for k, v in idle.items()
                       if k.startswith(PROGRAM_PREFIX)) / total


def _in_window(events, name):
    lo, hi = window(events)
    return [s for s in _host(events)
            if s.name == name and s.start_ns >= lo and s.end_ns <= hi]


def _inside(parents, spans):
    """For each parent, the ``spans`` that lie inside it."""
    spans = sorted(spans, key=lambda s: s.start_ns)
    starts = [s.start_ns for s in spans]
    out = []
    for p in parents:
        i = bisect.bisect_left(starts, p.start_ns)
        j = bisect.bisect_right(starts, p.end_ns)
        out.append([s for s in spans[i:j] if s.end_ns <= p.end_ns])
    return out


def _self_ms(parents, events, wait_name):
    if not parents:
        return None
    waits = _inside(parents, [s for s in _host(events) if s.name == wait_name])
    return 1e-6 * statistics.median(
        p.dur_ns - sum(w.dur_ns for w in ws) for p, ws in zip(parents, waits))


def round_host_ms(events) -> Optional[float]:
    return _self_ms(_in_window(events, "fednano.round"), events,
                    "fednano.round.wait")


def host_transfer_mb(events) -> Optional[float]:
    rounds = _in_window(events, "fednano.round")
    if not rounds:
        return None
    phases = _inside(rounds, [s for s in _host(events)
                              if s.name.startswith("fednano.round.")])
    return 1e-6 * statistics.mean(
        sum(int(s.stats.get("bytes_to_device", 0)) + int(s.stats.get("bytes_to_host", 0))
            for s in spans) for spans in phases)


def decode_host_ms(events) -> Optional[float]:
    return _self_ms(_in_window(events, "fednano.serve.decode"), events,
                    "fednano.serve.decode.wait")


def adapter_miss_share(events) -> Optional[float]:
    acq = _in_window(events, "fednano.serve.adapter")
    hits = sum(int(s.stats.get("hit", 0)) for s in acq)
    misses = sum(int(s.stats.get("miss", 0)) for s in acq)
    if hits + misses == 0:
        return None
    return 100.0 * misses / (hits + misses)


READINGS = {"round_host_ms": round_host_ms, "host_transfer_mb": host_transfer_mb,
            "decode_host_ms": decode_host_ms, "adapter_miss_share": adapter_miss_share}


def summarize(events) -> Dict:
    """Everything ``program`` reports about a window's events."""
    idle = idle_by_span(events)
    out = {k: f(events) for k, f in READINGS.items()}
    out.update(idle)
    out["program_idle_share"] = program_idle_share(idle["idle_by_span"])
    counts = defaultdict(int)
    for s in _host(events):
        if s.name.startswith(PROGRAM_PREFIX):
            counts[s.name] += 1
    out["spans"] = dict(sorted(counts.items()))
    # device operations started in each second of the window: a trace whose
    # device events stop early (the profiler's buffer full) shows zeros at
    # the end, and its idle share reads too high
    lo, hi = window(events)
    per_s = [0] * int(math.ceil((hi - lo) * 1e-9))
    for e in events:
        if e.plane.startswith(trace.DEVICE_PREFIX) and lo <= e.start_ns < hi:
            per_s[int((e.start_ns - lo) * 1e-9)] += 1
    out["device_ops_per_s"] = per_s
    return out


def excerpt(events, before: int = 100, after: int = 150) -> List[trace.Event]:
    """Device ops around the window's longest idle gap, host spans cut to them.

    The excerpt's own ``bench.window`` runs from its first op's start to
    its last op's end.
    """
    import dataclasses

    lo, hi = window(events)
    dev = sorted((e for e in events if e.plane.startswith(trace.DEVICE_PREFIX)
                  and e.opcode not in trace.CONTROL_OPS
                  and e.start_ns >= lo and e.end_ns <= hi), key=lambda e: e.start_ns)
    reach, best, at = dev[0].end_ns, -1.0, 0
    for i, e in enumerate(dev[1:], 1):
        if e.start_ns - reach > best:
            best, at = e.start_ns - reach, i
        reach = max(reach, e.end_ns)
    keep = dev[max(0, at - before):at + after]
    a, b = keep[0].start_ns, max(e.end_ns for e in keep)
    host = []
    for s in _host(events):
        if s.name != trace.WINDOW_SPAN and s.end_ns > a and s.start_ns < b:
            start = max(s.start_ns, a)
            host.append(dataclasses.replace(s, start_ns=start,
                                            dur_ns=min(s.end_ns, b) - start))
    plane, line = (host[0].plane, host[0].line) if host else ("/host:CPU", "python3")
    win = trace.Event(plane, line, trace.WINDOW_SPAN, a, b - a, {})
    return keep + sorted(host, key=lambda s: s.start_ns) + [win]


def run(cell, args, devices, t_start: float):
    """One traced run of ``cell``: (result, checks, every event of the trace)."""
    import shutil

    import jax

    from bench import harness, spec

    class Tracer(harness.Tracer):
        """The harness's tracer; it also keeps every event for the reduction here."""

        def stop(self):
            jax.profiler.stop_trace()
            try:
                harness_events = trace.load_events(self._dir)
                self.summary = trace.reduce(harness_events)
                self.events = harness_events + load_program_spans(self._dir)
            finally:
                shutil.rmtree(self._dir, ignore_errors=True)
                self._dir = None

    tracer = Tracer(True)
    kind = importlib.import_module(f"bench.kinds.{cell.kind}")
    result, e2e, ctx, readings, info = kind.run(cell, args, t_start, devices, tracer)
    ok, checks = harness.judge(readings, cell.limits)
    result["correct"] = bool(ok)
    result["end_to_end"] = e2e
    result["metrics"] = spec.read_per_layer(cell, ctx)
    result["breakdown"] = tracer.summary.breakdown()
    result["program"] = summarize(tracer.events)
    for k, v in info.items():
        print(f"info {k}: {v}", file=sys.stderr, flush=True)
    return result, checks, tracer.events


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--excerpt", default=None)
    args = ap.parse_args(argv)
    args.trace = 1

    import jax

    from repro.launch.common import enable_compile_cache

    from bench import harness, peaks, spec

    cell = spec.load_cell(args.workload)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"program_spans: needs {cell.chips} TPU chip(s), found "
              f"{len(devices)} {devices[0].platform}", file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    peaks.peaks(devices[0].device_kind)
    result, checks, events = run(cell, args, devices, T_START)
    harness.emit(result, checks)
    if args.excerpt:
        with open(args.excerpt, "w") as f:
            json.dump([vars(e) for e in excerpt(events)], f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Reduce a profiler trace to device busy time, per-operation time and idle gaps.

``load_events`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event rows; ``reduce`` works on those rows alone, so it is tested on
a small recorded excerpt (``bench/tests/data/trace_excerpt.json``).

* Device events are those on planes named ``/device:TPU:<n>``, line
  ``XLA Ops``: one event per executed HLO operation (a Pallas kernel is a
  ``tpu_custom_call`` operation; the row keeps every stat the profiler gave,
  where its HLO name and its source op path are).
* Busy time is the union of a device's operation intervals inside the
  window, averaged over the devices used; idle share is 1 - busy / window.
* The window is the harness span ``bench.window`` on the host plane when
  the trace has it, else the first to last device event.
* Each idle gap on a device is attributed to the innermost harness span
  (``bench.*``) active on the host at the gap's midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
# control-flow ops span their bodies' ops; busy time and per-op time count
# the ops that compute
CONTROL_OPS = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, str]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def opcode(self) -> str:
        m = re.match(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=", self.name)
        return m.group(1) if m else self.name.split(" ", 1)[0]

    def label(self, width: int = 120) -> str:
        """The op's HLO text without layouts, cut to ``width`` characters."""
        return re.sub(r"\{[^{}]*\}", "", self.name)[:width]

    def text(self) -> str:
        """Name and stats in one string, for matching kernels by name."""
        return " ".join([self.name] + [f"{k}={v}" for k, v in self.stats.items()])


def load_events(trace_dir: str) -> List[Event]:
    """Device ops and harness spans of the newest trace under ``trace_dir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in pd.planes:
        is_dev = plane.name.startswith(DEVICE_PREFIX)
        is_host = plane.name.startswith("/host:")
        if not (is_dev or is_host):
            continue
        for line in plane.lines:
            if is_dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if is_host and not ev.name.startswith(SPAN_PREFIX):
                    continue
                stats = {}
                if is_dev:
                    stats = {str(k): str(v) for k, v in ev.stats}
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns), stats))
    return out


def read_events(path: str) -> List[Event]:
    with open(path) as f:
        return [Event(**row) for row in json.load(f)]


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


@dataclasses.dataclass
class Summary:
    n_devices: int
    window_s: float
    busy_s: float                       # mean over devices
    op_seconds: Dict[str, float]        # op name -> device seconds, mean over devices
    ops: List[Event]                    # device op events inside the window
    idle_by_span: Dict[str, float]      # harness span -> idle seconds, mean over devices

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def seconds_matching(self, needles: Sequence[str]) -> float:
        """Device seconds (mean over devices) of ops whose text has any needle."""
        total = sum(e.dur_ns for e in self.ops
                    if any(n in e.text() for n in needles))
        return total * 1e-9 / max(self.n_devices, 1)

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


class _Spans:
    """Innermost harness span at a time: the window, or a span inside it.

    Spans other than the window do not overlap one another (the harness
    opens them one after another), so the one that holds a time is the last
    that started before it.
    """

    def __init__(self, spans: List[Event]):
        inner = sorted((s for s in spans if s.name != WINDOW_SPAN),
                       key=lambda s: s.start_ns)
        self.starts = [s.start_ns for s in inner]
        self.inner = inner
        self.windows = [s for s in spans if s.name == WINDOW_SPAN]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.inner[i].end_ns >= t:
            return self.inner[i].name
        if any(w.start_ns <= t <= w.end_ns for w in self.windows):
            return WINDOW_SPAN
        return "outside harness spans"


def reduce(events: Sequence[Event]) -> Summary:
    dev = [e for e in events if e.plane.startswith(DEVICE_PREFIX)
           and e.opcode not in CONTROL_OPS]
    spans = [e for e in events if not e.plane.startswith(DEVICE_PREFIX)]
    if not dev:
        raise ValueError("the trace holds no device operation")
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0].start_ns, windows[0].end_ns
    else:
        lo, hi = min(e.start_ns for e in dev), max(e.end_ns for e in dev)
    planes = sorted({e.plane for e in dev})
    at = _Spans(spans).at
    busy_total = 0.0
    idle_by_span: Dict[str, float] = defaultdict(float)
    op_seconds: Dict[str, float] = defaultdict(float)
    inside = []
    for p in planes:
        evs = [e for e in dev if e.plane == p and e.end_ns > lo and e.start_ns < hi]
        inside += evs
        busy = _clip(_union((e.start_ns, e.end_ns) for e in evs), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle_by_span[at((a + b) / 2)] += (b - a) * 1e-9
        for e in evs:
            op_seconds[e.label()] += e.dur_ns * 1e-9
    n = len(planes)
    return Summary(
        n_devices=n, window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
        op_seconds={k: v / n for k, v in op_seconds.items()}, ops=inside,
        idle_by_span={k: v / n for k, v in idle_by_span.items()})

"""What every kind of cell shares: clocks, spans, the trace, the result line."""
from __future__ import annotations

import contextlib
import json
import math
import shutil
import sys
import tempfile
from typing import Dict, Optional, Sequence

from bench import trace as trace_lib


class CompileWatch:
    """Counts JAX compilations (traces, lowerings, compiles) while open."""

    def __init__(self):
        self.events = 0
        self.seconds = 0.0

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.events += 1
            self.seconds += duration

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class Tracer:
    """The profiler over the measured window, with harness spans on its clock.

    Off (``enabled=False``) it records nothing and costs nothing. The trace
    goes to a temporary directory under ``TMPDIR`` and is deleted once read.
    """

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self._dir: Optional[str] = None
        self.summary: Optional[trace_lib.Summary] = None

    def start(self):
        if self.enabled:
            import jax

            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self._dir)

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    def open_window(self):
        self._window = self.span("window")
        self._window.__enter__()

    def close_window(self):
        window = getattr(self, "_window", None)
        if window is not None:
            window.__exit__(None, None, None)
            self._window = None

    def stop(self):
        if not self.enabled or self._dir is None:
            return
        import jax

        jax.profiler.stop_trace()
        try:
            self.summary = trace_lib.reduce(trace_lib.load_events(self._dir))
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None


def device_block(devices: Sequence) -> Dict:
    d = devices[0]
    peaks = [(x.memory_stats() or {}).get("peak_bytes_in_use", 0) for x in devices]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
            "memory_peak_bytes": int(max(peaks))}


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation, numpy's default."""
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def judge(readings: Dict[str, float], limits: Dict[str, float]):
    """Each compared number beside its limit; correct iff all are within."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = readings.get(name)
        good = v is not None and math.isfinite(v) and v <= limit
        ok = ok and good
        checks[name] = {"value": v, "limit": limit}
    return ok, checks


def emit(result: Dict, checks: Dict) -> None:
    """Compared numbers last on stderr; the JSON result last on stdout."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def free_device_memory():
    """Drop what the program left on the device before the reference runs."""
    import gc

    gc.collect()

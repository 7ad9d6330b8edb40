"""Serving cells: ``ServingEngine`` under an open-loop, multi-tenant stream.

Set-up builds the seed's frozen backbone, every tenant's NanoAdapters and
the engine, then warms every program the window uses (prefill, the page
write, the decode step, adapter loads into every bank slot and evictions)
on a few requests of its own. The window offers the mix's requests at
their due times, ``--seconds`` long, and then drains: every request due
in the window is served to its end.

Times, on the host clock: a request's first token is stamped when its
admission returns (the engine has synced the token then), and each later
token when the decode step that made it returns (also synced). TTFT is
first-token time minus *due* time, so a stall counts against every request
waiting behind it; its median and 95th percentile are reported on stderr,
with how late the generator handed requests over.

``correct``: once the window has closed and the engine is freed, a sample
of finished requests drawn from the seed (the longest among them) is run
through the plain float32 reference, teacher-forced with the served tokens,
each with its tenant's adapters. The number compared is the widest gap by
which a served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import math
import time
from collections import deque
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, traffic_gen
from bench.models import common

DRAIN_LIMIT_S = 60.0


class Record:
    """Host-clock stamps and the counts the per-layer metrics read."""

    def __init__(self):
        self.first: Dict[int, float] = {}
        self.last: Dict[int, float] = {}
        self.itl: List[float] = []
        self.prefill_s: List[float] = []
        self.prefill_lens: List[int] = []
        self.step_s: List[float] = []
        self.step_positions: List[List[int]] = []


def drive_once(engine, pending: deque, done: Dict, rec: Record, tracer) -> None:
    """One engine iteration as ``ServingEngine.run`` makes it: admit, then step.

    Requests are handed to the engine one at a time so each admission has
    its own first-token stamp.
    """
    while pending and engine.slots.n_free > 0:
        r = pending.popleft()
        t0 = time.perf_counter()
        with tracer.span("admit"):
            engine.submit(r)
            engine._admit(done)
        t1 = time.perf_counter()
        rec.first[r.rid] = rec.last[r.rid] = t1
        rec.prefill_s.append(t1 - t0)
        rec.prefill_lens.append(len(r.prompt))
    if engine._active:
        live = [(c.rid, int(engine.slots.pos[s])) for s, c in engine._active.items()]
        t0 = time.perf_counter()
        with tracer.span("step"):
            engine._step(done)
        t1 = time.perf_counter()
        rec.step_s.append(t1 - t0)
        rec.step_positions.append([p for _, p in live])
        for rid, _ in live:
            rec.itl.append(t1 - rec.last[rid])
            rec.last[rid] = t1


def _program_requests(reqs):
    from repro.serving import Request

    return [Request(rid=r.rid, tenant=r.tenant, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens) for r in reqs]


def _warm(engine, tr, vocab):
    """Every program and path the window uses, on requests of set-up's own."""
    names = traffic_gen.tenant_names(tr)
    n = min(len(names), tr["adapter_slots"] + 2)
    rng = np.random.default_rng(0)
    from repro.serving import Request

    reqs = deque(Request(rid=-1 - i, tenant=names[i],
                         prompt=rng.integers(0, vocab, 1 + (7 + 37 * i) % tr["prefill_len"]
                                             ).astype(np.int32),
                         max_new_tokens=3) for i in range(n))
    done: Dict = {}
    rec = Record()
    null = harness.Tracer(False)
    while reqs or engine._active:
        drive_once(engine, reqs, done, rec, null)
    return len(done)


def build(cell, seed: int):
    """The seed's backbone, every tenant's adapters and a warmed engine."""
    import jax

    from repro.serving import ServingEngine

    tr = cell.traffic
    sz = cell.model.sizes(cell.config)
    cfg = cell.model_config(use_pallas=True)
    backbone = cell.model.backbone_weights(seed, sz, cell.config["dtype"])
    tenants = {t: common.adapter_set(seed, sz, t) for t in traffic_gen.tenant_names(tr)}
    engine = ServingEngine(
        cfg, backbone, max_slots=tr["slots"], prefill_len=tr["prefill_len"],
        max_new_tokens=tr["output_len"]["max"], adapter_slots=tr["adapter_slots"],
        adapter_loader=tenants.__getitem__, use_pallas_grouped=True)
    _warm(engine, tr, sz.vocab)
    jax.block_until_ready(engine.slots.state)
    return engine


class Offered:
    """What one window of offered load left behind."""

    def __init__(self, reqs, seconds):
        self.reqs, self.seconds = reqs, seconds
        self.due = {r.rid: r.due_s for r in reqs}
        self.done: Dict = {}
        self.rec = Record()
        self.lateness: List[float] = []
        self.backlog_at_close = 0
        self.compiles = 0
        self.t0 = self.t_end = 0.0

    @property
    def finished(self):
        return [r for r in self.reqs if r.rid in self.done]

    def ttft(self) -> List[float]:
        return [self.rec.first[r.rid] - self.t0 - self.due[r.rid] for r in self.finished]


def offer(engine, reqs, seconds: float, tracer) -> Offered:
    """Offer ``reqs`` at their due times, then drain every one of them."""
    out = Offered(reqs, seconds)
    queue = deque(_program_requests(reqs))
    pending: deque = deque()
    closed = False
    with harness.CompileWatch() as watch:
        out.t0 = t0 = time.perf_counter()
        tracer.open_window()
        while True:
            now = time.perf_counter() - t0
            while queue and out.due[queue[0].rid] <= now:
                r = queue.popleft()
                out.lateness.append(now - out.due[r.rid])
                pending.append(r)
            if not closed and now >= seconds:
                closed = True
                out.backlog_at_close = len(queue) + len(pending)
            if not queue and not pending and not engine._active:
                break
            if now > seconds + DRAIN_LIMIT_S:
                break
            if pending or engine._active:
                drive_once(engine, pending, out.done, out.rec, tracer)
            else:
                with tracer.span("wait"):
                    time.sleep(max(0.0, out.due[queue[0].rid] - now))
        out.t_end = time.perf_counter()
        tracer.close_window()
    out.compiles = watch.events
    return out


def run(cell, args, t_start: float, devices, tracer: harness.Tracer):
    from bench import peaks as peaks_lib

    tr, seed, model = cell.traffic, args.seed, cell.model
    sz = model.sizes(cell.config)
    engine = build(cell, seed)
    reqs = traffic_gen.serve_requests(seed, sz.vocab, tr, args.seconds)
    tracer.start()
    o = offer(engine, reqs, args.seconds, tracer)
    tracer.stop()
    setup_s = o.t0 - t_start
    window_s = o.t_end - o.t0
    device = harness.device_block(devices)
    finished = o.finished
    served = {r.rid: list(o.done[r.rid].tokens) for r in finished}
    ttft = o.ttft()
    rec = o.rec
    pk = peaks_lib.peaks(devices[0].device_kind)
    bound_s = 0.0
    for length in rec.prefill_lens:
        f, b = model.prefill_cost(sz, length)
        bound_s += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    for pos in rec.step_positions:
        f, b = model.decode_cost(sz, pos)
        bound_s += max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    del engine
    harness.free_device_memory()

    t_ref = time.perf_counter()
    readings = check_served(model, seed, sz, tr, finished, served)
    ref_s = time.perf_counter() - t_ref

    result = {"correct": None, "attempted": len(reqs),
              "failed": len(reqs) - len(finished), "device": device}
    e2e = {
        "itl_p95_ms": {"value": 1e3 * harness.percentile(rec.itl, 95) if rec.itl else math.inf,
                       "unit": "ms"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    ctx = {"kind": "serve", "model": model, "sz": sz, "traffic": tr, "chips": len(devices),
           "device_kind": devices[0].device_kind, "window_s": window_s,
           "trace": tracer.summary, "roofline_bound_s": bound_s,
           "step_s": rec.step_s, "prefill_s": rec.prefill_s}
    info = {"requests": len(reqs), "finished": len(finished),
            "window_s": window_s, "decode_steps": len(rec.step_s),
            "backlog_at_close": o.backlog_at_close,
            "lateness_p95_ms": 1e3 * harness.percentile(o.lateness, 95) if o.lateness else 0.0,
            "lateness_max_ms": 1e3 * max(o.lateness) if o.lateness else 0.0,
            "ttft_p50_ms": 1e3 * harness.percentile(ttft, 50) if ttft else None,
            "ttft_p95_ms": 1e3 * harness.percentile(ttft, 95) if ttft else None,
            "compiles_in_window": o.compiles, "reference_s": ref_s}
    return result, e2e, ctx, readings, info


# ---------------------------------------------------------------------------
# the reference over a sample of served requests
# ---------------------------------------------------------------------------

def check_sample(seed, tr, finished, served) -> List:
    """Requests the reference reads: the longest and a seeded draw."""
    if not finished:
        return []
    total = lambda r: len(r.prompt) + len(served[r.rid])
    longest = max(finished, key=total)
    rng = np.random.default_rng([seed, 3])
    rest = [r for r in finished if r.rid != longest.rid]
    n = min(len(rest), tr["check_requests"] - 1)
    picks = [rest[i] for i in sorted(rng.choice(len(rest), size=n, replace=False))] if n else []
    return [longest] + picks


@jax.jit
def _gaps(logits, toks):
    """Best logit minus the logit of ``toks``, per position."""
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, toks[..., None], axis=-1)[..., 0]


def reference_gaps(model, seed, sz, tr, sample, served, quant=None, block: int = 4):
    """Per request: the gaps of its served tokens in the logits of the
    reference of family module ``model``.

    Returns, per sampled request, (gap of each served token, gap of the
    token the ``quant`` pass ranks first, or None). A gap is the
    reference's best logit minus its logit of that token. Every block has
    the same shape (``block`` requests padded to prefill_len + the longest
    output), so the programs compile once per mix.
    """
    length = tr["prefill_len"] + tr["output_len"]["max"]
    n_pos = tr["output_len"]["max"]
    out = []
    with jax.default_matmul_precision("highest"):
        ref = model.Reference(seed, sz)
        low = model.Reference(seed, sz, quant) if quant else None
        for i in range(0, len(sample), block):
            part = sample[i:i + block]
            rows = part + [part[0]] * (block - len(part))
            toks = np.zeros((block, length), np.int32)
            pos = np.zeros((block, n_pos), np.int32)
            want = np.zeros((block, n_pos), np.int32)
            for j, r in enumerate(rows):
                got = np.asarray(served[r.rid], np.int32)
                seq = np.concatenate([r.prompt, got[:-1]])
                toks[j, :len(seq)] = seq
                pos[j, :len(got)] = len(r.prompt) - 1 + np.arange(len(got))
                want[j, :len(got)] = got
            adps = [common.adapter_set(seed, sz, r.tenant) for r in rows]
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *adps)
            tk, ps, wt = jnp.asarray(toks[:, None]), jnp.asarray(pos), jnp.asarray(want)

            def logits_of(r):
                return r.logits_at(r.hidden(r.embed(stacked, tk, None)), ps)

            ref_lg = logits_of(ref)
            gap = np.asarray(_gaps(ref_lg, wt))
            lgap = None
            if low is not None:
                top = jnp.argmax(logits_of(low), axis=-1).astype(jnp.int32)
                lgap = np.asarray(_gaps(ref_lg, top))
            for j, r in enumerate(part):
                n = len(served[r.rid])
                out.append((gap[j, :n], None if lgap is None else lgap[j, :n]))
    return out


def check_served(model, seed, sz, tr, finished, served) -> Dict[str, float]:
    sample = check_sample(seed, tr, finished, served)
    if not sample:
        return {"logit_gap": math.inf}
    gaps = reference_gaps(model, seed, sz, tr, sample, served)
    return {"logit_gap": float(max(np.max(g) for g, _ in gaps))}

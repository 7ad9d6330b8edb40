"""Federated-round cells: ``run_federated`` driven from the benchmark's data.

Set-up builds one server (the seed's frozen backbone and global
NanoAdapters) and the population's rows, then drives the program's public
entry, ``run_federated(..., final_eval=False)``, twice with that same
server: two rounds (the first compiles, the second sizes the window), then
the window itself, whose round count (two at least) makes the whole rounds
fill about ``--seconds``. A sampler of the benchmark's own wraps the mix's
sampler and stamps each ``select``, so the window starts when its first
round starts and client initialisation stays in set-up. Every call gets an
identity server step of the benchmark's own (a ``ServerOpt``, the
program's public hook after each merge) that keeps a reference to the
merged global adapters of the rounds the check reads; it copies nothing.

``correct`` compares rounds of the window itself, once it has closed and
the program's state is freed:

* its first two rounds, which the plain float32 reference follows for every
  client of both cohorts (the local AdamW steps and the Fisher pass, layer
  by layer, then FedNano's Eq. 1 merge). Round 0 starts where the window
  did: the global adapters the window was given, and fresh clients. Round 1
  starts from the program's merged adapters of round 0 (the one input the
  reference takes from the program), and every client that took part in
  round 0 carries its AdamW state into it (the reference its own). Compared:
  each round's mean loss, and per leaf the norm of the round's change of the
  global adapters.
* its last merge: Eq. 1 over the adapters and Fisher diagonals that the
  last round's clients hold in the program's result, against the global
  adapters the program ended with; per leaf, the norm of the change from
  the global adapters that round started from.
* the last round's Fisher pass: the reference's Fisher diagonals at the
  adapters each of those clients ended with, on the rows the program's pass
  read, against the program's, per client and leaf (``fisher``). A Fisher
  diagonal is a mean squared gradient, which a lower precision moves in
  proportion; the merged change is AdamW's, whose first steps move every
  entry by about the learning rate whatever its gradient.

Each norm is compared as a gap relative to max(the reference leaf's norm,
the median leaf's), the worst leaf (and client) taken. Leaves whose
reference gradient (round 0's first step, all clients) is under a
thousandth of the median leaf's are left out (none are, with ``up`` != 0
at the start).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List

import numpy as np

from bench import harness, traffic_gen
from bench.models import common

EXCLUDE_BELOW = 1e-3
CHECKED_ROUNDS = 2


class StampSampler:
    """Delegates to the mix's sampler; stamps and spans each round.

    With ``window`` set, the first ``select`` also opens the traced
    ``bench.window`` span: the window starts when its first round does.
    """

    def __init__(self, inner, offset: int, tracer=None, window: bool = False):
        self.inner, self.offset, self.tracer = inner, offset, tracer
        self.window = window
        self.stamps: List[float] = []
        self.cohorts: List[List[int]] = []
        self._span = None

    def select(self, r, cids):
        now = time.perf_counter()
        self.close()
        if self.tracer is not None:
            if self.window and not self.stamps:
                self.tracer.open_window()
            self._span = self.tracer.span("round")
            self._span.__enter__()
        cohort = list(self.inner.select(r + self.offset, cids))
        self.stamps.append(now)
        self.cohorts.append(cohort)
        return cohort

    def close(self):
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None


def merge_recorder(keep):
    """An identity server step that keeps the merged adapters of rounds ``keep``."""
    from repro.strategies.server_opt import ServerOpt

    @dataclasses.dataclass(frozen=True, eq=False)
    class MergeRecorder(ServerOpt):
        keep: frozenset = frozenset()
        merged: Dict = dataclasses.field(default_factory=dict)
        rounds: List = dataclasses.field(default_factory=lambda: [0])

        def apply(self, opt_state, global_params, merged):
            r = self.rounds[0]
            if r in self.keep:
                self.merged[r] = merged
            self.rounds[0] = r + 1
            return merged, opt_state

    return MergeRecorder(keep=frozenset(keep))


def _sampler(tr: Dict, seed: int):
    from repro.strategies.sampling import ClientSampler, FixedSizeSampler

    kind = tr["sampler"]["kind"]
    if kind == "full":
        return ClientSampler()
    if kind == "fixed":
        return FixedSizeSampler(n=tr["sampler"]["n"], seed=seed & 0x7FFFFFFF)
    raise ValueError(f"unknown sampler {kind!r}")


def _host(tree):
    import jax

    return jax.tree.map(lambda x: np.asarray(x, np.float64), jax.device_get(tree))


def _round_work(pop, cohort, tr):
    """(sequences, loss positions) one round pushes through the backbone."""
    seqs = loss_pos = 0
    for cid in cohort:
        rows = pop[cid]
        nb = rows.tokens.shape[0]
        picks = ([t % nb for t in range(tr["local_steps"])]
                 + list(range(min(nb, tr["fisher_batches"]))))
        seqs += len(picks) * tr["batch"]
        loss_pos += int(sum(rows.mask[i].sum() for i in picks))
    return seqs, loss_pos


def run(cell, args, t_start: float, devices, tracer: harness.Tracer):
    import jax

    from repro.core.client import HyperParams
    from repro.core.federated import run_federated
    from repro.core.server import ServerState

    tr, seed, model = cell.traffic, args.seed, cell.model
    sz = model.sizes(cell.config)
    cfg = cell.model_config(use_pallas=True)
    sharding = None
    if tr["engine"] == "sharded":
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        sharding = NamedSharding(Mesh(np.array(devices), ("clients",)), PartitionSpec())
    backbone = model.backbone_weights(seed, sz, cell.config["dtype"], sharding)
    global0 = common.adapter_set(seed, sz, "global")
    pop = traffic_gen.round_population(seed, sz.vocab, sz.frontend, tr)
    data = {cid: traffic_gen.to_batches(rows) for cid, rows in pop.items()}
    hp = HyperParams(lr=tr["lr"], grad_clip=tr["grad_clip"], weight_decay=0.0,
                     local_steps=tr["local_steps"],
                     fisher_batches=tr["fisher_batches"])
    inner = _sampler(tr, seed)
    kw = dict(strategy="fednano", hp=hp, use_pallas=True, engine=tr["engine"],
              agg_chunk=tr.get("agg_chunk"), final_eval=False)
    if tr["engine"] == "sharded":
        kw["devices"] = len(devices)
    key = common.seed_key(seed)
    server = ServerState(cfg=cfg, backbone=backbone, global_adapters=global0)

    # two rounds: the first compiles, the second (warm) sizes the window
    s0 = StampSampler(inner, 0)
    res = run_federated(key, cfg, data, {}, rounds=2, server=server, sampler=s0,
                        server_opt=merge_recorder(()), **kw)
    jax.block_until_ready(res.server.global_adapters)
    round_s = time.perf_counter() - s0.stamps[1]
    server = res.server
    del res
    n_rounds = max(CHECKED_ROUNDS, int(round(args.seconds / round_s)))
    start = _host(server.global_adapters)
    recorder = merge_recorder({0, 1, n_rounds - 2, n_rounds - 1})

    s2 = StampSampler(inner, 2, tracer, window=True)
    tracer.start()
    with harness.CompileWatch() as watch:
        res = run_federated(key, cfg, data, {}, rounds=n_rounds, server=server,
                            sampler=s2, server_opt=recorder, **kw)
        jax.block_until_ready(res.server.global_adapters)
        t_end = time.perf_counter()
    s2.close()
    tracer.close_window()
    tracer.stop()
    window_s = t_end - s2.stamps[0]
    setup_s = s2.stamps[0] - t_start
    losses = [m["mean_loss"] for m in res.round_metrics]
    device = harness.device_block(devices)

    seqs = loss_pos = 0
    for cohort in s2.cohorts:
        a, b = _round_work(pop, cohort, tr)
        seqs, loss_pos = seqs + a, loss_pos + b
    tokens = seqs * (tr["text_len"] + image_len(sz))
    need_flops = model.round_flops(sz, sequences=seqs, text_len=tr["text_len"],
                                   image_len=image_len(sz), loss_positions=loss_pos)

    rec = {r: _host(t) for r, t in recorder.merged.items()}
    states = {s.cid: s for s in res.clients}
    last = s2.cohorts[-1]
    got = {
        "loss": losses[:CHECKED_ROUNDS],
        "start": [start, rec[0]],
        "global": [rec[0], rec[1]],
        "last": {"start": rec[n_rounds - 2], "global": _host(res.server.global_adapters),
                 "thetas": [_host(states[c].adapters) for c in last],
                 "fishers": [_host(states[c].fisher) for c in last],
                 "sizes": [pop[c].tokens.shape[0] for c in last]},
    }
    recorder.merged.clear()
    del res, server, backbone, states
    harness.free_device_memory()

    t_ref = time.perf_counter()
    ref = reference_rounds(model, seed, sz, tr, pop, s2.cohorts[:CHECKED_ROUNDS], start,
                           g1=got["start"][1])
    readings = compare(got, ref)
    readings["merge_last"] = last_merge_gap(got["last"], ref["keep"])
    fisher = reference_fisher(model, seed, sz, tr, pop, last, got["last"]["thetas"])
    readings["fisher"] = fisher_gap(got["last"]["fishers"], fisher, ref["keep"])
    ref_s = time.perf_counter() - t_ref

    result = {
        "correct": None, "attempted": n_rounds * len(s2.cohorts[0]),
        "failed": sum(1 for l in losses if l is None or not math.isfinite(l))
                  * len(s2.cohorts[0]),
        "device": device,
    }
    ctx = {"kind": "round", "model": model, "sz": sz, "traffic": tr, "chips": len(devices),
           "device_kind": devices[0].device_kind, "window_s": window_s,
           "tokens": tokens, "rounds": n_rounds, "need_flops": need_flops,
           "sequences": seqs, "trace": tracer.summary,
           "cohort": len(s2.cohorts[0])}
    info = {"rounds": n_rounds, "round_s_warm": round_s, "window_s": window_s,
            "compiles_in_window": watch.events, "reference_s": ref_s,
            "round_s": np.diff(s2.stamps + [t_end]).tolist(),
            "round1_returning_clients": len(set(s2.cohorts[0]) & set(s2.cohorts[1])),
            "readings": readings}
    e2e = {"round_tokens_per_s": {"value": tokens / window_s, "unit": "tokens/s"},
           "setup_s": {"value": setup_s, "unit": "s"}}
    return result, e2e, ctx, readings, info


def image_len(sz) -> int:
    return sz.image_patches if sz.frontend else 0


# ---------------------------------------------------------------------------
# the reference follows the window's first two rounds
# ---------------------------------------------------------------------------

def _stack_rows(pop, cohort, picks_fn):
    """(K, n, B, ...) arrays of the rows each client reads, in pick order."""
    def take(attr):
        arrs = []
        for cid in cohort:
            rows = pop[cid]
            a = getattr(rows, attr)
            if a is None:
                return None
            arrs.append(a[picks_fn(rows.tokens.shape[0])])
        return np.stack(arrs)
    return take("tokens"), take("labels"), take("mask"), take("patches")


def _reference_round(ref, tr, pop, cohort, start, carry, half):
    """One round of the reference from global adapters ``start``.

    ``carry`` maps a client to the AdamW state (m, v, steps) it ends its
    last round with; a client not in it starts fresh. Returns the round's
    mean loss, the merged adapters, the first step's gradients (stacked
    over clients) and the cohort's carry.
    """
    import jax
    import jax.numpy as jnp

    k = len(cohort)
    steps, fb = tr["local_steps"], tr["fisher_batches"]
    train = _stack_rows(pop, cohort, lambda nb: [t % nb for t in range(steps)])
    fish = _stack_rows(pop, cohort, lambda nb: list(range(min(nb, fb))))
    keep = k
    if half and tr["batch"] >= 2:
        cut = lambda a: None if a is None else a[:, :, : tr["batch"] // 2]
        train, fish = tuple(map(cut, train)), tuple(map(cut, fish))
    elif half:
        keep = max(1, k // 2)
    g = jax.tree.map(jnp.asarray, start)
    zero = jax.tree.map(jnp.zeros_like, g)
    stack = lambda trees: jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    theta = jax.tree.map(lambda x: jnp.broadcast_to(x, (k,) + x.shape), g)
    m = stack([carry[c][0] if c in carry else zero for c in cohort])
    v = stack([carry[c][1] if c in carry else zero for c in cohort])
    done = np.array([carry[c][2] if c in carry else 0 for c in cohort], np.int32)
    losses, grad1 = [], None
    for t in range(steps):
        sl = [None if a is None else a[:, t] for a in train]
        loss, grad = ref.loss_and_grads(theta, *sl)
        losses.append(np.asarray(loss))
        if grad1 is None:
            grad1 = grad
        theta, m, v = common.adamw_step(grad, m, v, theta, jnp.asarray(done + t + 1),
                                        lr=tr["lr"], grad_clip=tr["grad_clip"])
    fisher = _fisher(ref, fish, theta)
    rows = lambda tree: [jax.tree.map(lambda x, i=i: x[i], tree) for i in range(k)]
    merged = common.fisher_merge(rows(theta)[:keep], rows(fisher)[:keep],
                                 [pop[c].tokens.shape[0] for c in cohort[:keep]])
    ms, vs = rows(m), rows(v)
    out_carry = {c: (ms[i], vs[i], int(done[i]) + steps) for i, c in enumerate(cohort)}
    return float(np.mean(np.stack(losses))), merged, grad1, out_carry


def _fisher(ref, fish, theta):
    """Diagonal Fisher of each client (stacked) at ``theta`` over the
    (K, n, B, ...) rows ``fish``: the mean squared gradient, plus 1e-8."""
    import jax
    import jax.numpy as jnp

    fsum = jax.tree.map(jnp.zeros_like, theta)
    nf = fish[0].shape[1]
    for f in range(nf):
        sl = [None if a is None else a[:, f] for a in fish]
        _, grad = ref.loss_and_grads(theta, *sl)
        fsum = jax.tree.map(lambda s, x: s + x * x, fsum, grad)
    return jax.tree.map(lambda s: s / max(nf, 1) + 1e-8, fsum)


def reference_fisher(model, seed, sz, tr, pop, cohort, thetas, quant=None,
                     half=False) -> List[Dict]:
    """The reference's Fisher diagonals of ``cohort`` at the adapters
    ``thetas`` (a tree a client), over the rows the program's Fisher pass
    reads; ``quant`` and ``half`` (for batches of two rows or more) as in
    ``reference_rounds``."""
    import jax
    import jax.numpy as jnp

    fb = tr["fisher_batches"]
    fish = _stack_rows(pop, cohort, lambda nb: list(range(min(nb, fb))))
    if half and tr["batch"] >= 2:
        fish = tuple(None if a is None else a[:, :, : tr["batch"] // 2] for a in fish)
    theta = jax.tree.map(lambda *xs: jnp.stack([jnp.asarray(x, jnp.float32) for x in xs]),
                         *thetas)
    with jax.default_matmul_precision("highest"):
        fisher = _host(_fisher(model.Reference(seed, sz, quant), fish, theta))
    return [jax.tree.map(lambda x, i=i: x[i], fisher) for i in range(len(cohort))]


def reference_rounds(model, seed, sz, tr, pop, cohorts, start, g1=None, quant=None,
                     half=False, round0=None) -> Dict:
    """The reference of family module ``model`` over rounds 0 and 1 of
    ``cohorts``, from ``start``.

    Round 1 starts from ``g1`` (the merge of round 0 that the stand-in in
    the program's place made), or from the reference's own merge when
    ``g1`` is None. ``quant`` computes it with float8 weights (the control);
    ``half`` plants the fault "half of the batch left out, the mean over the
    rest": each batch keeps its first half of rows or, for batches of one
    row, each merge keeps the first half of its cohort. ``round0`` is the
    ``"round0"`` of an earlier call on the same cohorts, not made again.
    """
    import jax

    with jax.default_matmul_precision("highest"):
        ref = model.Reference(seed, sz, quant)
        if round0 is None:
            loss0, merged0, grad1, carry = _reference_round(
                ref, tr, pop, cohorts[0], start, {}, half)
            round0 = (loss0, _host(merged0), kept_leaves(_host(grad1)), carry)
        loss0, merged0, keep, carry = round0
        g1 = merged0 if g1 is None else g1
        loss1, merged1, _, _ = _reference_round(ref, tr, pop, cohorts[1], g1,
                                                carry, half)
    return {"loss": [loss0, loss1], "start": [start, g1],
            "global": [merged0, _host(merged1)], "keep": keep, "round0": round0}


def _norms(tree) -> Dict[str, float]:
    out = {}
    for mod, leaves in tree.items():
        for name, x in leaves.items():
            out[f"{mod}.{name}"] = float(np.linalg.norm(np.asarray(x, np.float64)))
    return out


def _minus(a, b):
    return {m: {n: np.asarray(a[m][n], np.float64) - np.asarray(b[m][n], np.float64)
                for n in a[m]} for m in a}


def worst_gap(prog: Dict, ref: Dict, keep: List[str]) -> float:
    """Worst over leaves of |norm_prog - norm_ref| / max(norm_ref, median)."""
    pn, rn = _norms(prog), _norms(ref)
    med = float(np.median([rn[k] for k in keep])) if keep else 0.0
    gaps = [abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30) for k in keep]
    return max(gaps) if gaps else 0.0


def kept_leaves(grad: Dict) -> List[str]:
    """Leaves whose gradient norm is at least a thousandth of the median leaf's."""
    gn = _norms(grad)
    med = float(np.median(list(gn.values())))
    return [k for k, v in gn.items() if v >= EXCLUDE_BELOW * med]


def compare(got: Dict, ref: Dict) -> Dict[str, float]:
    """Loss and global change of each checked round, the worst round taken."""
    keep = ref["keep"]
    loss = max(abs(g - r) / abs(r) if g is not None else math.inf
               for g, r in zip(got["loss"], ref["loss"]))
    delta = max(worst_gap(_minus(got["global"][r], got["start"][r]),
                          _minus(ref["global"][r], ref["start"][r]), keep)
                for r in range(CHECKED_ROUNDS))
    return {"loss": loss, "global_delta": delta}


def fisher_gap(prog: List[Dict], ref: List[Dict], keep: List[str]) -> float:
    """Worst over clients and kept leaves of the gap of Fisher norms."""
    return max((worst_gap(p, r, keep) for p, r in zip(prog, ref)), default=0.0)


def last_merge_gap(last: Dict, keep: List[str]) -> float:
    """The program's last merge against Eq. 1 over its clients' final state."""
    want = common.fisher_merge(last["thetas"], last["fishers"], last["sizes"])
    return worst_gap(_minus(last["global"], last["start"]),
                     _minus(want, last["start"]), keep)

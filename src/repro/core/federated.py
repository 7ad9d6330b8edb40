"""Federated orchestration — Alg. 1 of the paper as a strategy-agnostic engine.

``run_federated`` is a thin loop over the ``repro.strategies`` hooks:

    sampler.select          -> which clients run this round
    client.local_update     -> T local steps via the strategy's loss/fisher hooks
    strategy.post_local_update -> what each client offers for upload
    transforms[*].apply     -> DP / quantization / sparsification on the wire
    strategy.aggregate      -> merge (via server.server_aggregate, which logs comm)
    server_opt.apply        -> optional FedOpt step on the merged pseudo-gradient
    strategy.eval_params    -> which params each client evaluates at the end

Methods are plugins (``repro.strategies``): the engine never branches on a
strategy name. Strings like ``strategy="fednano"`` resolve through the
registry, so the legacy API keeps working.

Four execution engines share those hooks:

  * ``engine="sequential"`` — one client at a time, a Python loop of jitted
    steps. Reference semantics; handles ragged per-client data.
  * ``engine="vmap"`` — the round's cohort is grouped by scheduling flags,
    per-client state pytrees are stacked, and each group runs as ``vmap``
    (clients) of ``lax.scan`` (local steps): one dispatch per group instead
    of K·T. Seeded metrics match the sequential engine (pinned against
    ``tests/golden/strategy_parity.json``). With ``agg_chunk=c`` the cohort
    is processed in chunks of ``c`` and folded into a running merge through
    the strategy's ``agg_stream_*`` hooks, so server memory is O(c) in the
    cohort size.
  * ``engine="sharded"`` — the vmap layout partitioned over a 1-D
    ``("clients",)`` device mesh (``repro.sharding.client_mesh``): the same
    stacked cohorts are wrapped in ``shard_map`` so each of D devices runs
    K/D clients in parallel with unchanged per-client arithmetic (seeded
    metrics match ``engine="vmap"``). Cohorts that don't divide D are
    padded by repeating the last client's row; padding rows never reach
    aggregation, metrics, or comm accounting. With ``overlap=True`` the
    engine keeps a two-deep dispatch pipeline — host-side stack/unstack of
    cohort k+1 overlaps device compute of cohort k (JAX dispatch is async;
    the blocking ``device_get`` happens one cohort late). Cohorts are
    dispatched in cache-sized chunks (width ≤ ``_CHUNK_WIDTH_CAP``), chunk
    state stays device-resident across rounds (stacked outputs feed the
    next round's dispatch directly; ``materialize`` writes true rows back
    before checkpoints, reshuffles, or run end), placed batch stacks are
    cached per chunk, and — when every upload is the raw adapter tree —
    aggregation runs device-side: all chunk outputs fold into the merge in
    one fused dispatch per round (padding rows zero-weighted), with losses
    gathered in a single batched ``device_get``. Each round's metrics
    list, under ``cohort_devices``, how many devices the stacked outputs
    of its cohorts span.
  * ``engine="buffered"`` — FedBuff-style async simulation: clients run
    against the global version they last downloaded, a completion-ordered
    event loop fills a server buffer, and every ``buffer_size`` arrivals are
    merged with staleness-discounted weights n_k/(1+τ)^p. Stragglers delay
    only their own upload, never the round. ``failures=`` draws are wired
    into each dispatch attempt: dropped clients never enqueue an upload,
    crashed clients lose their local progress, stragglers complete with
    extra staleness — all counted per merge in round metrics and carried in
    checkpoints so resume-replay stays deterministic.

Fault tolerance rides on the same loop: ``checkpoint_dir`` periodically
snapshots the *entire* round state (``repro.checkpoint.RunState``: θ_global,
ServerOpt moments, every client's AdamW/warmup state, transform residuals,
CommLog, round RNG identity, and the buffered engine's event queue +
version refcounts), ``resume=`` restores one and replays deterministically
— a resumed run's metrics equal the uninterrupted run's — and
``failures=FailureModel(...)`` injects seeded client dropout, mid-update
crashes, and stragglers so long-horizon runs are testable under churn.
"""
from __future__ import annotations

import dataclasses
import heapq
import os
import time
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import (
    BufferedState,
    CheckpointError,
    RunState,
    load_run_state,
    read_run_meta,
    resolve_run_state_dir,
    save_run_state,
)
from repro.checkpoint.io import _key_data
from repro.core import client as client_lib
from repro.core import server as server_lib
from repro.core.client import ClientState, HyperParams
from repro.core.comm import CommLog, RoundTraffic
from repro.core.failures import FailureModel
from repro.core.types import Batch
from repro.strategies.base import Strategy, get_strategy
from repro.strategies.sampling import ClientSampler
from repro.strategies.server_opt import ServerOpt
from repro.strategies.transforms import (
    TransformCtx,
    UpdateTransform,
    default_transforms,
)
from repro.tracing import span
from repro.utils import host_bytes, tree_bytes

ENGINES = ("sequential", "vmap", "sharded", "buffered")

# without agg_chunk, the sharded engine splits each flag-group into at least
# this many dispatch chunks (rounded up to a multiple of the mesh size) so
# the double buffer has successive launches to overlap — and caps the chunk
# width at _CHUNK_WIDTH_CAP so each dispatch's working set stays cache-sized
# no matter how large the cohort grows (empirically the larger lever on CPU
# meshes: the per-1k-clients step cost is flat for widths 32–128 and ~35%
# worse by width 256, so a 10k cohort runs as ~80 width-128 chunks rather
# than 16 width-632 ones); dispatch width never changes aggregation
# numerics — offers are buffered per client and folded at agg_chunk
# boundaries regardless of how cohorts were batched on device
_PIPELINE_CHUNKS = 16
_CHUNK_WIDTH_CAP = 128

# buffered-engine event kinds: RUN completes a local update; RETRY is a
# failed attempt (dropout/crash) coming back for re-dispatch
_EV_RUN = 0
_EV_RETRY = 1


@dataclass
class FederatedResult:
    strategy: str
    round_metrics: List[Dict] = field(default_factory=list)
    client_accuracy: Dict[int, float] = field(default_factory=dict)
    avg_accuracy: float = 0.0
    comm_totals: Dict[str, int] = field(default_factory=dict)
    server: Optional[object] = None
    clients: Optional[List[ClientState]] = None
    engine: str = "sequential"
    server_opt_state: Optional[object] = None  # final ServerOpt moments
                                               # (checkpointable; see
                                               # save_server_checkpoint)
    setup_s: float = 0.0          # wall seconds spent initializing clients
                                  # (batched vs per-client; engine_bench rows)


class _Checkpointer:
    """Writes versioned RunState snapshots under ``dirpath``.

    Each snapshot lands in ``round_<n>/`` and ``LATEST`` is updated after a
    successful save, so ``resume=<dirpath>`` picks up the newest complete
    one even if the process died mid-write (a snapshot without its
    meta.json — written last — is invisible to the resolver).
    """

    def __init__(self, dirpath: str, every: int, *, key, engine: str,
                 strat, hp, cfg, cids, transforms, failures,
                 start: int = 0):
        self.dirpath = dirpath
        self.every = every
        self.engine = engine
        self.strat = strat
        self.cids = list(cids)
        self.transforms = transforms
        self._last = start
        self._key_data = _key_data(key)
        self._meta_extra = {
            "cfg_name": cfg.name,
            "hp": dataclasses.asdict(hp),
            "strategy_meta": strat.checkpoint_meta(),
            "transforms": [type(t).__name__ for t in transforms],
            "failure_model": failures.to_dict() if failures is not None else None,
        }

    def would_save(self, n: int) -> bool:
        return self.every > 0 and n > self._last and n % self.every == 0

    def maybe_save(self, n: int, **kw) -> None:
        if self.would_save(n):
            self.save(n, **kw)

    def final_save(self, n: int, **kw) -> None:
        if n > self._last:
            self.save(n, **kw)

    def save(self, n: int, *, server, clients, tstates, opt_state,
             metrics, buffered: Optional[BufferedState] = None) -> None:
        rs = RunState(
            engine=self.engine,
            strategy=self.strat.name,
            round_idx=n,
            server_round_idx=server.round_idx,
            rng_key=self._key_data,
            global_adapters=server.global_adapters,
            server_opt_state=opt_state,
            clients=list(clients),
            tstates=[list(tstates[cid]) for cid in self.cids],
            round_metrics=list(metrics),
            comm_rounds=server.comm.state_dict(),
            buffered=buffered,
            meta_extra=self._meta_extra,
        )
        sub = f"round_{n:06d}"
        save_run_state(os.path.join(self.dirpath, sub), rs)
        with open(os.path.join(self.dirpath, "LATEST"), "w") as f:
            f.write(sub)
        self._last = n


def _load_resume(resume: str, *, key, engine, strat, hp, cfg, server,
                 clients, server_opt, transforms) -> RunState:
    """Restore + validate a RunState against this run's configuration.

    Resume means *deterministic replay*: the checkpoint must have been
    written by a run with the same seed, config, strategy, hyperparameters,
    engine, and transform chain — anything else is a fork, and forks should
    go through explicit state surgery, not a resume flag.
    """
    dirpath = resolve_run_state_dir(resume)
    meta = read_run_meta(dirpath)

    def bail(what, saved, current):
        raise CheckpointError(
            f"cannot resume from {dirpath!r}: checkpoint {what} is "
            f"{saved!r}, this run uses {current!r} — resuming would not "
            "replay the original run (start a fresh run or convert the "
            "checkpoint explicitly)")

    if meta["engine"] != engine:
        bail("engine", meta["engine"], engine)
    if meta.get("strategy_meta") != strat.checkpoint_meta():
        bail("strategy", meta.get("strategy_meta"), strat.checkpoint_meta())
    if meta.get("cfg_name") != cfg.name:
        bail("config", meta.get("cfg_name"), cfg.name)
    if meta.get("hp") != dataclasses.asdict(hp):
        bail("hyperparameters", meta.get("hp"), dataclasses.asdict(hp))
    tnames = [type(t).__name__ for t in transforms]
    if meta.get("transforms") != tnames:
        bail("transform chain", meta.get("transforms"), tnames)

    rs = load_run_state(
        dirpath,
        clients_ref=clients,
        global_ref=server.global_adapters,
        server_opt_state_ref=(server_opt.init(server.global_adapters)
                              if server_opt is not None else None),
        transform_templates=[t.state_template(server.global_adapters)
                             for t in transforms],
    )
    kd = _key_data(key)
    if not np.array_equal(np.asarray(rs.rng_key), np.asarray(kd)):
        raise CheckpointError(
            f"cannot resume from {dirpath!r}: the checkpoint was written "
            "under a different root PRNG key — the frozen backbone and "
            "client init are re-derived from the seed at resume, so the "
            "same key/seed is required for faithful replay")
    return rs


def run_federated(
    key,
    cfg,
    train_data: Dict[int, List[Batch]],
    eval_data: Dict[int, List[Batch]],
    *,
    strategy: Union[str, Strategy] = "fednano",
    rounds: int = 10,
    hp: HyperParams = HyperParams(),
    use_pallas: bool = False,
    server: Optional[server_lib.ServerState] = None,
    verbose: bool = False,
    transforms: Optional[Sequence[UpdateTransform]] = None,
    server_opt: Optional[ServerOpt] = None,
    sampler: Optional[ClientSampler] = None,
    engine: str = "sequential",
    agg_chunk: Optional[int] = None,
    devices: Optional[int] = None,
    overlap: bool = True,
    buffer_size: Optional[int] = None,
    staleness_power: float = 0.5,
    latency_fn: Optional[Callable[[int, int], int]] = None,
    final_eval: bool = True,
    failures: Optional[FailureModel] = None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 0,
    resume: Optional[str] = None,
) -> FederatedResult:
    """Run R rounds of federated NanoAdapter tuning.

    ``transforms`` defaults to the ``hp``-driven chain (DP, then int8+EF);
    ``server_opt`` defaults to the strategy's own (usually None = identity);
    ``sampler`` defaults to full participation. ``engine`` picks the
    execution path (see module docstring); ``agg_chunk`` bounds server-side
    aggregation memory by folding cohort chunks through the strategy's
    streaming-merge hooks. ``devices`` (sharded engine only) caps the mesh
    at the first N local devices (default: all — on CPU force a topology
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``);
    ``overlap=False`` disables the sharded engine's two-deep
    prepare/compute double buffer (for benchmarking the overlap win).
    ``buffer_size`` / ``staleness_power`` /
    ``latency_fn(cid, version) -> int`` configure the buffered async engine
    (``rounds`` then counts server merges, not synchronized rounds).
    ``final_eval=False`` skips the end-of-run accuracy pass (benchmarks
    timing 10k-client rounds don't want 10k eval dispatches).

    Fault tolerance: ``failures`` injects seeded client churn (see
    :class:`repro.core.failures.FailureModel`); ``checkpoint_dir`` +
    ``checkpoint_every=k`` snapshot the full round state every k rounds
    (merges, for the buffered engine) plus once at run end (``k=0`` keeps
    only the final snapshot); ``resume=<dir>`` restores a snapshot — pass
    the same key/cfg/hp/strategy and the run replays exactly where it left
    off, with metrics and comm totals matching an uninterrupted run.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; choose from {ENGINES}")
    if devices is not None and engine != "sharded":
        raise ValueError("devices= only applies to engine='sharded'")
    mesh = None
    if engine == "sharded":
        from repro.sharding import client_mesh

        mesh = client_mesh(devices)
    strat = get_strategy(strategy)
    if transforms is None:
        transforms = default_transforms(hp)
    if server_opt is None:
        server_opt = strat.server_opt()
    if sampler is None:
        sampler = ClientSampler()

    k_server, k_clients = jax.random.split(key)
    if server is None:
        # under a mesh the backbone is born replicated on it, so placing it
        # below moves nothing and no device ever holds two copies
        server = server_lib.init_server(
            k_server, cfg,
            sharding=NamedSharding(mesh, PartitionSpec()) if mesh is not None else None)
    cids = sorted(train_data)
    index_of = {cid: i for i, cid in enumerate(cids)}
    ckeys = jax.random.split(k_clients, len(cids))
    t0 = time.perf_counter()
    # batched vmapped init when the strategy uses the stock client layout;
    # bit-identical to the per-client loop (counter-based PRNG), which
    # strategies with custom/ragged state fall back to automatically
    clients = strat.init_clients(
        ckeys, cfg, cids, [len(train_data[cid]) for cid in cids])
    setup_s = time.perf_counter() - t0
    tstates = {cid: [None] * len(transforms) for cid in cids}

    resume_state = None
    if resume is not None:
        resume_state = _load_resume(
            resume, key=key, engine=engine, strat=strat, hp=hp, cfg=cfg,
            server=server, clients=clients, server_opt=server_opt,
            transforms=transforms)
        server = dataclasses.replace(
            server,
            global_adapters=resume_state.global_adapters,
            comm=CommLog.from_state_dict(resume_state.comm_rounds),
            round_idx=resume_state.server_round_idx,
        )
        clients[:] = resume_state.clients
        for i, cid in enumerate(cids):
            tstates[cid] = list(resume_state.tstates[i])
        if verbose:
            print(f"  [{strat.name}] resumed at "
                  f"{'merge' if engine == 'buffered' else 'round'} "
                  f"{resume_state.round_idx} from {resume}")

    ckpt = None
    if checkpoint_dir:
        ckpt = _Checkpointer(
            checkpoint_dir, checkpoint_every, key=key, engine=engine,
            strat=strat, hp=hp, cfg=cfg, cids=cids, transforms=transforms,
            failures=failures,
            start=resume_state.round_idx if resume_state is not None else 0)

    if engine == "buffered":
        result, server = _run_buffered(
            cfg, server, strat, clients, cids, index_of, train_data, hp,
            transforms, tstates, server_opt, rounds=rounds,
            buffer_size=buffer_size, staleness_power=staleness_power,
            latency_fn=latency_fn, use_pallas=use_pallas, verbose=verbose,
            failures=failures, ckpt=ckpt, resume_state=resume_state,
        )
    else:
        result, server = _run_sync(
            cfg, server, strat, clients, cids, index_of, train_data, hp,
            transforms, tstates, server_opt, sampler, rounds=rounds,
            engine=engine, agg_chunk=agg_chunk, use_pallas=use_pallas,
            verbose=verbose, failures=failures, ckpt=ckpt,
            resume_state=resume_state, mesh=mesh, overlap=overlap,
        )
    result.setup_s = setup_s

    # final evaluation: every client, on the params its strategy designates
    # (global adapters for most; LocFT/FedDPA-F evaluate personalized params).
    if final_eval:
        for cid in cids:
            adp, ladp = strat.eval_params(server.global_adapters, clients[index_of[cid]])
            acc = client_lib.eval_client(cfg, server.backbone, adp, ladp, eval_data[cid])
            result.client_accuracy[cid] = acc
        result.avg_accuracy = (
            sum(result.client_accuracy.values()) / max(len(cids), 1)
        )
    result.comm_totals = server.comm.totals()
    result.server = server
    result.clients = clients
    return result


def _chunks(seq: List, width: int):
    for i in range(0, len(seq), width):
        yield seq[i : i + width]


def _rounds(sampler, cids, start: int, stop: int):
    """(round, cohort) pairs; each round's body runs inside its
    ``fednano.round`` span, which opens once the sampler has chosen the
    cohort and closes when the loop asks for the next round."""
    for r in range(start, stop):
        cohort = list(sampler.select(r, cids))
        with span("round", round=r, clients=len(cohort)):
            yield r, cohort


def _run_sync(
    cfg, server, strat, clients, cids, index_of, train_data, hp,
    transforms, tstates, server_opt, sampler, *, rounds, engine, agg_chunk,
    use_pallas, verbose, failures=None, ckpt=None, resume_state=None,
    mesh=None, overlap=True,
):
    """Synchronized rounds: ``engine`` is "sequential", "vmap" or "sharded"."""
    streaming = bool(agg_chunk) and strat.aggregates
    opt_state = server_opt.init(server.global_adapters) if server_opt else None
    result = FederatedResult(strategy=strat.name, engine=engine)
    start_round = 0
    if resume_state is not None:
        start_round = resume_state.round_idx
        if resume_state.server_opt_state is not None:
            opt_state = resume_state.server_opt_state
        result.round_metrics = list(resume_state.round_metrics)

    backbone_dev = server.backbone
    if mesh is not None:
        # replicate the frozen backbone over the mesh once for the whole run;
        # the (changing) global adapters are re-placed at each round start
        _rep = NamedSharding(mesh, PartitionSpec())
        backbone_dev = jax.device_put(server.backbone, _rep)

    # chunk-resident client state (sharded engine): a chunk's stacked AdamW
    # state — and, in rounds that qualify for device-side stacked
    # aggregation, its adapters and Fisher diagonals too — never leaves the
    # devices between rounds. Last round's stacked outputs feed the next
    # round's dispatch (and the aggregation folds) directly, skipping the
    # per-round device→host gather and host→device restack. The matching
    # ``ClientState`` fields go stale while a cid has an entry in ``home``;
    # ``materialize`` writes the true rows back before anything reads them
    # (checkpoint snapshots, a reshuffled cohort, run end).
    resident: Dict[tuple, dict] = {}   # chunk key -> {k, opt, adp, fish}
    home: Dict[int, tuple] = {}        # cid -> chunk key holding its rows
    # client batch lists are immutable within a run, so a chunk's stacked +
    # mesh-placed (train, warm, fisher) batches are identical every round it
    # reappears — cache them keyed by the exact chunk membership
    batch_cache: Dict[tuple, tuple] = {}

    def materialize(cids_needed=None, moved=None):
        keys = ({home[c] for c in cids_needed if c in home}
                if cids_needed is not None else set(home.values()))
        for ck in keys:
            ent = resident[ck]
            kk = ent["k"]
            opt_rows = client_lib._host_unstack(ent["opt"], kk, moved)
            adp_rows = (client_lib._host_unstack(ent["adp"], kk, moved)
                        if ent["adp"] is not None else None)
            fish_rows = (client_lib._host_unstack(ent["fish"], kk, moved)
                         if ent["fish"] is not None else None)
            for j, c in enumerate(ck):
                if home.get(c) != ck:
                    continue
                fields = {"opt_state": opt_rows[j]}
                if adp_rows is not None:
                    fields["adapters"] = adp_rows[j]
                if fish_rows is not None:
                    fields["fisher"] = fish_rows[j]
                clients[index_of[c]] = dataclasses.replace(
                    clients[index_of[c]], **fields)
                del home[c]

    for r, cohort in _rounds(sampler, cids, start_round, rounds):
        gbytes = tree_bytes(server.global_adapters)
        down_bytes = 0
        wire_up = 0
        n_dropped = n_crashed = 0
        # failure injection: dropped clients never start (no bytes, no
        # compute); crashed clients pull the global (bytes charged), then
        # die mid-update — local progress lost, state untouched, no upload
        if failures is not None and failures.active:
            alive = []
            for cid in cohort:
                if failures.drops(cid, r):
                    n_dropped += 1
                else:
                    alive.append(cid)
            cohort = []
            for cid in alive:
                if failures.crashes(cid, r):
                    st = clients[index_of[cid]]
                    if strat.downloads_global(st.rounds_participated):
                        down_bytes += gbytes
                    n_crashed += 1
                else:
                    cohort.append(cid)
        losses: List[float] = []           # cohort order
        spans: set = set()                 # devices each mesh cohort spans
        updates: List[tuple] = []          # (theta, fisher, size), cohort order
        stream_acc = strat.agg_stream_init() if streaming else None
        stream_buf: List[tuple] = []
        stream_bytes = {"param_up": 0, "fisher_up": 0}
        folded_any = False
        # device-side stacked aggregation (sharded engine fast path): chunk
        # outputs fold into the merge where they live, padding rows masked
        # with zero weight — no per-client upload tree ever exists. Folds
        # are deferred to one fused dispatch at round end (the stacks stay
        # device-resident regardless, so deferral costs no extra memory).
        fast_acc = None
        fast_pend: List[tuple] = []    # (theta_stack, fisher_stack, weights)
        fast_losses: List[tuple] = []  # (chunk, device losses, real k)
        fast_bytes = {"param_up": 0, "fisher_up": 0}

        def apply_transforms(cid: int, theta):
            ctx = TransformCtx(cid=cid, round_idx=r)
            theta_wire = None
            for j, t in enumerate(transforms):
                theta, tstates[cid][j], w = t.apply(
                    ctx, theta, server.global_adapters, tstates[cid][j]
                )
                if w is not None:
                    theta_wire = w
            return theta, (theta_wire if theta_wire is not None else tree_bytes(theta))

        def offer(cid: int, state: ClientState, loss_mean: float):
            nonlocal wire_up, folded_any
            theta = strat.post_local_update(state, server.global_adapters, r)
            theta, wbytes = apply_transforms(cid, theta)
            wire_up += wbytes
            losses.append(loss_mean)
            if streaming:
                stream_buf.append((theta, state.fisher, state.n_examples))
                if len(stream_buf) >= agg_chunk:
                    fold_stream()
            else:
                updates.append((theta, state.fisher, state.n_examples))

        def fold_stream():
            nonlocal stream_acc, folded_any
            if not stream_buf:
                return
            ts = [u[0] for u in stream_buf]
            fs = [u[1] for u in stream_buf]
            ws = [u[2] for u in stream_buf]
            stream_bytes["param_up"] += sum(tree_bytes(t) for t in ts)
            stream_bytes["fisher_up"] += sum(
                tree_bytes(f) for f in fs if f is not None)
            with span("round.merge", bytes_to_device=host_bytes([ts, fs])):
                stream_acc = strat.agg_stream_fold(
                    stream_acc, ts, fs, ws, use_pallas=use_pallas)
            folded_any = True
            stream_buf.clear()

        if engine == "sequential":
            for cid in cohort:
                i = index_of[cid]
                if strat.downloads_global(clients[i].rounds_participated):
                    down_bytes += gbytes
                clients[i], metrics = client_lib.local_update(
                    cfg, server.backbone, clients[i], train_data[cid], hp,
                    strat, server.global_adapters, round_idx=r,
                )
                offer(cid, clients[i], metrics["loss_mean"])
        else:  # engine "vmap"/"sharded": group cohort by flags, then batch
            groups: Dict[tuple, List[int]] = {}
            for cid in cohort:
                st = clients[index_of[cid]]
                p = st.rounds_participated
                flags = (
                    strat.downloads_global(p),
                    st.local_adapters is not None and strat.local_warmup(p, hp),
                )
                groups.setdefault(flags, []).append(cid)

            global_dev = server.global_adapters
            if mesh is not None:
                global_dev = jax.device_put(
                    server.global_adapters, NamedSharding(mesh, PartitionSpec()))

            # dispatch plan: (downloads, chunk) across all flag-groups. The
            # dispatch width never changes aggregation numerics (offers are
            # replayed per client, in plan order, and streamed folds trigger
            # at agg_chunk boundaries only), so the sharded engine is free
            # to split groups into pipeline-sized, mesh-aligned chunks.
            plan: List[tuple] = []
            for (downloads, _), gcids in groups.items():
                if mesh is None:
                    width = agg_chunk if agg_chunk else len(gcids)
                else:
                    from repro.sharding import pad_to_multiple

                    width = (agg_chunk if agg_chunk
                             else min(_CHUNK_WIDTH_CAP,
                                      max(1, -(-len(gcids) // _PIPELINE_CHUNKS))))
                    width = pad_to_multiple(width, mesh.size)
                for chunk in _chunks(gcids, width):
                    plan.append((downloads, chunk))

            # device-side aggregation applies when every upload is the raw
            # adapter tree (stock post_local_update, no wire transforms, no
            # dual-adapter rows) and every chunk re-downloads the global —
            # then the stacked outputs ARE the uploads, and the fold can run
            # on the mesh with pad rows zero-weighted. Anything fancier
            # falls back to the per-client offer path below.
            fast_agg = (
                mesh is not None and strat.aggregates and not use_pallas
                and not transforms
                and type(strat).post_local_update is Strategy.post_local_update
                and all(flags[0] for flags in groups)
                and not any(
                    clients[index_of[gcids[0]]].local_adapters is not None
                    for gcids in groups.values())
            )

            # non-streaming aggregation must see cohort order; buffer per-cid
            pending: Dict[int, tuple] = {}
            # two-deep double buffer (sharded + overlap): while cohort k
            # computes on the devices, the host stacks and launches k+1 —
            # collect_cohort's device_get is the only blocking point, and it
            # always trails the most recent launch by one chunk
            depth = 2 if (mesh is not None and overlap) else 1
            inflight: deque = deque()

            def collect_one():
                nonlocal down_bytes, wire_up
                downloads, chunk, launched = inflight.popleft()
                kc = len(chunk)
                if fast_agg:
                    # nothing leaves the devices here: adapters/opt/fisher
                    # queue for the round-end fused stacked merge, losses
                    # for one round-end batched gather
                    new_states, loss_dev = client_lib.collect_cohort_deferred(
                        launched)
                    outs = launched.outs
                    wants_f = launched.prepared.wants_fisher is not None
                    ck = tuple(chunk)
                    resident[ck] = {"k": kc, "opt": outs[1], "adp": outs[0],
                                    "fish": outs[4] if wants_f else None}
                    for c in chunk:
                        home[c] = ck
                    width = jax.tree_util.tree_leaves(outs[0])[0].shape[0]
                    weights = [float(clients[index_of[c]].n_examples)
                               for c in chunk] + [0.0] * (width - kc)
                    fast_pend.append(
                        (outs[0], outs[4] if wants_f else None, weights))
                    row_pb = tree_bytes(outs[0]) // width
                    fast_bytes["param_up"] += row_pb * kc
                    wire_up += row_pb * kc
                    if wants_f:
                        fast_bytes["fisher_up"] += (
                            tree_bytes(outs[4]) // width) * kc
                elif mesh is not None:
                    # keep the new opt tree on the devices; per-client
                    # opt_state goes stale until materialize
                    new_states, mets = client_lib.collect_cohort(
                        launched, with_opt=False)
                    ck = tuple(chunk)
                    resident[ck] = {"k": kc, "opt": launched.outs[1],
                                    "adp": None, "fish": None}
                    for c in chunk:
                        home[c] = ck
                else:
                    new_states, mets = client_lib.collect_cohort(launched)
                if downloads:
                    down_bytes += gbytes * kc
                if fast_agg:
                    for c, ns in zip(chunk, new_states):
                        clients[index_of[c]] = ns
                    fast_losses.append((chunk, loss_dev, kc))
                    return
                with span("round.offer", clients=kc):
                    for c, ns, m in zip(chunk, new_states, mets):
                        clients[index_of[c]] = ns
                        pending[c] = m["loss_mean"]
                        offer(c, ns, m["loss_mean"])

            for downloads, chunk in plan:
                with span("round.prepare", clients=len(chunk)) as sp:
                    moved = Counter()
                    opt0 = bx = None
                    if mesh is not None:
                        ck = tuple(chunk)
                        bx = batch_cache.get(ck)
                        if (all(home.get(c) == ck for c in chunk)
                                and (downloads or resident[ck]["adp"] is None)):
                            opt0 = resident[ck]["opt"]
                        else:
                            # cohort reshuffled (or stale adapters would be
                            # stacked): pull resident rows back to their
                            # ClientStates before stacking from the host
                            needs = [c for c in chunk if c in home]
                            if needs:
                                materialize(needs, moved)
                    idxs = [index_of[c] for c in chunk]
                    prepared = client_lib.prepare_cohort(
                        cfg, [clients[i] for i in idxs],
                        [train_data[c] for c in chunk], hp, strat, mesh=mesh,
                        opt0_override=opt0, batches_override=bx, moved=moved)
                    if mesh is not None and bx is None:
                        batch_cache[ck] = prepared.args[4:7]
                    sp.set_metadata(bytes_to_device=moved["bytes_to_device"],
                                    bytes_to_host=moved["bytes_to_host"])
                with span("round.launch"):
                    launched = client_lib.launch_cohort(
                        prepared, backbone_dev, global_dev)
                if mesh is not None:
                    spans.update(len(leaf.sharding.device_set)
                                 for leaf in jax.tree.leaves(launched.outs))
                inflight.append((downloads, chunk, launched))
                if len(inflight) >= depth:
                    collect_one()
            while inflight:
                collect_one()
            # drop resident chunks no cid points at anymore (reshuffles),
            # and cached batch stacks for chunk keys this round didn't use
            if resident:
                live = set(home.values())
                for ck in [k for k in resident if k not in live]:
                    del resident[ck]
            if batch_cache:
                used = {tuple(chunk) for _, chunk in plan}
                for ck in [k for k in batch_cache if k not in used]:
                    del batch_cache[ck]
            if fast_losses:
                all_mets = client_lib.loss_metrics_deferred(
                    [l for _, l, _ in fast_losses],
                    [kk for _, _, kk in fast_losses])
                for (chunk, _, _), mets in zip(fast_losses, all_mets):
                    for c, m in zip(chunk, mets):
                        pending[c] = m["loss_mean"]
            # keep round metrics in cohort order regardless of grouping
            losses = [pending[c] for c in cohort if c in pending]

        if streaming:
            fold_stream()  # the last part-chunk, in a merge span of its own
        with span("round.merge",
                  bytes_to_device=host_bytes([u[:2] for u in updates])):
            if fast_pend:
                fast_acc = strat.agg_stream_fold_stacked(
                    None, [p[0] for p in fast_pend],
                    [p[1] for p in fast_pend], [p[2] for p in fast_pend],
                    use_pallas=use_pallas)
            if fast_acc is not None:
                # device-side stacked merge: finalize where the folds ran, then
                # commit with byte totals identical to the per-client path
                # (k identical rows ⇒ k·row_bytes)
                prev_global = server.global_adapters
                merged = strat.agg_stream_finalize(fast_acc, use_pallas=use_pallas)
                server = server_lib.server_commit(
                    server, merged,
                    param_up=fast_bytes["param_up"],
                    fisher_up=fast_bytes["fisher_up"],
                    param_down=down_bytes, wire_up=wire_up,
                )
                if server_opt is not None:
                    new_global, opt_state = server_opt.apply(
                        opt_state, prev_global, server.global_adapters
                    )
                    server = dataclasses.replace(server, global_adapters=new_global)
            elif strat.aggregates and (updates or stream_buf or folded_any):
                prev_global = server.global_adapters
                if streaming:
                    merged = strat.agg_stream_finalize(stream_acc, use_pallas=use_pallas)
                    server = server_lib.server_commit(
                        server, merged,
                        param_up=stream_bytes["param_up"],
                        fisher_up=stream_bytes["fisher_up"],
                        param_down=down_bytes, wire_up=wire_up,
                    )
                else:
                    thetas = [u[0] for u in updates]
                    fishers = [u[1] for u in updates]
                    sizes = [u[2] for u in updates]
                    server = server_lib.server_aggregate(
                        server, strat, thetas, fishers, sizes,
                        use_pallas=use_pallas, wire_up=wire_up,
                        down_bytes=down_bytes,
                    )
                if server_opt is not None:
                    new_global, opt_state = server_opt.apply(
                        opt_state, prev_global, server.global_adapters
                    )
                    server = dataclasses.replace(server, global_adapters=new_global)
            elif down_bytes:
                # no merge this round (e.g. LocFT, or every starter crashed) but
                # clients still pulled the global at round start — that
                # broadcast crossed the wire
                server_lib.log_downloads(server, r, down_bytes)

        n = len(losses)
        # an empty cohort must be distinguishable from a perfect round:
        # participants==0 carries mean_loss=None, never a fake 0.0
        rm = {"round": r,
              "mean_loss": (sum(losses) / n) if n else None,
              "participants": n}
        if failures is not None:
            rm["dropped"] = n_dropped
            rm["crashed"] = n_crashed
        if mesh is not None:
            rm["cohort_devices"] = sorted(spans)
        result.round_metrics.append(rm)
        if verbose:
            shown = "skipped (no participants)" if n == 0 else f"mean local loss {rm['mean_loss']:.4f}"
            print(f"  [{strat.name}] round {r}: {shown}")

        if ckpt is not None and ckpt.would_save(r + 1):
            with span("round.checkpoint"):
                if home:
                    materialize()  # snapshots need true per-client state rows
                ckpt.save(r + 1, server=server, clients=clients,
                          tstates=tstates, opt_state=opt_state,
                          metrics=result.round_metrics)

    if home:
        materialize()
    if ckpt is not None:
        ckpt.final_save(rounds, server=server, clients=clients,
                        tstates=tstates, opt_state=opt_state,
                        metrics=result.round_metrics)
    result.server_opt_state = opt_state
    return result, server


def _run_buffered(
    cfg, server, strat, clients, cids, index_of, train_data, hp,
    transforms, tstates, server_opt, *, rounds, buffer_size, staleness_power,
    latency_fn, use_pallas, verbose, failures=None, ckpt=None,
    resume_state=None,
):
    """FedBuff-style async engine: merge every ``buffer_size`` completions.

    Simulated time advances in integer server ticks; ``latency_fn(cid,
    version)`` says how many ticks a client's local run takes (default 1 —
    homogeneous clients degenerate to synchronized rounds). A client always
    trains against the global *version it last downloaded*; its upload is
    merged with weight n_k/(1+τ)^p where τ is the number of server merges
    that happened while it was running. ``rounds`` counts server merges.

    Failure semantics (per *dispatch attempt*, keyed by the simulated tick):
    a dropped client never downloads and retries next tick; a crashed
    client downloads (bytes charged), trains for its latency, then its
    upload is lost and it re-dispatches. Stragglers add
    ``straggler_ticks`` to their completion time, so their uploads arrive
    stale and take the staleness discount.

    Checkpoints are taken at tick boundaries once ``checkpoint_every``
    merges have accumulated: the snapshot carries the event heap, live
    version snapshots with refcounts, and the partially-filled merge
    buffer, so a resumed run pops the identical completion order the
    uninterrupted run would have.
    """
    if not strat.aggregates:
        raise ValueError(
            f"engine='buffered' needs an aggregating strategy; {strat.name!r} "
            "never merges (local-only)")
    bsize = buffer_size if buffer_size else max(1, len(cids) // 2)
    bsize = min(bsize, len(cids))
    if latency_fn is None:
        latency_fn = lambda cid, version: 1  # noqa: E731
    opt_state = server_opt.init(server.global_adapters) if server_opt else None
    result = FederatedResult(strategy=strat.name, engine="buffered")
    gbytes = tree_bytes(server.global_adapters)

    # version -> [global snapshot, in-flight refcount]; clients in flight pin
    # the snapshot they downloaded, so memory is O(distinct live versions)
    version = 0
    snapshots: Dict[int, list] = {version: [server.global_adapters, 0]}
    events: List[tuple] = []  # (finish_tick, cid, version_started, kind)
    merges = 0
    # per-merge accumulators; the failure counters ride in the same dict so
    # checkpoints carry them and resume-replay reports identical metrics
    acc_up = {"param_up": 0, "fisher_up": 0, "wire_up": 0, "down": 0,
              "dropped": 0, "crashed": 0, "straggled": 0}
    buffer: List[tuple] = []  # (theta, fisher, size, loss_mean, staleness)

    def dispatch(cid: int, now: int):
        if failures is not None and failures.drops(cid, now):
            # offline this tick: no download, no snapshot pin, nothing ever
            # enqueued for upload; retry next tick
            acc_up["dropped"] += 1
            heapq.heappush(events, (now + 1, cid, version, _EV_RETRY))
            return
        st = clients[index_of[cid]]
        if strat.downloads_global(st.rounds_participated):
            acc_up["down"] += gbytes
        lat = max(1, int(latency_fn(cid, version)))
        if failures is not None and failures.straggles(cid, now):
            # slow attempt: completes, but ``straggler_ticks`` later — by
            # then the server has merged more versions, so this upload lands
            # with extra staleness and takes the n/(1+τ)^p discount
            acc_up["straggled"] += 1
            lat += failures.straggler_ticks
        if failures is not None and failures.crashes(cid, now):
            # downloaded, then died mid-update: the broadcast crossed the
            # wire but nothing comes back and no snapshot stays pinned
            acc_up["crashed"] += 1
            heapq.heappush(events, (now + lat, cid, version, _EV_RETRY))
            return
        snapshots[version][1] += 1
        heapq.heappush(events, (now + lat, cid, version, _EV_RUN))

    if resume_state is not None:
        b = resume_state.buffered
        if b is None:
            raise CheckpointError(
                "checkpoint has no buffered-engine state; it was written by "
                "a synchronized engine")
        version = b.version
        snapshots = dict(b.snapshots)
        # the current version's snapshot IS the restored global (saved once)
        snapshots.setdefault(version, [server.global_adapters, 0])
        events = list(b.events)  # a valid heap, restored verbatim
        buffer = list(b.buffer)
        acc_up = dict(b.acc_up)
        for k in ("dropped", "crashed", "straggled"):
            acc_up.setdefault(k, 0)  # pre-failure-counter checkpoints
        merges = resume_state.round_idx
        if resume_state.server_opt_state is not None:
            opt_state = resume_state.server_opt_state
        result.round_metrics = list(resume_state.round_metrics)
    else:
        for cid in cids:
            dispatch(cid, 0)

    while merges < rounds:
        if ckpt is not None:
            ckpt.maybe_save(
                merges, server=server, clients=clients, tstates=tstates,
                opt_state=opt_state, metrics=result.round_metrics,
                buffered=BufferedState(
                    version=version, events=list(events),
                    snapshots=snapshots, buffer=buffer, acc_up=acc_up))
        # drain every completion in this simulated tick before re-dispatching
        # any of them: a client re-downloads only after its upload is acked,
        # by which point the server has folded everything this tick produced
        # (so uniform latency degenerates to synchronized zero-staleness
        # rounds instead of racing re-downloads against the merge)
        now = events[0][0]
        done_this_tick: List[int] = []
        while events and events[0][0] == now and merges < rounds:
            _, cid, v_start, kind = heapq.heappop(events)
            done_this_tick.append(cid)
            if kind != _EV_RUN:
                continue  # failed attempt coming back for re-dispatch
            snap_global, _ = snapshots[v_start]
            i = index_of[cid]
            clients[i], metrics = client_lib.local_update(
                cfg, server.backbone, clients[i], train_data[cid], hp, strat,
                snap_global, round_idx=merges,
            )
            theta = strat.post_local_update(clients[i], snap_global, merges)
            ctx = TransformCtx(cid=cid, round_idx=merges)
            theta_wire = None
            for j, t in enumerate(transforms):
                theta, tstates[cid][j], w = t.apply(ctx, theta, snap_global,
                                                    tstates[cid][j])
                if w is not None:
                    theta_wire = w
            acc_up["wire_up"] += theta_wire if theta_wire is not None else tree_bytes(theta)
            acc_up["param_up"] += tree_bytes(theta)
            if clients[i].fisher is not None:
                acc_up["fisher_up"] += tree_bytes(clients[i].fisher)
            buffer.append((theta, clients[i].fisher, clients[i].n_examples,
                           metrics["loss_mean"], version - v_start))
            snapshots[v_start][1] -= 1
            if snapshots[v_start][1] == 0 and v_start != version:
                del snapshots[v_start]

            if len(buffer) >= bsize:
                weights = [n / (1.0 + tau) ** staleness_power
                           for _, _, n, _, tau in buffer]
                sacc = strat.agg_stream_init()
                sacc = strat.agg_stream_fold(
                    sacc, [b[0] for b in buffer], [b[1] for b in buffer], weights,
                    use_pallas=use_pallas)
                merged = strat.agg_stream_finalize(sacc, use_pallas=use_pallas)
                prev_global = server.global_adapters
                server = server_lib.server_commit(
                    server, merged,
                    param_up=acc_up["param_up"], fisher_up=acc_up["fisher_up"],
                    param_down=acc_up["down"], wire_up=acc_up["wire_up"],
                )
                if server_opt is not None:
                    new_global, opt_state = server_opt.apply(
                        opt_state, prev_global, server.global_adapters)
                    server = dataclasses.replace(server, global_adapters=new_global)
                blosses = [b[3] for b in buffer]
                bstale = [b[4] for b in buffer]
                rm = {"round": merges,
                      "mean_loss": sum(blosses) / len(blosses),
                      "participants": len(buffer),
                      "mean_staleness": sum(bstale) / len(bstale)}
                if failures is not None:
                    # failed/slow dispatch attempts since the last merge
                    rm["dropped"] = acc_up["dropped"]
                    rm["crashed"] = acc_up["crashed"]
                    rm["straggled"] = acc_up["straggled"]
                result.round_metrics.append(rm)
                if verbose:
                    print(f"  [{strat.name}] merge {merges}: mean loss "
                          f"{rm['mean_loss']:.4f} staleness {rm['mean_staleness']:.2f}")
                merges += 1
                version += 1
                snapshots[version] = [server.global_adapters, 0]
                buffer.clear()
                acc_up = {"param_up": 0, "fisher_up": 0, "wire_up": 0, "down": 0,
                          "dropped": 0, "crashed": 0, "straggled": 0}

        for cid in done_this_tick:
            dispatch(cid, now)

    if ckpt is not None:
        # the exit-state snapshot lets a later run extend this one with more
        # merges (resume + larger ``rounds``); note that stopping at exactly
        # ``rounds`` merges leaves same-tick completions undrained, so an
        # extended run is a continuation of THIS schedule, not a replay of a
        # longer uninterrupted one — mid-run snapshots (checkpoint_every)
        # are the replay-equivalent ones
        ckpt.final_save(
            merges, server=server, clients=clients, tstates=tstates,
            opt_state=opt_state, metrics=result.round_metrics,
            buffered=BufferedState(
                version=version, events=list(events), snapshots=snapshots,
                buffer=buffer, acc_up=acc_up))
    result.server_opt_state = opt_state
    return result, server


def run_centralized(
    key,
    cfg,
    train_data: Dict[int, List[Batch]],
    eval_data: Dict[int, List[Batch]],
    *,
    steps: int = 100,
    hp: HyperParams = HyperParams(),
    verbose: bool = False,
) -> FederatedResult:
    """Upper bound: one 'client' holding the union of all data."""
    all_train: List[Batch] = []
    for cid in sorted(train_data):
        all_train.extend(train_data[cid])
    k_server, k_client = jax.random.split(key)
    server = server_lib.init_server(k_server, cfg)
    state = client_lib.init_client(
        k_client, cfg, cid=0, n_examples=len(all_train), strategy="fedavg"
    )
    hp_c = HyperParams(
        lr=hp.lr, weight_decay=hp.weight_decay, grad_clip=hp.grad_clip,
        local_steps=steps, prox_mu=hp.prox_mu, fisher_batches=hp.fisher_batches,
    )
    state, metrics = client_lib.local_update(
        cfg, server.backbone, state, all_train, hp_c, "fedavg",
        server.global_adapters, round_idx=0,
    )
    result = FederatedResult(strategy="centralized")
    result.round_metrics.append(
        {"round": 0, "mean_loss": metrics["loss_mean"], "participants": 1}
    )
    # the centralized upper bound still moves bytes: one initial broadcast
    # down to the lone trainer, one adapter upload back — log it so comm
    # tables comparing against this bound don't silently read zeros
    server.comm.log_round(RoundTraffic(
        round_idx=0,
        param_up=tree_bytes(state.adapters),
        param_down=tree_bytes(server.global_adapters),
        param_up_wire=tree_bytes(state.adapters),
    ))
    for cid in sorted(eval_data):
        acc = client_lib.eval_client(cfg, server.backbone, state.adapters, None, eval_data[cid])
        result.client_accuracy[cid] = acc
    result.avg_accuracy = sum(result.client_accuracy.values()) / len(result.client_accuracy)
    result.comm_totals = server.comm.totals()
    result.server = server
    result.clients = [state]
    if verbose:
        print(f"  [centralized] acc {result.avg_accuracy:.4f}")
    return result

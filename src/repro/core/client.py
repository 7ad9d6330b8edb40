"""Client-side local tuning (Alg. 1, ClientUpdate).

Each client trains ONLY its NanoAdapters (optionally a dual local adapter for
the FedDPA-F baseline). The backbone is a frozen constant — gradients are
taken w.r.t. the adapter pytree alone, so the server-hosted LLM weights are
never perturbed and nothing model-sized is ever shipped.

Strategy-specific behaviour is injected through the ``repro.strategies``
hooks (``wrap_local_loss``, ``wants_fisher``, ``downloads_global``,
``local_warmup``); this module only knows how to run T adamw steps over a
wrapped objective and estimate the diagonal FIM. ``strategy`` arguments
accept either a registered name ("fednano", "fedprox", …) or a ``Strategy``
instance — names are resolved through the registry.

Three execution paths share the same step bodies (one source of numerics):

  * ``local_update``       — one client, Python loop over T jitted steps.
  * ``local_update_many``  — a cohort of homogeneous clients at once:
    per-client state pytrees are stacked along a new leading axis and the
    whole round runs as ``vmap`` (over clients) of ``lax.scan`` (over local
    steps), so a 1k-client round costs one dispatch instead of 1k·T.
  * the same stacked layout partitioned over a 1-D ``("clients",)`` device
    mesh: ``make_many_update(..., mesh=...)`` wraps the identical vmapped
    body in ``shard_map``, so every device runs K/D clients in parallel
    with unchanged per-client arithmetic (the sharded engine pads ragged
    cohorts by repeating the last row; padding rows are sliced off before
    any state, metric, or byte leaves this module).

``local_update_many`` is itself split into ``prepare_cohort`` (host-side
validation + stacking + device placement), ``launch_cohort`` (the async
device dispatch), and ``collect_cohort`` (device→host unstack + state
rebuild), so the round engine can double-buffer: prepare cohort k+1 on the
host while cohort k computes on the devices.
"""
from __future__ import annotations

import dataclasses
import functools
from collections import Counter
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import adapters as adapters_lib
from repro.core.fisher import FisherAccumulator, fisher_pass
from repro.core.types import Batch
from repro.optim import adamw_init, adamw_update
from repro.tracing import span
from repro.utils import device_bytes, host_bytes, tree_bytes
from repro.utils import tree_stack  # noqa: F401  (re-export for tests)


@dataclass(frozen=True)
class HyperParams:
    lr: float = 1e-3
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    local_steps: int = 10          # T local steps per round (paper: 1 epoch)
    prox_mu: float = 0.01          # FedProx proximal coefficient
    fisher_batches: int = 4        # batches for the dedicated FIM pass
    dpa_warmup_rounds: int = 1     # FedDPA-F: rounds that train the local adapter
    # --- beyond-paper extensions (repro.core.{compression,privacy}) ---
    compress_uploads: bool = False # int8 delta quantization + error feedback
    dp_clip: float = 0.0           # client-level DP: L2 clip of the delta (0 = off)
    dp_noise: float = 0.0          # client-level DP: Gaussian noise multiplier


@dataclass
class ClientState:
    cid: int
    adapters: Dict            # global/shared NanoAdapters (uploaded)
    opt_state: Any
    n_examples: int
    local_adapters: Optional[Dict] = None   # FedDPA-F personal adapter
    fisher: Optional[Dict] = None           # last computed diagonal FIM
    rounds_participated: int = 0            # local_update calls so far (drives
                                            # download/warmup under sampling)
    local_opt_state: Any = None             # personal-adapter AdamW moments,
                                            # carried across warmup rounds


def init_client(key, cfg, cid: int, n_examples: int, strategy) -> ClientState:
    """Build a client via the strategy's ``init_client`` hook."""
    from repro.strategies.base import get_strategy

    return get_strategy(strategy).init_client(key, cfg, cid, n_examples)


@functools.lru_cache(maxsize=16)
def _make_batched_init(cfg, dual: bool) -> Callable:
    """Jitted vmapped variant of the base ``Strategy.init_client`` body.

    jax.random is counter-based (threefry): ``vmap(split)`` /
    ``vmap(init_nanoedge)`` over stacked keys draw bit-identical values to K
    sequential per-key calls, so the fast path is exact, not approximate.
    """

    def one(key):
        k1, k2 = jax.random.split(key)
        adp = adapters_lib.init_nanoedge(k1, cfg)
        local = adapters_lib.init_nanoedge(k2, cfg) if dual else None
        return adp, adamw_init(adp), local

    return jax.jit(jax.vmap(one))


def init_clients_batched(strategy, keys, cfg, cids, n_examples) -> List[ClientState]:
    """Batch-initialize a homogeneous cohort in one device dispatch.

    Per-client ``init_client`` costs O(K) dispatches and dominates setup
    wall-clock at 10k clients; this stacks the PRNG keys and runs ONE jitted
    vmap, then unstacks through numpy views. Only valid for strategies using
    the base ``Strategy.init_client`` body (the ``Strategy.init_clients``
    hook guards this and falls back to the loop otherwise).
    """
    k = len(cids)
    assert len(keys) == k and len(n_examples) == k
    adp, opt, local = _make_batched_init(cfg, bool(strategy.dual_adapters))(
        jnp.stack(list(keys)))
    adp_list = _host_unstack(adp, k)
    opt_list = _host_unstack(opt, k)
    local_list = (_host_unstack(local, k)
                  if strategy.dual_adapters else [None] * k)
    return [
        ClientState(cid=cid, adapters=adp_list[i], opt_state=opt_list[i],
                    n_examples=n, local_adapters=local_list[i])
        for i, (cid, n) in enumerate(zip(cids, n_examples))
    ]


def client_ref_like(state: ClientState) -> ClientState:
    """Reference structures for restoring a checkpointed ``ClientState``.

    A freshly-initialized client may carry ``None`` where a checkpointed one
    holds arrays (the FIM after its first round, the personal-adapter AdamW
    moments after warmup). This fills those slots with structure templates —
    fisher trees are float32 adapter-shaped (both the dedicated pass and the
    streaming EF estimator accumulate squared grads in float32), and the
    personal optimizer template is a fresh ``adamw_init`` — so strict
    shape/dtype restoration has something to restore into. Values are
    irrelevant; only structure, shapes, and dtypes matter.
    """
    fisher = state.fisher
    if fisher is None:
        fisher = jax.tree.map(
            lambda x: jnp.zeros(jnp.shape(x), jnp.float32), state.adapters)
    local_opt_state = state.local_opt_state
    if local_opt_state is None and state.local_adapters is not None:
        local_opt_state = adamw_init(state.local_adapters)
    return dataclasses.replace(
        state, fisher=fisher, local_opt_state=local_opt_state)


def _combined_loss(cfg, backbone, adapters, local_adapters, batch):
    """FedDPA composition: shared adapter then personal adapter."""
    if local_adapters is None:
        return adapters_lib.fednano_loss(cfg, backbone, adapters, batch)
    # compose: run NanoEdge with the shared adapters, then apply the personal
    # adapters on the resulting embeddings (dual-adapter design).
    embeds, positions, labels, mask, enc = adapters_lib.nanoedge_forward(
        cfg, backbone, adapters, batch
    )
    kw = dict(rank=cfg.adapter.rank, alpha=cfg.adapter.alpha, use_pallas=cfg.use_pallas)
    if "text" in local_adapters:
        embeds = adapters_lib.nano_adapter_apply(local_adapters["text"], embeds, **kw)
    if enc is not None and "image" in local_adapters:
        enc = adapters_lib.nano_adapter_apply(local_adapters["image"], enc, **kw)
    from repro.models import model as model_lib

    loss, aux = model_lib.loss_fn(cfg, backbone, embeds, positions, labels, mask, enc)
    return loss, aux


def _train_step_body(cfg, strategy, hp, backbone, adapters, local_adapters,
                     opt_state, batch, global_ref, ef_sum, ef_cnt):
    """One local AdamW step on the shared adapters (pure; traced by both the
    per-client jitted step and the vmap/scan engine — single numerics source)."""

    def base_loss(adp):
        return _combined_loss(cfg, backbone, adp, local_adapters, batch)

    loss_fn = strategy.wrap_local_loss(base_loss, hp, global_ref)
    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(adapters)
    new_adapters, new_opt = adamw_update(
        grads, opt_state, adapters,
        lr=hp.lr, weight_decay=hp.weight_decay, grad_clip=hp.grad_clip,
    )
    # streaming (EF) Fisher accumulation — free squared grads
    new_ef_sum = jax.tree.map(
        lambda s, g: s + jnp.square(g.astype(s.dtype)), ef_sum, grads
    )
    return new_adapters, new_opt, loss, new_ef_sum, ef_cnt + 1.0


def _fisher_grad_body(cfg, backbone, adapters, batch):
    """grad of the plain task loss (no prox) — used by the dedicated FIM pass."""

    def loss_fn(adp):
        loss, _ = adapters_lib.fednano_loss(cfg, backbone, adp, batch)
        return loss

    return jax.grad(loss_fn)(adapters)


def _local_adapter_step_body(cfg, hp, backbone, adapters, local_adapters, opt_state, batch):
    """FedDPA-F warmup step: train the PERSONAL adapter (shared adapter frozen)."""

    def loss_fn(ladp):
        loss, _ = _combined_loss(cfg, backbone, adapters, ladp, batch)
        return loss

    loss, grads = jax.value_and_grad(loss_fn)(local_adapters)
    new_local, new_opt = adamw_update(
        grads, opt_state, local_adapters, lr=hp.lr, grad_clip=hp.grad_clip
    )
    return new_local, new_opt, loss


@functools.lru_cache(maxsize=64)
def make_train_step(cfg, strategy, hp: HyperParams) -> Callable:
    """Jitted local train step, shared across clients (compiled once per
    (cfg, strategy, hp) — strategies are frozen dataclasses, so value-equal
    instances hit the same cache entry)."""

    def step(backbone, adapters, local_adapters, opt_state, batch, global_ref, ef_sum, ef_cnt):
        return _train_step_body(cfg, strategy, hp, backbone, adapters,
                                local_adapters, opt_state, batch, global_ref,
                                ef_sum, ef_cnt)

    return jax.jit(step)


@functools.lru_cache(maxsize=64)
def make_fisher_grad(cfg) -> Callable:
    def gfn(backbone, adapters, batch):
        return _fisher_grad_body(cfg, backbone, adapters, batch)

    return jax.jit(gfn)


@functools.lru_cache(maxsize=64)
def make_local_adapter_step(cfg, hp: HyperParams) -> Callable:
    def step(backbone, adapters, local_adapters, opt_state, batch):
        return _local_adapter_step_body(cfg, hp, backbone, adapters,
                                        local_adapters, opt_state, batch)

    return jax.jit(step)


def local_update(
    cfg,
    backbone,
    state: ClientState,
    batches: List[Batch],
    hp: HyperParams,
    strategy,
    global_adapters,
    round_idx: int,
) -> Tuple[ClientState, Dict]:
    """Run T local steps (+ FIM estimation) for one client. Returns metrics."""
    from repro.strategies.base import get_strategy

    strategy = get_strategy(strategy)
    # scheduling hooks see the client's own participation count, not the
    # global round index: under partial participation a client's first
    # round may be round r > 0, and its download/warmup schedule must
    # start then (with full participation the two indices coincide).
    participated = state.rounds_participated
    # round start: adopt the global adapters (Alg. 1 ClientUpdate line 1)
    # unless the strategy skips the download (LocFT after its first round).
    if strategy.downloads_global(participated):
        adapters = jax.tree.map(jnp.copy, global_adapters)
    else:
        adapters = state.adapters
    opt_state = state.opt_state

    # personal-adapter warmup rounds (FedDPA-F). The optimizer state is
    # carried in ClientState across rounds — re-initializing it every warmup
    # round would silently discard the Adam moments between rounds.
    local_adapters = state.local_adapters
    local_opt_state = state.local_opt_state
    if local_adapters is not None and strategy.local_warmup(participated, hp):
        lstep = make_local_adapter_step(cfg, hp)
        if local_opt_state is None:
            local_opt_state = adamw_init(local_adapters)
        for batch in batches[: hp.local_steps]:
            local_adapters, local_opt_state, _ = lstep(
                backbone, adapters, local_adapters, local_opt_state, batch
            )

    step_fn = make_train_step(cfg, strategy, hp)
    ef_sum = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), adapters)
    ef_cnt = jnp.zeros((), jnp.float32)
    losses = []
    for t in range(hp.local_steps):
        batch = batches[t % len(batches)]
        adapters, opt_state, loss, ef_sum, ef_cnt = step_fn(
            backbone, adapters, local_adapters, opt_state, batch, global_adapters,
            ef_sum, ef_cnt,
        )
        losses.append(float(loss))

    fisher = None
    if strategy.wants_fisher == "dedicated":
        gfn = make_fisher_grad(cfg)
        fisher = fisher_pass(
            lambda adp, b: gfn(backbone, adp, b),
            adapters,
            batches[: hp.fisher_batches],
        )
    elif strategy.wants_fisher == "streaming":
        acc = FisherAccumulator(sum_sq=ef_sum, count=ef_cnt)
        fisher = acc.finalize()

    new_state = dataclasses.replace(
        state,
        adapters=adapters,
        opt_state=opt_state,
        local_adapters=local_adapters,
        local_opt_state=local_opt_state,
        fisher=fisher,
        rounds_participated=participated + 1,
    )
    if losses:
        metrics = {"loss_first": losses[0], "loss_last": losses[-1],
                   "loss_mean": sum(losses) / len(losses)}
    else:  # hp.local_steps == 0: a no-op round must stay NaN-free
        metrics = {"loss_first": 0.0, "loss_last": 0.0, "loss_mean": 0.0}
    return new_state, metrics


# ---------------------------------------------------------------------------
# vectorized many-client path (engine="vmap")
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def make_many_update(cfg, strategy, hp: HyperParams, *, downloads: bool,
                     warmup: bool, has_local: bool, train_t: int, warm_t: int,
                     fish_t: int, shared_batches: bool,
                     mesh: Optional[Mesh] = None) -> Callable:
    """Jitted whole-round update for a stacked cohort.

    One compiled program runs ``vmap`` over the client axis of ``lax.scan``
    over local steps, reusing the exact per-client step bodies of the
    sequential path. Static knobs (download/warmup flags, step counts,
    whether every client trains on the same batches) are part of the cache
    key; array shapes carry the cohort size K.

    Batch pytrees arrive client-major: leaves ``(K, T, B, ...)``, or
    ``(T, B, ...)`` when ``shared_batches`` (then broadcast via in_axes=None
    instead of materializing K copies).

    With ``mesh`` (a 1-D ``("clients",)`` mesh from
    :func:`repro.sharding.client_mesh`), the vmapped body is wrapped in
    ``shard_map``: client-stacked arguments are partitioned over the mesh
    axis (K must divide the device count — the caller pads), the backbone /
    global adapters / shared batches are replicated, and each device runs
    its K/D clients with per-client arithmetic identical to the plain vmap
    path (clients never interact inside a round, so partitioning the client
    axis is numerics-free).
    """

    def one_client(backbone, global_adapters, adapters, opt_state, local,
                   lopt, train_b, warm_b, fish_b):
        if downloads:
            adapters = global_adapters  # vmap broadcast == per-client copy
        if warmup:
            def wstep(carry, batch):
                la, lo = carry
                la, lo, wloss = _local_adapter_step_body(
                    cfg, hp, backbone, adapters, la, lo, batch)
                return (la, lo), wloss

            (local, lopt), _ = jax.lax.scan(wstep, (local, lopt), warm_b)

        ef_sum = jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), adapters)
        ef_cnt = jnp.zeros((), jnp.float32)
        if train_t > 0:
            def tstep(carry, batch):
                adp, opt, es, ec = carry
                adp, opt, loss, es, ec = _train_step_body(
                    cfg, strategy, hp, backbone, adp, local, opt, batch,
                    global_adapters, es, ec)
                return (adp, opt, es, ec), loss

            (adapters, opt_state, ef_sum, ef_cnt), losses = jax.lax.scan(
                tstep, (adapters, opt_state, ef_sum, ef_cnt), train_b)
        else:
            losses = jnp.zeros((0,), jnp.float32)

        fisher = None
        if strategy.wants_fisher == "dedicated" and fish_t == 0:
            # fisher_pass over zero batches: the eps floor, nothing else
            fisher = jax.tree.map(lambda x: jnp.full_like(x, 1e-8), adapters)
        elif strategy.wants_fisher == "dedicated":
            def fstep(acc, batch):
                s, c = acc
                g = _fisher_grad_body(cfg, backbone, adapters, batch)
                s = jax.tree.map(
                    lambda ss, gg: ss + jnp.square(gg.astype(ss.dtype)), s, g)
                return (s, c + 1.0), None

            f0 = (jax.tree.map(jnp.zeros_like, adapters),
                  jnp.zeros((), jnp.float32))
            (fsum, fcnt), _ = jax.lax.scan(fstep, f0, fish_b)
            c = jnp.maximum(fcnt, 1.0)
            fisher = jax.tree.map(lambda s: s / c + 1e-8, fsum)
        elif strategy.wants_fisher == "streaming":
            c = jnp.maximum(ef_cnt, 1.0)
            fisher = jax.tree.map(lambda s: s / c + 1e-8, ef_sum)
        return adapters, opt_state, local, lopt, fisher, losses

    batch_ax = None if shared_batches else 0
    vm = jax.vmap(one_client,
                  in_axes=(None, None, 0, 0, 0, 0, batch_ax, batch_ax, batch_ax))
    if mesh is not None:
        rep, shd = P(), P(*(a for a in mesh.axis_names))
        bspec = rep if shared_batches else shd
        vm = jax.shard_map(
            vm, mesh=mesh,
            in_specs=(rep, rep, shd, shd, shd, shd, bspec, bspec, bspec),
            out_specs=shd, check_vma=False)
    return jax.jit(vm)


def _host_stack(trees, *, to_device: bool = True, moved: Optional[Dict] = None):
    """``tree_stack`` for the host side of the vmap path.

    ``jnp.stack`` over K device arrays and per-leaf device ops cost
    O(K·leaves) dispatches — at 10k clients that dwarfs the round itself. On
    the CPU backend ``np.asarray`` of a jax array is a zero-copy view, so
    stacking through numpy is one C-level memcpy + one transfer per leaf.

    ``to_device=False`` keeps the stacked leaves as numpy: the sharded path
    scatters them straight to the mesh with one ``device_put`` per leaf, so
    the intermediate copy onto the default device would be pure waste.

    ``moved`` (a ``Counter``) gains ``bytes_to_host``, what is fetched from
    the device, and with ``to_device`` ``bytes_to_device``, the stacked
    leaves placed.
    """
    td = jax.tree.structure(trees[0])
    flat = [jax.tree.flatten(t)[0] for t in trees]
    if moved is not None:
        moved["bytes_to_host"] += device_bytes(flat)
    # one batched device_get (single sync) beats per-leaf np.asarray, which
    # pays ~100µs of sync overhead per call — O(K·leaves) of them here
    flat = jax.device_get(flat)
    conv = jnp.asarray if to_device else (lambda x: x)
    leaves = [conv(np.stack(col)) for col in zip(*flat)]
    if to_device and moved is not None:
        moved["bytes_to_device"] += tree_bytes(leaves)
    return jax.tree.unflatten(td, leaves)


def _host_unstack(tree, n: int, moved: Optional[Dict] = None):
    """Inverse of :func:`_host_stack`: numpy views per client, no device ops.

    The returned per-client leaves are numpy arrays (views into the stacked
    result); downstream jax ops convert them back for free on CPU.
    ``moved`` (a ``Counter``) gains ``bytes_to_host``, what is fetched.
    """
    leaves, td = jax.tree.flatten(tree)
    if moved is not None:
        moved["bytes_to_host"] += device_bytes(leaves)
    host = jax.device_get(leaves)
    return [jax.tree.unflatten(td, [h[i] for h in host]) for i in range(n)]


def _stack_batch_rows(batch_lists: Sequence[List[Batch]], picks, *,
                      shared: bool, to_device: bool = True,
                      moved: Optional[Dict] = None):
    """Stack per-client batch selections into scan xs.

    ``picks(batches)`` yields the Batch sequence one client scans over.
    Returns leaves ``(T, B, ...)`` when ``shared`` (every client trains on
    the same list object — broadcast instead of K copies), else
    ``(K, T, B, ...)``.
    """
    if shared:
        row = list(picks(batch_lists[0]))
        return _host_stack(row, to_device=to_device, moved=moved) if row else None
    rows = []
    for bl in batch_lists:
        row = list(picks(bl))
        if not row:
            return None
        rows.append(_host_stack(row, to_device=False, moved=moved))
    return _host_stack(rows, to_device=to_device, moved=moved)


@dataclass
class PreparedCohort:
    """Host-side product of :func:`prepare_cohort`: stacked (and, under a
    mesh, padded + device-placed) inputs plus the compiled update fn.

    ``k`` is the number of *real* clients; padded rows (``pad_to`` under a
    mesh) duplicate the last real client and are sliced off in
    :func:`collect_cohort` before any state, metric, or byte accounting
    sees them.
    """

    states: List[ClientState]
    k: int
    fn: Callable
    args: tuple                  # (adapters0, opt0, local0, lopt0, xs...)
    has_local: bool
    warmup: bool
    train_t: int
    wants_fisher: Optional[str]
    mesh: Optional[Mesh] = None


@dataclass
class LaunchedCohort:
    """An in-flight cohort dispatch: outputs are jax async futures, so the
    host is free to prepare the next cohort while devices compute."""

    prepared: PreparedCohort
    outs: tuple


def prepare_cohort(
    cfg,
    states: List[ClientState],
    batch_lists: Sequence[List[Batch]],
    hp: HyperParams,
    strategy,
    *,
    mesh: Optional[Mesh] = None,
    pad_to: Optional[int] = None,
    opt0_override=None,
    batches_override=None,
    moved: Optional[Dict] = None,
) -> PreparedCohort:
    """Validate + stack a homogeneous cohort (the host half of a dispatch).

    All clients must share the same scheduling flags this round (the engine
    groups cohorts by ``downloads_global``/``local_warmup``), the same batch
    shapes, and the same warmup/Fisher batch counts; heterogeneous cohorts
    raise ``ValueError`` (fall back to ``engine="sequential"``).

    With ``mesh`` the stacked leaves are placed with a
    ``NamedSharding(mesh, P("clients"))`` along the client axis; the cohort
    is padded up to ``pad_to`` (default: the next multiple of the mesh size)
    by repeating the last client's row. Padding rows compute and are
    discarded — they are never returned, never aggregated, never counted.

    ``opt0_override`` supplies the stacked AdamW state directly (an already
    padded, already device-placed tree — normally last round's ``new_opt``
    output for the identical chunk), skipping the host stack + transfer.
    The caller owns the invariant that it matches these clients' true
    current optimizer state; see the engine's chunk-resident opt cache.

    ``batches_override`` likewise supplies an already stacked + placed
    ``(train_xs, warm_xs, fish_xs)`` triple for this exact cohort — client
    batch lists are immutable within a run, so the engine reuses the placed
    stacks across rounds instead of re-stacking identical data every round.

    ``moved`` (a ``Counter``) gains ``bytes_to_device`` and
    ``bytes_to_host``, the bytes this call copies each way.
    """
    from repro.sharding import CLIENT_AXIS, pad_to_multiple
    from repro.strategies.base import get_strategy

    strategy = get_strategy(strategy)
    k = len(states)
    assert k > 0

    participated = [s.rounds_participated for s in states]
    downloads = strategy.downloads_global(participated[0])
    has_local = states[0].local_adapters is not None
    warmup = has_local and strategy.local_warmup(participated[0], hp)
    for s, p in zip(states[1:], participated[1:]):
        if (strategy.downloads_global(p) != downloads
                or (s.local_adapters is not None) != has_local
                or ((s.local_adapters is not None)
                    and strategy.local_warmup(p, hp)) != warmup):
            raise ValueError(
                "local_update_many needs a cohort with uniform download/"
                "warmup schedules; group clients by these flags first")

    real_states, real_lists = states, list(batch_lists)
    if mesh is not None:
        nd = mesh.size
        width = pad_to if pad_to is not None else pad_to_multiple(k, nd)
        if width % nd != 0:
            raise ValueError(
                f"pad_to={width} must be a multiple of the mesh size {nd}")
        if width < k:
            raise ValueError(f"pad_to={width} is smaller than the cohort ({k})")
        pad = width - k
        states = states + [states[-1]] * pad
        batch_lists = list(batch_lists) + [batch_lists[-1]] * pad
    del real_states, real_lists

    warm_ts = {min(len(bl), hp.local_steps) for bl in batch_lists} if warmup else {0}
    fish_ts = ({min(len(bl), hp.fisher_batches) for bl in batch_lists}
               if strategy.wants_fisher == "dedicated" else {0})
    if len(warm_ts) > 1 or len(fish_ts) > 1:
        raise ValueError(
            "local_update_many needs uniform per-client batch counts for the "
            "warmup/Fisher passes; use engine='sequential' for ragged shards")
    warm_t, fish_t = warm_ts.pop(), fish_ts.pop()
    train_t = hp.local_steps

    shared = all(bl is batch_lists[0] for bl in batch_lists)
    # under a mesh the stacked leaves go straight from numpy to their mesh
    # shards (one device_put below); staging them on the default device
    # first would pay a second full copy of the cohort
    to_dev = mesh is None
    if batches_override is not None:
        train_xs, warm_xs, fish_xs = batches_override
    else:
        try:
            train_xs = _stack_batch_rows(
                batch_lists, lambda bl: (bl[t % len(bl)] for t in range(train_t)),
                shared=shared, to_device=to_dev, moved=moved)
            warm_xs = _stack_batch_rows(
                batch_lists, lambda bl: bl[:warm_t], shared=shared,
                to_device=to_dev, moved=moved) if warmup else None
            fish_xs = _stack_batch_rows(
                batch_lists, lambda bl: bl[:fish_t], shared=shared,
                to_device=to_dev, moved=moved) if fish_t else None
        except ValueError as e:  # jnp.stack shape mismatch
            raise ValueError(
                "local_update_many needs identical batch shapes across the "
                f"cohort ({e}); use engine='sequential' for ragged shards") from e
    if train_t > 0 and train_xs is None:
        raise ValueError("clients with no training batches cannot run local steps")

    stack = functools.partial(_host_stack, to_device=to_dev, moved=moved)
    adapters0 = (None if downloads
                 else stack([s.adapters for s in states]))
    opt0 = (opt0_override if opt0_override is not None
            else stack([s.opt_state for s in states]))
    local0 = (stack([s.local_adapters for s in states])
              if has_local else None)
    lopt0 = None
    if warmup:
        lopt0 = stack([
            s.local_opt_state if s.local_opt_state is not None
            else adamw_init(s.local_adapters) for s in states
        ])

    if mesh is not None:
        # direct host->device scatter per shard: each device receives only
        # its K/D client rows (replicated args are placed at launch)
        shd = NamedSharding(mesh, P(CLIENT_AXIS))
        rep = NamedSharding(mesh, P())
        bshard = rep if shared else shd
        if moved is not None:
            moved["bytes_to_device"] += host_bytes(
                [adapters0, None if opt0_override is not None else opt0, local0,
                 lopt0, None if batches_override is not None
                 else (train_xs, warm_xs, fish_xs)])
        adapters0 = jax.device_put(adapters0, shd) if adapters0 is not None else None
        if opt0_override is None:  # an override is already mesh-placed
            opt0 = jax.device_put(opt0, shd)
        local0 = jax.device_put(local0, shd) if local0 is not None else None
        lopt0 = jax.device_put(lopt0, shd) if lopt0 is not None else None
        if batches_override is None:
            train_xs = (jax.device_put(train_xs, bshard)
                        if train_xs is not None else None)
            warm_xs = (jax.device_put(warm_xs, bshard)
                       if warm_xs is not None else None)
            fish_xs = (jax.device_put(fish_xs, bshard)
                       if fish_xs is not None else None)

    fn = make_many_update(
        cfg, strategy, hp, downloads=downloads, warmup=warmup,
        has_local=has_local, train_t=train_t, warm_t=warm_t, fish_t=fish_t,
        shared_batches=shared, mesh=mesh)
    return PreparedCohort(
        states=states[:k], k=k, fn=fn,
        args=(adapters0, opt0, local0, lopt0, train_xs, warm_xs, fish_xs),
        has_local=has_local, warmup=warmup, train_t=train_t,
        wants_fisher=strategy.wants_fisher, mesh=mesh)


def launch_cohort(prepared: PreparedCohort, backbone, global_adapters) -> LaunchedCohort:
    """Dispatch a prepared cohort. Returns immediately (async futures): the
    caller may overlap host work with device compute before collecting.

    Under a mesh, ``backbone`` / ``global_adapters`` should already be
    replicated over the mesh (the engine places them once per run/round);
    ``device_put`` below is then a no-op, and otherwise pays one broadcast.
    """
    if prepared.mesh is not None:
        rep = NamedSharding(prepared.mesh, P())
        backbone = jax.device_put(backbone, rep)
        global_adapters = jax.device_put(global_adapters, rep)
    adapters0, opt0, local0, lopt0, train_xs, warm_xs, fish_xs = prepared.args
    outs = prepared.fn(backbone, global_adapters, adapters0, opt0, local0,
                       lopt0, train_xs, warm_xs, fish_xs)
    return LaunchedCohort(prepared=prepared, outs=outs)


def collect_cohort(launched: LaunchedCohort, *, with_opt: bool = True,
                   ) -> Tuple[List[ClientState], List[Dict]]:
    """Block on a launched cohort and rebuild per-client states + metrics.

    Only the first ``k`` (real) rows are unstacked — under a mesh the
    padded tail rows never leave this function.

    ``with_opt=False`` skips the device→host gather of the AdamW state: the
    returned states keep their (now stale) previous ``opt_state``, and the
    caller takes ownership of ``launched.outs[1]`` — the stacked new opt
    tree, still on the devices — materializing rows only when a per-client
    value is actually needed (checkpointing, cohort reshuffle, run end).

    The wait for the device is its own span (``fednano.round.wait``), ahead
    of the unstack's (``fednano.round.unstack``), whose ``bytes_to_host``
    counts what it fetches.
    """
    p = launched.prepared
    k = p.k
    new_adp, new_opt, new_local, new_lopt, fishers, losses = launched.outs

    with span("round.wait"):
        jax.block_until_ready(launched.outs)
    with span("round.unstack") as sp:
        moved = Counter()
        adp_list = _host_unstack(new_adp, k, moved)
        opt_list = _host_unstack(new_opt, k, moved) if with_opt else None
        local_list = _host_unstack(new_local, k, moved) if p.has_local else [None] * k
        lopt_list = _host_unstack(new_lopt, k, moved) if p.warmup else [None] * k
        fisher_list = (_host_unstack(fishers, k, moved)
                       if p.wants_fisher is not None else [None] * k)

        if p.train_t > 0:
            moved["bytes_to_host"] += device_bytes(losses)
            losses_np = np.asarray(losses)[:k]
        else:
            losses_np = np.zeros((k, 0), np.float32)
        new_states = []
        for i, s in enumerate(p.states):
            new_states.append(dataclasses.replace(
                s,
                adapters=adp_list[i],
                opt_state=opt_list[i] if with_opt else s.opt_state,
                local_adapters=local_list[i] if p.has_local else s.local_adapters,
                local_opt_state=lopt_list[i] if p.warmup else s.local_opt_state,
                fisher=fisher_list[i],
                rounds_participated=s.rounds_participated + 1,
            ))
        metrics = _loss_metrics(losses_np)
        sp.set_metadata(bytes_to_host=moved["bytes_to_host"])
    return new_states, metrics


def _loss_metrics(losses_np) -> List[Dict]:
    """Per-client loss metrics from a (k, T) host array — identical
    arithmetic to the sequential path: python floats, summed in step order,
    so seeded metrics match bit-for-bit."""
    metrics = []
    for row in losses_np:
        ls = [float(x) for x in row]
        if ls:
            metrics.append({"loss_first": ls[0], "loss_last": ls[-1],
                            "loss_mean": sum(ls) / len(ls)})
        else:
            metrics.append({"loss_first": 0.0, "loss_last": 0.0,
                            "loss_mean": 0.0})
    return metrics


def collect_cohort_deferred(launched: LaunchedCohort,
                            ) -> Tuple[List[ClientState], Optional[jax.Array]]:
    """Collect only participation counts from a launched cohort; nothing is
    pulled off the devices.

    The adapter / optimizer / Fisher outputs stay stacked on the devices —
    the caller takes ownership of ``launched.outs`` (the sharded engine
    parks them in its chunk-resident cache and folds them straight into the
    stacked aggregation hooks). The second return value is the still-device
    ``(width, T)`` losses array (or None with no train steps): the engine
    gathers every chunk's losses in ONE batched ``device_get`` at round end
    (via :func:`loss_metrics_deferred`) instead of paying a cross-device
    sync per chunk. Returned states keep their previous (now stale)
    ``adapters``/``opt_state``/``fisher`` until the engine materializes the
    resident rows.
    """
    p = launched.prepared
    new_states = [
        dataclasses.replace(s, rounds_participated=s.rounds_participated + 1)
        for s in p.states
    ]
    return new_states, (launched.outs[5] if p.train_t > 0 else None)


def loss_metrics_deferred(loss_arrays, ks) -> List[List[Dict]]:
    """One batched gather of many chunks' device losses → per-chunk metric
    lists (same arithmetic as :func:`_loss_metrics`). ``ks`` holds each
    chunk's real (unpadded) client count; ``None`` entries (no train steps)
    yield zero-loss metrics. The gather is the host waiting on the round's
    devices: a ``fednano.round.wait`` span with its ``bytes_to_host``."""
    live = [a for a in loss_arrays if a is not None]
    with span("round.wait", bytes_to_host=device_bytes(live)):
        gathered = jax.device_get(live)
    it = iter(gathered)
    out = []
    for a, k in zip(loss_arrays, ks):
        rows = (np.asarray(next(it))[:k] if a is not None
                else np.zeros((k, 0), np.float32))
        out.append(_loss_metrics(rows))
    return out


def local_update_many(
    cfg,
    backbone,
    states: List[ClientState],
    batch_lists: Sequence[List[Batch]],
    hp: HyperParams,
    strategy,
    global_adapters,
    *,
    mesh: Optional[Mesh] = None,
    pad_to: Optional[int] = None,
) -> Tuple[List[ClientState], List[Dict]]:
    """Vectorized ``local_update`` over a homogeneous cohort.

    The fused prepare → launch → collect path (see the module docstring for
    the pipelined variant the sharded engine uses). ``mesh`` partitions the
    stacked cohort over a ``("clients",)`` device mesh via ``shard_map``,
    padding to ``pad_to`` rows (default: next multiple of the mesh size).
    """
    prepared = prepare_cohort(cfg, states, batch_lists, hp, strategy,
                              mesh=mesh, pad_to=pad_to)
    return collect_cohort(launch_cohort(prepared, backbone, global_adapters))


@functools.lru_cache(maxsize=64)
def _make_eval_fn(cfg, has_local: bool) -> Callable:
    def acc_fn(backbone, adapters, local_adapters, batch):
        embeds, positions, labels, mask, enc = adapters_lib.nanoedge_forward(
            cfg, backbone, adapters, batch
        )
        if has_local:
            kw = dict(rank=cfg.adapter.rank, alpha=cfg.adapter.alpha, use_pallas=False)
            if "text" in local_adapters:
                embeds = adapters_lib.nano_adapter_apply(local_adapters["text"], embeds, **kw)
            if enc is not None and "image" in local_adapters:
                enc = adapters_lib.nano_adapter_apply(local_adapters["image"], enc, **kw)
        from repro.models import model as model_lib
        from repro.models.layers import token_accuracy

        hidden, _ = model_lib.forward(cfg, backbone, embeds, positions, enc)
        lg = model_lib.logits(cfg, backbone, hidden)
        return token_accuracy(lg, labels, mask)

    return jax.jit(acc_fn)


def eval_client(cfg, backbone, adapters, local_adapters, batches: List[Batch]) -> float:
    """Answer-token accuracy under teacher forcing (the VQA-accuracy proxy)."""
    acc_fn = _make_eval_fn(cfg, local_adapters is not None)
    accs = [float(acc_fn(backbone, adapters, local_adapters, b)) for b in batches]
    return sum(accs) / max(len(accs), 1)

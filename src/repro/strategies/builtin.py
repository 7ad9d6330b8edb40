"""The six paper strategies as registry plugins, plus server-opt variants.

Each class is the strategy column of paper Tab. 2 expressed through the
``Strategy`` hooks — no engine changes, no if/elif chains. The seeded
numerics match the pre-plugin string-dispatch implementation exactly
(tests/golden/strategy_parity.json).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.strategies.base import Strategy, register


def _fisher_fold_tree(num, den, theta, fisher, w, *, use_pallas=False):
    """Fold one client's (θ, F, w) into the running f32 num/den trees.

    The jitted jnp path fuses the fold into one elementwise pass per leaf;
    ``use_pallas`` routes each leaf through the fused ``fisher_fold`` Pallas
    kernel instead (interpret mode off-TPU, same numerics)."""
    if use_pallas:
        from repro.kernels.fisher_merge import ops as fm_ops

        folded = jax.tree.map(
            lambda nm, dn, t, f: fm_ops.fisher_fold(nm, dn, t, f, w),
            num, den, theta, fisher)
    else:
        folded = _fisher_fold_tree_jit(num, den, theta, fisher, w)
    new_num = jax.tree.map(lambda p: p[0], folded,
                           is_leaf=lambda p: isinstance(p, tuple))
    new_den = jax.tree.map(lambda p: p[1], folded,
                           is_leaf=lambda p: isinstance(p, tuple))
    return new_num, new_den


@jax.jit
def _fisher_fold_tree_jit(num, den, theta, fisher, w):
    return jax.tree.map(
        lambda nm, dn, t, f: (
            nm + w * f.astype(jnp.float32) * t.astype(jnp.float32),
            dn + w * f.astype(jnp.float32)),
        num, den, theta, fisher)


@jax.jit
def _fisher_fold_stacks_jit(theta_stacks, fisher_stacks, ws):
    """Σ over stacked ``(K, ...)`` chunks of (Σ wFθ, Σ wF) in one dispatch:
    the client-axis reductions run where the stacks live (sharded over the
    mesh under the sharded engine), so no per-client tree ever reaches the
    host — and fusing all chunks into one call pays the cross-device
    reduction barrier once per round instead of once per chunk."""
    num = den = None
    for t, f, w in zip(theta_stacks, fisher_stacks, ws):
        n = jax.tree.map(
            lambda tt, ff, w=w: jnp.tensordot(
                w, ff.astype(jnp.float32) * tt.astype(jnp.float32), axes=1),
            t, f)
        d = jax.tree.map(
            lambda ff, w=w: jnp.tensordot(w, ff.astype(jnp.float32), axes=1),
            f)
        num = n if num is None else jax.tree.map(jnp.add, num, n)
        den = d if den is None else jax.tree.map(jnp.add, den, d)
    return num, den


@register("fedavg")
@dataclass(frozen=True)
class FedAvg(Strategy):
    """Data-size-weighted parameter averaging (McMahan et al. 2017)."""


@register("fedprox")
@dataclass(frozen=True)
class FedProx(FedAvg):
    """FedAvg + (μ/2)·‖θ − θ_global‖² proximal term in the local loss."""

    def wrap_local_loss(self, loss_fn, hp, global_ref):
        from repro.utils import tree_sq_norm, tree_sub

        def wrapped(adp):
            loss, aux = loss_fn(adp)
            loss = loss + 0.5 * hp.prox_mu * tree_sq_norm(tree_sub(adp, global_ref))
            return loss, aux

        return wrapped


@register("fednano")
@dataclass(frozen=True)
class FedNano(Strategy):
    """The paper's method: dedicated diagonal-FIM pass + Fisher merge."""

    wants_fisher: Optional[str] = "dedicated"

    def aggregate(self, thetas, fishers, data_sizes, *, use_pallas=False):
        from repro.core import aggregation

        return aggregation.fisher_merge(
            thetas, fishers, data_sizes, use_pallas=use_pallas
        )

    # streaming Fisher merge: fold Σ wFθ / Σ wF ONE CLIENT AT A TIME into
    # running f32 sums — no (K, ...) stack ever exists, so server memory is
    # O(1) in the client count (the chunked/buffered engines hand us their
    # buffered uploads; we still never stack them). finalize reproduces
    # Eq. 1 with the eps floor scaled by the total weight
    # (num/(den+eps·W) == (num/W)/((den/W)+eps), the batch formula).
    def agg_stream_fold(self, acc, thetas, fishers, weights, *, use_pallas=False):
        if fishers is None or any(f is None for f in fishers):
            raise ValueError("fednano streaming merge needs a FIM per upload")
        if acc is None:
            like = jax.tree.map(lambda x: x.dtype, thetas[0])
            acc = {"num": jax.tree.map(
                       lambda x: jnp.zeros(x.shape, jnp.float32), thetas[0]),
                   "den": jax.tree.map(
                       lambda x: jnp.zeros(x.shape, jnp.float32), thetas[0]),
                   "w": 0.0, "like": like}
        num, den = acc["num"], acc["den"]
        for theta, fisher, w in zip(thetas, fishers, weights):
            num, den = _fisher_fold_tree(num, den, theta, fisher,
                                         jnp.float32(w), use_pallas=use_pallas)
        return {"num": num, "den": den,
                "w": acc["w"] + float(sum(float(w) for w in weights)),
                "like": acc["like"]}

    def agg_stream_fold_stacked(self, acc, theta_stack, fisher_stack,
                                weights, *, use_pallas=False):
        if not isinstance(theta_stack, (list, tuple)):
            theta_stack = [theta_stack]
            fisher_stack = [fisher_stack]
            weights = [weights]
        if fisher_stack is None or any(f is None for f in fisher_stack):
            raise ValueError("fednano streaming merge needs a FIM per upload")
        ws = tuple(jnp.asarray(list(w), jnp.float32) for w in weights)
        num, den = _fisher_fold_stacks_jit(
            tuple(theta_stack), tuple(fisher_stack), ws)
        wsum = float(sum(float(x) for w in weights for x in w))
        if acc is None:
            return {"num": num, "den": den, "w": wsum,
                    "like": jax.tree.map(lambda x: x.dtype, theta_stack[0])}
        from repro.utils import tree_add

        return {"num": tree_add(acc["num"], num),
                "den": tree_add(acc["den"], den),
                "w": acc["w"] + wsum, "like": acc["like"]}

    def agg_stream_finalize(self, acc, *, use_pallas=False, eps: float = 1e-8):
        if acc is None:
            return None
        floor = eps * acc["w"]
        return jax.tree.map(
            lambda n, d, t: (n / (d + floor)).astype(t),
            acc["num"], acc["den"], acc["like"])


@register("fednano_ef")
@dataclass(frozen=True)
class FedNanoEF(FedNano):
    """FedNano with the FIM accumulated from training-step grads (Tab. 7)."""

    wants_fisher: Optional[str] = "streaming"


@register("feddpa_f")
@dataclass(frozen=True)
class FedDPAF(FedAvg):
    """Dual adapters: fedavg the shared one, keep a frozen personal one
    trained in the warmup round(s) only."""

    dual_adapters = True

    def local_warmup(self, rounds_participated, hp):
        return rounds_participated < hp.dpa_warmup_rounds

    def eval_params(self, global_adapters, client):
        return global_adapters, client.local_adapters


@register("locft")
@dataclass(frozen=True)
class LocFT(Strategy):
    """Local-only fine-tuning: no upload, no download after round 0."""

    aggregates = False

    def downloads_global(self, rounds_participated):
        return rounds_participated == 0

    def aggregate(self, thetas, fishers, data_sizes, *, use_pallas=False):
        return None

    def eval_params(self, global_adapters, client):
        return client.adapters, None


@register("fedavgm")
@dataclass(frozen=True)
class FedAvgM(FedAvg):
    """FedAvg + server momentum on the round pseudo-gradient (Hsu et al.)."""

    server_lr: float = 1.0
    beta: float = 0.9

    def server_opt(self):
        from repro.strategies.server_opt import FedAvgMOpt

        return FedAvgMOpt(lr=self.server_lr, beta=self.beta)


@register("fedadam")
@dataclass(frozen=True)
class FedAdam(FedAvg):
    """FedAvg + adaptive Adam server step (FedOpt, Reddi et al. 2021)."""

    server_lr: float = 0.1
    b1: float = 0.9
    b2: float = 0.99
    eps: float = 1e-3

    def server_opt(self):
        from repro.strategies.server_opt import FedAdamOpt

        return FedAdamOpt(lr=self.server_lr, b1=self.b1, b2=self.b2, eps=self.eps)

"""Server-side aggregation strategies (paper §3.4, Eq. 1).

``fisher_merge`` is the paper's contribution: Laplace-posterior merging with
diagonal FIM precision, weighted by client data share p_k = |D_k| / Σ|D_j|:

    θ_global = ( Σ_k p_k F_k θ_k ) / ( Σ_k p_k F_k )        (elementwise)

``fedavg`` is the isotropic special case (F_k ≡ 1). FedProx uses fedavg
aggregation (its difference is the client-side proximal term). FedDPA-F
fedavg-aggregates only the *global* adapter of its dual pair.
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.utils import tree_stack


def _norm_weights(sizes: Sequence[float], n: int):
    if sizes is None:
        w = jnp.ones((n,), jnp.float32) / n
    else:
        w = jnp.asarray(sizes, jnp.float32)
        # guard an all-zero-weight cohort (e.g. every row masked out):
        # 0/0 would poison the merge with NaN; fall back to uniform
        total = jnp.sum(w)
        w = jnp.where(total > 0, w / jnp.where(total > 0, total, 1.0),
                      jnp.ones_like(w) / n)
    return w


def fedavg(thetas: List, data_sizes: Optional[Sequence[float]] = None):
    """Data-size-weighted parameter average (McMahan et al. 2017)."""
    w = _norm_weights(data_sizes, len(thetas))
    stacked = tree_stack(thetas)
    return jax.tree.map(
        lambda s: jnp.tensordot(w.astype(s.dtype), s, axes=1), stacked
    )


def fisher_merge(
    thetas: List,
    fishers: List,
    data_sizes: Optional[Sequence[float]] = None,
    *,
    eps: float = 1e-8,
    use_pallas: bool = False,
):
    """Eq. 1: elementwise Fisher-weighted merge over K clients."""
    k = len(thetas)
    assert len(fishers) == k
    w = _norm_weights(data_sizes, k)
    ts = tree_stack(thetas)   # leaves (K, ...)
    fs = tree_stack(fishers)

    if use_pallas:
        from repro.kernels.fisher_merge import ops as fm_ops

        return jax.tree.map(
            lambda t, f: fm_ops.fisher_merge(t, f, w, eps=eps), ts, fs
        )

    def merge(t, f):
        tf = t.astype(jnp.float32)
        ff = f.astype(jnp.float32)
        ww = w.reshape((k,) + (1,) * (t.ndim - 1))
        num = jnp.sum(ww * ff * tf, axis=0)
        den = jnp.sum(ww * ff, axis=0)
        return (num / (den + eps)).astype(t.dtype)

    return jax.tree.map(merge, ts, fs)


STRATEGIES = ("fednano", "fednano_ef", "fedavg", "fedprox", "feddpa_f", "locft")


def aggregate(strategy: str, thetas, fishers, data_sizes, *, use_pallas: bool = False):
    if strategy in ("fednano", "fednano_ef"):
        return fisher_merge(thetas, fishers, data_sizes, use_pallas=use_pallas)
    if strategy in ("fedavg", "fedprox", "feddpa_f"):
        return fedavg(thetas, data_sizes)
    if strategy == "locft":
        return None  # no aggregation: clients stay local
    raise ValueError(f"unknown strategy {strategy!r}")

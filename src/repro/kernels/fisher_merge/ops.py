"""Jitted public wrappers for the Fisher-merge kernels (arbitrary leaf shapes).

Two forms of paper Eq. 1:

  * ``fisher_merge``      — materializing: takes the (K, ...) client stack.
  * ``fisher_fold``       — streaming: folds ONE client's (θ, F, w) into
    running f32 (num, den) sums, so the server never holds a (K, ...) stack;
    ``repro.strategies`` builds FedNano's ``agg_stream_*`` hooks on it.

``block_n=None`` consults the tuning table (numerics-free: element blocks
are independent). ``interpret=None`` follows the platform
(``repro.kernels.platform``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import tuning
from repro.kernels.fisher_merge.fisher_merge import fisher_fold_2d, fisher_merge_2d
from repro.kernels.platform import interpret_mode


@functools.partial(jax.jit, static_argnames=("eps", "block_n", "interpret"))
def _fisher_merge_jit(theta, fisher, weights, *, eps, block_n, interpret):
    k = theta.shape[0]
    rest = theta.shape[1:]
    t = theta.reshape(k, -1)
    f = fisher.reshape(k, -1)
    out = fisher_merge_2d(t, f, weights, eps=eps, block_n=block_n, interpret=interpret)
    return out.reshape(rest)


def fisher_merge(theta, fisher, weights, *, eps: float = 1e-8,
                 block_n: int = None, interpret: Optional[bool] = None):
    """theta/fisher (K, ...) stacked client leaves; weights (K,).

    Returns the merged leaf of shape (...). ``block_n=None`` → tuning table.
    """
    if block_n is None:
        n = 1
        for s in theta.shape[1:]:
            n *= int(s)
        block_n = tuning.fisher_block_n(theta.shape[0], n)
    return _fisher_merge_jit(theta, fisher, weights, eps=eps, block_n=block_n,
                             interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _fisher_fold_jit(num, den, theta, fisher, w, *, block_n, interpret):
    shape = theta.shape
    num_new, den_new = fisher_fold_2d(
        num.reshape(-1), den.reshape(-1), theta.reshape(-1), fisher.reshape(-1),
        w, block_n=block_n, interpret=interpret)
    return num_new.reshape(shape), den_new.reshape(shape)


def fisher_fold(num, den, theta, fisher, w, *, block_n: int = None,
                interpret: Optional[bool] = None):
    """Streaming fold of one client leaf: returns (num + w·F·θ, den + w·F).

    num/den are float32 running sums shaped like the leaf; ``w`` is a scalar
    (jnp or python). O(1) server memory in the client count.
    """
    if block_n is None:
        n = 1
        for s in theta.shape:
            n *= int(s)
        block_n = tuning.fisher_block_n(1, n)
    return _fisher_fold_jit(num, den, theta, fisher, w, block_n=block_n,
                            interpret=interpret_mode(interpret))

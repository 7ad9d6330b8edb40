"""Jitted public wrapper for the grouped expert matmul, with an input-only VJP.

``gmm(lhs, rhs, group_sizes)`` is differentiable in ``lhs`` alone: its
backward pass is the same kernel on the transposed weights,
``d lhs = gmm(g, rhs^T)``. The weights are frozen under FedNano, so their
gradient (megablox's ``tgmm``) is never traced, compiled or run; the
cotangent of ``rhs`` is a symbolic zero.

Rows are padded to a whole row tile inside the launcher and cut after, so
any row count works. ``interpret=None`` follows the platform
(``repro.kernels.platform``); the tiling comes from ``repro.kernels.tuning``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import tuning
from repro.kernels.moe_gmm.moe_gmm import grouped_matmul
from repro.kernels.platform import interpret_mode


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "interpret"))
def _gmm_jit(lhs, rhs, group_sizes, *, transpose_rhs, interpret):
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiling = tuning.gmm_tiling(m, k, n)
    pad = (-m) % tiling[0]
    x = jnp.pad(lhs, ((0, pad), (0, 0))) if pad else lhs
    sizes = group_sizes.at[-1].add(pad) if pad else group_sizes
    out = grouped_matmul(x, rhs, sizes, tiling=tiling, transpose_rhs=transpose_rhs,
                         interpret=interpret)
    return out[:m] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret):
    return _gmm_jit(lhs, rhs, group_sizes, transpose_rhs=transpose_rhs,
                    interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, transpose_rhs, interpret):
    return _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret), (rhs, group_sizes)


def _gmm_bwd(transpose_rhs, interpret, res, g):
    rhs, group_sizes = res
    dlhs = _gmm_jit(g, rhs, group_sizes, transpose_rhs=not transpose_rhs,
                    interpret=interpret)
    return dlhs, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
        interpret: Optional[bool] = None):
    """lhs (m, k) rows sorted by group; rhs (G, k, n) (``transpose_rhs``:
    (G, n, k)); group_sizes (G + 1,) int32, the last entry the rows of no
    held group, which come out as zeros. Returns (m, n) in lhs's type.
    Differentiable in ``lhs`` only."""
    return _gmm(lhs, rhs, group_sizes.astype(jnp.int32), transpose_rhs,
                interpret_mode(interpret))

"""Grouped matrix multiplication over the experts a chip holds — Pallas TPU kernel.

Rows of ``lhs`` are sorted by expert; ``group_sizes`` counts the rows of
each held expert. Rows past the held groups (assignments to experts on
other chips, and row-tile padding) are neither visited nor written, so
their output is unspecified. Row block i of group g is multiplied by
``rhs[g]``: no capacity, no padding per expert, no dropped row.

The kernel is the installed ``jax.experimental.pallas.ops.tpu.megablox``
grouped matmul (a grid over (n tiles, the row tiles that hold a group,
k tiles), with the groups' offsets prefetched as scalars so that only the
tiles holding rows of a held expert are visited, and, with one size for
each group of ``rhs``, nothing past them is zero-filled); this module fixes
its tiling and its output type. Its weight-gradient companion (``tgmm``) is
never used: the backbone is frozen.
"""
from __future__ import annotations

from jax.experimental.pallas.ops.tpu.megablox.ops import backend as _megablox


def grouped_matmul(lhs, rhs, group_sizes, *, tiling, transpose_rhs: bool = False,
                   interpret: bool = False):
    """lhs (m, k) rows sorted by group; rhs (G, k, n), or (G, n, k) with
    ``transpose_rhs``; group_sizes (G,) int32, summing to at most m.
    Returns (m, n) in lhs's type, unspecified past the groups' rows. m must
    be a multiple of the row tile.

    Not jitted itself (megablox's own jit is stepped over too), so that a
    launch takes the name of the jitted launcher that calls it
    (``ops._gmm_jit``), which is how the trace names it."""
    return _megablox.gmm.__wrapped__(lhs, rhs, group_sizes, lhs.dtype, tiling,
                                     transpose_rhs=transpose_rhs, interpret=interpret)

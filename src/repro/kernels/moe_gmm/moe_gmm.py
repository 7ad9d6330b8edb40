"""Grouped matrix multiplication over the experts a chip holds — Pallas TPU kernel.

Rows of ``lhs`` are sorted by expert; ``group_sizes`` counts the rows of
each held expert, then, as one last entry, the rows that belong to no held
expert (assignments to experts on other chips), which sit at the end and
come out as zeros. Row block i of group g is multiplied by ``rhs[g]``: no
capacity, no padding per expert, no dropped row.

The kernel is the installed ``jax.experimental.pallas.ops.tpu.megablox``
grouped matmul (a grid over (n tiles, the row tiles that hold a group,
k tiles), with the groups' offsets prefetched as scalars so that only the
tiles holding rows of a held expert are visited); this module fixes its
tiling and its output type. Its weight-gradient companion (``tgmm``) is
never used: the backbone is frozen.
"""
from __future__ import annotations

from jax.experimental.pallas.ops.tpu.megablox.ops import backend as _megablox


def grouped_matmul(lhs, rhs, group_sizes, *, tiling, transpose_rhs: bool = False,
                   interpret: bool = False):
    """lhs (m, k) rows sorted by group; rhs (G, k, n), or (G, n, k) with
    ``transpose_rhs``; group_sizes (G + 1,) int32, the last entry the rows
    of no held group. Returns (m, n) in lhs's type, zeros past the held
    groups' rows. m must be a multiple of the row tile.

    Not jitted itself (megablox's own jit is stepped over too), so that a
    launch takes the name of the jitted launcher that calls it
    (``ops._gmm_jit``), which is how the trace names it."""
    return _megablox.gmm.__wrapped__(lhs, rhs, group_sizes, lhs.dtype, tiling,
                                     transpose_rhs=transpose_rhs, interpret=interpret)

"""Pure-jnp oracle for the grouped expert matmul: one masked matmul a group."""
from __future__ import annotations

import jax.numpy as jnp


def grouped_matmul(lhs, rhs, group_sizes, *, transpose_rhs: bool = False):
    """lhs (m, k) rows sorted by group; rhs (G, k, n) (``transpose_rhs``:
    (G, n, k)); group_sizes (G + 1,), the last entry the rows of no held
    group. Row r of group g gets ``lhs[r] @ rhs[g]``; rows past the held
    groups get zeros. Float32 accumulation, output in lhs's type."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(lhs.shape[0])
    out = 0.0
    for g in range(rhs.shape[0]):
        w = rhs[g].T if transpose_rhs else rhs[g]
        inside = (row >= starts[g]) & (row < ends[g])
        y = jnp.dot(lhs, w, preferred_element_type=jnp.float32)
        out = out + jnp.where(inside[:, None], y, 0.0)
    return jnp.asarray(out, jnp.float32).astype(lhs.dtype)

"""Pytree utilities used across the framework.

Everything here is a thin, well-tested wrapper over ``jax.tree_util`` —
we build on pure JAX (no flax/optax in this environment), so the optimizer,
aggregation, and checkpoint layers all speak "pytree of arrays".
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def tree_size(tree) -> int:
    """Total number of scalar parameters in a pytree."""
    # math.prod over the shape tuple, not np.prod: the engines call this
    # per client per round, and np.prod's ufunc dispatch is ~100x slower
    # on a small tuple than the C-level math.prod
    return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))


def tree_bytes(tree) -> int:
    """Total bytes of a pytree (uses each leaf's dtype itemsize)."""
    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        itemsize = np.dtype(x.dtype).itemsize
        total += math.prod(x.shape) * itemsize
    return total


def host_bytes(tree) -> int:
    """Bytes of a pytree's NumPy leaves: what placing it on a device copies."""
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)
               if isinstance(x, (np.ndarray, np.generic)))


def device_bytes(tree) -> int:
    """Bytes of a pytree's ``jax.Array`` leaves: what fetching it to the host copies."""
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)
               if isinstance(x, jax.Array))


def tree_zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a, b):
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree, s):
    return jax.tree.map(lambda x: x * s, tree)


def tree_dot(a, b):
    """Sum of elementwise products across two pytrees (a scalar)."""
    leaves = jax.tree.map(lambda x, y: jnp.sum(x * y), a, b)
    return jax.tree_util.tree_reduce(jnp.add, leaves, jnp.float32(0.0))


def tree_sq_norm(tree):
    return tree_dot(tree, tree)


def tree_stack(trees):
    """Stack a list of identically-structured pytrees along a new axis 0.

    Host-resident leaves (numpy, as produced by the vmap engine's unstack)
    take a C-level ``np.stack`` + one transfer instead of a K-operand device
    op — at 10k clients the difference is the aggregation's wall-clock.
    Tracers and device arrays fall through to ``jnp.stack`` unchanged.
    """

    def _stack(*xs):
        if all(type(x) is np.ndarray for x in xs):
            return jnp.asarray(np.stack(xs, axis=0))
        return jnp.stack(xs, axis=0)

    return jax.tree.map(_stack, *trees)


def tree_unstack(tree, n: int):
    """Inverse of :func:`tree_stack` — returns a list of ``n`` pytrees."""
    return [jax.tree.map(lambda x, i=i: x[i], tree) for i in range(n)]


def tree_weighted_sum(trees, weights):
    """Σ_k w_k · tree_k, accumulated in float32 (streaming-merge building
    block: callers fold fixed-size chunks so memory stays O(chunk))."""
    w = jnp.asarray(weights, jnp.float32)
    stacked = tree_stack(trees)
    return jax.tree.map(
        lambda s: jnp.tensordot(w, s.astype(jnp.float32), axes=1), stacked
    )


def tree_cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


def tree_allclose(a, b, rtol=1e-5, atol=1e-6) -> bool:
    oks = jax.tree.map(
        lambda x, y: bool(np.allclose(np.asarray(x), np.asarray(y), rtol=rtol, atol=atol)),
        a,
        b,
    )
    return all(jax.tree_util.tree_leaves(oks))


def fmt_params(n: int) -> str:
    if n >= 1e9:
        return f"{n / 1e9:.2f}B"
    if n >= 1e6:
        return f"{n / 1e6:.2f}M"
    if n >= 1e3:
        return f"{n / 1e3:.2f}K"
    return str(n)


def fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return f"{n:.2f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"

"""What every family shares: FedNano's adapters, its local optimizer and merge.

The NanoAdapters (rank r, scale alpha / r) sit at the connector-to-LLM
interface, ``y = x + scale (x down) up``, whatever backbone is under them;
the clients' AdamW and the server's Fisher merge (FedNano's Eq. 1) read
adapters only. So these live here, once, and a family module holds its
backbone alone. Nothing here imports the program.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def path_key(key, path: str):
    """The key of one leaf, named by its path, under ``key``."""
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def adapter_set(seed: int, sz, tag: str) -> Dict:
    """A trained-looking NanoAdapter set (``up`` != 0), float32, named ``tag``."""
    key = path_key(seed_key(seed), "adapters/" + tag)
    out = {}
    for j, mod in enumerate(sz.modalities):
        kd, ku = jax.random.split(jax.random.fold_in(key, j))
        out[mod] = {
            "down": jax.random.normal(kd, (sz.d, sz.rank)) * sz.d ** -0.5,
            "up": jax.random.normal(ku, (sz.rank, sz.d)) * 0.05,
        }
    return out


def adapt(sz, adp: Dict, x):
    """NanoAdapter residual, float32."""
    return x + sz.scale * (x @ adp["down"]) @ adp["up"]


@functools.partial(jax.jit, static_argnames=("lr", "grad_clip"))
def adamw_step(g, m, v, p, step, *, lr: float, grad_clip: float,
               b1=0.9, b2=0.999, eps=1e-8):
    """Decoupled AdamW (no weight decay) with global-norm clipping, per
    client: every tree is stacked over clients on its leading axis, and
    ``step`` (K,) is each client's count of steps, this one included."""
    def one(g, m, v, p, step):
        if grad_clip:
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
            g = jax.tree.map(lambda x: x * jnp.minimum(1.0, grad_clip / (norm + 1e-9)), g)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        step = step.astype(jnp.float32)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step
        p = jax.tree.map(lambda w, a, s: w - lr * ((a / c1) / (jnp.sqrt(s / c2) + eps)),
                         p, m, v)
        return p, m, v

    return jax.vmap(one)(g, m, v, p, step)


def fisher_merge(thetas: Sequence[Dict], fishers: Sequence[Dict],
                 sizes_: Sequence[float], eps: float = 1e-8) -> Dict:
    """FedNano's Eq. 1: sum_k p_k F_k theta_k / (sum_k p_k F_k + eps)."""
    w = np.asarray(sizes_, np.float64)
    w = w / w.sum()
    num = jax.tree.map(lambda *ts: sum(float(wk) * t for wk, t in zip(w, ts)),
                       *[jax.tree.map(lambda t, f: f * t, th, fi)
                         for th, fi in zip(thetas, fishers)])
    den = jax.tree.map(lambda *fs: sum(float(wk) * f for wk, f in zip(w, fs)),
                       *fishers)
    return jax.tree.map(lambda n, d: n / (d + eps), num, den)

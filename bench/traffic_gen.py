"""The one generator that every traffic file feeds.

A traffic file (``bench/traffic/<mix>.json``) holds parameters only. Two
kinds exist:

* ``round``: a federated population. Each client's share of the synthetic
  VQA topics is drawn from Dirichlet(``dirichlet_alpha``); its rows are
  token ids from its topics' bands of the vocabulary, an answer span that
  the loss reads, and (for multimodal mixes) image patches around its
  topics' centres. Sizes of the client shards follow the same draw.
* ``serve``: an open-loop request stream. The arrival times, request
  shapes and tenant ranks are drawn once from the mix's ``shape_seed``; a
  run's ``--seed`` draws the token ids and the tenants' names and
  adapters, so every seed offers the same trace of work.

The program receives only what is generated here.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np


def _rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([seed, *tags])


# ---------------------------------------------------------------------------
# federated rounds
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ClientRows:
    """One client's shard, host arrays, batch-major."""

    tokens: np.ndarray             # (n_batches, B, S) int32
    labels: np.ndarray             # (n_batches, B, S) int32
    mask: np.ndarray               # (n_batches, B, S) float32
    patches: Optional[np.ndarray]  # (n_batches, B, M, F) float32 or None


def round_population(seed: int, vocab: int, frontend: int, tr: Dict
                     ) -> Dict[int, ClientRows]:
    """Every client's rows for a ``round`` mix."""
    n_clients, topics = tr["clients"], tr["topics"]
    batch, s = tr["batch"], tr["text_len"]
    m = tr.get("image_patches", 0) if frontend else 0
    lo, hi = tr["batches_per_client"]
    need = max(tr["local_steps"], tr["fisher_batches"])
    if lo < need:
        raise ValueError(f"batches_per_client {lo} < {need} batches a round reads")
    a_lo, a_hi = tr["answer_len"]
    rng = _rng(seed, 1)
    mix = rng.dirichlet([tr["dirichlet_alpha"]] * topics, size=n_clients)
    share = rng.dirichlet([tr["dirichlet_alpha"]] * n_clients)
    n_batches = np.clip(np.round(lo + share * n_clients * (hi - lo) / 2),
                        lo, hi).astype(int)
    band = vocab // topics
    centres = rng.standard_normal((topics, frontend)).astype(np.float32) if m else None
    out = {}
    for cid in range(n_clients):
        nb = int(n_batches[cid])
        t = rng.choice(topics, size=(nb, batch), p=mix[cid])
        tok = (t[..., None] * band
               + rng.integers(0, band, size=(nb, batch, s))).astype(np.int32)
        labels = np.concatenate([tok[..., 1:], tok[..., :1]], axis=-1)
        ans = rng.integers(a_lo, a_hi + 1, size=(nb, batch))
        pos = np.arange(s)
        mask = ((pos >= s - 1 - ans[..., None]) & (pos < s - 1)).astype(np.float32)
        patches = None
        if m:
            patches = (centres[t][..., None, :]
                       + 0.5 * rng.standard_normal((nb, batch, m, frontend))
                       ).astype(np.float32)
        out[cid] = ClientRows(tok, labels, mask, patches)
    return out


def to_batches(rows: ClientRows):
    """The program's ``Batch`` list for one client, on the default device."""
    import jax.numpy as jnp
    from repro.core.types import Batch

    out = []
    for i in range(rows.tokens.shape[0]):
        out.append(Batch(
            tokens=jnp.asarray(rows.tokens[i]), labels=jnp.asarray(rows.labels[i]),
            mask=jnp.asarray(rows.mask[i]),
            patches=None if rows.patches is None else jnp.asarray(rows.patches[i])))
    return out


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ServeRequest:
    rid: int
    due_s: float           # offset from the window's start
    tenant: str
    prompt: np.ndarray     # (L,) int32
    max_new_tokens: int


def _clipped_lognormal(rng, spec: Dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(int)


def tenant_names(tr: Dict) -> List[str]:
    return [f"tenant{i:03d}" for i in range(tr["tenants"])]


def serve_shapes(tr: Dict, seconds: float) -> Tuple[np.ndarray, ...]:
    """The fixed multiset: (gaps, prompt lens, output lens, tenant ranks)."""
    n = max(1, int(round(tr["rate_per_s"] * seconds)))
    rng = _rng(tr["shape_seed"], n)
    gaps = rng.exponential(1.0, size=n)
    gaps *= seconds / gaps.sum()
    prompt = _clipped_lognormal(rng, tr["prompt_len"], n)
    output = _clipped_lognormal(rng, tr["output_len"], n)
    ranks = np.arange(1, tr["tenants"] + 1, dtype=np.float64)
    pop = ranks ** -tr["zipf_s"]
    tenant = rng.choice(tr["tenants"], size=n, p=pop / pop.sum())
    return gaps, prompt, output, tenant


def serve_requests(seed: int, vocab: int, tr: Dict, seconds: float
                   ) -> List[ServeRequest]:
    """Requests due in [0, seconds), in arrival order.

    Arrival times, prompt and output lengths and each request's tenant
    rank are the mix's own (``shape_seed``): a queue's tail depends on the
    order of bursts, not only on their sizes, so every seed offers the same
    trace. The seed draws the token ids and which tenant name (and so which
    adapters) holds each popularity rank.
    """
    gaps, prompt, output, tenant = serve_shapes(tr, seconds)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    rng = _rng(seed, 2)
    names = tenant_names(tr)
    name_of = rng.permutation(len(names))
    return [ServeRequest(
        rid=i, due_s=float(due[i]), tenant=names[name_of[tenant[i]]],
        prompt=rng.integers(0, vocab, size=int(prompt[i])).astype(np.int32),
        max_new_tokens=int(output[i])) for i in range(len(gaps))]

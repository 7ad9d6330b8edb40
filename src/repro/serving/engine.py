"""Multi-tenant continuous-batching serving engine.

One frozen backbone, many tenants' NanoAdapters — the deployment half of
FedNano. The engine composes three pieces:

  * :class:`~repro.serving.adapter_bank.AdapterBank` + ``AdapterCache`` —
    per-tenant adapters hot-swapped from federated checkpoints into stacked
    bank arrays; the decode step selects them per row (grouped LoRA).
  * :class:`~repro.serving.kv_cache.KVSlotManager` — a fixed pool of decode
    pages; admission = prefill, then the page write installs the page in
    place; completion frees it.
  * a continuous-batching loop: every engine step first admits queued
    requests into free pages, then runs ONE fixed-shape jitted decode step
    over all pages (per-page positions, ``model.decode_pages``), so
    mixed-tenant, mixed-length traffic never recompiles and never waits for
    the slowest request of a static batch.

The pool is updated in place. The page write and the decode step donate
it, and the decode step writes one K/V row per live page per layer into
it, so no step copies the pool or a layer's pages. ``stats["pool_donations"]``
counts the calls whose donation took effect (every decode step and page
write, unless the backend refused a donation).

Exactness: prompts are right-padded to ``prefill_len``. Under a causal mask
pad rows never influence real rows, and pad KV written at slots
``[L_real, prefill_len)`` is only ever attended AFTER decode has overwritten
it (decode at position p puts its own row in slot p before attending slots
<= p), so the padded prefill + batched decode is token-identical to the
one-request-at-a-time path — pinned by tests/test_serving.py. For ring-buffer
(sliding-window) archs the same argument needs the padded prefill to fit the ring,
which __init__ asserts. Recurrent-state families (ssm / hybrid) integrate
every prefill step into their terminal state, so the engine passes the true
prompt length down to ``model.prefill`` — recurrent sub-layers gate pad
steps to an exact identity (dt=0 for SSM, (a,b)=(1,0) for RG-LRU) and slice
their conv windows at the valid length.
"""
from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import adapters as nano
from repro.core.types import Batch
from repro.models import model as model_lib
from repro.serving.adapter_bank import (
    AdapterBank,
    AdapterCache,
    grouped_adapter_apply,
)
from repro.serving.kv_cache import KVSlotManager, consumed
from repro.tracing import span


@dataclass
class Request:
    """One generation request: a tenant id (None = base model, no adapter),
    an unpadded prompt, optionally a modality stream, and a token budget."""

    rid: int
    tenant: Optional[str]
    prompt: np.ndarray                    # (L,) int32, L <= prefill_len
    patches: Optional[np.ndarray] = None  # (M, frontend_dim) f32
    max_new_tokens: int = 8


@dataclass
class Completion:
    rid: int
    tenant: Optional[str]
    tokens: List[int] = field(default_factory=list)


def _min_window(cfg) -> Optional[int]:
    ws = []
    if cfg.sliding_window is not None:
        ws.append(cfg.sliding_window)
    if cfg.family == "hybrid" and cfg.rglru is not None:
        ws.append(cfg.rglru.local_window)
    return min(ws) if ws else None


class ServingEngine:
    def __init__(self, cfg, backbone, *, max_slots: int = 8,
                 prefill_len: int = 32, max_new_tokens: int = 32,
                 n_patches: Optional[int] = None, adapter_slots: int = 8,
                 adapter_loader=None, stop_token: Optional[int] = None,
                 use_pallas_grouped: bool = False):
        from repro.models.vision_stub import num_patches

        self.cfg = cfg
        self.backbone = backbone
        self.max_slots = max_slots
        self.prefill_len = prefill_len
        self.stop_token = stop_token
        self.use_pallas_grouped = use_pallas_grouped

        if cfg.frontend_dim:
            self.n_patches = n_patches if n_patches else num_patches(cfg)
        else:
            self.n_patches = 0
        # image tokens prepend to the decoder stream (vlm); the audio enc
        # stream runs through cross-attention and occupies no decoder slots
        self.img_prefix = (
            self.n_patches if (cfg.frontend_dim and cfg.family != "audio") else 0
        )
        self.capacity = self.img_prefix + prefill_len + max_new_tokens + 1
        w = _min_window(cfg)
        if w is not None and self.img_prefix + prefill_len > w:
            raise ValueError(
                f"padded prefill ({self.img_prefix + prefill_len}) exceeds the "
                f"attention window ({w}): pad slots would evict live KV from "
                "the ring — lower prefill_len or serve a longer-window config")

        self.bank = AdapterBank(cfg, adapter_slots)
        self.cache = AdapterCache(self.bank, loader=adapter_loader)
        self.slots = KVSlotManager(cfg, max_slots, self.capacity,
                                   model_lib.param_dtype(cfg))

        self._aslot = np.full((max_slots,), -1, np.int32)   # bank slot per page
        self._last_tok = np.zeros((max_slots,), np.int32)
        self._active: Dict[int, Completion] = {}
        self._budget: Dict[int, int] = {}
        self._queue: "deque[Request]" = deque()
        self.stats = {"decode_steps": 0, "prefills": 0, "occupancy_sum": 0,
                      "pool_donations": 0}

        capacity = self.capacity

        def _gather_adapters(bank_data, aslot):
            """Per-request adapter set from the bank (-1 => exact identity)."""
            live = (aslot >= 0).astype(list(bank_data.values())[0]["up"].dtype)
            safe = jnp.clip(aslot, 0, None)
            return {
                mod: {"down": d["down"][safe], "up": d["up"][safe] * live}
                for mod, d in bank_data.items()
            }

        @jax.jit
        def _prefill(backbone_, bank_data, aslot, tokens, patches, last_idx):
            adapters = _gather_adapters(bank_data, aslot)
            batch = Batch(
                tokens=tokens,
                labels=jnp.zeros_like(tokens),
                mask=jnp.zeros(tokens.shape, jnp.float32),
                patches=patches,
            )
            embeds, positions, _, _, enc = nano.nanoedge_forward(
                cfg, backbone_, adapters, batch)
            state, hidden = model_lib.prefill(
                cfg, backbone_, embeds, positions, capacity, enc_embeds=enc,
                length=last_idx + 1)
            last_h = jax.lax.dynamic_slice_in_dim(hidden, last_idx, 1, axis=1)
            lg = model_lib.logits(cfg, backbone_, last_h)
            return state, jnp.argmax(lg[0, 0], axis=-1).astype(jnp.int32)

        def _apply_text_bank(bank_data, emb, aslots):
            if "text" not in bank_data:
                return emb
            bank = self.bank  # shapes/scale only; arrays come from bank_data
            down, up = bank_data["text"]["down"], bank_data["text"]["up"]
            if self.use_pallas_grouped:
                from repro.kernels.lora import ops as lora_ops

                flat = lora_ops.grouped_lora_residual(
                    emb[:, 0, :], down, up, aslots, scale=bank.scale)
            else:
                from repro.kernels.lora import ref as lora_ref

                flat = lora_ref.grouped_lora_residual(
                    emb[:, 0, :], down, up, aslots, scale=bank.scale)
            return flat[:, None, :]

        @functools.partial(jax.jit, donate_argnums=2)
        def _decode(backbone_, bank_data, pool, toks, pos, aslots):
            # ONE jitted step: embed -> grouped per-tenant adapter -> decode
            # of every page; the donated pool is written by row in place.
            emb = model_lib.embed_tokens(cfg, backbone_, toks[:, None])
            emb = _apply_text_bank(bank_data, emb, aslots)
            lg, pool = model_lib.decode_pages(cfg, backbone_, emb, pool, pos)
            nxt = jnp.argmax(lg[:, 0, :], axis=-1).astype(jnp.int32)
            return nxt, pool

        self._prefill_fn = _prefill
        self._decode_fn = _decode

    # -- queue interface ----------------------------------------------------

    def submit(self, request: Request) -> None:
        if len(request.prompt) > self.prefill_len:
            raise ValueError(
                f"prompt of {len(request.prompt)} exceeds prefill_len="
                f"{self.prefill_len}")
        self._queue.append(request)

    def run(self, requests: Optional[List[Request]] = None) -> Dict[int, Completion]:
        """Drain the queue; returns {rid: Completion} in submission order."""
        for r in requests or []:
            self.submit(r)
        done: Dict[int, Completion] = {}
        while self._queue or self._active:
            self._admit(done)
            self._step(done)
        return done

    # -- internals ----------------------------------------------------------

    def _admit(self, done: Dict[int, Completion]) -> None:
        while self._queue and self.slots.n_free > 0:
            r = self._queue.popleft()
            with span("serve.admit", rid=r.rid, tenant=str(r.tenant),
                      prompt_len=len(r.prompt)):
                self._admit_one(r, done)

    def _admit_one(self, r: Request, done: Dict[int, Completion]) -> None:
        """Prefill one request into a free page; its spans nest in ``admit``."""
        c = self.cache
        with span("serve.adapter") as sp:
            before = (c.hits, c.misses, c.evictions)
            aslot = c.acquire(r.tenant)
            sp.set_metadata(hit=c.hits - before[0], miss=c.misses - before[1],
                            evicted=c.evictions - before[2])
        with span("serve.prefill"):
            prompt = np.asarray(r.prompt, np.int32)
            L = len(prompt)
            tokens = np.zeros((1, self.prefill_len), np.int32)
            tokens[0, :L] = prompt
            patches = None
            if r.patches is not None:
                patches = jnp.asarray(r.patches, jnp.float32)[None]
            last_idx = self.img_prefix + L - 1
            page, tok0 = self._prefill_fn(
                self.backbone, self.bank.data, jnp.int32(aslot),
                jnp.asarray(tokens), patches, jnp.int32(last_idx))
            self.stats["prefills"] += 1
        with span("serve.prefill.wait"):
            tok0 = int(tok0)
        comp = Completion(rid=r.rid, tenant=r.tenant, tokens=[tok0])
        if r.max_new_tokens <= 1 or tok0 == self.stop_token:
            self.cache.release(r.tenant)
            done[r.rid] = comp
            return
        with span("serve.page_write") as sp:
            slot = self.slots.alloc()
            donated = self.slots.write(slot, page, start_pos=last_idx + 1)
            self.stats["pool_donations"] += donated
            sp.set_metadata(donated=int(donated))
            self._aslot[slot] = aslot
            self._last_tok[slot] = tok0
            self._active[slot] = comp
            self._budget[slot] = r.max_new_tokens - 1

    def _step(self, done: Dict[int, Completion]) -> None:
        if not self._active:
            return
        with span("serve.decode", step=self.stats["decode_steps"],
                  live=len(self._active)) as sp:
            with span("serve.decode.dispatch"):
                old = self.slots.state
                nxt, self.slots.state = self._decode_fn(
                    self.backbone, self.bank.data, old,
                    jnp.asarray(self._last_tok),
                    jnp.asarray(self.slots.decode_positions()),
                    jnp.asarray(self._aslot))
                donated = consumed(old)
            self.stats["pool_donations"] += donated
            sp.set_metadata(donated=int(donated))
            with span("serve.decode.wait"):
                nxt = np.asarray(nxt)
            with span("serve.decode.bookkeep"):
                self._bookkeep(nxt, done)

    def _bookkeep(self, nxt: np.ndarray, done: Dict[int, Completion]) -> None:
        """Append each live slot's token; retire finished requests."""
        self.stats["decode_steps"] += 1
        self.stats["occupancy_sum"] += len(self._active)
        for slot in sorted(self._active):
            comp = self._active[slot]
            tok = int(nxt[slot])
            comp.tokens.append(tok)
            self.slots.pos[slot] += 1
            self._last_tok[slot] = tok
            self._budget[slot] -= 1
            if self._budget[slot] <= 0 or tok == self.stop_token:
                self.cache.release(comp.tenant)
                self.slots.free(slot)
                self._aslot[slot] = -1
                del self._active[slot]
                del self._budget[slot]
                done[comp.rid] = comp

    def mean_occupancy(self) -> float:
        s = self.stats
        return s["occupancy_sum"] / max(1, s["decode_steps"])


# ---------------------------------------------------------------------------
# naive per-tenant loop — the pre-engine serving path, kept as the baseline
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=16)
def _naive_steps(cfg):
    """The OLD launch/serve.py shape: jitted prefill + jitted decode with the
    per-token text-adapter apply in un-jitted host Python between them."""

    @functools.partial(jax.jit, static_argnames=("capacity",))
    def prefill(backbone, embeds, positions, enc, *, capacity):
        state, hidden = model_lib.prefill(cfg, backbone, embeds, positions,
                                          capacity, enc_embeds=enc)
        return state, model_lib.logits(cfg, backbone, hidden[:, -1:, :])

    @jax.jit
    def decode(backbone, state, emb, pos):
        return model_lib.decode_step(cfg, backbone, emb, state, pos)

    return prefill, decode


def generate_naive(cfg, backbone, requests: List[Request],
                   adapters_by_tenant: Optional[Dict[str, Dict]] = None,
                   *, stop_token: Optional[int] = None) -> Dict[int, Completion]:
    """Serve requests one at a time with one adapter set resident at a time.

    Unpadded prompts (every new length recompiles prefill), host-Python
    adapter math inside the decode loop, no cross-request batching: exactly
    the path the engine replaces, and the reference it must match token-for-
    token (tests/test_serving.py) and beat on throughput (serve_bench).
    """
    adapters_by_tenant = adapters_by_tenant or {}
    identity = nano.init_nanoedge(jax.random.PRNGKey(0), cfg)
    identity = jax.tree.map(jnp.zeros_like, identity)
    prefill, decode = _naive_steps(cfg)
    kw = dict(rank=cfg.adapter.rank, alpha=cfg.adapter.alpha)
    done: Dict[int, Completion] = {}
    for r in requests:
        adapters = adapters_by_tenant.get(r.tenant, identity)
        prompt = jnp.asarray(np.asarray(r.prompt, np.int32)[None])
        patches = None
        if r.patches is not None:
            patches = jnp.asarray(r.patches, jnp.float32)[None]
        batch = Batch(tokens=prompt, labels=jnp.zeros_like(prompt),
                      mask=jnp.zeros(prompt.shape, jnp.float32), patches=patches)
        embeds, positions, _, _, enc = nano.nanoedge_forward(
            cfg, backbone, adapters, batch)
        capacity = embeds.shape[1] + r.max_new_tokens + 1
        state, last = prefill(backbone, embeds, positions, enc,
                              capacity=capacity)
        tok = jnp.argmax(last[:, 0], axis=-1).astype(jnp.int32)
        comp = Completion(rid=r.rid, tenant=r.tenant, tokens=[int(tok[0])])
        for step in range(r.max_new_tokens - 1):
            if comp.tokens[-1] == stop_token:
                break
            pos = jnp.int32(embeds.shape[1] + step)
            emb = model_lib.embed_tokens(cfg, backbone, tok[:, None])
            if "text" in adapters:
                emb = nano.nano_adapter_apply(adapters["text"], emb, **kw)
            lg, state = decode(backbone, state, emb, pos)
            tok = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
            comp.tokens.append(int(tok[0]))
        done[r.rid] = comp
    return done

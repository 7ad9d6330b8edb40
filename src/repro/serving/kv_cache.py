"""Slot-paged decode-state pool for the serving engine.

The engine owns ONE fixed-shape decode state for ``n_slots`` concurrent
requests (the page pool) — for attention archs that is the stacked KV cache
(L, n_slots, C, n_kv, hd); for SSM/RG-LRU archs the recurrent states; for
enc-dec both self- and cross-KV. A request occupies exactly one page (slot)
from admission to completion; the page write installs a freshly computed
single-request prefill state into its page, and finishing frees the page
for the next request in the queue. Because the pool's shape never changes,
the jitted decode step is compiled once and mixed-length, mixed-tenant
traffic never recompiles.

The pool is updated in place: the page write and the engine's decode step
both donate it, and their output pool takes over its buffers, so neither
copies the pool. ``consumed`` tells whether a donation took effect.

Per-slot decode positions are tracked host-side: attention validity inside
the decode derives from the position (slot j valid iff j <= pos), so a
freed page needs no scrubbing — its stale KV is unreachable until the next
page write installs a new page over it.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import numpy as np

from repro.models import model as model_lib
from repro.utils import tree_bytes


@functools.partial(jax.jit, donate_argnums=0)
def _write_page(pool, page, slot):
    """Install a B=1 state in pool slot ``slot`` (batch axis 1 of every leaf),
    in place: the pool is donated."""
    return jax.tree.map(
        lambda p, s: jax.lax.dynamic_update_index_in_dim(
            p, s[:, 0].astype(p.dtype), slot, axis=1),
        pool, page)


def consumed(tree) -> bool:
    """True when every leaf of ``tree`` was donated to a call and is gone."""
    return all(leaf.is_deleted() for leaf in jax.tree.leaves(tree))


class KVSlotManager:
    """Fixed pool of decode pages over the model's stacked decode state."""

    def __init__(self, cfg, n_slots: int, capacity: int, dtype):
        self.cfg = cfg
        self.n_slots = n_slots
        self.capacity = capacity
        self.state = model_lib.init_state(cfg, n_slots, capacity, dtype)
        self._free: List[int] = list(range(n_slots))
        self.pos = np.zeros((n_slots,), np.int32)  # next decode position

    @property
    def n_free(self) -> int:
        return len(self._free)

    def alloc(self) -> Optional[int]:
        """Claim a free page; None when the pool is saturated."""
        if not self._free:
            return None
        return self._free.pop(0)

    def free(self, slot: int) -> None:
        if slot in self._free:
            raise ValueError(f"double free of slot {slot}")
        self._free.append(slot)
        self._free.sort()  # deterministic reuse order
        self.pos[slot] = 0

    def write(self, slot: int, page, start_pos: int) -> bool:
        """Install a single-request prefill state into ``slot``, in place.

        Returns whether the old pool's buffers were donated to the write.
        """
        old = self.state
        self.state = _write_page(old, page, slot)
        self.pos[slot] = start_pos
        return consumed(old)

    def decode_positions(self) -> np.ndarray:
        """Each page's next decode position, -1 on a free page."""
        pos = self.pos.copy()
        pos[self._free] = -1
        return pos

    def page_bytes(self) -> int:
        """Bytes of one page — what admitting a request actually costs."""
        return tree_bytes(self.state) // self.n_slots

    def pool_bytes(self) -> int:
        return tree_bytes(self.state)

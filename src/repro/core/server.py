"""Server state: the frozen LLM + global NanoAdapters (Alg. 1, ServerUpdate).

In a real deployment this process owns the TPU mesh; ``repro.launch`` wires
the same functions under pjit. Here the server also performs Fisher-guided
aggregation and tracks communication.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax

from repro.core import adapters as adapters_lib
from repro.core.comm import CommLog, RoundTraffic
from repro.models import model as model_lib
from repro.utils import tree_bytes


@dataclass
class ServerState:
    cfg: object
    backbone: Dict                  # frozen — never updated after init
    global_adapters: Dict           # current θ_global
    comm: CommLog = field(default_factory=CommLog)
    round_idx: int = 0


def init_server(key, cfg, *, sharding=None) -> ServerState:
    """Fresh server; ``sharding`` places the backbone where it will live."""
    kb, ka = jax.random.split(key)
    backbone = model_lib.init_backbone(kb, cfg, sharding)
    global_adapters = adapters_lib.init_nanoedge(ka, cfg)
    return ServerState(cfg=cfg, backbone=backbone, global_adapters=global_adapters)


def server_commit(
    server: ServerState,
    merged: Optional[Dict],
    *,
    param_up: int,
    fisher_up: int = 0,
    param_down: int = 0,
    wire_up: Optional[int] = None,
) -> ServerState:
    """Install a merged result and log the round's traffic.

    The low-level half of :func:`server_aggregate`, used directly by engines
    that already hold the merged tree (streaming/chunked aggregation and the
    buffered async mode fold uploads incrementally, so the full ``thetas``
    list never exists server-side).
    """
    traffic = RoundTraffic(
        round_idx=server.round_idx,
        param_up=param_up,
        fisher_up=fisher_up,
        param_down=param_down,
        param_up_wire=wire_up if wire_up is not None else param_up,
    )
    comm = server.comm
    comm.log_round(traffic)
    return dataclasses.replace(
        server,
        global_adapters=merged if merged is not None else server.global_adapters,
        comm=comm,
        round_idx=server.round_idx + 1,
    )


def log_downloads(server: ServerState, round_idx: int, down_bytes: int) -> None:
    """Record broadcast traffic for a round with no server aggregation
    (e.g. LocFT's round-0 init download): bytes still crossed the wire."""
    if down_bytes:
        server.comm.log_round(RoundTraffic(round_idx=round_idx, param_down=down_bytes))


def server_aggregate(
    server: ServerState,
    strategy,
    thetas: List[Dict],
    fishers: Optional[List[Dict]],
    data_sizes: List[int],
    *,
    use_pallas: bool = False,
    wire_up: Optional[int] = None,
    down_bytes: Optional[int] = None,
) -> ServerState:
    """Alg. 1 line 7: θ_global <- ServerAgg({θ_k, F_k}).

    ``strategy`` is a registered name or a ``Strategy`` instance; ``wire_up``
    is the transformed upload size in bytes (defaults to the raw fp32 size).
    ``down_bytes`` is what the round's cohort actually pulled from the server
    at round start — the engine passes it so broadcast cost is charged to the
    clients that download, not to this round's uploaders (the two differ
    under partial participation and download-skipping strategies). Without
    it, falls back to the legacy uploader-count estimate.
    """
    from repro.strategies.base import get_strategy

    merged = get_strategy(strategy).aggregate(
        thetas, fishers, data_sizes, use_pallas=use_pallas
    )
    param_up = sum(tree_bytes(t) for t in thetas)
    # a mixed cohort may carry FIMs for only some clients (tree_bytes(None)
    # is 0 via the empty pytree, but gating on fishers[0] miscounted)
    fisher_up = sum(tree_bytes(f) for f in fishers if f is not None) if fishers else 0
    if down_bytes is None:
        down_bytes = tree_bytes(merged) * len(thetas) if merged is not None else 0
    return server_commit(
        server, merged,
        param_up=param_up, fisher_up=fisher_up, param_down=down_bytes,
        wire_up=wire_up,
    )

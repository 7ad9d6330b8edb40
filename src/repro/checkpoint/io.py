"""Checkpointing: pytree <-> npz with path-keyed entries (no orbax offline).

Saves any params/opt-state pytree; restores require the reference structure
(standard practice — the training script always has it). Restores are
*strict*: a leaf whose shape or dtype differs from the reference raises
instead of silently casting (a checkpoint saved at a different precision
must be converted deliberately, never on load), and unexpected extra keys
are rejected unless ``strict=False``.

Server + client states round-trip through ``save_server_checkpoint`` /
``load_server_checkpoint``; full engine state (ServerOpt moments, per-client
optimizer state, transform residuals, round RNG, CommLog) goes through
``repro.checkpoint.run_state``. Every on-disk format carries a
``format_version`` in ``meta.json``; mismatches raise
:class:`CheckpointVersionError` rather than mis-restoring.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Mapping, Optional

import jax
import numpy as np

# On-disk format of save_server_checkpoint. v1 (implicit, no version field)
# dropped ServerOpt moments and the round RNG on the floor — a "resumed" run
# silently restarted the server optimizer from zero. v2 persists both and
# stamps the version so stale checkpoints fail loudly.
SERVER_CHECKPOINT_VERSION = 2


class CheckpointError(ValueError):
    """A checkpoint could not be restored (corrupt, incomplete, mismatched)."""


class CheckpointVersionError(CheckpointError):
    """The checkpoint's on-disk format version doesn't match this code."""


def _flatten(tree) -> Dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        key = "/".join(_path_str(p) for p in path)
        out[key] = np.asarray(leaf)
    return out


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def flatten_pytree(tree, *, prefix: str = "") -> Dict[str, np.ndarray]:
    """Flatten a pytree to ``{path: np.ndarray}`` (the npz entry layout).

    A non-empty ``prefix`` namespaces the keys (``prefix/leafpath``) so many
    pytrees can share one archive — the ``RunState`` format builds on this.
    A pytree that is a single bare array maps to the prefix itself.
    """
    flat = _flatten(tree)
    if not prefix:
        return flat
    return {f"{prefix}/{k}" if k else prefix: v for k, v in flat.items()}


def unflatten_pytree(reference, data: Mapping[str, np.ndarray], *,
                     prefix: str = "", where: str = "checkpoint"):
    """Rebuild ``reference``'s structure from path-keyed arrays.

    Shape AND dtype of every leaf must match the reference exactly —
    restoring a checkpoint saved at a different precision through a silent
    cast corrupts optimizer moments and DP noise scales, so it is an error.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(reference)
    leaves = []
    for p, ref_leaf in flat:
        key = "/".join(_path_str(q) for q in p)
        if prefix:
            key = f"{prefix}/{key}" if key else prefix
        if key not in data:
            raise CheckpointError(f"{where} missing key {key!r}")
        arr = data[key]
        ref_arr = np.asarray(ref_leaf)
        if tuple(arr.shape) != tuple(ref_arr.shape):
            raise CheckpointError(
                f"shape mismatch for {key}: {where} has {arr.shape}, "
                f"reference expects {ref_arr.shape}")
        if arr.dtype != ref_arr.dtype:
            raise CheckpointError(
                f"dtype mismatch for {key}: {where} holds {arr.dtype}, "
                f"reference expects {ref_arr.dtype}; convert the checkpoint "
                "explicitly instead of relying on a silent cast")
        leaves.append(jax.numpy.asarray(arr))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(reference), leaves)


def save_pytree(path: str, tree) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    np.savez(path, **flatten_pytree(tree))


def load_pytree(path: str, reference, *, strict: bool = True):
    """Restore into the structure of ``reference`` (shapes/dtypes enforced).

    ``strict=True`` (default) also rejects archives carrying keys the
    reference doesn't know about — an extra key means the file was written
    against a different structure, and half-matching it hides real drift.
    """
    data = np.load(path, allow_pickle=False)
    restored = unflatten_pytree(reference, data, where=os.path.basename(path))
    if strict:
        expected = set(flatten_pytree(reference))
        extra = sorted(set(data.files) - expected)
        if extra:
            raise CheckpointError(
                f"{os.path.basename(path)} carries keys not in the reference "
                f"structure: {extra[:5]}{'...' if len(extra) > 5 else ''} "
                "(pass strict=False to ignore)")
    return restored


def load_adapters(path: str, reference):
    """Restore a NanoAdapter pytree for serving/hot-swap.

    ``path`` is either a bare ``.npz`` written by :func:`save_pytree`, or a
    :func:`save_server_checkpoint` directory — in that case only
    ``global_adapters.npz`` is read (the serving engine never needs the
    backbone copy: it is frozen and shared across tenants by construction).
    """
    if os.path.isdir(path):
        inner = os.path.join(path, "global_adapters.npz")
        if not os.path.exists(inner):
            raise CheckpointError(
                f"{path!r} is a directory without global_adapters.npz — not "
                "a server checkpoint")
        return load_pytree(inner, reference)
    if not os.path.exists(path):
        raise CheckpointError(f"no adapter checkpoint at {path!r}")
    return load_pytree(path, reference)


def _key_data(key) -> Optional[np.ndarray]:
    """Raw uint32 data of a PRNG key (old-style arrays pass through)."""
    if key is None:
        return None
    try:
        if jax.numpy.issubdtype(key.dtype, jax.dtypes.prng_key):
            return np.asarray(jax.random.key_data(key))
    except (AttributeError, TypeError):
        pass
    return np.asarray(key)


def save_server_checkpoint(dirpath: str, server, round_idx: int, *,
                           server_opt_state=None, rng_key=None,
                           backbone: bool = True) -> None:
    """Persist a server snapshot: backbone, global adapters, CommLog, and —
    the pieces v1 silently dropped — the ServerOpt moments and round RNG.

    ``backbone=False`` skips the frozen backbone (12.6 GiB at llava-1.5-7b
    width); :func:`load_server_checkpoint` then keeps the caller's, which
    must be rebuilt from the same seed."""
    os.makedirs(dirpath, exist_ok=True)
    if backbone:
        save_pytree(os.path.join(dirpath, "backbone.npz"), server.backbone)
    save_pytree(os.path.join(dirpath, "global_adapters.npz"),
                server.global_adapters)
    if server_opt_state is not None:
        save_pytree(os.path.join(dirpath, "server_opt_state.npz"),
                    server_opt_state)
    kd = _key_data(rng_key)
    if kd is not None:
        np.savez(os.path.join(dirpath, "rng_key.npz"), rng_key=kd)
    meta = {
        "format_version": SERVER_CHECKPOINT_VERSION,
        "round_idx": round_idx,
        "cfg_name": server.cfg.name,
        "server_round_idx": server.round_idx,
        "has_backbone": backbone,
        "has_server_opt_state": server_opt_state is not None,
        "has_rng_key": kd is not None,
        "comm_rounds": [r.to_dict() for r in server.comm.rounds],
    }
    # meta.json is written last: a checkpoint without it is unreadable by
    # design, so a crash mid-save never yields a half-restorable directory
    with open(os.path.join(dirpath, "meta.json"), "w") as f:
        json.dump(meta, f)


def load_server_checkpoint(dirpath: str, server, *, server_opt_state=None):
    """Restore a server snapshot saved by :func:`save_server_checkpoint`.

    ``server_opt_state`` is the *reference* structure for the ServerOpt
    moments (``server_opt.init(global_adapters)``); when the checkpoint has
    moments they are returned under ``meta["server_opt_state"]`` (and the
    restored RNG key, if any, under ``meta["rng_key"]``). Checkpoints from a
    different format version raise :class:`CheckpointVersionError` — v1
    checkpoints never stored the optimizer moments, so "restoring" one into
    a FedOpt run would silently zero the server momentum.
    """
    import dataclasses

    from repro.core.comm import CommLog, RoundTraffic

    meta_path = os.path.join(dirpath, "meta.json")
    if not os.path.exists(meta_path):
        raise CheckpointError(f"no checkpoint at {dirpath!r} (meta.json missing)")
    with open(meta_path) as f:
        meta = json.load(f)
    version = meta.get("format_version")
    if version != SERVER_CHECKPOINT_VERSION:
        raise CheckpointVersionError(
            f"checkpoint at {dirpath!r} has format_version={version!r}, this "
            f"code reads v{SERVER_CHECKPOINT_VERSION}; older checkpoints "
            "lack the ServerOpt moments / round RNG and cannot be resumed "
            "faithfully — re-save with the current code")
    backbone = server.backbone
    if meta.get("has_backbone", True):
        backbone = load_pytree(os.path.join(dirpath, "backbone.npz"), backbone)
    adapters = load_pytree(os.path.join(dirpath, "global_adapters.npz"),
                           server.global_adapters)
    comm = CommLog(rounds=[RoundTraffic.from_dict(d)
                           for d in meta.get("comm_rounds", [])])
    if meta.get("has_server_opt_state"):
        if server_opt_state is None:
            raise CheckpointError(
                f"checkpoint at {dirpath!r} carries ServerOpt moments; pass "
                "the reference structure via server_opt_state= (e.g. "
                "server_opt.init(global_adapters)) so they are not dropped")
        meta["server_opt_state"] = load_pytree(
            os.path.join(dirpath, "server_opt_state.npz"), server_opt_state)
    if meta.get("has_rng_key"):
        meta["rng_key"] = np.load(
            os.path.join(dirpath, "rng_key.npz"))["rng_key"]
    return dataclasses.replace(
        server, backbone=backbone, global_adapters=adapters, comm=comm,
        round_idx=meta.get("server_round_idx", meta["round_idx"]),
    ), meta

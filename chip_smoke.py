"""Bring-up check on a TPU: FedNano's main path at full llava-1.5-7b width.

    python chip_smoke.py             # one chip: trainer + serving phases
    python chip_smoke.py --chips 4   # four chips: sharded engine vs one device

One process drives everything (a second process could not reach a chip the
first holds). The backbone is llava-1.5-7b at its published config — 32
layers, d_model 4096, 32 heads, d_ff 11008, vocab 32000, frontend 1024,
rank-64 text and image NanoAdapters, bf16 — with random weights from the
seed.

One chip:
  * trainer: ``fednano`` through ``repro.launch.train`` on the vmap engine,
    4 clients x 2 rounds x 2 local steps + 2 Fisher batches, batch 4,
    seq_len 128 (+64 image patches); once with the Pallas kernels compiled
    and once without, on the same backbone. Every round loss must be finite
    and the two runs' round losses must agree (``ROUND0_RTOL``,
    ``LATER_RTOL``). The gradient of one batch's loss at the trained
    global adapters, taken through the Fisher pass's jitted gradient with
    and without kernels, must agree leaf by leaf (``GRAD_RTOL``): the
    backward through flash attention's custom VJP is checked there.
  * serving: ``ServingEngine`` on the trained backbone, with the global
    adapters and three clients' adapters as 4 tenants; 8 requests,
    prefill_len 64, 8 new tokens each, grouped-LoRA kernel compiled.

Four chips: the same trainer on ``engine="sharded"`` with 8 clients over a
4-device ``("clients",)`` mesh against the same run with ``devices=1``
(whose arrays are released first). Round losses must agree within
``MESH_RTOL`` and every round's cohorts must span the 4 devices (the
engine's ``cohort_devices`` round metric).

Each phase prints its wall time, compile time and the device's
``peak_bytes_in_use``. The last line of stdout is the JSON verdict, printed
only when every phase passed; any failure exits non-zero. Without a TPU the
script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import sys
import time

# Limits, each set between what sound runs read on a v5e and what a broken
# path gives. Pallas vs jnp differ by design: flash attention keeps an f32
# online softmax where the jnp path rounds probabilities to bf16, so one
# batch's adapter gradients differ by 1.5-2.1 % (relative L2 per leaf) and
# round 0's mean loss (~ln 32000) by 6e-5 to 3.1e-4.
ROUND0_RTOL = 2e-3
# After the first merge the gap grows: AdamW's first step is lr·g/|g|, so
# any gradient difference flips the update of the weights whose gradient
# is near zero, and those flips compound. Swapping the jnp path's bf16
# LoRA for f32 LoRA leaves one batch's loss and gradients bit-equal, yet
# moves round 1 by 0.43 %; Pallas vs jnp read 1.05 % and 2.26 % there.
LATER_RTOL = 5e-2
# Per-leaf relative L2 gap of the Pallas gradient from the jnp gradient.
GRAD_RTOL = 5e-2
# Sharded vs one device runs the same per-client arithmetic; round losses
# read 6.0e-5 to 1.7e-4 apart.
MESH_RTOL = 1e-3

ARCH = "llava-1.5-7b"
SEED = 0
TRAIN_ARGV = [
    "--arch", ARCH, "--strategy", "fednano", "--engine", "vmap",
    "--clients", "4", "--rounds", "2", "--local-steps", "2",
    "--fisher-batches", "2", "--batch-size", "4", "--seq-len", "128",
    "--examples-per-client", "16", "--seed", str(SEED),
]
SERVE_TENANTS, SERVE_REQUESTS, PREFILL_LEN, NEW_TOKENS = 4, 8, 64, 8
OUT_DIR = os.path.join("runs", "chip_smoke")


class SmokeFailure(RuntimeError):
    pass


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def phase(name):
    """Print one phase's wall time, compile time and device peak memory."""
    import jax

    compile_s = 0.0

    def on_event(event, duration, **_):
        nonlocal compile_s
        if event.startswith("/jax/core/compile/"):
            compile_s += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    status, t0 = "failed", time.perf_counter()
    try:
        yield
        status = "ok"
    finally:
        wall = time.perf_counter() - t0
        jax.monitoring.unregister_event_duration_listener(on_event)
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
        peak_s = f"{peak} B ({peak / 2**30:.2f} GiB)" if peak else "not reported"
        print(f"[{name}] {status}: wall {wall:.1f}s, compile {compile_s:.1f}s, "
              f"peak_bytes_in_use {peak_s}", flush=True)


def _round_losses(res, label):
    losses = [m["mean_loss"] for m in res.round_metrics]
    print(f"  {label}: round mean losses {losses}", flush=True)
    _check(losses and all(l is not None and math.isfinite(l) for l in losses),
           f"{label}: non-finite or missing round loss {losses}")
    return losses


def _agree(a, b, what, rtols):
    """Round r of ``a`` and ``b`` agree within ``rtols[min(r, last)]``."""
    _check(len(a) == len(b), f"{what}: round counts differ ({a} vs {b})")
    for r, (x, y) in enumerate(zip(a, b)):
        rtol = rtols[min(r, len(rtols) - 1)]
        _check(abs(x - y) <= rtol * abs(y),
               f"{what}: round {r} loss {x} vs {y} differs by more than "
               f"rtol {rtol}")


def _grad_gaps(kern_cfg, ref_cfg, backbone, adapters, batch):
    """Relative L2 gap, per adapter leaf, of the Pallas gradient from jnp's."""
    import jax
    import jax.numpy as jnp

    from repro.core.client import make_fisher_grad

    kern = make_fisher_grad(kern_cfg)(backbone, adapters, batch)
    ref = make_fisher_grad(ref_cfg)(backbone, adapters, batch)
    gaps = {}
    for (path, k), r in zip(jax.tree_util.tree_leaves_with_path(kern),
                            jax.tree.leaves(ref)):
        k, r = k.astype(jnp.float32), r.astype(jnp.float32)
        gaps[jax.tree_util.keystr(path)] = float(
            jnp.linalg.norm(k - r) / jnp.linalg.norm(r))
    return gaps


def _fresh(server):
    """The same frozen backbone and initial adapters with an empty CommLog."""
    from repro.core.comm import CommLog

    return dataclasses.replace(server, comm=CommLog())


def trainer_phase(width_argv):
    """Pallas vs jnp rounds on one backbone; returns (cfg, result)."""
    import jax

    from repro.core import server as server_lib
    from repro.kernels.platform import interpret_mode
    from repro.launch import train

    argv = width_argv + TRAIN_ARGV + ["--out", os.path.join(OUT_DIR, "train")]
    ref_args = train.parse_args(argv)
    kern_args = train.parse_args(argv + ["--use-pallas"])
    kern_cfg, ref_cfg = train.build_config(kern_args), train.build_config(ref_args)
    if jax.default_backend() == "tpu":
        _check(not interpret_mode(), "kernels would run in interpret mode on the TPU")

    # one backbone for both runs, from the key run_federated would derive
    k_server, _ = jax.random.split(jax.random.PRNGKey(SEED))
    server0 = server_lib.init_server(k_server, ref_cfg)
    res, _ = train.run(kern_args, kern_cfg, server=_fresh(server0))
    kern = _round_losses(res, "pallas kernels")
    ref_res, _ = train.run(ref_args, ref_cfg, server=_fresh(server0))
    ref = _round_losses(ref_res, "jnp reference")
    _agree(kern, ref, "pallas vs jnp", (ROUND0_RTOL, LATER_RTOL))
    del ref_res

    batch = train.make_data(ref_args, ref_cfg)[0][0][0]
    gaps = _grad_gaps(kern_cfg, ref_cfg, server0.backbone,
                      res.server.global_adapters, batch)
    print(f"  gradient gap, pallas vs jnp, relative L2 per leaf: {gaps}",
          flush=True)
    _check(all(math.isfinite(g) and g <= GRAD_RTOL for g in gaps.values()),
           f"pallas vs jnp gradients differ by more than {GRAD_RTOL}: {gaps}")
    return kern_cfg, res


def serving_phase(cfg, res):
    """Serve the trained adapters on the trained backbone."""
    from repro.launch.serve import make_requests
    from repro.serving import ServingEngine

    tenants = {"global": res.server.global_adapters}
    for c in res.clients[: SERVE_TENANTS - 1]:
        tenants[f"client{c.cid}"] = c.adapters
    reqs = make_requests(cfg, list(tenants), SERVE_REQUESTS, PREFILL_LEN,
                         NEW_TOKENS, SEED)
    engine = ServingEngine(
        cfg, res.server.backbone, max_slots=SERVE_TENANTS,
        prefill_len=PREFILL_LEN, max_new_tokens=NEW_TOKENS,
        adapter_slots=SERVE_TENANTS, adapter_loader=tenants.__getitem__,
        use_pallas_grouped=True)
    done = engine.run(reqs)
    for r in reqs:
        got = done.get(r.rid)
        _check(got is not None and len(got.tokens) == NEW_TOKENS,
               f"request {r.rid} returned {None if got is None else got.tokens}")
    print(f"  {len(reqs)} requests x {NEW_TOKENS} tokens; occupancy "
          f"{engine.mean_occupancy():.2f}/{SERVE_TENANTS}; adapter cache "
          f"{engine.cache.stats()}", flush=True)


def sharded_phase(width_argv, n_devices=4):
    """engine="sharded" on ``n_devices`` vs the same run on one device."""
    from repro.launch import train

    argv = (width_argv + TRAIN_ARGV
            + ["--engine", "sharded", "--clients", "8",
               "--out", os.path.join(OUT_DIR, "sharded")])
    one_args = train.parse_args(argv + ["--devices", "1"])
    res, _ = train.run(one_args, train.build_config(one_args))
    one = _round_losses(res, "1 device")
    del res  # release the one-device backbone before the mesh holds its own
    gc.collect()

    mesh_args = train.parse_args(argv + ["--devices", str(n_devices)])
    res, _ = train.run(mesh_args, train.build_config(mesh_args))
    _agree(_round_losses(res, f"{n_devices} devices"), one,
           f"{n_devices} devices vs 1 device", (MESH_RTOL,))
    spans = [m["cohort_devices"] for m in res.round_metrics]
    _check(all(s == [n_devices] for s in spans),
           f"cohort outputs span {spans} devices per round, expected "
           f"[{n_devices}]")
    print(f"  every round's cohorts span {n_devices} devices", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-engine comparison")
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: src/repro not found next to this script; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    from repro.launch.common import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {cache}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU visible; this check runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} visible",
              file=sys.stderr)
        return 1

    width = ["--full-width"]
    try:
        if args.chips == 4:
            with phase("sharded engine, 4 chips vs 1"):
                sharded_phase(width)
        else:
            with phase("trainer"):
                cfg, res = trainer_phase(width)
            with phase("serving"):
                serving_phase(cfg, res)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Multi-tenant serving driver (``python -m repro.launch.serve``).

The deployment half of FedNano: ONE frozen backbone serves many tenants,
each tenant being a federated client whose tuned NanoAdapters are
hot-swapped into the engine's adapter bank. Requests from different
tenants with different prompt lengths are continuously batched — admission
prefills into a free decode slot, then every engine step decodes all
occupied slots in one fixed-shape jitted call with per-row grouped-LoRA
adapter selection, so mixed traffic never recompiles.

Adapters come from ``--ckpt-root`` (a directory of per-tenant federated
checkpoints: ``<root>/<tenant>`` as a ``save_server_checkpoint`` dir or a
bare ``.npz``) or, without one, are synthesized per tenant so the
multi-tenant path is exercisable standalone. ``--naive`` cross-checks the
engine against the one-request-at-a-time loop (the pre-engine serving
path) and reports token parity + speedup.

By default the backbone is the CPU smoke cut of ``--arch``;
``--full-width`` builds the published config, which needs an accelerator.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.core import adapters as nano
from repro.launch.common import add_model_args, enable_compile_cache, model_config
from repro.models import model as backbone_lib
from repro.models.vision_stub import num_patches
from repro.serving import (
    Request,
    ServingEngine,
    checkpoint_adapter_loader,
    generate_naive,
)


def synth_tenant_adapters(key, cfg, tenants):
    """Deterministic non-identity adapter sets, one per tenant name."""
    out = {}
    for i, t in enumerate(tenants):
        ad = nano.init_nanoedge(jax.random.fold_in(key, 100 + i), cfg)
        ad = jax.tree.map(
            lambda a, j=i: jax.random.normal(
                jax.random.fold_in(key, 1000 + j * 7 + a.size % 97),
                a.shape, a.dtype) * 0.05,
            ad)
        out[t] = ad
    return out


def make_requests(cfg, tenants, n_requests, prefill_len, gen_tokens, seed):
    """Mixed workload: tenants round-robin (every 5th request tenantless),
    prompt lengths cycling through [2, prefill_len]."""
    rng = np.random.default_rng(seed)
    m = num_patches(cfg) if cfg.frontend_dim else 0
    reqs = []
    for i in range(n_requests):
        tenant = None if (i % 5 == 4) else tenants[i % len(tenants)]
        length = 2 + (i * 3) % (prefill_len - 1)
        patches = (rng.standard_normal((m, cfg.frontend_dim)).astype(np.float32)
                   if cfg.frontend_dim else None)
        reqs.append(Request(
            rid=i, tenant=tenant,
            prompt=rng.integers(0, cfg.vocab_size, length).astype(np.int32),
            patches=patches, max_new_tokens=gen_tokens))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    add_model_args(ap)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--gen-tokens", type=int, default=8)
    ap.add_argument("--prefill-len", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="concurrent decode slots (page pool size)")
    ap.add_argument("--adapter-slots", type=int, default=8,
                    help="adapter bank size (LRU over tenants)")
    ap.add_argument("--ckpt-root", default=None,
                    help="directory of per-tenant federated checkpoints; "
                         "tenant names are the entries inside")
    ap.add_argument("--pallas-grouped", action="store_true",
                    help="run the grouped-LoRA Pallas kernel (compiled on a "
                         "TPU, interpreted elsewhere) instead of the jnp "
                         "reference")
    ap.add_argument("--naive", action="store_true",
                    help="also run the one-request-at-a-time loop, check "
                         "token parity, and report the speedup")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    key = jax.random.PRNGKey(args.seed)
    cfg = model_config(args)
    backbone = backbone_lib.init_backbone(key, cfg)

    if args.ckpt_root:
        import os

        tenant_names = sorted(
            os.path.splitext(e)[0] for e in os.listdir(args.ckpt_root))
        if not tenant_names:
            raise SystemExit(f"--ckpt-root {args.ckpt_root!r} is empty")
        tenant_names = tenant_names[: args.tenants]
        loader = checkpoint_adapter_loader(cfg, args.ckpt_root)
        adapters_by_tenant = {t: loader(t) for t in tenant_names}
        print(f"serving {len(tenant_names)} tenants from {args.ckpt_root}")
    else:
        tenant_names = [f"tenant{i}" for i in range(args.tenants)]
        adapters_by_tenant = synth_tenant_adapters(key, cfg, tenant_names)
        loader = adapters_by_tenant.__getitem__
        print(f"serving {len(tenant_names)} synthetic tenants "
              "(no --ckpt-root)")

    reqs = make_requests(cfg, tenant_names, args.requests, args.prefill_len,
                         args.gen_tokens, args.seed)
    engine = ServingEngine(
        cfg, backbone, max_slots=args.slots, prefill_len=args.prefill_len,
        max_new_tokens=args.gen_tokens, adapter_slots=args.adapter_slots,
        adapter_loader=loader, use_pallas_grouped=args.pallas_grouped)

    t0 = time.time()
    done = engine.run(reqs)
    dt = time.time() - t0
    n_tok = sum(len(c.tokens) for c in done.values())
    print(f"arch={args.arch} engine: {len(reqs)} requests, {n_tok} tokens "
          f"in {dt:.2f}s ({n_tok / dt:.1f} tok/s on "
          f"{jax.devices()[0].device_kind}) | "
          f"occupancy {engine.mean_occupancy():.2f}/{args.slots} | "
          f"adapter cache {engine.cache.stats()}")
    for rid in sorted(done)[:4]:
        c = done[rid]
        print(f"  req {rid} [{c.tenant or 'base'}]: {c.tokens}")

    if args.naive:
        t0 = time.time()
        ref = generate_naive(cfg, backbone, reqs, adapters_by_tenant)
        dt_naive = time.time() - t0
        mismatch = [r.rid for r in reqs if done[r.rid].tokens != ref[r.rid].tokens]
        if mismatch:
            raise SystemExit(f"TOKEN MISMATCH vs naive loop: rids {mismatch}")
        print(f"naive loop: {n_tok} tokens in {dt_naive:.2f}s "
              f"({n_tok / dt_naive:.1f} tok/s) — token parity OK, "
              f"engine speedup {dt_naive / dt:.2f}x")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

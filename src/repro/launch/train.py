"""Federated training driver (``python -m repro.launch.train``).

Runs the full FedNano protocol (or any baseline strategy) with the
synthetic non-IID VQA corpus — the runnable end-to-end entry point
(examples/federated_vqa.py wraps this with a narrative). By default the
backbone is the CPU smoke cut of ``--arch``; ``--full-width`` builds the
published config (e.g. llava-1.5-7b: 32 layers, d_model 4096, bf16), which
needs an accelerator. Checkpoints + metrics land under --out; a full-width
run writes no backbone snapshot (its weights come from ``--seed``).
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from repro.checkpoint import save_server_checkpoint
from repro.core import FailureModel, HyperParams, run_centralized, run_federated
from repro.data import make_federated_data
from repro.launch.common import add_model_args, enable_compile_cache, model_config
from repro.strategies import UniformSampler, available_strategies
from repro.strategies.server_opt import FedAdamOpt, FedAvgMOpt


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    add_model_args(ap)
    ap.add_argument("--strategy", default="fednano",
                    choices=list(available_strategies()) + ["centralized"])
    ap.add_argument("--server-opt", default=None, choices=["fedavgm", "fedadam"],
                    help="FedOpt server step applied to the merged pseudo-gradient")
    ap.add_argument("--server-lr", type=float, default=None,
                    help="server-optimizer learning rate (default: the opt's own)")
    ap.add_argument("--client-frac", type=float, default=1.0,
                    help="fraction of clients sampled per round (C in C·K)")
    ap.add_argument("--engine", default="sequential",
                    choices=["sequential", "vmap", "sharded", "buffered"],
                    help="round engine: per-client loop, vectorized vmap/scan "
                         "cohort, the vmap layout sharded over a clients "
                         "device mesh, or FedBuff-style buffered async")
    ap.add_argument("--devices", type=int, default=None,
                    help="mesh size for --engine sharded (default: all "
                         "visible devices; on CPU export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first)")
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable the sharded engine's prepare/compute "
                         "double buffer")
    ap.add_argument("--agg-chunk", type=int, default=None,
                    help="fold cohort chunks of this size into a streaming "
                         "merge (O(chunk) server memory; vmap engine)")
    ap.add_argument("--buffer-size", type=int, default=None,
                    help="server buffer size for --engine buffered "
                         "(default: half the population)")
    ap.add_argument("--clients", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--local-steps", type=int, default=8)
    ap.add_argument("--fisher-batches", type=int, default=4,
                    help="batches in the dedicated Fisher pass")
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--rank", type=int, default=None, help="NanoAdapter rank override")
    ap.add_argument("--examples-per-client", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="runs/train")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="snapshot the full round state every N rounds under "
                         "<out>/state (0 = only the final snapshot)")
    ap.add_argument("--resume", default=None, metavar="DIR",
                    help="resume from a RunState snapshot directory (pass the "
                         "snapshot itself or its parent; LATEST is followed). "
                         "Use the same seed/arch/strategy flags as the "
                         "original run — replay is deterministic")
    ap.add_argument("--dropout-prob", type=float, default=0.0,
                    help="per-round probability a sampled client never starts")
    ap.add_argument("--crash-prob", type=float, default=0.0,
                    help="per-round probability a client dies mid-update "
                         "(download charged, progress lost)")
    ap.add_argument("--straggler-prob", type=float, default=0.0,
                    help="probability a buffered-engine client is delayed")
    ap.add_argument("--failure-seed", type=int, default=0,
                    help="seed for the failure schedule (independent of --seed)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="route attention/LoRA/Fisher-merge through the Pallas "
                         "kernels (compiled on a TPU, interpreted elsewhere)")
    return ap.parse_args(argv)


def build_config(args):
    cfg = model_config(args)
    if args.rank:
        import dataclasses

        cfg = cfg.with_(adapter=dataclasses.replace(cfg.adapter, rank=args.rank))
    if args.use_pallas:
        cfg = cfg.with_(use_pallas=True)
    return cfg


def make_data(args, cfg):
    """The run's (train, eval) batches per client, from ``--seed``."""
    train, evald, _ = make_federated_data(
        cfg, n_clients=args.clients, examples_per_client=args.examples_per_client,
        alpha=args.alpha, batch_size=args.batch_size, seq_len=args.seq_len,
        seed=args.seed,
    )
    return train, evald


def run(args, cfg, *, server=None):
    """Run the configured strategy; returns (FederatedResult, wall seconds).

    ``server`` reuses an already-built server (its frozen backbone) instead
    of initializing one from ``--seed``.
    """
    scale = "published config" if args.full_width else "smoke config"
    print(f"== FedNano driver: arch={args.arch} ({scale}) strategy={args.strategy} "
          f"K={args.clients} R={args.rounds} α={args.alpha} rank={cfg.adapter.rank}")
    train, evald = make_data(args, cfg)
    hp = HyperParams(lr=args.lr, local_steps=args.local_steps,
                     fisher_batches=args.fisher_batches)
    key = jax.random.PRNGKey(args.seed)
    t0 = time.time()
    if args.strategy == "centralized":
        res = run_centralized(key, cfg, train, evald,
                              steps=args.rounds * args.local_steps * args.clients,
                              hp=hp, verbose=True)
    else:
        server_opt = None
        if args.server_opt:
            cls = {"fedavgm": FedAvgMOpt, "fedadam": FedAdamOpt}[args.server_opt]
            server_opt = cls(lr=args.server_lr) if args.server_lr is not None else cls()
        sampler = UniformSampler(frac=args.client_frac, seed=args.seed) \
            if args.client_frac < 1.0 else None
        failures = None
        if args.dropout_prob or args.crash_prob or args.straggler_prob:
            failures = FailureModel(dropout_prob=args.dropout_prob,
                                    crash_prob=args.crash_prob,
                                    straggler_prob=args.straggler_prob,
                                    seed=args.failure_seed)
        res = run_federated(key, cfg, train, evald, strategy=args.strategy,
                            rounds=args.rounds, hp=hp, verbose=True,
                            use_pallas=args.use_pallas, server=server,
                            server_opt=server_opt, sampler=sampler,
                            engine=args.engine, agg_chunk=args.agg_chunk,
                            devices=args.devices,
                            overlap=not args.no_overlap,
                            buffer_size=args.buffer_size,
                            failures=failures,
                            checkpoint_dir=os.path.join(args.out, "state"),
                            checkpoint_every=args.checkpoint_every,
                            resume=args.resume)
    return res, time.time() - t0


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    res, dt = run(args, build_config(args))
    key = jax.random.PRNGKey(args.seed)

    os.makedirs(args.out, exist_ok=True)
    summary = {
        "arch": args.arch,
        "strategy": args.strategy,
        "avg_accuracy": res.avg_accuracy,
        "client_accuracy": res.client_accuracy,
        "rounds": res.round_metrics,
        "comm_totals": res.comm_totals,
        "wall_s": dt,
    }
    with open(os.path.join(args.out, f"{args.arch}_{args.strategy}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if res.server is not None:
        save_server_checkpoint(os.path.join(args.out, "ckpt"), res.server,
                               round_idx=args.rounds,
                               server_opt_state=res.server_opt_state,
                               rng_key=key, backbone=not args.full_width)
    print(f"== done in {dt:.1f}s: avg client accuracy {res.avg_accuracy:.4f}")
    print(f"   per-client: { {k: round(v, 4) for k, v in res.client_accuracy.items()} }")
    if res.comm_totals:
        up = res.comm_totals["param_up"] / 1024**2
        print(f"   param-plane traffic: {up:.2f} MiB up over {args.rounds} rounds")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

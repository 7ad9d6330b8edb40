"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload <cell> [--layers L] [--slots S]

Lowers the programs the cell's window runs, at the cell's sizes, against a
described ``v5e:2x2`` topology (one of its chips) and prints each one's
``memory_analysis()``: the round program of a ``round`` cell; the prefill,
page write and decode step of a ``serve`` cell. ``--layers`` and
``--slots`` override the configuration's depth and the mix's slot count,
to find the largest that fits. Nothing runs; only shapes are used, so no
weight is ever built here.

The Pallas kernels decide interpret mode by the default backend, which is
this host's CPU; for the compile they are told they are on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2 ** 30


def _compile_for_chip():
    from repro.kernels.fisher_merge import ops as fm_ops
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.lora import ops as lora_ops

    def on_chip(interpret=None):
        return False if interpret is None else interpret

    for mod in (fa_ops, lora_ops, fm_ops):
        mod.interpret_mode = on_chip


def _report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    row = {"program": name,
           "arguments_gib": m.argument_size_in_bytes / GIB,
           "outputs_gib": m.output_size_in_bytes / GIB,
           "temporaries_gib": m.temp_size_in_bytes / GIB,
           "aliased_gib": m.alias_size_in_bytes / GIB,
           "total_gib": total / GIB,
           "kernels": compiled.as_text().count("tpu_custom_call")}
    print(json.dumps(row), flush=True)
    return row


def _spec_tree(tree, sharding):
    import jax

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                       sharding=sharding), tree)


def round_programs(cell, sz, cfg, chip):
    import jax
    import jax.numpy as jnp

    from repro.core.client import HyperParams, make_many_update
    from repro.core.types import Batch
    from repro.optim import adamw_init
    from repro.strategies import get_strategy

    from bench.models import common

    tr = cell.traffic
    k = tr["sampler"].get("n", tr["clients"])
    t, f, b, s = tr["local_steps"], tr["fisher_batches"], tr["batch"], tr["text_len"]
    m = sz.image_patches if sz.frontend else 0
    hp = HyperParams(lr=tr["lr"], grad_clip=tr["grad_clip"], local_steps=t,
                     fisher_batches=f)
    fn = make_many_update(cfg, get_strategy("fednano"), hp, downloads=True,
                          warmup=False, has_local=False, train_t=t, warm_t=0,
                          fish_t=f, shared_batches=False)
    bb = cell.model.backbone_shapes(sz, cell.config["dtype"])
    adp = jax.eval_shape(lambda: common.adapter_set(0, sz, "global"))
    opt = jax.eval_shape(lambda: jax.vmap(lambda _: adamw_init(
        jax.tree.map(jnp.zeros_like, common.adapter_set(0, sz, "g"))))(jnp.arange(k)))

    def rows(n):
        sd = jax.ShapeDtypeStruct
        return Batch(tokens=sd((k, n, b, s), jnp.int32, sharding=chip),
                     labels=sd((k, n, b, s), jnp.int32, sharding=chip),
                     mask=sd((k, n, b, s), jnp.float32, sharding=chip),
                     patches=(sd((k, n, b, m, sz.frontend), jnp.float32, sharding=chip)
                              if m else None))

    compiled = fn.lower(_spec_tree(bb, chip), _spec_tree(adp, chip), None,
                        _spec_tree(opt, chip), None, None, rows(t), None,
                        rows(f)).compile()
    return [_report(f"round: {k} clients x ({t} steps + {f} Fisher) x "
                    f"{b} x ({s}+{m})", compiled)]


def serve_programs(cell, sz, cfg, chip, slots):
    import jax
    import jax.numpy as jnp

    from repro.models import model as model_lib
    from repro.serving import ServingEngine
    from repro.serving.kv_cache import _write_page

    tr = cell.traffic
    eng = ServingEngine(cfg, None, max_slots=1, prefill_len=tr["prefill_len"],
                        max_new_tokens=tr["output_len"]["max"],
                        adapter_slots=tr["adapter_slots"], use_pallas_grouped=True)
    bb = _spec_tree(cell.model.backbone_shapes(sz, cell.config["dtype"]), chip)
    bank = _spec_tree(eng.bank.data, chip)
    dt = model_lib.param_dtype(cfg)
    pool = _spec_tree(jax.eval_shape(
        lambda: model_lib.init_state(cfg, slots, eng.capacity, dt)), chip)
    page = _spec_tree(jax.eval_shape(
        lambda: model_lib.init_state(cfg, 1, eng.capacity, dt)), chip)
    sd = lambda shape, d: jax.ShapeDtypeStruct(shape, d, sharding=chip)
    out = []
    pre = eng._prefill_fn.lower(bb, bank, sd((), jnp.int32),
                                sd((1, tr["prefill_len"]), jnp.int32), None,
                                sd((), jnp.int32)).compile()
    out.append(_report(f"prefill: 1 x {tr['prefill_len']}", pre))
    wp = _write_page.lower(pool, page, sd((), jnp.int32)).compile()
    out.append(_report(f"page write: {slots} slots x {eng.capacity}", wp))
    dec = eng._decode_fn.lower(bb, bank, pool, sd((slots,), jnp.int32),
                               sd((slots,), jnp.int32), sd((slots,), jnp.int32)).compile()
    out.append(_report(f"decode: {slots} slots x {eng.capacity}", dec))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int)
    ap.add_argument("--slots", type=int)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import dataclasses

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import spec

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    sz = cell.model.sizes(cell.config)
    extra = {"use_pallas": True}
    if args.layers:
        sz = dataclasses.replace(sz, layers=args.layers)
        extra["n_layers"] = args.layers
    cfg = cell.model_config(**extra)
    _compile_for_chip()
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    if cell.kind == "round":
        round_programs(cell, sz, cfg, chip)
    else:
        serve_programs(cell, sz, cfg, chip, args.slots or cell.traffic["slots"])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Tiny stand-ins for the benchmark's cells, for CPU tests.

Each keeps its cell's family, attention layout (GQA or MHA, QKV bias,
M-RoPE or RoPE), adapter modalities and traffic kind, at sizes a test run
can hold: 2 layers, d_model 256, vocab 512, short sequences.

The numbers compared are the cells' own, but a tiny model's rounding is
not a full-width model's, so the limits are set for this size the way
the cells' limits are set on the chip: above what the sound program reads
(three seeds on this host's CPU) and below what the float8 control reads.
Readings (three seeds; program max / control min, and the half-batch
fault's min where the control does not read three times the program):
silo-vqa loss 6.1e-5 / 9.2e-5 (half 4.6e-3), global_delta 0.0012 /
0.0067, fisher 0.012 / 0.058 (half 1.7); xdevice loss 1.6e-4 /
7.6e-4, global_delta 0.00082 / 0.0013 (half 0.17); merge_last at most
2.6e-7 on both, where a merged update applied twice reads 1; chat-zipf
logit_gap 0.0047 / 0.035.
"""
from __future__ import annotations

import copy
import json
import os
import types

from bench import spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def _tiny_config(name: str):
    cfg = copy.deepcopy(_load("configs", name + ".json"))
    run = cfg["run"]
    heads, kv = 4, (2 if run["num_key_value_heads"] < run["num_attention_heads"] else 4)
    run.update(hidden_size=256, num_hidden_layers=2, num_attention_heads=heads,
               num_key_value_heads=kv, head_dim=64, intermediate_size=512,
               vocab_size=512)
    over = dict(cfg["overrides"], n_layers=2, d_model=256, n_heads=heads,
                n_kv_heads=kv, head_dim=64, d_ff=512, vocab_size=512)
    if run.get("mrope_section"):
        run["mrope_section"] = [8, 12, 12]
        over["mrope_sections"] = (8, 12, 12)
    if run.get("frontend_dim"):
        run["frontend_dim"] = 128
        over["frontend_dim"] = 128
    cfg["overrides"] = over
    cfg["adapter"] = dict(cfg["adapter"], rank=8, alpha=16.0)
    return cfg


TINY_LIMITS = {
    "round.qwen2-vl-72b.silo-vqa": {"loss": 3e-4, "global_delta": 0.003,
                                    "merge_last": 1e-5, "fisher": 0.03},
    "round.qwen1.5-4b.xdevice": {"loss": 4e-4, "global_delta": 0.004,
                                 "merge_last": 1e-5},
    "serve.qwen1.5-4b.chat-zipf": {"logit_gap": 0.02},
}


def tiny_cell(name: str) -> spec.Cell:
    """The named cell of BENCHMARK.json, cut to test size."""
    bench = spec.load_benchmark()
    w = {c["name"]: c for c in bench["workloads"]}[name]
    tr = copy.deepcopy(_load("traffic", w["traffic"] + ".json"))
    if tr["kind"] == "round":
        tr.update(clients=min(tr["clients"], 6), text_len=32)
        if tr["sampler"]["kind"] == "fixed":
            tr["sampler"] = {"kind": "fixed", "n": 4}
    else:
        tr.update(prefill_len=32, slots=4, rate_per_s=4.0, check_requests=4,
                  adapter_slots=4, tenants=6)
        tr["prompt_len"] = dict(tr["prompt_len"], median=12, min=4, max=32)
        tr["output_len"] = dict(tr["output_len"], median=5, min=2, max=8)
    return spec.Cell(name=name, chips=1, config=_tiny_config(w["config"]),
                     traffic=tr, limits=dict(TINY_LIMITS[name]),
                     end_to_end=[m for m in bench["end_to_end"]
                                 if name in m.get("workloads", [name])],
                     per_layer=[])


def run_tiny(cell, seed: int, seconds: float = 0.5):
    """A whole run on this host's CPU, past the look for a chip."""
    import jax

    from bench import peaks
    from bench.run import run_cell

    kind = jax.devices()[0].device_kind
    peaks.PEAKS.setdefault(kind, peaks.PEAKS["TPU v5 lite"])
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    return run_cell(cell, args, jax.devices()[:1], 0.0)

"""Find a cell's pieces by name: BENCHMARK.json, its config, traffic and limits.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a data file of its own under ``bench/``:

    bench/configs/<config>.json   sizes as run, published values, cuts
    bench/traffic/<traffic>.json  the mix's parameters; ``kind`` picks the
                                  general driver ``bench/kinds/<kind>.py``
    bench/limits/<cell>.json      the limits ``correct`` is judged by
    bench/metrics/<metric>.py     one reader per per-layer metric
    bench/models/<family>.py      the model module of every configuration
                                  whose ``family`` key names it

A family module holds one backbone architecture: its sizes, its weights
from the seed, its plain reference and its FLOP and byte counts, under the
names that ``bench/models/__init__.py`` lists. The kinds, the metric
readers and ``bench/aot.py`` reach it through ``Cell.model`` alone, and
FedNano's own pieces (adapters, AdamW, the Fisher merge) through
``bench/models/common.py``. So a new cell, configuration, architecture or
metric adds files and edits none.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import sys
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(RuntimeError):
    pass


def _load_json(path: str) -> Dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing benchmark file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def family_path(config: Dict, root: str = ROOT) -> str:
    """``<root>/bench/models/<family>.py`` of a configuration; it must exist."""
    family = config.get("family")
    if not family:
        raise SpecError(f"configuration {config.get('name')!r} names no family")
    path = os.path.join(root, "bench", "models", family + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"configuration {config.get('name')!r} names family "
                        f"{family!r}, which has no module at "
                        f"{os.path.relpath(path, root)}")
    return path


@functools.cache
def _load_family(path: str):
    family = os.path.basename(path)[:-len(".py")]
    name = "bench_family_" + family.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod      # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def family_module(config: Dict, root: str = ROOT):
    """The family module of a configuration, loaded by path once a process
    (so its jitted functions and its ``Sizes`` class are one)."""
    return _load_family(family_path(config, root))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: str = ROOT

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def model(self):
        """The module of the configuration's family (``bench/models/<family>.py``)."""
        return family_module(self.config, self.root)

    def model_config(self, **extra):
        """The program's ``ModelConfig`` as the cell runs it."""
        from repro.configs import AdapterConfig, get_config

        a = self.config["adapter"]
        kw = dict(self.config["overrides"])
        kw["adapter"] = AdapterConfig(rank=a["rank"], alpha=float(a["alpha"]),
                                      modalities=tuple(a["modalities"]))
        kw["dtype"] = self.config["dtype"]
        kw.update(extra)
        return get_config(self.config["arch"]).with_(**kw)


def load_benchmark(root: str = ROOT) -> Dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[name]
    bdir = os.path.join(root, "bench")
    config = _load_json(os.path.join(bdir, "configs", w["config"] + ".json"))
    traffic = _load_json(os.path.join(bdir, "traffic", w["traffic"] + ".json"))
    limits = _load_json(os.path.join(bdir, "limits", name + ".json"))
    family_path(config, root)

    def reports(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer, root=root)


def metric_reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"per-layer metric {name!r} has no reader at "
                        f"{os.path.relpath(path, root)}")
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, ctx) -> Dict[str, Dict]:
    """Every per-layer metric of the cell whose reader found something."""
    out = {}
    for m in cell.per_layer:
        value: Optional[float] = metric_reader(m["name"], cell.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out

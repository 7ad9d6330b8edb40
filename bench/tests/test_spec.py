"""BENCHMARK.json against the benchmark's contract, and every piece found by name."""
import ast
import json
import os
import re
import shutil

import pytest

from bench import spec

B = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in B["workloads"]]


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert len(json.dumps(B)) < 64 * 1024


def test_names_units_and_entry_keys():
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25 for m in B["end_to_end"])


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    for m in B["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert cell in e2e[m["moves"]].get("workloads", CELLS)
    for m in B["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_pieces_by_name(cell):
    c = spec.load_cell(cell)
    assert c.kind in ("round", "serve")
    assert os.path.isfile(os.path.join(spec.BENCH_DIR, "kinds", c.kind + ".py"))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
    assert c.limits and all(v > 0 for v in c.limits.values())


def _config(entry):
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("entry", B["configs"], ids=lambda c: c["name"])
def test_config_files_match_the_program_config(entry):
    cfg = _config(entry)
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key, cut in cfg["reduced"].items():
        assert cfg["published"][key] == cut["published"] and cfg["run"][key] == cut["run"]
    for key, value in cfg["published"].items():
        if key in cfg["run"] and key not in cfg["reduced"]:
            assert cfg["run"][key] == value, key
    cell = spec.Cell(cfg["name"], 1, cfg, {}, {}, [], [])
    sz = cell.model.sizes(cfg)
    m = cell.model_config()
    assert (m.d_model, m.n_layers, m.vocab_size) == (sz.d, sz.layers, sz.vocab)
    assert m.frontend_dim == sz.frontend and m.adapter.rank == sz.rank
    assert tuple(m.adapter.modalities) == sz.modalities and m.dtype == cfg["dtype"]


DENSE = [c for c in B["configs"] if _config(c)["family"] == "dense"]


@pytest.mark.parametrize("entry", DENSE, ids=lambda c: c["name"])
def test_dense_config_files_match_the_program_config(entry):
    cfg = _config(entry)
    cell = spec.Cell(cfg["name"], 1, cfg, {}, {}, [], [])
    sz = cell.model.sizes(cfg)
    m = cell.model_config()
    assert (m.n_heads, m.n_kv_heads, m.resolved_head_dim, m.d_ff) == (
        sz.heads, sz.kv_heads, sz.head_dim, sz.ff)
    assert m.rope_theta == sz.rope_theta and m.qkv_bias and not m.tie_embeddings
    assert tuple(m.mrope_sections) == sz.mrope_sections


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_family_module(cell):
    c = spec.load_cell(cell)
    want = os.path.join(spec.BENCH_DIR, "models", c.config["family"] + ".py")
    assert c.model.__file__ == want
    assert spec.load_cell(cell).model is c.model      # loaded once a process


@pytest.mark.parametrize("cell", ["round.qwen2-vl-72b.silo-vqa", "round.qwen1.5-4b.xdevice",
                                  "serve.qwen1.5-4b.chat-zipf"])
def test_the_qwen_cells_resolve_to_the_dense_module(cell):
    model = spec.load_cell(cell).model
    assert model.__file__ == os.path.join(spec.BENCH_DIR, "models", "dense.py")


@pytest.mark.parametrize("family, message", [("nope", "bench/models/nope.py"),
                                             (None, "names no family")])
def test_a_family_without_a_module_is_refused(family, message):
    cfg = {"name": "x"} if family is None else {"name": "x", "family": family}
    cell = spec.Cell("x", 1, cfg, {"kind": "round"}, {}, [], [])
    with pytest.raises(spec.SpecError, match=message):
        cell.model


FAMILIES = sorted(f[:-3] for f in os.listdir(os.path.join(spec.BENCH_DIR, "models"))
                  if f.endswith(".py") and f not in ("__init__.py", "common.py"))


def _imported(path):
    """Every module name an ``import`` in the file at ``path`` can bind."""
    rel = os.path.relpath(path, spec.ROOT)
    package = os.path.dirname(rel).replace(os.sep, ".")
    out = []
    for node in ast.walk(ast.parse(open(path).read())):
        if isinstance(node, ast.Import):
            out += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package.split(".")[:len(package.split(".")) - node.level + 1]
                base = ".".join(up + ([base] if base else []))
            out += [base] + [f"{base}.{a.name}" for a in node.names]
    return out


def test_no_harness_file_names_a_family_module():
    assert "dense" in FAMILIES
    named = {"bench.models." + f for f in FAMILIES}
    files = [os.path.join(d, f) for d, _, fs in os.walk(spec.BENCH_DIR)
             for f in fs if f.endswith(".py")
             and os.path.dirname(os.path.join(d, f)) != os.path.join(spec.BENCH_DIR, "models")]
    assert any(f.endswith(os.path.join("kinds", "round.py")) for f in files)
    for path in files:
        bad = [m for m in _imported(path)
               if any(m == n or m.startswith(n + ".") for n in named)]
        assert not bad, (os.path.relpath(path, spec.ROOT), bad)


def test_a_family_is_added_by_files_alone(tmp_path):
    """A copy of dense.py under another name, a config naming it and a tiny
    round cell, all new files in a tree of their own: the cell's counts and
    reference equal dense's to the bit."""
    import dataclasses

    import jax
    import numpy as np

    from bench import traffic_gen
    from bench.kinds import round as rk
    from bench.models import common
    from bench.tests.cells import TINY_LIMITS, _tiny_config, tiny_cell

    name, silo = "round.tiny-twin.tiny-vqa", "round.qwen2-vl-72b.silo-vqa"
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(bench / "models" / "dense.py", bench / "models" / "dense_twin.py")
    cfg = dict(_tiny_config("qwen2-vl-72b.stage"), name="tiny-twin", family="dense_twin")
    (bench / "configs" / "tiny-twin.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-vqa.json").write_text(json.dumps(tiny_cell(silo).traffic))
    (bench / "limits" / f"{name}.json").write_text(json.dumps(TINY_LIMITS[silo]))
    cells = B["workloads"] + [{"name": name, "config": "tiny-twin", "traffic": "tiny-vqa",
                               "chips": 1, "why": "a second family by files alone"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(dict(B, workloads=cells)))

    cell = spec.load_cell(name, root=str(tmp_path))
    twin = cell.model
    dense = spec.family_module(dict(cfg, family="dense"))
    assert twin.__file__ == str(bench / "models" / "dense_twin.py") and twin is not dense
    st, sd = twin.sizes(cell.config), dense.sizes(cell.config)
    assert dataclasses.asdict(st) == dataclasses.asdict(sd)
    counts = lambda m, sz: (
        m.round_flops(sz, sequences=7, text_len=32, image_len=64, loss_positions=40),
        m.prefill_cost(sz, 30), m.decode_cost(sz, [0, 5, 31]), m.weight_bytes(sz),
        m.kv_bytes_per_position(sz), m.flash_launch_cost(sz, [5, 2, 4, 128, 64], 96))
    assert counts(twin, st) == counts(dense, sd)

    tr, seed = cell.traffic, 2**33 + 5
    pop = traffic_gen.round_population(seed, st.vocab, st.frontend, tr)
    sampler = rk._sampler(tr, seed)
    cohorts = [list(sampler.select(r, sorted(pop))) for r in range(rk.CHECKED_ROUNDS)]
    start = rk._host(common.adapter_set(seed, st, "global"))
    got = rk.reference_rounds(twin, seed, st, tr, pop, cohorts, start)
    want = rk.reference_rounds(dense, seed, sd, tr, pop, cohorts, start)
    assert got["loss"] == want["loss"]
    for g, w in zip(got["global"], want["global"]):
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            np.testing.assert_array_equal(a, b)


def test_a_checkout_without_the_program_prints_no_result(tmp_path):
    import shutil
    import subprocess
    import sys

    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_without_a_chip_it_prints_no_result():
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR="")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", CELLS[0],
                        "--seed", str(2**33), "--seconds", "1", "--trace", "1"],
                       cwd=spec.ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 1 and p.stdout == ""
    assert "no TPU" in p.stderr

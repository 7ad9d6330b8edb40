"""BENCHMARK.json against the benchmark's contract, and every piece found by name."""
import json
import os
import re

import pytest

from bench import spec
from bench.models import dense

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


@pytest.mark.parametrize("entry", B["configs"], ids=lambda c: c["name"])
def test_config_files_match_the_program_config(entry):
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    for key, cut in cfg["reduced"].items():
        assert cfg["published"][key] == cut["published"] and cfg["run"][key] == cut["run"]
    for key, value in cfg["published"].items():
        if key in cfg["run"] and key not in cfg["reduced"]:
            assert cfg["run"][key] == value, key
    sz = dense.sizes(cfg)
    cell = spec.Cell(cfg["name"], 1, cfg, {}, {}, [], [])
    m = cell.model_config()
    assert (m.d_model, m.n_layers, m.n_heads, m.n_kv_heads, m.resolved_head_dim,
            m.d_ff, m.vocab_size) == (sz.d, sz.layers, sz.heads, sz.kv_heads,
                                      sz.head_dim, sz.ff, sz.vocab)
    assert m.rope_theta == sz.rope_theta and m.qkv_bias and not m.tie_embeddings
    assert tuple(m.mrope_sections) == sz.mrope_sections
    assert m.frontend_dim == sz.frontend and m.adapter.rank == sz.rank
    assert tuple(m.adapter.modalities) == sz.modalities and m.dtype == cfg["dtype"]


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

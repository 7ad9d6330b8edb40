"""The trace reduction, on a recorded excerpt and on hand-made events."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_excerpt.json")


def ev(plane, name, start, dur, line="XLA Ops"):
    return trace.Event(plane, line, name, float(start), float(dur), {})


DEV = "/device:TPU:0"


def test_busy_is_the_union_of_op_intervals():
    events = [ev(DEV, "%fusion.1 = f32[] fusion()", 0, 10),
              ev(DEV, "%fusion.2 = f32[] fusion()", 5, 10),      # overlaps
              ev(DEV, "%copy.3 = f32[] copy()", 30, 10),
              ev("/host:CPU", "bench.window", 0, 50, line="python3")]
    s = trace.reduce(events)
    assert s.window_s == pytest.approx(50e-9)
    assert s.busy_s == pytest.approx(25e-9)
    assert s.idle_share == pytest.approx(0.5)


def test_control_flow_ops_count_neither_as_busy_nor_as_op_time():
    events = [ev(DEV, "%while.7 = (s32[]) while(s32[] %p), body=%b", 0, 100),
              ev(DEV, "%fusion.1 = f32[] fusion()", 10, 20),
              ev("/host:CPU", "bench.window", 0, 100, line="python3")]
    s = trace.reduce(events)
    assert s.busy_s == pytest.approx(20e-9)
    assert all("while" not in k for k in s.op_seconds)


def test_gaps_go_to_the_innermost_harness_span():
    events = [ev(DEV, "%fusion.1 = f32[] fusion()", 0, 10),
              ev(DEV, "%fusion.2 = f32[] fusion()", 40, 10),
              ev(DEV, "%fusion.3 = f32[] fusion()", 80, 20),
              ev("/host:CPU", "bench.window", 0, 100, line="python3"),
              ev("/host:CPU", "bench.admit", 12, 25, line="python3"),
              ev("/host:CPU", "bench.step", 52, 20, line="python3")]
    s = trace.reduce(events)
    assert s.idle_by_span == pytest.approx({"bench.admit": 30e-9,
                                            "bench.step": 30e-9})


def test_devices_are_averaged():
    events = [ev(DEV, "%fusion.1 = f32[] fusion()", 0, 10),
              ev("/device:TPU:1", "%fusion.1 = f32[] fusion()", 0, 30),
              ev("/host:CPU", "bench.window", 0, 40, line="python3")]
    s = trace.reduce(events)
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx(20e-9)


def test_recorded_excerpt():
    s = trace.reduce(trace.read_events(DATA))
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(0.06)
    assert 0 < s.busy_s < s.window_s
    # the host's work between two rounds leaves the device idle
    assert set(s.idle_by_span) == {"bench.round"}
    assert s.idle_by_span["bench.round"] == pytest.approx(s.window_s - s.busy_s)
    # the Pallas kernels carry their jitted wrappers' names
    assert 0 < s.seconds_matching(["_fa_jit"]) < s.busy_s
    b = s.breakdown(top=10)
    assert len(b["device_ops"]) == 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert all("{" not in k for k, _ in b["device_ops"])   # layouts stripped


def test_flash_roofline_counts_each_launch_of_the_kernel():
    import dataclasses

    from bench import spec

    s = trace.reduce(trace.read_events(DATA))
    read = spec.metric_reader("flash_attention_roofline")
    cell = spec.load_cell("round.qwen2-vl-72b.silo-vqa")
    ctx = {"kind": "round", "trace": s, "model": cell.model,
           "sz": cell.model.sizes(cell.config), "traffic": cell.traffic,
           "device_kind": "TPU v5 lite", "chips": 1}
    # one launch in the excerpt: output bf16[5,2,64,384,128] (5 clients x 2
    # rows, 64 heads, 320 positions padded to 384), 1317.66875 us. Bytes
    # bound it: q and o 2 x 10*64*320*128*2, k and v 2 x 10*8*320*128*2,
    # LSE 10*64*320*4 = 118,784,000 B at 819 GB/s = 145.035 us (FLOPs
    # 2*10*64*320*320*128 = 1.678e10 at 197 TFLOP/s take 85.2 us)
    want = 100 * (118_784_000 / 819e9) / 1317.66875e-6
    fa = [e for e in s.ops if e.name.startswith("%_fa_jit")]
    assert len(fa) == 1
    assert read(ctx) == pytest.approx(want)
    # a second launch (remat's forward again) counts on both sides
    s.ops.append(dataclasses.replace(fa[0], start_ns=fa[0].end_ns))
    assert read(ctx) == pytest.approx(want)


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.reduce([ev("/host:CPU", "bench.window", 0, 10, line="python3")])

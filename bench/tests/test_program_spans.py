"""The reduction of the program's spans, on hand-made events."""
import pytest

from bench import program_spans as ps
from bench import trace

DEV, HOST = "/device:TPU:0", "/host:CPU"


def op(start, dur, name="%fusion.1 = f32[] fusion()"):
    return trace.Event(DEV, "XLA Ops", name, float(start), float(dur), {})


def sp(name, start, end, **stats):
    return trace.Event(HOST, "python3", name, float(start), float(end - start), stats)


def decode_step():
    """One traced decode step: the device runs while the host waits."""
    return [op(0, 10), op(22, 36), op(85, 15),
            sp("bench.window", 0, 100),
            sp("bench.step", 10, 90),
            sp("fednano.serve.decode", 12, 80, step=0, live=3),
            sp("fednano.serve.decode.dispatch", 12, 20),
            sp("fednano.serve.decode.wait", 20, 60),
            sp("fednano.serve.decode.bookkeep", 60, 70)]


def test_a_gap_after_a_child_ends_goes_to_its_parent():
    events = decode_step()
    got = ps.idle_by_span(events)
    # gaps: [10, 22) at 16 inside dispatch; [58, 85) at 71.5, after the
    # bookkeeping ended, inside the decode step
    assert got["idle_by_span"] == pytest.approx({
        "fednano.serve.decode": 27e-9, "fednano.serve.decode.dispatch": 12e-9})
    # the harness's rule, given the same spans, gives the second gap to the
    # window: bookkeep started last, and had ended
    assert trace.reduce(events).idle_by_span == pytest.approx({
        "fednano.serve.decode.dispatch": 12e-9, "bench.window": 27e-9})
    s = trace.reduce(events)
    assert got["busy_s"] == pytest.approx(s.busy_s)
    assert got["window_s"] == pytest.approx(s.window_s)
    assert ps.program_idle_share(got["idle_by_span"]) == pytest.approx(100.0)


def test_gaps_outside_every_span_go_to_the_window():
    events = [op(0, 10), op(50, 50), sp("bench.window", 0, 100),
              sp("fednano.round", 12, 20)]
    assert ps.idle_by_span(events)["idle_by_span"] == pytest.approx(
        {"bench.window": 40e-9})
    assert ps.program_idle_share({"bench.window": 1.0}) == 0.0
    assert ps.program_idle_share({}) is None


def rounds():
    """Two rounds in the window, and one outside it that must not count."""
    ev = [op(0, 1), sp("bench.window", 0, 1000)]
    for i, (a, wait) in enumerate([(100, 300), (500, 100), (1100, 50)]):
        ev += [sp("fednano.round", a, a + 400, round=i, clients=4),
               sp("fednano.round.prepare", a, a + 50, clients=4,
                  bytes_to_device=1_000_000 * (i + 1), bytes_to_host=0),
               sp("fednano.round.launch", a + 50, a + 60),
               sp("fednano.round.wait", a + 60, a + 60 + wait),
               sp("fednano.round.unstack", a + 60 + wait, a + 80 + wait,
                  bytes_to_host=2_000_000),
               sp("fednano.round.merge", a + 80 + wait, a + 90 + wait,
                  bytes_to_device=500_000)]
    return ev


def test_round_readings():
    ev = rounds()
    # host time per round: 400 - 300 and 400 - 100 ns; the median of two
    assert ps.round_host_ms(ev) == pytest.approx(1e-6 * (100 + 300) / 2)
    # (1 + 2 + 0.5) MB and (2 + 2 + 0.5) MB
    assert ps.host_transfer_mb(ev) == pytest.approx((3.5 + 4.5) / 2)
    assert ps.decode_host_ms(ev) is None
    assert ps.adapter_miss_share(ev) is None


def test_serve_readings():
    ev = decode_step() + [
        sp("fednano.serve.adapter", 1, 2, hit=1, miss=0, evicted=0),
        sp("fednano.serve.adapter", 3, 4, hit=0, miss=1, evicted=1),
        sp("fednano.serve.adapter", 5, 6, hit=0, miss=0, evicted=0),  # no tenant
        sp("fednano.serve.adapter", 7, 8, hit=1, miss=0, evicted=0),
        sp("fednano.serve.adapter", 200, 201, hit=0, miss=1, evicted=0)]  # outside
    assert ps.adapter_miss_share(ev) == pytest.approx(100 / 3)
    # 68 ns of step less its 40 ns wait
    assert ps.decode_host_ms(ev) == pytest.approx(28e-6)
    assert ps.round_host_ms(ev) is None


def test_summary_and_excerpt():
    ev = rounds()[:2] + [op(5, 1), op(7, 1), op(300, 5), op(306, 2)] + rounds()[2:]
    s = ps.summarize(ev)
    assert s["spans"]["fednano.round"] == 3
    assert s["round_host_ms"] == ps.round_host_ms(ev)
    assert s["device_ops_per_s"] == [5]        # a 1 us window: one bucket
    cut = ps.excerpt(ev, before=2, after=2)
    # the longest gap is [8, 300): two ops either side of it, a window of
    # their own, host spans cut to it
    assert [e.start_ns for e in cut if e.plane == DEV] == [5, 7, 300, 306]
    win = [e for e in cut if e.name == trace.WINDOW_SPAN]
    assert [(w.start_ns, w.end_ns) for w in win] == [(5.0, 308.0)]
    assert all(5 <= e.start_ns and e.end_ns <= 308 for e in cut)


def test_recorded_excerpt_puts_idle_down_to_program_spans():
    """A chip excerpt of a traced xdevice round boundary (bench/program_spans.py
    --excerpt): the device sits idle while the round engine works on the
    host, and the program's spans name that time."""
    import os

    events = trace.read_events(os.path.join(os.path.dirname(__file__), "data",
                                            "program_trace_excerpt.json"))
    got = ps.idle_by_span(events)
    s = trace.reduce(events)
    # busy is the union of the op intervals, as the harness computes it
    ops = [e for e in events if e.plane == DEV and e.opcode not in trace.CONTROL_OPS]
    union = trace._union((e.start_ns, e.end_ns) for e in ops)
    assert got["busy_s"] == pytest.approx(sum(b - a for a, b in union) * 1e-9)
    assert got["busy_s"] == pytest.approx(s.busy_s)
    assert got["window_s"] == pytest.approx(s.window_s)
    idle = got["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    assert ps.program_idle_share(idle) >= 80.0
    # every program span carries the arguments the program gave it
    rounds = [e for e in events if e.name.startswith("fednano.round.")
              and e.name.rsplit(".", 1)[1] in ("prepare", "unstack", "merge")]
    assert rounds and all(any(k.startswith("bytes_to_") for k in e.stats)
                          for e in rounds)

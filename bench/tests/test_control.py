"""The control, kept as a test at a size a test run can hold.

The plain reference computed with float8 (e4m3) weights stands in the
program's place; judged by the same numbers, it must come out not
correct, in every cell. (On the chip, at the cells' own sizes, see
``bench/control.py`` and PERF.md for the readings the limits came from.)
"""
import pytest

from bench import control, harness
from bench.tests.cells import tiny_cell

SEED = 3 * 2**31 + 7


@pytest.mark.parametrize("name", ["round.qwen2-vl-72b.silo-vqa",
                                  "round.qwen1.5-4b.xdevice"])
def test_round_control_is_not_correct(name):
    cell = tiny_cell(name)
    rows = {r["stand_in"]: r for r in control.round_rows(cell, SEED)}
    ok, checks = harness.judge(rows["control_fp8"], cell.limits)
    assert not ok, checks
    ok, checks = harness.judge(rows["half"], cell.limits)
    assert not ok, checks


def test_serve_control_is_not_correct():
    cell = tiny_cell("serve.qwen1.5-4b.chat-zipf")
    rows = {r["stand_in"]: r for r in control.serve_rows(cell, SEED, 3.0)}
    assert harness.judge(rows["program"], cell.limits)[0]
    ok, checks = harness.judge(rows["control_fp8"], cell.limits)
    assert not ok, checks

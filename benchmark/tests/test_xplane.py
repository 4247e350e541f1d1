"""The trace reduction against a small trace recorded on a v5e
(``tests/record_trace.py``: three 51 us steps, a 20 ms sleep after each)
and against hand-made intervals.

    python -m pytest benchmark/tests/test_xplane.py
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

from lib import xplane  # noqa: E402

TRACE = os.path.join(HERE, "tests", "data", "probe.xplane.pb")


def test_union_counts_overlap_once():
    assert xplane.union_seconds([(0, 4), (2, 6), (10, 11)]) == 7
    assert xplane.union_seconds([]) == 0


def test_self_time_takes_out_nested_events():
    # a while of 10 holding two body operations of 3 and 4
    events = [(0, 10, "while"), (1, 4, "a"), (5, 9, "b"), (12, 13, "c")]
    assert dict(xplane.self_times(events)) == {
        "while": 3, "a": 3, "b": 4, "c": 1}


def test_gaps_are_the_complement_inside_the_window():
    assert xplane.gaps_of([(2, 3), (5, 7)], 0, 10) == [(0, 2), (3, 5), (7, 10)]
    assert xplane.gaps_of([(0, 10)], 0, 10) == []


@pytest.mark.parametrize("text, name, code", [
    ("%fusion.4 = bf16[8,128]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[8]{0} %p)",
     "fusion.4", "fusion"),
    ("%step.1 = (bf16[2,4]{1,0:T(8,128)(2,1)}, f32[2]{0}) custom-call(%c), "
     "custom_call_target=\"tpu_custom_call\"", "step.1", "custom-call"),
    ("%ag = bf16[4]{0} all-gather-start(bf16[2]{0} %x)", "ag",
     "all-gather-start"),
])
def test_instruction_and_opcode(text, name, code):
    assert xplane.instruction(text) == name
    assert xplane.opcode(text) == code


def test_collectives_are_told_from_compute():
    assert xplane.is_collective("%ag = bf16[4]{0} all-gather-done(%s)")
    assert xplane.is_collective("%cp = f32[2]{0} collective-permute(%s)")
    assert not xplane.is_collective("%f = f32[2]{0} fusion(%s)")


def test_recorded_trace_reduces_to_what_was_run():
    r = xplane.reduce(TRACE)
    assert len(r["devices"]) == 1
    # three steps of 51.7 us by the trace's own module events
    assert 150e-6 < r["busy_s"] < 160e-6
    # the window held three 20 ms sleeps
    assert 0.060 < r["window_s"] < 0.075
    ops = dict(r["breakdown"]["device_ops"])
    top = max(ops, key=ops.get)
    assert top.endswith(":custom-call")          # the flash kernel
    assert 70e-6 < ops[top] < 75e-6              # 3 x 24.08 us
    idle = dict(r["breakdown"]["idle_gaps"])
    assert max(idle, key=idle.get) == "bench/probe.sleep"
    assert abs(sum(idle.values()) + r["busy_s"] - r["window_s"]) < 1e-6
    seconds, count = xplane.op_seconds(
        r, lambda t: xplane.opcode(t) == "custom-call")
    assert count == 3 and abs(seconds - ops[top]) < 1e-9

"""The four readers of the program's own tick spans: on a hand-made ring and
hand-made ``ticks_seen`` with the numbers worked by hand, and on one run of
the tiny serving preset on the CPU.

    python -m pytest benchmark/tests/test_program_spans.py
"""

import collections
import importlib
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

from apex_tpu.observability import spans    # noqa: E402
from drivers.serve import Tick              # noqa: E402
import run as harness                       # noqa: E402

READERS = ("decode_host_ms_p50", "decode_call_ms_p50", "prefill_call_ms_p50",
           "prefill_fill_share")
PRESETS = os.path.join(HERE, "tests", "presets")


def read(name, view):
    return importlib.import_module("metrics." + name).read(view)


def made(name, start, end, ident, parent=0, **fields):
    s = spans.span(name, **fields)
    s.start, s.end, s.id, s.parent = start, end, ident, parent
    return s


def tick(ident, start, phases, **fields):
    """One ``serving/tick`` from ``start`` (seconds) whose phases, given in
    milliseconds, follow each other 1 ms apart."""
    fields = dict({"prefill_tokens": 0, "prefill_capacity": 0}, **fields)
    out, at = [], start + 0.001
    for i, (phase, ms) in enumerate(phases):
        out.append(made("serving/tick/" + phase, at, at + ms / 1e3,
                        ident + 1 + i, parent=ident))
        at += ms / 1e3 + 0.001
    return out + [made("serving/tick", start, at, ident, **fields)]


PLAIN = (("admit", 1.0), ("decode_plan", 2.0), ("decode_dispatch", 10.0),
         ("decode_fetch", 200.0), ("deliver", 3.0))
CHUNK = (("admit", 1.0), ("prefill_plan", 4.0), ("prefill_dispatch", 20.0),
         ("prefill_fetch", 330.0), ("prefill_deliver", 1.0),
         ("decode_plan", 2.0), ("decode_dispatch", 12.0),
         ("decode_fetch", 208.0), ("deliver", 3.0))


@pytest.fixture
def ring(monkeypatch):
    made_ring = collections.deque(maxlen=64)
    monkeypatch.setattr(spans, "_RING", made_ring)
    return made_ring


def view_of(ticks):
    return {"observed": {"ticks_seen": ticks}}


def test_readers_by_hand(ring):
    # warm-up: a compiling first tick and a plain one, before the window
    ring.extend(tick(10, 0.0, (("admit", 1.0), ("decode_plan", 900.0),
                               ("decode_dispatch", 4000.0),
                               ("decode_fetch", 50.0), ("deliver", 1.0))))
    ring.extend(tick(20, 6.0, PLAIN))
    # the window: plain, chunk, plain, plain with a slower host
    ring.extend(tick(30, 10.0, PLAIN))
    ring.extend(tick(40, 11.0, CHUNK, prefill_tokens=200,
                     prefill_capacity=16384))
    ring.extend(tick(50, 12.0, PLAIN))
    slow = (("admit", 5.0),) + PLAIN[1:3] + (("decode_fetch", 190.0),
                                             ("deliver", 9.0))
    ring.extend(tick(60, 13.0, slow))
    ring.extend(tick(70, 14.0, CHUNK, prefill_tokens=56,
                     prefill_capacity=16384))
    # and one tick after it
    ring.extend(tick(80, 20.0, PLAIN))
    seen = [Tick(10.25, 260.0, False, 64, []), Tick(11.7, 750.0, True, 64, []),
            Tick(12.3, 350.0, False, 64, []), Tick(13.3, 350.0, False, 64, []),
            Tick(14.65, 640.0, True, 64, [])]
    view = view_of(seen)
    # a plain tick: 1 + 2 + 10 + 200 + 3 ms of phases and 6 gaps of 1 ms;
    # host = 222 - 210 = 12; the slow one: 5 + 2 + 10 + 190 + 9 + 6 = 222,
    # host 22; median of (12, 12, 22)
    assert read("decode_host_ms_p50", view) == pytest.approx(12.0)
    assert read("decode_call_ms_p50", view) == pytest.approx(210.0)
    assert read("prefill_call_ms_p50", view) == pytest.approx(350.0)
    assert read("prefill_fill_share", view) == pytest.approx(
        100 * 256 / 32768)
    # a window of plain ticks has no prefill call to read
    view = view_of(seen[2:4])
    assert read("decode_host_ms_p50", view) == pytest.approx(17.0)
    assert read("decode_call_ms_p50", view) == pytest.approx(205.0)
    assert read("prefill_call_ms_p50", view) is None
    assert read("prefill_fill_share", view) is None


def test_nothing_recorded_reads_nothing(ring, monkeypatch):
    seen = [Tick(10.25, 260.0, False, 64, [])]
    for name in READERS:
        assert read(name, view_of(seen)) is None        # an empty ring
    ring.extend(tick(30, 10.0, PLAIN))
    for name in READERS:
        assert read(name, view_of([])) is None          # no tick seen
    # a program from before the ring
    monkeypatch.delattr(spans, "recorded")
    for name in READERS:
        assert read(name, view_of(seen)) is None


def test_tiny_serving_run_gives_all_four():
    import jax

    bench = harness.load_json(PRESETS, "BENCHMARK.json")
    full = harness.load_json(ROOT, "BENCHMARK.json")
    bench["end_to_end"], bench["per_layer"] = full["end_to_end"], []
    cell = harness.Cell(bench, "gpt-tiny.serve", 2 ** 31 + 5, 1.0, False,
                        jax.devices()[:1], root=PRESETS, data=PRESETS)
    out = cell.driver.run(cell)
    view = {"observed": out["observed"]}
    values = {name: read(name, view) for name in READERS}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["prefill_fill_share"] <= 100.0
    # the program's split of a plain tick adds up to the harness's own
    # timing of it, which lies round the program's span
    ticks = out["observed"]["ticks_seen"]
    plain = sorted(t.ms for t in ticks if not t.prefill)
    assert values["decode_host_ms_p50"] + values["decode_call_ms_p50"] \
        <= plain[-1]
    # every tick of the window is in the ring, none from warm-up
    from lib import program_spans
    assert len(program_spans.window_ticks(view)) == len(ticks)
    declared = {m["name"]: m for m in full["per_layer"]}
    for name in READERS:
        assert declared[name]["workloads"] == ["gpt2-medium.serve-decode"]

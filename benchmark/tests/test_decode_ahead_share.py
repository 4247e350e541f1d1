"""The reader of ``decode_ahead_share`` (ISSUE 34) on hand-made spans, on a
program whose spans lack the field, and on one run of the tiny ``mimo``
preset on the CPU.

    python -m pytest benchmark/tests/test_decode_ahead_share.py
"""

import collections
import importlib
import json
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

NAME = "decode_ahead_share"


def read(view):
    return importlib.import_module("metrics." + NAME).read(view)


def made(name, start, end, ident, parent=0, **fields):
    s = spans.span(name, **fields)
    s.start, s.end, s.id, s.parent = start, end, ident, parent
    return s


def tick(ident, start, dispatch, decode=True):
    """One ``serving/tick`` of 60 ms at ``start``; with ``decode`` its three
    decode phases, the dispatch span holding the fields ``dispatch``."""
    phases = [("admit", {})]
    if decode:
        phases += [("decode_plan", {"kv_tokens": 5}),
                   ("decode_dispatch", dispatch), ("decode_fetch", {})]
    out, at = [], start + 0.001
    for i, (phase, fields) in enumerate(phases):
        out.append(made("serving/tick/" + phase, at, at + 0.01,
                        ident + 1 + i, parent=ident, **fields))
        at += 0.011
    return out + [made("serving/tick", start, start + 0.06, ident)]


@pytest.fixture
def ring(monkeypatch):
    made_ring = collections.deque(maxlen=64)
    monkeypatch.setattr(spans, "_RING", made_ring)
    return made_ring


def view_of(starts):
    return {"observed": {"ticks_seen": [Tick(t + 0.06, 60.0, False, 64, [])
                                        for t in starts]}}


def test_the_share_of_dispatches_that_ran_ahead(ring):
    # warm-up, outside the window: not counted
    ring.extend(tick(10, 5.0, {"ahead": 0}))
    # the window: a call after a settle, three ahead, a tick with no decode
    ring.extend(tick(20, 10.0, {"ahead": 0}))
    for i, start in enumerate((11.0, 12.0, 13.0)):
        ring.extend(tick(30 + 10 * i, start, {"ahead": 1}))
    ring.extend(tick(60, 14.0, {}, decode=False))
    assert read(view_of([10.0, 11.0, 12.0, 13.0, 14.0])) \
        == pytest.approx(75.0)
    assert read(view_of([11.0, 12.0])) == pytest.approx(100.0)
    assert read(view_of([10.0])) == 0.0     # read, and none ran ahead
    assert read(view_of([14.0])) is None    # no decode call in the window


def test_a_program_whose_spans_lack_the_field_reads_nothing(ring):
    """The parent of ISSUE 34, and the uniform lowering after it: a
    ``decode_dispatch`` span without ``ahead`` is nothing to read, never 0."""
    for i, start in enumerate((10.0, 11.0)):
        ring.extend(tick(10 + 10 * i, start, {}))
    view = view_of([10.0, 11.0])
    assert read(view) is None
    assert read(view_of([])) is None
    ring.clear()
    assert read(view) is None


def test_the_cells_that_report_it_are_the_two_with_cache_groups():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "decode_tokens_per_s",
        "workloads": ["mimo-v2-flash.serve-long-answer",
                      "deepseek-v2.serve-long-context"]}
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:-1]}


def test_a_tiny_run_of_the_engine_reads_nearly_all_ahead():
    """The tiny ``mimo`` preset through the cell's own driver: a correct
    run, and all but the calls after a prompt's settle ran ahead."""
    import jax

    from lib import check

    presets = os.path.join(HERE, "tests", "presets_mimo")
    bench = harness.load_json(presets, "BENCHMARK.json")
    bench["end_to_end"], bench["per_layer"] = [], []
    cell = harness.Cell(bench, "mimo-tiny.serve", 2 ** 31 + 7, 0.4, False,
                        jax.devices()[:1], root=presets, data=presets)
    out = cell.driver.run(cell)
    assert check.passed(out["compared"]), out["compared"]
    share = read({"observed": out["observed"]})
    assert share is not None and 80.0 <= share <= 100.0, share

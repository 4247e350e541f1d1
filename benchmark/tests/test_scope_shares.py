"""The readers of device time by layer (ISSUE 35) on a hand-made trace and
hand-made scope tables, on a program that registers none, and on the
tables the tiny ``mimo`` preset registers on the CPU (what it serves is
``test_mimo_cell.py``'s to check).

    python -m pytest benchmark/tests/test_scope_shares.py
"""

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
from metrics import _scopes                 # noqa: E402
import run as harness                       # noqa: E402

NAMES = ("decode_named_time_share", "sampling_time_share",
         "dense_time_share", "router_time_share")
SERVING = ["gpt2-medium.serve-decode", "mimo-v2-flash.serve-long-answer",
           "deepseek-v2.serve-long-context"]


def read(name, view):
    return importlib.import_module("metrics." + name).read(view)


def event(name, opcode, operands=("x.1",), shape="bf16[64,4096]{1,0}",
          more=""):
    """An event's name as the profiler writes it: sigils, and each
    operand's shape before it."""
    args = ", ".join(f"bf16[64,4096]{{1,0:T(8,128)(2,1)}} %{o}"
                     for o in operands)
    return f"%{name} = {shape} {opcode}({args}){more}"


def line(name, opcode, operands=("x.1",), shape="bf16[64,4096]{1,0}",
         more="", scope=None):
    """The same instruction as a module's text lists it."""
    args = ", ".join("%" + o for o in operands)
    path = f"jit(step)/apex/{scope}/mul" if scope else "mul"
    return (f"  %{name} = {shape} {opcode}({args}){more}, "
            f'metadata={{op_name="{path}" stack_frame_id=3}}, '
            'backend_config={"flag_configs":[],"window_config":{"a":"}"}}')


def module(*lines):
    return "\n".join(("HloModule jit_step, is_scheduled=true", "",
                      "ENTRY %main.1 (x.1: bf16[64,4096]) -> bf16[64] {")
                     + lines + ("}", ""))


CALL = ', custom_call_target="tpu_custom_call"'
DECODE = module(
    line("fusion.1", "fusion", scope="attn_proj"),
    line("fusion.2", "fusion", scope="sample"),
    line("sort", "sort", scope="sample"),
    line("fusion.3", "fusion", scope="moe_router"),
    line("fusion.4", "fusion", scope="moe_experts"),
    line("moe_experts.7", "custom-call", more=CALL, scope="moe_experts"),
    line("paged_decode_full.2", "custom-call", more=CALL,
         scope="paged_decode_full"),
    line("fusion.5", "fusion", scope="lm_head"),
    line("fusion.6", "fusion", scope="norm"),
    line("copy.9", "copy"))
PREFILL = module(
    # the same name, another scope, another instruction
    line("fusion.1", "fusion", operands=("x.1", "y.2"), scope="dense_ffn"),
    # the same name and scope: nothing to settle
    line("fusion.6", "fusion", shape="bf16[8,4096]{1,0}", scope="norm"),
    # the same name, another scope, the same instruction
    line("fusion.5", "fusion", scope="embed"),
    # the same name, with no scope here
    line("fusion.2", "fusion", shape="f32[64]{0}"))

# own seconds; a tenth of the busy time each
OPS = {
    event("fusion.1", "fusion"): 0.1,                           # attn_proj
    event("fusion.1", "fusion", operands=("x.1", "y.2")): 0.1,  # dense_ffn
    event("fusion.2", "fusion"): 0.05,                          # sample
    event("sort", "sort"): 0.05,                                # sample
    event("fusion.3", "fusion"): 0.1,                           # moe_router
    event("fusion.4", "fusion"): 0.1,                           # moe_experts
    event("moe_experts.7", "custom-call", more=CALL): 0.1,
    event("paged_decode_full.2", "custom-call", more=CALL): 0.1,
    event("fusion.5", "fusion"): 0.1,                           # ambiguous
    event("fusion.6", "fusion"): 0.05,                          # norm
    event("copy.9", "copy"): 0.05,                              # no scope
    event("fusion.77", "fusion"): 0.1,                          # no program
}


def view_of(ops):
    chip = {"busy_s": 1.0, "ops": {t: [s, 3] for t, s in ops.items()}}
    idle = {"busy_s": 0.2, "ops": {event("fusion.1", "fusion"): [0.2, 1]}}
    return {"trace": {"devices": [idle, chip]}}


@pytest.fixture
def programs(monkeypatch):
    kept = {}
    monkeypatch.setattr(spans, "_PROGRAMS", kept)
    return kept


def test_seconds_by_scope_and_the_four_shares(programs):
    programs.update({"serving/decode": spans.scopes_of(DECODE),
                     "serving/prefill": spans.scopes_of(PREFILL)})
    seconds = _scopes.by_scope(view_of(OPS))
    assert {k: round(v, 6) for k, v in seconds.items()} == {
        "attn_proj": 0.1, "dense_ffn": 0.1, "sample": 0.1,
        "moe_router": 0.1, "moe_experts": 0.2, "paged_decode_full": 0.1,
        "ambiguous": 0.1, "norm": 0.05, None: 0.15}
    # every second of the busiest chip is somewhere
    assert sum(seconds.values()) == pytest.approx(1.0)
    view = view_of(OPS)
    assert read("decode_named_time_share", view) == pytest.approx(75.0)
    assert read("sampling_time_share", view) == pytest.approx(10.0)
    assert read("dense_time_share", view) == pytest.approx(20.0)
    # the router and what of the experts' scope is not their kernel
    assert read("router_time_share", view) == pytest.approx(20.0)


def test_a_shared_name_is_settled_by_scope_then_text_or_is_ambiguous(
        programs):
    programs.update({"serving/decode": spans.scopes_of(DECODE),
                     "serving/prefill": spans.scopes_of(PREFILL)})
    tables = spans.program_scopes()
    assert _scopes.scope_of(event("fusion.1", "fusion"), tables) \
        == "attn_proj"
    assert _scopes.scope_of(event("fusion.1", "fusion",
                                  operands=("x.1", "y.2")), tables) \
        == "dense_ffn"
    # neither program's instruction
    assert _scopes.scope_of(event("fusion.1", "fusion", operands=("z.9",)),
                            tables) == _scopes.AMBIGUOUS
    # both programs' instruction
    assert _scopes.scope_of(event("fusion.5", "fusion"), tables) \
        == _scopes.AMBIGUOUS
    # one scope in both: the text is not asked
    assert _scopes.scope_of(event("fusion.6", "fusion",
                                  shape="bf16[1,1]{1,0}"), tables) == "norm"
    # a scope in one program and none in the other are two answers
    assert _scopes.scope_of(event("fusion.2", "fusion"), tables) == "sample"
    assert _scopes.scope_of(event("fusion.2", "fusion", shape="f32[64]{0}"),
                            tables) is None
    assert _scopes.scope_of(event("fusion.77", "fusion"), tables) is None


def test_a_program_that_registers_nothing_reads_nothing(programs,
                                                        monkeypatch):
    view = view_of(OPS)
    assert _scopes.by_scope(view) is None
    assert [read(n, view) for n in NAMES] == [None] * 4
    # the parent of ISSUE 35: no tables in the program at all
    monkeypatch.delattr(spans, "program_scopes")
    assert [read(n, view) for n in NAMES] == [None] * 4


def test_a_cell_without_experts_reads_no_router_time(programs):
    programs["serving/decode"] = spans.scopes_of(DECODE)
    ops = {t: s for t, s in OPS.items() if "moe" not in t
           and "fusion.3" not in t and "fusion.4" not in t}
    assert read("router_time_share", view_of(ops)) == 0.0
    assert read("sampling_time_share", view_of(ops)) == pytest.approx(10.0)


def test_benchmark_declares_the_four_on_the_serving_cells():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    entries = bench["per_layer"][-4:]
    assert [m["name"] for m in entries] == list(NAMES)
    layers = {m["layer"] for m in bench["per_layer"][:-4]}
    for m in entries:
        assert (m["unit"], m["source"], m["moves"]) == (
            "%", "device_trace", "decode_tokens_per_s")
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]
        assert m["layer"] in layers
    assert [m["better"] for m in entries] == ["higher", "lower", "lower",
                                              "lower"]
    assert [m["layer"] for m in entries] == ["serving engine"] * 3 + [
        "router"]
    assert [m["workloads"] for m in entries] == [SERVING] * 3 + [SERVING[1:]]


def test_a_tiny_run_registers_tables_that_outlive_the_engine(programs):
    """The tiny ``mimo`` preset through the cell's own driver, which drops
    the engine and clears JAX's caches before the reference runs: both
    programs' tables are there afterwards, and the catalog is in them."""
    import jax

    presets = os.path.join(HERE, "tests", "presets_mimo")
    bench = harness.load_json(presets, "BENCHMARK.json")
    bench["end_to_end"], bench["per_layer"] = [], []
    cell = harness.Cell(bench, "mimo-tiny.serve", 2 ** 31 + 7, 0.4, False,
                        jax.devices()[:1], root=presets, data=presets)
    out = cell.driver.run(cell)
    assert out["observed"]["tokens"] > 0
    # nobody asked yet: no table was built, nothing was compiled for one
    assert not any(isinstance(kept, dict) for kept in programs.values())
    tables = spans.program_scopes()
    assert sorted(tables) == ["serving/decode", "serving/prefill"]
    for table in tables.values():
        scopes = {scope for scope, _ in table.values()}
        assert {"embed", "norm", "attn_proj", "rope", "cache_write",
                "attention", "dense_ffn", "moe_router", "moe_experts",
                "lm_head", "sample"} <= scopes
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        assert set(NAMES) <= {m["name"] for m in json.load(f)["per_layer"]}

"""Device time by layer (ISSUE 35): ``spans.scopes_of`` on small programs
(nested scopes, a ``jit`` inside a scope, ``grad``, ``checkpoint``, a scan),
the two programs each kind of tiny serving engine registers (every catalog
name, nine instructions in ten under a scope, nothing kept alive), and the
scopes as metadata only: the programs are the same text with and without.

CPU, tiny engines; Pallas kernels run in the interpreter.
"""

import collections
import contextlib
import gc
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from drivers import deepseek_program, mimo_program         # noqa: E402
from reference import deepseek_v2, mimo_v2_flash           # noqa: E402

from apex_tpu import parallel                              # noqa: E402
from apex_tpu.observability import spans                   # noqa: E402
from apex_tpu.observability.metrics import MetricRegistry  # noqa: E402
from apex_tpu.serving import ServingConfig, ServingEngine  # noqa: E402
from apex_tpu.serving import model as serving_model        # noqa: E402
from apex_tpu.serving import paged_attention               # noqa: E402
from apex_tpu.transformer import moe                       # noqa: E402
from apex_tpu.transformer.testing import TransformerConfig  # noqa: E402
from apex_tpu.transformer.testing.gpt_parallel_train import (  # noqa: E402
    build_gpt_3d,
)

# --------------------------------------------------------------- the parser


def table_of(fn, *args):
    return spans.scopes_of(jax.jit(fn).lower(*args).compile().as_text())


def scopes_by_opcode(table):
    """``{opcode: {scopes}}``; an instruction's opcode is the word before
    its operands."""
    out = collections.defaultdict(set)
    for scope, text in table.values():
        found = spans._OPERANDS.search(text, text.find(" = "))
        out[found.group(0).strip(" (") if found else ""].add(scope)
    return out


@jax.jit
def _sorted(x):
    return jnp.sort(x, axis=-1)


def layers(x, w):
    with spans.named_span("outer"):
        y = jnp.tanh(x @ w)
        with spans.named_span("inner"):
            z = _sorted(y) @ w              # a jit inside two scopes
    with spans.named_span("zero/reduce_scatter/bucket3"):
        z = jnp.cos(z)
    return z @ w                            # under no scope


def test_innermost_scope_of_nested_scopes_and_of_a_jit_inside_one():
    x = jnp.ones((8, 16))
    table = table_of(layers, x, jnp.ones((16, 16)))
    by_op = scopes_by_opcode(table)
    assert by_op["sort"] == {"inner"}
    assert by_op["dot"] == {"outer", "inner", None}
    # a name of several parts reads as its first
    assert by_op["cosine"] == {"zero"}
    assert {scope for scope, _ in table.values()} == {
        "outer", "inner", "zero", None}
    # the text is the event's: no sigil, no metadata, operands by name
    name, (_, text) = next(
        (n, v) for n, v in table.items() if v[1].startswith("sort"))
    assert text.startswith(f"{name} = ") and "metadata" not in text
    assert "%" not in text


def test_scope_is_found_through_grad_checkpoint_and_scan():
    def loss(x, w):
        def body(carry, _):
            with spans.named_span("layer"):
                carry = jnp.tanh(carry @ w)
            return carry, None

        with spans.named_span("stack"):
            y, _ = jax.lax.scan(jax.checkpoint(body), x, None, length=3)
        with spans.named_span("head"):
            return jnp.sum(y * y)

    x, w = jnp.ones((4, 8)), jnp.ones((8, 8)) * 0.1
    table = table_of(jax.grad(loss, argnums=1), x, w)
    by_op = scopes_by_opcode(table)
    # forward, recomputed and transposed products alike
    assert by_op["dot"] == {"layer"} and len(
        [1 for _, t in table.values() if " dot(" in t]) >= 3
    assert "stack" in by_op["while"]
    assert "head" in {scope for scope, _ in table.values()}


LOOP = """HloModule jit_step, is_scheduled=true

%adder (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a.1, %b.1), metadata={op_name="reduce_sum"}
}

%fused_scatter (p.0: f32[8], p.1: f32[8]) -> f32[8] {
  %p.0 = f32[8]{0} parameter(0)
  %p.1 = f32[8]{0} parameter(1)
  %convert.1 = f32[8]{0} convert(%p.1), metadata={op_name="jit(step)/apex/layer_scan/while/body/apex/cache_write/convert_element_type"}
  %negate.1 = f32[8]{0} negate(%convert.1), metadata={op_name="jit(step)/apex/layer_scan/while/body/apex/cache_write/neg"}
  %abs.1 = f32[8]{0} abs(%negate.1), metadata={op_name="jit(step)/apex/layer_scan/while/body/apex/rope/abs"}
  ROOT %scatter.1 = f32[8]{0} add(%p.0, %abs.1)
}

%body.2 (arg.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.3 = f32[8]{0} get-tuple-element(%arg.1), index=1
  %copy.4 = f32[8]{0} copy(%get-tuple-element.3)
  %fusion.5 = f32[8]{0} fusion(%copy.4, %copy.4), kind=kCustom, calls=%fused_scatter
  %tanh.5 = f32[8]{0} tanh(%copy.4), metadata={op_name="jit(step)/apex/layer_scan/while/body/apex/norm/tanh"}
  %reduce.6 = f32[] reduce(%tanh.5, %c.0), dimensions={0}, to_apply=%adder, metadata={op_name="jit(step)/apex/layer_scan/while/body/apex/norm/reduce_sum"}
  ROOT %tuple.7 = (s32[], f32[8]{0}) tuple(%get-tuple-element.3, %tanh.5)
}

%cond.3 (arg.2: (s32[], f32[8])) -> pred[] {
  %arg.2 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.8 = pred[] compare(%arg.2, %arg.2), direction=LT
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %copy.1 = f32[8]{0} copy(%x.1)
  %while.2 = (s32[], f32[8]{0}) while(%copy.1), condition=%cond.3, body=%body.2, metadata={op_name="jit(step)/apex/layer_scan/while"}
  ROOT %get-tuple-element.9 = f32[8]{0} get-tuple-element(%while.2), index=1
}
"""


def test_an_instruction_without_a_path_takes_its_callers_scope():
    table = spans.scopes_of(LOOP)
    scope = {name: s for name, (s, _) in table.items()}
    # the copy XLA put into the loop's body, and the loop's condition
    assert scope["copy.4"] == scope["lt.8"] == "layer_scan"
    assert scope["while.2"] == "layer_scan"
    # its own scope where it has one; the adder is the reduction's
    assert scope["tanh.5"] == scope["reduce.6"] == scope["add.9"] == "norm"
    # a fusion without a path is what most of what it fused is, and its
    # root with it
    assert scope["fusion.5"] == scope["scatter.1"] == "cache_write"
    assert scope["abs.1"] == "rope"
    # outside any loop there is nobody to ask
    assert scope["copy.1"] is None and scope["x.1"] is None
    assert table["while.2"][1] == "while.2 = (s32[], f32[8]{0}) " \
        "while(copy.1)"


def test_text_of_an_event_and_of_its_line_agree():
    line = ('  ROOT %fusion.4 = bf16[64,128]{1,0:T(8,128)(2,1)} fusion('
            '%copy-done.1, %p.2), kind=kOutput, calls=%fused_computation.1, '
            'metadata={op_name="jit(f)/apex/a/dot_general" '
            'source_file="a}b.py"}, backend_config={"x":{"y":"}"}}')
    event = ('%fusion.4 = bf16[64,128]{1,0:T(8,128)(2,1)} fusion('
             'bf16[64,64]{1,0:T(8,128)(2,1)S(1)} %copy-done.1, '
             '(bf16[8]{0}, u32[]{:S(2)}) %p.2), kind=kOutput, '
             'calls=%fused_computation.1')
    module = "ENTRY %main (p.2: bf16[8]) -> bf16[64,128] {\n" + line + "\n}"
    assert spans.scopes_of(module) == {"fusion.4": (
        "a", "fusion.4 = bf16[64,128]{1,0:T(8,128)(2,1)} "
        "fusion(copy-done.1, p.2)")}
    assert spans.instruction_text(event) == spans.scopes_of(
        module)["fusion.4"][1]
    assert spans.instruction_text("%c.1 = f32[] constant(0)") \
        == "c.1 = f32[] constant(0)"


def test_program_scopes_builds_on_the_first_ask_and_keeps_the_table(
        monkeypatch):
    monkeypatch.setattr(spans, "_PROGRAMS", {})
    x, w = jnp.ones((8, 16)), jnp.ones((16, 16))
    made = []

    def later():
        made.append(1)
        return jax.jit(layers).lower(x, w)

    spans.register_program("a", jax.jit(layers).lower(x, w))
    spans.register_program("b", later)
    assert not made
    tables = spans.program_scopes()
    assert sorted(tables) == ["a", "b"] and tables["a"] == tables["b"]
    assert spans.program_scopes()["b"] is tables["b"] and made == [1]
    # a second registration takes the name over
    spans.register_program("a", jax.jit(jnp.sin).lower(x))
    assert {s for s, _ in spans.program_scopes()["a"].values()} == {None}
    # a program that no longer lowers costs its own table, no more
    spans.register_program("c", lambda: 1 / 0)
    assert spans.program_scopes()["c"] == {}
    assert spans.program_scopes()["b"] is tables["b"]


# -------------------------------------------------------------- the engines

PRESETS = os.path.join(BENCH, "tests")
COMMON = {"embed", "norm", "attn_proj", "cache_write", "attention",
          "dense_ffn", "lm_head", "sample"}
CATALOG = {
    "uniform": COMMON | {"layer_scan", "paged_decode", "paged_prefill"},
    "hybrid": COMMON | {
        "rope", "moe_router", "moe_experts", "paged_decode_full",
        "paged_decode_window", "paged_prefill_full",
        "paged_prefill_window"},
    "latent": COMMON | {
        "rope", "moe_router", "moe_experts", "moe_shared", "mla_absorb_q",
        "mla_expand_o", "paged_decode_latent", "paged_prefill_latent"},
}
NOT_OPERATIONS = ("parameter", "constant", "tuple", "get-tuple-element",
                  "bitcast")


def one_chip():
    return parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])


def build_uniform():
    mesh = one_chip()
    cfg = TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=4,
        padded_vocab_size=64, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis="tp",
        use_flash_attention=True)
    init_fn, _, _ = build_gpt_3d(cfg, num_chunks=2, num_microbatches=1,
                                 mesh=mesh)
    params, _ = init_fn(jax.random.PRNGKey(0), jnp.zeros((2, 4), jnp.int32))
    return ServingEngine(
        cfg, ServingConfig(max_batch=4, block_size=4, max_seq=32,
                           prefill_len=8, prefix_caching=False),
        params, mesh=mesh, registry=MetricRegistry())


def build_groups(program, reference, preset):
    mesh = one_chip()
    with open(os.path.join(PRESETS, preset)) as f:
        sizes = reference.sizes_of(json.load(f))
    weights = reference.init_weights(program.seed_key(5), sizes)
    return ServingEngine(
        program.transformer_config(sizes, jnp.float32),
        ServingConfig(max_batch=4, max_seq=64, prefill_len=8, block_size=4,
                      n_blocks=64, prefix_caching=False),
        program.program_params(weights, sizes, jnp.float32), mesh=mesh,
        registry=MetricRegistry())


BUILD = {
    "uniform": build_uniform,
    "hybrid": lambda: build_groups(
        mimo_program, mimo_v2_flash, "presets_mimo/configs/mimo-tiny.json"),
    "latent": lambda: build_groups(
        deepseek_program, deepseek_v2,
        "presets_deepseek/configs/deepseek-tiny.json"),
}


def serve(kind):
    """An engine of ``kind`` that has served two requests to the end."""
    engine = BUILD[kind]()
    rng = np.random.default_rng(0)
    for n in (5, 11):
        engine.submit(rng.integers(0, 64, n).tolist(), 3)
    engine.run_until_drained()
    return engine


def lowered(kept):
    return kept() if callable(kept) else kept


@pytest.fixture(scope="module", params=sorted(BUILD))
def life(request):
    """One engine's life as a serving cell's driver leads it: built, two
    requests served, dropped with its world and JAX's caches before anyone
    asks for a table.  Gives the kind, what it registered, and the live
    arrays' bytes before it, while it served, and after it."""
    kind = request.param
    registered, spans._PROGRAMS = spans._PROGRAMS, {}
    try:
        gc.collect()
        before = sum(a.nbytes for a in jax.live_arrays())
        engine = serve(kind)
        held = sum(a.nbytes for a in jax.tree_util.tree_leaves(
            (engine.arenas, engine.params)))
        during = sum(a.nbytes for a in jax.live_arrays())
        del engine
        parallel.destroy_model_parallel()
        jax.clear_caches()
        gc.collect()
        after = sum(a.nbytes for a in jax.live_arrays())
        names = sorted(spans._PROGRAMS)
        # lowered once, for the tables and for the comparison alike
        for name, kept in list(spans._PROGRAMS.items()):
            spans._PROGRAMS[name] = kept() if callable(kept) else kept
        yield (kind, names, dict(spans._PROGRAMS),
               (before, held, during, after))
    finally:
        spans._PROGRAMS = registered


def test_engine_registers_two_programs_and_keeps_nothing_alive(life):
    _, names, _, (before, held, during, after) = life
    assert names == ["serving/decode", "serving/prefill"]
    assert during >= before + held > before
    # the arenas and the parameters went with the engine
    assert after <= before


def test_tables_hold_the_catalog_and_nine_operations_in_ten(life):
    kind, names, _, _ = life
    tables = spans.program_scopes()
    assert sorted(tables) == names
    for name, table in tables.items():
        operations = [scope for scope, text in table.values()
                      if not any(f" {op}(" in text for op in NOT_OPERATIONS)]
        named = sum(scope is not None for scope in operations)
        assert named >= 0.9 * len(operations) > 90, (name, named,
                                                     len(operations))
        wanted = {s for s in CATALOG[kind] if not s.startswith(
            "paged_prefill" if name.endswith("decode") else "paged_decode")}
        assert wanted <= set(operations), (name, wanted - set(operations))
    # built once
    assert spans.program_scopes()["serving/decode"] is tables[
        "serving/decode"]


def instructions(text):
    """``[result and opcode]`` of a module's instructions, in its order.
    Names stay out: the CPU compiler names a called computation's
    parameter after the scope round the call."""
    out = []
    for line in text.splitlines():
        if spans._INSTRUCTION.match(line):
            said = spans.instruction_text(spans._without(line, "metadata")[0])
            opcode = spans._OPERANDS.search(said, said.find(" = "))
            out.append(said[said.find(" = "):opcode.end()] if opcode
                       else said)
    return out


def test_scopes_are_metadata_only(life, monkeypatch):
    kind, _, named, _ = life
    bare = {}
    monkeypatch.setattr(spans, "_PROGRAMS", bare)
    for module in (spans, serving_model, moe, paged_attention):
        monkeypatch.setattr(module, "named_span",
                            lambda name: contextlib.nullcontext())
    serve(kind)
    bare = {name: lowered(kept) for name, kept in bare.items()}
    assert sorted(named) == sorted(bare) == ["serving/decode",
                                             "serving/prefill"]
    for name in named:
        assert "apex/sample" in named[name].as_text(debug_info=True)
        assert "apex/" not in bare[name].as_text(debug_info=True)
        assert named[name].as_text() == bare[name].as_text()
    if kind == "uniform":       # the optimized programs too, at one size
        for name in named:
            with_scopes = named[name].compile().as_text()
            assert "apex/norm" in with_scopes
            assert len(instructions(with_scopes)) > 500
            assert instructions(with_scopes) == instructions(
                bare[name].compile().as_text())


def test_registered_prefill_is_the_module_the_call_compiles(monkeypatch):
    """The prefill program is lowered for whoever asks from the first
    call's abstract arguments: the same module as the call's own, so the
    table's names are the executed program's (and its compilation a hit in
    a persistent cache)."""
    monkeypatch.setattr(spans, "_PROGRAMS", {})
    engine = build_uniform()
    own, step = [], engine._prefill

    def tapped(*args):
        if not own:
            own.append(step.lower(*args).as_text())
        return step(*args)

    tapped.lower = step.lower
    engine._prefill = tapped
    engine.submit([1, 2, 3, 4, 5], 2)
    engine.run_until_drained()
    assert lowered(spans._PROGRAMS["serving/prefill"]).as_text() == own[0]

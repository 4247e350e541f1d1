"""The serving tick measured from inside: the host-span primitive
(``observability.spans.span``: ring, parents, fields, ``self_ms``), the
spans and counters of ``ServingEngine.step``, ``introspect()``'s slowest
tick, ``last_logits()``, and the names of the paged kernels (metadata only:
the lowered programs are the same text with and without them).

CPU, tiny engine; Pallas kernels run in the interpreter.
"""

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import parallel
from apex_tpu.observability import spans
from apex_tpu.observability.metrics import MetricRegistry
from apex_tpu.serving import (
    SamplingParams,
    ServingConfig,
    ServingEngine,
    paged_attention,
)
from apex_tpu.transformer.testing import TransformerConfig
from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

VOCAB, MAX_SEQ, LAYERS = 64, 32, 8
PHASES = ("admit", "prefill_plan", "prefill_dispatch", "prefill_fetch",
          "prefill_deliver", "decode_plan", "decode_dispatch",
          "decode_fetch", "deliver")
TICK_FIELDS = {"step", "live", "waiting", "prefill_rows", "prefill_tokens",
               "prefill_capacity", "decode_slots"}


# ------------------------------------------------------------ the primitive


def test_span_records_name_clock_parent_and_fields():
    reg = MetricRegistry()
    with spans.span("t/outer", registry=reg, step=3) as outer:
        with spans.span("t/outer/inner", registry=reg) as inner:
            inner.note(rows=2)
        outer.note(done=1)
    assert (inner.parent, outer.parent) == (outer.id, 0)
    assert inner.id != outer.id
    assert outer.start <= inner.start <= inner.end <= outer.end
    assert outer.fields == {"step": 3, "done": 1}
    assert inner.fields == {"rows": 2}
    # finished spans join the ring, children before their parent
    assert spans.recorded()[-2:] == [inner, outer]
    assert spans.recorded(since=inner.start) == [inner]
    assert reg.histogram("span_ms/t/outer").count == 1
    assert reg.histogram("span_ms/t/outer/inner").last == \
        pytest.approx(inner.ms)


def test_self_ms_is_duration_less_children():
    with spans.span("t/a") as a:
        with spans.span("t/a/b") as b:
            with spans.span("t/a/b/c") as c:
                pass
        with spans.span("t/a/d") as d:
            pass
    own = spans.self_ms([a, b, c, d])
    assert own[c.id] == pytest.approx(c.ms)
    assert own[b.id] == pytest.approx(b.ms - c.ms)
    assert own[a.id] == pytest.approx(a.ms - b.ms - d.ms)
    assert all(v >= 0 for v in own.values())
    # a child whose parent is not among the records reduces nothing
    assert spans.self_ms([c, d]) == {c.id: c.ms, d.id: d.ms}


def test_span_that_raises_is_still_recorded_and_closed():
    reg = MetricRegistry()
    with pytest.raises(KeyError):
        with spans.span("t/raises", registry=reg) as s:
            raise KeyError("x")
    assert spans.recorded()[-1] is s and s.end >= s.start
    with spans.span("t/after") as after:
        pass
    assert after.parent == 0          # the stack was unwound
    assert reg.histogram("span_ms/t/raises").count == 1


def test_ring_is_bounded():
    bound = spans._RING.maxlen
    assert bound == 8192
    for _ in range(bound + 50):
        with spans.span("t/fill"):
            pass
    records = spans.recorded()
    assert len(records) == bound
    assert all(s.name == "t/fill" for s in records)


def test_spans_on_two_threads_do_not_adopt_each_other():
    inside, release = threading.Barrier(2, timeout=20), \
        threading.Barrier(2, timeout=20)
    seen = {}

    def work(tag):
        with spans.span(f"t/{tag}") as outer:
            inside.wait()             # both outers are open now
            with spans.span(f"t/{tag}/inner") as inner:
                release.wait()        # and both inners
            seen[tag] = (outer, inner)

    threads = [threading.Thread(target=work, args=(tag,))
               for tag in ("one", "two")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    for tag in ("one", "two"):
        outer, inner = seen[tag]
        assert outer.parent == 0
        assert inner.parent == outer.id
    assert len({s.id for pair in seen.values() for s in pair}) == 4


def test_many_threads_lose_no_span_and_share_no_id():
    """More threads than cores, a short switch interval: every span of
    every thread keeps a unique id, its own thread's parent, and lands in
    its registry's histogram (a lost update would drop a count)."""
    import sys

    reg = MetricRegistry()
    n_threads, n_spans = 16, 300
    kept = [[] for _ in range(n_threads)]

    def work(mine):
        for _ in range(n_spans):
            with spans.span("t/stress", registry=reg) as outer:
                with spans.span("t/stress/inner", registry=reg) as inner:
                    pass
            mine.append((outer, inner))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(kept[i],))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    pairs = [p for mine in kept for p in mine]
    assert len(pairs) == n_threads * n_spans
    assert all(o.parent == 0 and i.parent == o.id for o, i in pairs)
    assert len({s.id for p in pairs for s in p}) == 2 * len(pairs)
    assert reg.histogram("span_ms/t/stress").count == len(pairs)
    assert reg.histogram("span_ms/t/stress/inner").count == len(pairs)


# --------------------------------------------------------------- the engine


@pytest.fixture(scope="module")
def model():
    """(mesh, cfg, params) of a toy wide and deep enough for ticks of
    about 25 ms on the CPU: the 50-150 us between two phases (the host
    comes back cold from waiting on XLA's threads) are then well under a
    twentieth of a tick."""
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])
    cfg = TransformerConfig(
        hidden_size=256, num_layers=LAYERS, num_attention_heads=4,
        padded_vocab_size=VOCAB, max_position_embeddings=MAX_SEQ,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis="tp",
        use_flash_attention=True)
    init_fn, _, _ = build_gpt_3d(cfg, num_chunks=LAYERS,
                                 num_microbatches=1, mesh=mesh)
    params, _ = init_fn(jax.random.PRNGKey(0), jnp.zeros((2, 4), jnp.int32))
    return mesh, cfg, params


def build_engine(model, **serving):
    mesh, cfg, params = model
    conf = dict(max_batch=8, block_size=4, max_seq=MAX_SEQ, prefill_len=16,
                prefix_caching=False)
    conf.update(serving)
    return ServingEngine(cfg, ServingConfig(**conf), params, mesh=mesh,
                         registry=MetricRegistry())


def serve(engine, prompt_lengths=(5, 11, 7, 16, 3, 9, 12, 4, 8, 6),
          new_tokens=6, seed=0, sampled=()):
    """Submit, drain, and return the requests with this run's tick spans
    and their children by phase.  The requests whose index is in
    ``sampled`` draw their tokens (temperature 0.8); the others are
    greedy."""
    rng = np.random.default_rng(seed)
    t0 = spans.span("t/mark")
    with t0:
        pass
    reqs = [engine.submit(
        rng.integers(0, VOCAB, n).tolist(), new_tokens,
        sampling=SamplingParams(temperature=0.8, top_k=8, top_p=0.9,
                                seed=i) if i in sampled else None)
        for i, n in enumerate(prompt_lengths)]
    engine.run_until_drained()
    records = spans.recorded(since=t0.end)
    ticks = [s for s in records if s.name == "serving/tick"]
    children = {t.id: [s for s in records if s.parent == t.id]
                for t in ticks}
    return reqs, ticks, children


@pytest.fixture(scope="module")
def served(model):
    engine = build_engine(model)
    reqs, ticks, children = serve(engine)
    return engine, reqs, ticks, children


def test_tick_children_are_disjoint_ordered_and_named(served):
    engine, _, ticks, children = served
    assert len(ticks) == engine._steps > 4
    assert [t.fields["step"] for t in ticks] == list(range(len(ticks)))
    for tick in ticks:
        assert set(tick.fields) == TICK_FIELDS
        kids = sorted(children[tick.id], key=lambda s: s.start)
        assert kids and all(k.parent == tick.id for k in kids)
        names = [k.name for k in kids]
        assert all(n.startswith("serving/tick/") for n in names)
        order = [PHASES.index(n.rpartition("/")[2]) for n in names]
        assert order == sorted(set(order)), names     # in order, once each
        edges = [tick.start] + [e for k in kids for e in (k.start, k.end)] \
            + [tick.end]
        assert edges == sorted(edges), names          # disjoint, inside
        assert {"admit", "deliver"} <= {n.rpartition("/")[2] for n in names}


def coverage(ticks, children):
    return [sum(k.ms for k in children[t.id]) / t.ms for t in ticks]


def test_tick_children_cover_the_tick(model, served):
    """At least 95% of every tick lies in its phases.  The rest is a few
    dozen microseconds between two spans, which a loaded machine can
    stretch: a second and a third drained run may make the point."""
    _, _, ticks, children = served
    shares = coverage(ticks, children)
    for attempt in range(2):
        if min(shares) >= 0.95:
            break
        _, ticks, children = serve(build_engine(model), seed=attempt + 1)
        shares = coverage(ticks, children)
    assert min(shares) >= 0.95, shares


def test_tick_without_a_chunk_has_no_prefill_span(served):
    _, _, ticks, children = served
    with_chunk = [t for t in ticks if t.fields["prefill_rows"]]
    without = [t for t in ticks if not t.fields["prefill_rows"]]
    assert with_chunk and without
    for tick in without:
        names = {k.name.rpartition("/")[2] for k in children[tick.id]}
        assert not any(n.startswith("prefill_") for n in names), names
        assert tick.fields["prefill_tokens"] == 0
        assert tick.fields["prefill_capacity"] == 0
    for tick in with_chunk:
        names = {k.name.rpartition("/")[2] for k in children[tick.id]}
        assert {"prefill_plan", "prefill_dispatch", "prefill_fetch",
                "prefill_deliver"} <= names
        assert tick.fields["prefill_capacity"] == 8 * 16
        assert 0 < tick.fields["prefill_tokens"] <= 8 * 16
    decoding = [t for t in ticks if t.fields["decode_slots"]]
    for tick in decoding:
        names = {k.name.rpartition("/")[2] for k in children[tick.id]}
        assert {"decode_plan", "decode_dispatch", "decode_fetch"} <= names


class Spy:
    """One of the engine's compiled programs, with ``note(*args)`` told of
    each call before it runs."""

    def __init__(self, real, note):
        self._real, self._note = real, note

    def __call__(self, *args):
        self._note(*args)
        return self._real(*args)

    def __getattr__(self, name):              # lower, for the FLOPs probe
        return getattr(self._real, name)


def test_decode_plan_counts_the_kv_the_kernel_meets(model):
    """ISSUE 26: a decode tick's ``decode_plan`` span carries ``kv_tokens``
    (the history each decoding slot attends, its new row included) and
    ``kv_pages`` (the KV blocks that hold it), equal to the sums over the
    scheduler's running requests at the moment of the call and to the
    lengths the device program is given; the two counters grow by them."""
    engine = build_engine(model)
    bs = engine.cache.block_size
    seen = []

    def note(*args):
        decoding = [r for r in engine.scheduler.running()
                    if not r.prefilling]
        history = [r.cache_len + 1 for r in decoding]
        positions, active = np.asarray(args[3]), np.asarray(args[5])
        lengths = np.where(active, positions + 1, 0)
        assert sorted(lengths[active]) == sorted(history)
        seen.append((sum(history), sum(-(-h // bs) for h in history)))

    engine._decode = Spy(engine._decode, note)
    _, ticks, children = serve(engine)
    assert engine.scheduler.preemptions == 0
    plans = [k for t in ticks for k in children[t.id]
             if k.name == "serving/tick/decode_plan"]
    assert len(plans) == len(seen) > 4
    for plan, (tokens, pages) in zip(plans, seen):
        assert set(plan.fields) == {"preempted", "kv_tokens", "kv_pages",
                                    "drawn"}
        assert (plan.fields["kv_tokens"], plan.fields["kv_pages"]) == \
            (tokens, pages)
        assert 0 < pages <= tokens <= pages * bs
    snap = engine.registry.snapshot()
    assert snap["serving/decode_kv_tokens"] == sum(t for t, _ in seen)
    assert snap["serving/decode_kv_pages"] == sum(p for _, p in seen)
    # the live share of the old (slots, max_blocks) grid, from inside
    grid = snap["serving/decode_slot_steps"] * \
        engine.cache.max_blocks_per_request
    assert 0 < snap["serving/decode_kv_pages"] <= grid


def _plans(ticks, children, phase):
    return [k for t in ticks for k in children[t.id]
            if k.name == f"serving/tick/{phase}" and "drawn" in k.fields]


def test_all_greedy_engine_draws_nothing(served):
    """ISSUE 28: no slot of temperature > 0, so no call's in-graph draw
    ran: ``drawn`` is 0 on every plan and the counters stay at 0."""
    engine, _, ticks, children = served
    decode, prefill = (_plans(ticks, children, p)
                       for p in ("decode_plan", "prefill_plan"))
    snap = engine.registry.snapshot()
    assert len(decode) == snap["serving/decode_calls"] > 4
    assert len(prefill) == snap["serving/prefill_calls"] > 0
    assert {p.fields["drawn"] for p in decode + prefill} == {0}
    assert snap["serving/drawn_calls"] == snap["serving/drawn_rows"] == 0


def test_drawn_counts_the_sampled_slots_of_each_call(model):
    """Two sampled requests of four, in one wave: ``drawn`` on a decode or
    prefill plan is the number of running slots of temperature > 0 at the
    call (what the program's ``cond`` is decided by), a call counts in
    ``serving/drawn_calls`` where that is not 0, and ``serving/drawn_rows``
    sums it."""
    engine = build_engine(model)
    seen = {"decode": [], "prefill": []}

    def sampled_slots(calls):
        return lambda *args: calls.append(sum(
            1 for r in engine.scheduler.running()
            if r.sampling.temperature > 0))

    engine._decode = Spy(engine._decode, sampled_slots(seen["decode"]))
    engine._prefill = Spy(engine._prefill, sampled_slots(seen["prefill"]))
    # the prompt of 20 takes two chunks, so its request is a tick behind:
    # the calls hold two sampled slots at first and one at the end
    reqs, ticks, children = serve(engine, prompt_lengths=(5, 20, 7, 3),
                                  sampled=(1, 3))
    assert all(len(r.output_tokens) == 6 for r in reqs)
    decode, prefill = (_plans(ticks, children, p)
                       for p in ("decode_plan", "prefill_plan"))
    assert [p.fields["drawn"] for p in decode] == seen["decode"]
    assert [p.fields["drawn"] for p in prefill] == seen["prefill"]
    assert max(seen["decode"]) == 2 and max(seen["prefill"]) == 2
    assert 1 in seen["decode"] + seen["prefill"]
    snap = engine.registry.snapshot()
    every = seen["decode"] + seen["prefill"]
    assert snap["serving/drawn_calls"] == sum(1 for n in every if n)
    assert snap["serving/drawn_rows"] == sum(every)
    assert snap["serving/drawn_calls"] <= \
        snap["serving/decode_calls"] + snap["serving/prefill_calls"]


def test_counters_equal_what_was_submitted(served):
    engine, reqs, ticks, children = served
    snap = engine.registry.snapshot()
    prompts = sum(len(r.prompt) for r in reqs)
    generated = sum(len(r.output_tokens) for r in reqs)
    assert generated == 6 * len(reqs) == snap["serving/tokens_generated"]
    assert engine.scheduler.preemptions == 0
    assert snap["serving/ticks"] == len(ticks) == engine._steps
    assert snap["serving/prefill_tokens"] == prompts
    assert snap["serving/prefill_tokens"] == sum(
        t.fields["prefill_tokens"] for t in ticks)
    calls = sum(1 for t in ticks if t.fields["prefill_rows"])
    assert snap["serving/prefill_calls"] == calls
    assert snap["serving/prefill_capacity_tokens"] == calls * 8 * 16
    # every token but a request's first comes from a decode slot-step
    assert snap["serving/decode_slot_steps"] == generated - len(reqs)
    assert snap["serving/decode_slot_steps"] == engine._slot_steps == sum(
        t.fields["decode_slots"] for t in ticks)
    assert snap["serving/decode_calls"] == engine._decode_calls == sum(
        1 for t in ticks if t.fields["decode_slots"])
    # what the deliver phases counted is what decode produced
    delivered = sum(k.fields["tokens"] for t in ticks
                    for k in children[t.id]
                    if k.name == "serving/tick/deliver")
    assert delivered == generated - len(reqs)
    admitted = sum(k.fields["admitted"] for t in ticks
                   for k in children[t.id]
                   if k.name == "serving/tick/admit")
    assert admitted == len(reqs) == snap["serving/queue_wait_ms"]["count"]
    assert all(r.t_admit is not None and r.t_admit >= r.t_submit
               for r in reqs)
    # the spans feed span_ms/* of the engine's own registry
    assert snap["span_ms/serving/tick"]["count"] == len(ticks)
    assert snap["span_ms/serving/tick/decode_fetch"]["count"] == \
        snap["serving/decode_calls"]


def test_introspect_places_the_slowest_tick(served):
    engine, _, ticks, children = served
    info = engine.introspect()
    slowest = info["slowest_tick"]
    longest = max((s for s in spans.recorded() if s.name == "serving/tick"),
                  key=lambda s: s.ms)
    assert slowest["ms"] == pytest.approx(longest.ms, abs=1e-3)
    assert slowest["step"] == longest.fields["step"]
    assert set(slowest["phases"]) <= set(PHASES) | {"own"}
    assert {"admit", "deliver", "own"} <= set(slowest["phases"])
    assert sum(slowest["phases"].values()) == pytest.approx(
        slowest["ms"], abs=0.02)
    # the decode call's time is that of its two spans (this engine's last:
    # the ring is the process's and may hold another engine's since)
    last = [k for k in children[ticks[-1].id]
            if k.name in ("serving/tick/decode_dispatch",
                          "serving/tick/decode_fetch")]
    assert len(last) == 2
    assert info["last_decode_ms"] == pytest.approx(
        last[0].ms + last[1].ms, abs=1e-3)


def test_last_logits_are_the_decode_programs_own(model):
    engine = build_engine(model, max_batch=3)
    assert engine.last_logits() is None
    kept = []
    step = engine._decode

    def tapped(*args):
        out = step(*args)
        kept.append(out[-1])
        return out

    engine._decode = tapped           # looked up on the engine at each call
    rng = np.random.default_rng(3)
    reqs = [engine.submit(rng.integers(0, VOCAB, n).tolist(), 4)
            for n in (5, 9)]
    engine.step()
    logits, slots = engine.last_logits()
    assert len(kept) == 1 and logits is kept[0]
    assert logits.shape == (3, 1, VOCAB)
    assert slots == tuple(sorted(r.slot for r in reqs))
    # greedy requests took the argmax of exactly these rows
    for r in reqs:
        assert r.output_tokens[-1] == int(np.argmax(
            np.asarray(logits)[r.slot, 0]))
    engine.run_until_drained()
    assert engine.last_logits()[0] is kept[-1]
    assert len(kept) == engine._decode_calls


# ---------------------------------------------------------- the kernel names


def program_args(engine):
    b, mb = engine.serving.max_batch, engine.cache.max_blocks_per_request
    T = engine.prefill_len
    sampling = (np.zeros((b,), np.float32), np.zeros((b,), np.int32),
                np.ones((b,), np.float32), np.zeros((b,), np.uint32),
                np.zeros((b,), np.int32))
    decode = (engine.arenas, engine.params, np.zeros((b, 1), np.int32),
              np.zeros((b,), np.int32), jnp.zeros((b, mb), jnp.int32),
              np.zeros((b,), bool), np.zeros((b,), np.int32)) + sampling
    prefill = (engine.arenas, engine.params, np.zeros((b, T), np.int32),
               np.zeros((b, T), np.int32), jnp.zeros((b, mb), jnp.int32),
               np.zeros((b,), np.int32), np.zeros((b, T), np.int32),
               np.zeros((b, T), np.int32), np.zeros((b, T), np.int32),
               np.full((b,), T, np.int32)) + sampling
    return decode, prefill


@pytest.mark.parametrize("program,scope", [
    ("_decode", "apex/paged_decode"), ("_prefill", "apex/paged_prefill")])
def test_kernel_scopes_are_metadata_only(model, monkeypatch, program, scope):
    named = build_engine(model, max_batch=2)
    args = program_args(named)[program == "_prefill"]
    lowered = getattr(named, program).lower(*args)
    with_names = lowered.as_text()
    assert scope in lowered.as_text(debug_info=True)
    assert "apex/paged" not in with_names         # locations only

    monkeypatch.setattr(paged_attention, "named_span",
                        lambda name: contextlib.nullcontext())
    bare = build_engine(model, max_batch=2)
    lowered = getattr(bare, program).lower(*program_args(bare)[
        program == "_prefill"])
    assert scope not in lowered.as_text(debug_info=True)
    assert lowered.as_text() == with_names

"""What ``test_serving_hybrid.py`` and ``test_serving_latent.py`` share of
the engine that runs one decode call ahead (ISSUE 34): one scripted mix of
requests, served by one engine twice, settled after every tick and running
ahead, and the contract the benchmark's drivers hold, checked after every
``step()``.

The mix (slots 3, ``max_seq`` 40, chunks of 8): a greedy request that ends
at its budget, a sampled one whose prompt takes three chunks, a greedy one
that runs into the context cap, a greedy one that waits for a slot and
brings its prompt mid-stream, a sampled one that arrives later, a greedy one
that ends on its ``eos_id`` (the one end the plan cannot foresee: the row
dispatched after it is discarded), and the tenant of that slot after it.
"""

import numpy as np

from apex_tpu.observability import spans
from apex_tpu.serving import SamplingParams

ENGINE = dict(max_batch=3, max_seq=40, prefill_len=8, n_blocks=64)
EOS_CALLER = 5      # index in SCRIPT
# (tick of arrival, prompt tokens, budget, sampling seed or None)
SCRIPT = ((0, 5, 12, None), (0, 19, 14, 11), (0, 30, 30, None),
          (0, 6, 9, None), (5, 17, 8, 23), (7, 9, 16, None),
          (7, 11, 10, None), (20, 4, 6, 31))


def prompts_of(vocab):
    rng = np.random.default_rng(7)
    return [rng.integers(0, vocab, n).tolist() for _, n, _, _ in SCRIPT]


def serve(eng, vocab, *, settle_each_tick, eos_id=None, check=None,
          max_ticks=300):
    """Serve SCRIPT to the end; ``eos_id`` is the EOS_CALLER's.  Returns the
    requests in the script's order."""
    prompts = prompts_of(vocab)
    reqs = []
    for tick in range(max_ticks):
        for i, (at, _, budget, seed) in enumerate(SCRIPT):
            if at == tick:
                sampling = (None if seed is None else SamplingParams(
                    temperature=0.8, top_k=5, top_p=0.95, seed=seed))
                reqs.append(eng.submit(
                    prompts[i], budget, sampling=sampling,
                    eos_id=eos_id if i == EOS_CALLER else None))
        if check is not None:
            check.before(eng)
        eng.step()
        if settle_each_tick:
            eng.settle()
        if check is not None:
            check.after(eng)
        assert eng.decode_compile_count() <= 1
        if len(reqs) == len(SCRIPT) and eng.scheduler.idle:
            break
    assert eng.scheduler.idle and len(reqs) == len(SCRIPT)
    eng.settle()
    if check is not None:
        check.totals = check.counted(eng)
    return reqs


def an_eos_of(stream):
    """A token of ``stream`` at index 3 or later that no earlier one equals,
    and its index: as ``eos_id`` it ends the request right there."""
    for j in range(3, len(stream) - 2):
        if stream[j] not in stream[:j]:
            return stream[j], j
    raise AssertionError(f"no usable eos in {stream}")


COUNTERS = ("serving/decode_calls_ahead", "serving/decode_calls",
            "serving/decode_rows_discarded", "serving/tokens_generated")


class Contract:
    """What holds after every ``step()`` of an engine with cache groups,
    with or without a call in flight: what the benchmark's drivers read
    (``last_logits()``, ``last_expert_choices()``, the tick's spans, the
    scheduler's invariants) and what the counters say happened."""

    def __init__(self, eng, experts=True):
        self.experts = experts
        self.seen = eng.last_logits()
        self.tenants = []
        self.written = {}        # request id -> the next position to write
        self.tenancy = {}        # slot -> the request ids it held, in order
        self.dispatched = self.ahead = self.delivered_calls = 0
        self.t0 = spans.recorded()[-1].end if spans.recorded() else 0.0
        self.base = self.totals = None
        self.base = self.counted(eng)

    def counted(self, eng):
        """What the engine's counters grew by since this contract began."""
        snap = eng.registry.snapshot()
        return {name: snap.get(name, 0) - (self.base[name] if self.base
                                           else 0) for name in COUNTERS}

    def before(self, eng):
        self.tenants = list(eng.scheduler.slots)

    def after(self, eng):
        eng.scheduler.check()            # with a call in flight, too
        for req in eng.scheduler.running():
            held = self.tenancy.setdefault(req.slot, [])
            if req.rid not in held:
                held.append(req.rid)
            if not req.prefilling:
                assert req.cache_len == (len(req.prompt)
                                         + len(req.output_tokens) - 1)
        self._logits(eng)
        self._choices(eng)
        self._spans(eng)
        counted = self.counted(eng)
        assert counted["serving/decode_calls_ahead"] == self.ahead
        assert counted["serving/decode_calls"] == self.delivered_calls

    def _logits(self, eng):
        if eng.last_logits() is self.seen:
            return
        self.seen = eng.last_logits()
        self.delivered_calls += 1
        logits, slots = self.seen
        logits = np.asarray(logits)
        assert len(set(slots)) == len(slots)
        for slot in slots:
            # the request the row was made for: still there, or ended by
            # this very delivery
            req = eng.scheduler.slots[slot] or self.tenants[slot]
            assert req is not None and not req.prefilling
            assert req.cache_len == (len(req.prompt)
                                     + len(req.output_tokens) - 1)
            if req.sampling.temperature == 0.0:
                assert int(np.argmax(logits[slot, 0])) \
                    == req.output_tokens[-1]

    def _choices(self, eng):
        calls = eng.last_expert_choices()
        assert len(calls) <= 2
        for chosen, rows in calls:
            for rid, _, pos, n in rows:
                # a prompt's chunks from 0, then a row a decode call: each
                # the position the call writes, whatever is in flight
                assert pos == self.written.get(rid, 0)
                self.written[rid] = pos + n
            if self.experts:
                assert chosen.shape[0] > 0

    def _spans(self, eng):
        records = spans.recorded(since=self.t0)
        tick = [s for s in records if s.name == "serving/tick"][-1]
        self.t0 = tick.end
        phases = {s.name.rpartition("/")[2]: s for s in records
                  if s.parent == tick.id}
        if "decode_dispatch" not in phases:
            return
        self.dispatched += 1
        assert {"decode_plan", "decode_fetch"} <= set(phases)
        for field in ("kv_tokens", "kv_pages", "preempted", "drawn"):
            assert field in phases["decode_plan"].fields
        ahead = phases["decode_dispatch"].fields["ahead"]
        self.ahead += ahead
        fetched = phases["decode_fetch"].fields
        if ahead:
            # the call before's, fetched in this tick
            assert phases["decode_fetch"].start \
                > phases["decode_dispatch"].end
        if self.experts and fetched:
            assert {"moe_pairs", "moe_experts_hit", "moe_peak_pairs",
                    "moe_group_tokens"} <= set(fetched)


KINDS = ("ends at its budget", "sampled, a prompt of three chunks",
         "runs into the context cap", "waits for a slot", "sampled, late",
         "ends on its eos", "the eos slot's next tenant", "sampled, last")


def runs(eng, vocab, experts=True):
    """The scenario twice on one engine (one compile of each program):
    settled after every tick and running ahead, both under the contract.
    Before them the EOS_CALLER's request alone and without an eos: its
    stream says where one can end it.  Returns a namespace of the requests
    (``settled``, ``ahead``), the engine, the two contracts, and ``free``
    and ``at``: that stream and the index of the eos in it."""
    import types

    alone = eng.submit(prompts_of(vocab)[EOS_CALLER], SCRIPT[EOS_CALLER][2])
    eng.run_until_drained()
    eos_id, at = an_eos_of(alone.output_tokens)
    twin_check = Contract(eng, experts)
    settled = serve(eng, vocab, settle_each_tick=True, eos_id=eos_id,
                    check=twin_check)
    check = Contract(eng, experts)
    ahead = serve(eng, vocab, settle_each_tick=False, eos_id=eos_id,
                  check=check)
    return types.SimpleNamespace(
        free=alone.output_tokens, settled=settled, ahead=ahead, eng=eng,
        twin_check=twin_check, check=check, at=at)


def assert_same_stream(run, i):
    """Request ``i`` of the script: token for token what the engine settled
    after every tick served, to its budget, the context cap or the eos."""
    got = run.ahead[i].output_tokens
    assert got == run.settled[i].output_tokens and got
    budget, prompt = SCRIPT[i][2], SCRIPT[i][1]
    if i == EOS_CALLER:
        assert got == run.free[:run.at + 1] and len(got) < budget
    elif prompt + budget > ENGINE["max_seq"]:
        # truncated at the cap: the prompt's token and a row a position
        assert len(got) == ENGINE["max_seq"] - prompt + 1 < budget
    else:
        assert len(got) == budget


def assert_counted(run):
    """The counters and the ``ahead`` field say what happened: settled
    after every tick the engine never ran ahead and discarded nothing;
    left to itself it did on nearly every call and threw away the one row
    behind the eos."""
    twin, ahead = run.twin_check.totals, run.check.totals
    assert run.twin_check.ahead == 0 and run.twin_check.dispatched > 20
    assert twin["serving/decode_calls_ahead"] == 0
    assert twin["serving/decode_rows_discarded"] == 0
    assert ahead["serving/decode_rows_discarded"] == 1
    assert ahead["serving/decode_calls_ahead"] == run.check.ahead
    assert run.check.ahead >= 0.8 * run.check.dispatched
    assert ahead["serving/decode_calls"] == run.check.dispatched
    assert ahead["serving/tokens_generated"] \
        == twin["serving/tokens_generated"]
    # the discarded row was dispatched: one position past the eos
    for reqs, check, extra in ((run.settled, run.twin_check, 0),
                               (run.ahead, run.check, 1)):
        for i, req in enumerate(reqs):
            end = len(req.prompt) + len(req.output_tokens) - 1
            assert check.written[req.rid] == end + (
                extra if i == EOS_CALLER else 0)
    # the slot of the request that ended on its eos went to another, whose
    # stream is its own (``assert_same_stream``)
    ended = run.ahead[EOS_CALLER].rid
    after = [held[held.index(ended) + 1:] for held in
             run.check.tenancy.values() if ended in held]
    assert after and after[0]
    # zeros for the first call of all, the call before's tokens since
    assert run.eng.decode_compile_count() == 1
    assert run.eng.prefill_compile_count() == 1

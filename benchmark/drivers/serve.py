"""Driver of the serving cells: a ``ServingEngine`` with its default
``ServingConfig`` fed by a closed loop of callers, as
``chip_smoke.serve_phase`` builds it.

The benchmark times tokens itself, from outside: a token counts as
delivered when the ``engine.step()`` that produced it has returned.  The
window opens at the dispatch of a tick and closes when the first tick that
ends past ``t0 + seconds`` has returned; a rate is every token delivered
inside it over ``t1 - t0``.  A gap is the time between two deliveries of one
request's tokens.
"""

import collections
import time

import numpy as np

from lib import check, tracing, traffic as traffic_lib

# a finished request, and one engine tick; ``t`` is when the tick returned
Done = collections.namedtuple(
    "Done", "t caller j greedy prompt tokens state")
# ``prefill``: the tick also advanced a prompt; ``kv_tokens``: the history of
# each decoding slot
Tick = collections.namedtuple("Tick", "t ms prefill live kv_tokens")


class LogitTap:
    """Stands in for the engine's compiled decode step and keeps the logits
    of its last call: the program returns them (``[slots, 1, vocab]``, last
    output) and the engine drops them.  The engine hands out tokens only,
    so this is the one place the timed path's logits can be reached from
    outside; it leans on ``ServingEngine._decode``, and goes when the
    engine exposes its logits."""

    def __init__(self, step):
        self.step, self.logits = step, None

    def __call__(self, *args):
        out = self.step(*args)
        self.logits = out[-1]
        return out

    def __getattr__(self, name):           # _cache_size, lower, ...
        return getattr(self.step, name)


class Caller:
    def __init__(self, index):
        self.index, self.j = index, 0
        self.request = None          # the engine's Request
        self.greedy = True
        self.seen = 0                # tokens of it already delivered
        self.last = None             # time of its last delivery


def run(cell):
    import jax
    import jax.numpy as jnp

    from apex_tpu import parallel
    from apex_tpu.serving import SamplingParams, ServingConfig, ServingEngine
    from apex_tpu.serving.scheduler import RequestState
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    from drivers import gpt_program

    mix, config, reference = cell.traffic, cell.config, cell.reference
    sz = reference.sizes_of(config)
    eng = mix["engine"]
    key = gpt_program.seed_key(cell.seed)
    tp = len(cell.devices)
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=cell.devices)
    cfg = gpt_program.transformer_config(config, tp, sequence_parallel=False)
    init_fn, _, _ = build_gpt_3d(cfg, num_chunks=cfg.num_layers,
                                 num_microbatches=1, mesh=mesh)
    template, _ = init_fn(jax.random.PRNGKey(0), jnp.zeros((2, 2), jnp.int32))
    cell.mark("program_init")
    params = gpt_program.weights_maker(template, reference, sz)(key)
    del template
    engine = ServingEngine(
        cfg, ServingConfig(max_batch=eng["max_batch"], max_seq=eng["max_seq"],
                           prefill_len=eng["prefill_len"],
                           n_blocks=eng["n_blocks"]),
        params, mesh=mesh)
    del params
    tap = engine._decode = LogitTap(engine._decode)
    jax.block_until_ready(engine.arenas)
    cell.mark("engine")
    at_rest = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in cell.devices]
    if not (engine.serving.fused_attention and engine.serving.fuse_epilogue):
        raise RuntimeError("the default ServingConfig is not the fused one")

    loop = traffic_lib.ClosedLoop(mix, cell.seed, sz["vocab"])
    callers = [Caller(i) for i in range(loop.callers)]
    finished, ticks = [], []          # Done, Tick
    tapped_at, tapped = [], []        # moments; (logits, [(slot, sequence)])
    gaps = []                         # (time of delivery, milliseconds)
    delivered = []                    # (time, tokens) per tick

    def send(c, now):
        prompt, n_answer, sampled = loop.request(c.index, c.j)
        sampling = (SamplingParams(temperature=0.8, top_k=40, top_p=0.95,
                                   seed=int(cell.seed % (2 ** 31)) + c.index)
                    if sampled else None)
        c.request = engine.submit(prompt.tolist(), n_answer,
                                  sampling=sampling)
        c.greedy, c.seen, c.last = not sampled, 0, None

    def tick():
        """One engine tick, then the tokens it delivered."""
        tap.logits = None
        running = list(engine.scheduler.running())
        kv_tokens = [r.cache_len for r in running if not r.prefilling]
        prefill = (any(r.prefilling for r in running)
                   or bool(engine.scheduler.waiting))
        t_a = time.perf_counter()
        with tracing.span("bench/engine.step"):
            engine.step()
        now = time.perf_counter()
        n_new = 0
        for c in callers:
            req = c.request
            new = len(req.output_tokens) - c.seen
            for _ in range(new):
                if c.last is not None:
                    gaps.append((now, (now - c.last) * 1e3))
                c.last = now
            c.seen += new
            n_new += new
            if req.done:
                finished.append(Done(now, c.index, c.j, c.greedy,
                                     np.asarray(req.prompt),
                                     list(req.output_tokens), req.state))
                c.j += 1
                send(c, now)
        if tapped_at and now >= tapped_at[0] and tap.logits is not None:
            # keep this tick's logits (on the device) and what each decoding
            # slot had read when it made them: its sequence but for the
            # tick's own new token
            tapped_at.pop(0)
            tapped.append((tap.logits, [
                (r.slot, r.sequence_tokens()[:r.cache_len])
                for r in engine.scheduler.running() if not r.prefilling
                and r.cache_len == len(r.prompt) + len(r.output_tokens) - 1]))
        delivered.append((now, n_new))
        ticks.append(Tick(now, (now - t_a) * 1e3, prefill, len(running),
                          kv_tokens))
        return now

    # -- warm-up: every slot holds a request that is past its prompt
    now = time.perf_counter()
    for c in callers:
        send(c, now)
    n_warm = 0
    while (n_warm < mix["warmup_ticks"]
           or any(c.last is None for c in callers)):
        tick()
        n_warm += 1
        if n_warm == 1:
            cell.mark("first_tick")
    cell.setup_done()

    # -- the window
    trace = tracing.start(cell) if cell.trace else None
    seconds = mix["trace_seconds"] if cell.trace else cell.seconds
    t0 = time.perf_counter()
    # the ticks whose logits are compared: the first to end after each of
    # these moments, drawn from the seed
    tapped_at[:] = sorted(t0 + seconds * u for u in np.random.default_rng(
        [cell.seed, 3]).uniform(0.05, 0.95, mix["checked_ticks"]))
    while tick() < t0 + seconds:
        pass
    t1 = time.perf_counter()
    if trace is not None:
        trace = tracing.stop(trace)
    compiles = {"decode": engine.decode_compile_count(),
                "prefill": engine.prefill_compile_count()}
    memory = cell.read_memory()
    preemptions = engine.scheduler.preemptions
    occupancy = engine.scheduler.kv_occupancy()

    inside = lambda rows: [r for r in rows if t0 < r[0] <= t1]  # noqa: E731
    tokens = sum(n for _, n in inside(delivered))
    done = inside(finished)
    bad = [f for f in done if f.state is not RequestState.FINISHED
           or len(f.tokens) != loop.lengths(f.caller, f.j)[1]]
    window_ticks = inside(ticks)

    # -- the engine goes, then the reference reads a sample of what it served
    del engine
    parallel.destroy_model_parallel()
    jax.clear_caches()
    rng = np.random.default_rng([cell.seed, 2])
    greedy = sorted((f for f in done if f.greedy),
                    key=lambda f: -len(f.tokens))
    if len(greedy) > mix["checked_requests"]:
        rest = rng.choice(len(greedy) - 1, mix["checked_requests"] - 1,
                          replace=False) + 1
        greedy = [greedy[0]] + [greedy[i] for i in sorted(rest)]
    t_ref = time.perf_counter()
    weights = jax.jit(lambda k: reference.init_weights(k, sz))(key)
    pad = mix["prompt_tokens"][1] + mix["answer_tokens"][1]
    widest, n_checked = 0.0, 0
    for f in greedy:
        gap = reference.served_token_gaps(weights, f.prompt, f.tokens, sz,
                                          pad)
        widest = max(widest, float(np.max(gap)))
        n_checked += len(f.tokens)
    # the logits of the tapped ticks against the reference's, on rows drawn
    # from the seed with each tick's longest sequence among them
    got, sequences = [], []
    for logits, rows in tapped:
        rows = sorted(rows, key=lambda r: -len(r[1]))
        if len(rows) > mix["checked_rows"]:
            pick = rng.choice(len(rows) - 1, mix["checked_rows"] - 1,
                              replace=False) + 1
            rows = [rows[0]] + [rows[i] for i in sorted(pick)]
        host = np.asarray(logits)
        got += [host[slot, 0] for slot, _ in rows]
        sequences += [seq for _, seq in rows]
    del tapped
    rms = (check.logit_rms_gap(np.stack(got), reference.last_logits(
        weights, sequences, sz, pad)) if got else float("nan"))
    compared = {
        "logit_rms_gap": (rms, cell.limits["logit_rms_gap"]),
        "served_logit_gap": (widest if greedy else float("nan"),
                             cell.limits["served_logit_gap"]),
        "requests_failed": (len(bad), 0),
        "decode_compiles": (compiles["decode"], 1),
        "prefill_compiles": (compiles["prefill"], 1)}

    gap_ms = [v for _, v in inside(gaps)]
    # what the ticks took beyond the median of their kind (with or without a
    # prompt): the host's pauses inside the window, in one number
    excess_ms = 0.0
    for kind in (False, True):
        ms = [k.ms for k in window_ticks if k.prefill is kind]
        if ms:
            excess_ms += sum(max(0.0, v - float(np.median(ms))) for v in ms)
    return {
        "attempted": len(done) + len(callers), "failed": len(bad),
        "compared": compared,
        "end_to_end": {
            "decode_tokens_per_s": tokens / (t1 - t0),
            "tpot_ms_p95": float(np.percentile(gap_ms, 95))},
        "observed": {
            "window_s": t1 - t0, "ticks": len(window_ticks),
            "tokens": tokens, "requests_finished": len(done),
            "gaps": len(gap_ms),
            "slowest_tick_ms": max(k.ms for k in window_ticks),
            "tick_excess_ms": excess_ms,
            "gc_pause_ms": cell.gc_pause_ms(t0, t1),
            "checked_requests": len(greedy), "checked_tokens": n_checked,
            "checked_logit_rows": len(got),
            "preemptions": preemptions, "kv_occupancy": occupancy,
            "warmup_ticks": n_warm, "bytes_at_rest": max(at_rest),
            "reference_s": time.perf_counter() - t_ref,
            "block_size": ServingConfig.block_size,
            "ticks_seen": window_ticks, "memory": memory, "sizes": sz},
        "trace": trace,
    }

"""Driver of the serving cells of a model served with learned sparse
attention over a latent cache (``glm-5``): ``drivers/serve_latent.py``'s
closed loop under its rules, letter for letter (a token is delivered when
the ``engine.step()`` that produced it has returned; the window opens at the
dispatch of a tick and closes with the first tick that ends past ``t0 +
seconds``; the tapped ticks' logits from ``engine.last_logits()``; the
experts each token's routers chose from ``engine.last_expert_choices()``,
which the reference follows and holds to a margin; a caller's first request
a session met mid-answer), returning the same ``observed`` keys, so that the
readers of the serving metrics run on it unchanged.

What differs: the program is built by ``drivers/glm_program.py``; at each
tapped tick the positions every sparse layer's query attended are taken
with the logits (``engine.last_index_choices()``), and the reference's own
index scores at those queries hold them to ``index_choice_margin``
(``reference.selection_margin``: how far, in units of the spread of the
query's scores, a position only one of the two selected lies from the
reference's ``index_topk``-th score); the reference's sequences are padded
to the longest sequence of any caller, rounded up to 1,024; and
``observed`` also
carries what the engine's registry counted of the indexers' rows.

The reference reads one forward pass a request, where a forward of forty
thousand tokens costs a minute: ``checked_requests`` greedy callers are
drawn from the seed before the warm-up, the tapped rows are theirs
(another slot's only where they are fewer than ``checked_rows``), and one
pass over each watched request gives its served tokens' gaps, the logits
of its tapped rows and the index scores there.
"""

import time

import numpy as np

from drivers import glm_program
from drivers.serve import Caller, Done, Tick
from drivers.serve_hybrid import (LongAnswerLoop, choices_by_request, held,
                                  numbers)
from lib import tracing

PAD_STEP = 1024


def build_engine(cell, cfg, weights, sz):
    """The engine of the cell on ``weights``, as a deployment builds it."""
    from apex_tpu import parallel
    from apex_tpu.serving import ServingConfig, ServingEngine

    eng = cell.traffic["engine"]
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=cell.devices)
    return ServingEngine(
        cfg, ServingConfig(max_batch=eng["max_batch"], max_seq=eng["max_seq"],
                           prefill_len=eng["prefill_len"],
                           block_size=eng["block_size"],
                           n_blocks=eng["n_blocks"],
                           prefix_caching=False),
        glm_program.program_params(weights, sz, cfg.dtype), mesh=mesh)


def run(cell):
    import jax
    import jax.numpy as jnp

    from apex_tpu import parallel
    from apex_tpu.serving import SamplingParams
    from apex_tpu.serving.scheduler import RequestState

    mix, config, reference = cell.traffic, cell.config, cell.reference
    sz = reference.sizes_of(config)
    # the program's description first: a program that cannot describe these
    # layers says so before a weight is made
    cfg = glm_program.transformer_config(
        sz, jax.numpy.dtype(config["assumed"]["dtype"]))
    key = glm_program.seed_key(cell.seed)
    weights = reference.init_weights(key, sz)
    jax.block_until_ready(weights)
    cell.mark("weights")
    engine = build_engine(cell, cfg, weights, sz)
    jax.block_until_ready(engine.arenas)
    cell.mark("engine")
    at_rest = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in cell.devices]
    if not engine.serving.fused_attention:
        raise RuntimeError("the default ServingConfig is not the fused one")

    loop = LongAnswerLoop(mix, cell.seed, sz["vocab"])
    callers = [Caller(i) for i in range(loop.callers)]
    finished, ticks = [], []          # Done, Tick
    # moments; (logits, selections, the watched requests' [(slot, rid,
    # sequence)], the other slots')
    tapped_at, tapped = [], []
    routed = []                  # (choices, rows) of every call, on the device
    rids = {}                    # (caller, request of the caller) -> rid
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
        rids[c.index, c.j] = c.request.rid

    def tick():
        """One engine tick, then the tokens it delivered."""
        running = list(engine.scheduler.running())
        kv_tokens = [r.cache_len for r in running if not r.prefilling]
        prefill = (any(r.prefilling for r in running)
                   or bool(engine.scheduler.waiting))
        before = engine.last_logits()
        t_a = time.perf_counter()
        with tracing.span("bench/engine.step"):
            engine.step()
        now = time.perf_counter()
        routed.extend(engine.last_expert_choices())
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
        if (tapped_at and now >= tapped_at[0]
                and engine.last_logits() is not before):
            # keep this tick's logits (on the device, as the engine holds
            # them) and what each decoding slot had read when it made them:
            # its sequence but for the tick's own new token
            tapped_at.pop(0)
            logits, slots = engine.last_logits()
            rows = [(r.slot, r.rid, r.sequence_tokens()[:r.cache_len])
                    for r in engine.scheduler.running()
                    if r.slot in slots and not r.prefilling
                    and r.cache_len == len(r.prompt) + len(r.output_tokens)
                    - 1]
            mine = {callers[i].request.rid for i in watched}
            tapped.append((logits, engine.last_index_choices(),
                           [r for r in rows if r[1] in mine],
                           [r for r in rows if r[1] not in mine]))
        delivered.append((now, n_new))
        ticks.append(Tick(now, (now - t_a) * 1e3, prefill, len(running),
                          kv_tokens))
        return now

    # the greedy callers whose requests the reference reads: their served
    # tokens, and at the tapped ticks their logits and selections, all from
    # one forward pass a request
    greedy_callers = [c.index for c in callers
                      if c.index not in loop.sampled]
    watched = set(np.random.default_rng([cell.seed, 4]).choice(
        greedy_callers, min(mix["checked_requests"], len(greedy_callers)),
        replace=False).tolist())

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
    tapped_at[:] = sorted(t0 + seconds * u for u in np.random.default_rng(
        [cell.seed, 3]).uniform(0.05, 0.95, mix["checked_ticks"]))
    while tick() < t0 + seconds:
        pass
    t1 = time.perf_counter()
    if trace is not None:
        trace = tracing.stop(trace)
    compiles = {"decode": engine.decode_compile_count(),
                "prefill": engine.prefill_compile_count()}
    snapshot = engine.registry.snapshot()
    latent_bytes = snapshot.get("serving/kv_latent_bytes_per_token")
    dsa = {"dsa_" + name.split("/")[-1]: snapshot.get(name)
           for name in ("dsa/rows_scanned", "dsa/rows_selected",
                        "dsa/prefill_rows_scanned",
                        "dsa/prefill_rows_selected")}
    memory = cell.read_memory()
    preemptions = engine.scheduler.preemptions
    occupancy = engine.scheduler.kv_occupancy()
    engine.scheduler.check()

    inside = lambda rows: [r for r in rows if t0 < r[0] <= t1]  # noqa: E731
    tokens = sum(n for _, n in inside(delivered))
    done = inside(finished)
    bad = [f for f in done if f.state is not RequestState.FINISHED
           or len(f.tokens) != loop.lengths(f.caller, f.j)[1]]
    window_ticks = inside(ticks)

    # -- the engine goes, then the reference reads the watched callers'
    # requests (a request finished in the window, else the one running),
    # and any other that a tapped row names
    live = {rids[c.index, c.j]: (np.asarray(c.request.prompt),
                                 list(c.request.output_tokens))
            for c in callers}
    ended = {rids[f.caller, f.j]: (f.prompt, f.tokens) for f in finished}
    del engine
    parallel.destroy_model_parallel()
    jax.clear_caches()
    rng = np.random.default_rng([cell.seed, 2])
    checks = {}                   # rid -> (prompt, served, greedy)
    for i in sorted(watched):
        mine = sorted((f for f in done if f.caller == i),
                      key=lambda f: -len(f.tokens))
        if mine:
            checks[rids[i, mine[0].j]] = (mine[0].prompt, mine[0].tokens,
                                          True)
        elif len(callers[i].request.output_tokens) > 1:
            checks[rids[i, callers[i].j]] = live[rids[i, callers[i].j]] + (
                True,)
    t_ref = time.perf_counter()
    got, seq_rids, seq_at, picked = [], [], [], []
    for logits, selected, rows, others in tapped:
        # the watched requests' rows, and others only where they are fewer
        # than checked_rows
        rows = rows[:mix["checked_rows"]]
        pick = rng.choice(len(others), min(len(others), mix["checked_rows"]
                                           - len(rows)), replace=False)
        rows += [others[i] for i in sorted(pick)]
        host = np.asarray(logits)
        got += [host[slot, 0] for slot, _, _ in rows]
        chosen_rows = np.asarray(selected)
        picked += [chosen_rows[:, slot] for slot, _, _ in rows]
        seq_rids += [rid for _, rid, _ in rows]
        seq_at += [len(seq) - 1 for _, _, seq in rows]
        for _, rid, seq in rows:
            if rid not in checks:
                prompt, served = live.get(rid) or ended[rid]
                checks[rid] = (prompt, served, False)
    del tapped
    # what the program's routers chose at every position the reference is
    # about to read, for it to follow
    wanted = {rid: len(prompt) + len(served) - 1
              for rid, (prompt, served, _) in checks.items()}
    chosen = choices_by_request(routed, wanted)
    del routed
    # the longest sequence of any caller, not only of those read: the same
    # length in every run of a cell (its prompts are the same set, dealt
    # anew), so that the reference's programs compile once and the compile
    # cache serves every later run
    pad = -(-max([len(p) + len(t) - 1 for p, t in live.values()]
                 + list(wanted.values())) // PAD_STEP) * PAD_STEP
    unknown = sum(int(np.sum(c[0, :, 0] < 0)) for c in chosen.values())
    records = []                 # of every sequence the reference reads

    want = [None] * len(got)
    index = [None] * len(got)
    widest, n_checked = float("nan"), 0
    for rid, (prompt, served, greedy) in checks.items():
        here = [i for i, r in enumerate(seq_rids) if r == rid]
        records.append({"chosen": chosen[rid][:, :wanted[rid]]})
        ix = {"at": [seq_at[i] for i in here]} if here else None
        rows = reference.served_logits(weights, prompt, served, sz, pad,
                                       routing=records[-1], index=ix)
        if greedy:
            pick = jnp.take_along_axis(rows, jnp.asarray(served)[:, None],
                                       1)[:, 0]
            widest = float(np.nanmax([widest, float(jnp.max(
                jnp.max(rows, axis=-1) - pick))]))
            n_checked += len(served)
        for j, i in enumerate(here):
            want[i] = np.asarray(rows[seq_at[i] - (len(prompt) - 1)])
            index[i] = {"scores": ix["scores"][:, j:j + 1]}
        del rows                  # off the device before the next pass
    got = np.stack(got) if got else np.zeros((0, 1))
    want = np.stack(want) if len(want) else got
    values = numbers(got, want, max([0.0] + [r["margin"] for r in records]),
                     widest)
    topk = sz["kinds"][0]["index_topk"]
    values["index_choice_margin"] = max([0.0] + [
        reference.selection_margin(ix["scores"][layer, 0], mine[layer], topk)
        for ix, mine in zip(index, picked)
        for layer in range(len(mine))])
    # tokens whose choice was not the reference's own in some expert layer
    followed = sum(r["chosen"].shape[1] for r in records)
    flipped = sum(int(np.sum(
        (np.sort(r["chosen"], -1)
         != np.sort(r["own"][:, :r["chosen"].shape[1]], -1)).any((0, 2))))
        for r in records)
    compared = held(values, cell.limits)
    compared.update({
        "requests_failed": (len(bad), 0),
        "decode_compiles": (compiles["decode"], 1),
        "prefill_compiles": (compiles["prefill"], 1)})

    gap_ms = [v for _, v in inside(gaps)]
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
            "tpot_ms_p95": float(np.percentile(gap_ms, 95)),
            "slowest_tick_ms": max(k.ms for k in window_ticks),
            "tick_excess_ms": excess_ms,
            "gc_pause_ms": cell.gc_pause_ms(t0, t1),
            "checked_requests": sum(g for _, _, g in checks.values()),
            "checked_tokens": n_checked,
            "checked_logit_rows": len(got),
            **{k: v for k, v in values.items() if k not in cell.limits},
            "choices_followed_tokens": followed,
            "choices_flipped_tokens": flipped,
            "choices_unknown_positions": unknown,
            "preemptions": preemptions, "kv_occupancy": occupancy,
            "kv_latent_bytes_per_token": latent_bytes, **dsa,
            "warmup_ticks": n_warm, "bytes_at_rest": max(at_rest),
            "reference_s": time.perf_counter() - t_ref,
            "block_size": mix["engine"]["block_size"],
            "ticks_seen": window_ticks, "memory": memory, "sizes": sz},
        "trace": trace,
    }

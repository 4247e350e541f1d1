"""Driver of the training cell of a model whose layers are of more than one
kind: ``build_gpt_3d`` (its hybrid trainer behind it) + FusedAdam + the
``DynamicLossScale`` sentinel, built and driven as ``drivers/train.py``
drives the GPT cell.

One compiled step with its state is built in set-up, driven from the seed
through its warm-up steps (the first three are what ``correct`` compares)
and handed to the window.  The window dispatches steps without blocking on
each: the statistics of a step are fetched ``steps_in_flight`` steps later,
so that a pause of the host shorter than the queued work leaves the device
busy; the one block that counts is on the last step.  The window opens at
the dispatch of the first counted step and closes when the last dispatched
step has finished; the rate is all tokens of all counted steps over ``t1 -
t0``.

The routers' choices of the checked steps come out of the step with its
statistics (``TrainStats.moe_choices``): bfloat16 rounds a hidden state
further than the eighth and ninth scores lie apart, so the reference
follows the program's choices and holds each to a margin.
"""

import collections
import time

import numpy as np

from drivers.train import batch_of  # noqa: F401  (the train drivers' one)
from lib import check, tracing

# the program's trainer of hybrid layers: a program without it (this
# configuration's parent) stops here, before any step of set-up
from apex_tpu.transformer.testing.hybrid_train import (  # noqa: F401
    build_hybrid_train)


def choices_by_sequence(chosen, batch: int, seq: int) -> np.ndarray:
    """``[microbatches, expert layers, mb * seq, k]``, as the step hands the
    routers' choices out, -> ``[batch, expert layers, seq, k]``: a row a
    sequence, as the reference follows them."""
    chosen = np.asarray(chosen, np.int32)
    m, layers, _, k = chosen.shape
    return chosen.reshape(m, layers, batch // m, seq, k).transpose(
        0, 2, 1, 3, 4).reshape(batch, layers, seq, k)


def run(cell):
    import jax
    import jax.numpy as jnp

    from apex_tpu import parallel
    from apex_tpu.amp.scaler import DynamicLossScale
    from apex_tpu.observability import MetricRegistry, TrainStatsLogger
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import sentinel_init
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    from drivers import trinity_program

    traffic, config, reference = cell.traffic, cell.config, cell.reference
    sz = reference.sizes_of(config)
    lay = traffic["layout"]
    if lay["dp"] * lay["pp"] * lay["tp"] != len(cell.devices):
        raise SystemExit(f"layout {lay} does not cover {len(cell.devices)} "
                         "devices")
    hyper = traffic["adam"]
    batch, seq = traffic["batch"], traffic["seq"]
    tokens_per_step = batch * seq
    key = trinity_program.seed_key(cell.seed)
    dtype = {"bfloat16": jnp.bfloat16,
             "float32": jnp.float32}[config["assumed"]["dtype"]]

    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=lay["tp"],
        pipeline_model_parallel_size=lay["pp"], devices=cell.devices)
    cfg = trinity_program.transformer_config(sz, dtype)
    init_fn, _, make_train_step = build_gpt_3d(
        cfg, num_microbatches=traffic["microbatches"], mesh=mesh)
    sample = jnp.zeros((batch, seq), jnp.int32)
    template, specs = init_fn(jax.random.PRNGKey(0), sample)
    cell.mark("program_init")
    make_weights = trinity_program.weights_maker(template, reference, sz)
    del template
    params = make_weights(key)
    jax.block_until_ready(params)
    cell.mark("weights")

    scaler = DynamicLossScale()
    opt = FusedAdam(lr=hyper["lr"], betas=(hyper["beta1"], hyper["beta2"]),
                    eps=hyper["eps"], weight_decay=0.0)
    state = opt.init(params)
    sent = sentinel_init(scaler)
    step_fn = make_train_step(opt, specs, scaler=scaler, collect_stats=True)
    # the old parameters, optimizer state and sentinel are donated, as a
    # trainer's loop does
    compiled = jax.jit(step_fn, donate_argnums=(0, 1, 3)).lower(
        params, state, sample, sent).compile()
    cell.mark("compiled")
    stats_logger = TrainStatsLogger(MetricRegistry())
    vocab = sz["vocab"]

    # -- warm-up: the window's own call and feed; the first steps are read
    @jax.jit
    def first_grad_norms(exp_avg):
        # Adam's first moment after one step is (1 - beta1) * gradient
        norms = reference.leaf_norms(
            trinity_program.to_reference_names(exp_avg), sz)
        return {k: x / (1.0 - hyper["beta1"]) for k, x in norms.items()}

    @jax.jit
    def change_norms(params, key):
        # the weights the steps began from are made again from the seed
        # inside this program: they are its temporaries and never an array
        # beside the trainer's state, so the memory peak is the trainer's
        return reference.delta_norms(
            trinity_program.to_reference_names(params),
            trinity_program.to_reference_names(make_weights(key)), sz)

    n_checked = traffic["checked_steps"]
    seen = {"losses": [], "chosen": []}
    n_step = 0

    def one_step(params, state, sent, block):
        nonlocal n_step
        tokens = batch_of(cell.seed, n_step, traffic, vocab)
        with tracing.span("bench/train.step"):
            params, state, sent, _, stats = compiled(params, state, tokens,
                                                     sent)
            if block:
                jax.block_until_ready(stats)
        n_step += 1
        return params, state, sent, stats

    for i in range(max(traffic["warmup_steps"], n_checked)):
        params, state, sent, stats = one_step(params, state, sent, True)
        if i < n_checked:
            fetched = stats_logger.fetch(stats)
            seen["losses"].append(fetched["loss"])
            seen["chosen"].append(choices_by_sequence(
                stats.moe_choices, batch, seq))
        if i == 0:
            seen["grad_norms"] = {k: float(v) for k, v in first_grad_norms(
                state.slots["exp_avg"]).items()}
        if i == n_checked - 1:
            seen["delta_norms"] = {k: float(v) for k, v in change_norms(
                params, key).items()}
    jax.block_until_ready((params, state))
    cell.setup_done()

    # -- the window
    trace = tracing.start(cell) if cell.trace else None
    seconds = traffic["trace_seconds"] if cell.trace else cell.seconds
    in_flight = 1 if cell.trace else traffic["steps_in_flight"]
    step_ms, fetched, pending = [], [], collections.deque()
    n0 = n_step
    t0 = last = time.perf_counter()
    while True:
        params, state, sent, stats = one_step(params, state, sent,
                                              bool(cell.trace))
        pending.append(stats)
        if len(pending) > in_flight:
            fetched.append(stats_logger.fetch(pending.popleft()))
        now = time.perf_counter()
        step_ms.append((now - last) * 1e3)
        last = now
        if now >= t0 + seconds:
            break
    while pending:                                    # blocks on the last
        fetched.append(stats_logger.fetch(pending.popleft()))
    t1 = time.perf_counter()
    if trace is not None:
        trace = tracing.stop(trace)
    steps = n_step - n0
    skipped = int(sent.skipped_steps)
    finite = all(np.isfinite(f["loss"]) and f["nonfinite_leaves"] == 0
                 for f in fetched)
    # [steps, microbatches, expert layers, held]
    moe_pairs = np.asarray([f["moe_pairs"] for f in fetched], np.int64)
    load_peak = float(np.mean(moe_pairs.max(-1)
                              / np.maximum(moe_pairs.mean(-1), 1e-9)))
    memory = cell.read_memory()

    # -- the program's state goes, then the reference follows the first steps
    del params, state, sent, stats, compiled
    parallel.destroy_model_parallel()
    jax.clear_caches()
    batches = [jnp.asarray(batch_of(cell.seed, i, traffic, vocab))
               for i in range(n_checked)]
    t_ref = time.perf_counter()
    ref = reference.train(key, batches, sz, hyper, chosen=seen["chosen"])
    compared = check.training(seen, ref, cell.limits)
    compared["router_choice_margin"] = (
        ref["router_choice_margin"], cell.limits["router_choice_margin"])
    compared["skipped_steps"] = (skipped, 0)
    compared["nonfinite_steps"] = (0 if finite else 1, 0)

    return {
        "attempted": steps, "failed": skipped,
        "compared": compared,
        "end_to_end": {
            "train_tokens_per_s": steps * tokens_per_step / (t1 - t0)},
        "observed": {
            "steps": steps, "window_s": t1 - t0, "step_ms": step_ms,
            "loss_gaps": str(check.loss_gaps(seen, ref)),
            "choices_flipped": ref["choices_flipped"],
            "expert_load_peak": load_peak,
            "slowest_step_ms": max(step_ms),
            "tokens_per_step": tokens_per_step,
            "reference_s": time.perf_counter() - t_ref,
            "gc_pause_ms": cell.gc_pause_ms(t0, t1),
            "memory": memory, "sizes": sz, "moe_pairs": moe_pairs,
            "losses": seen["losses"]},
        "trace": trace,
    }

"""Driver of the training cells: ``build_gpt_3d`` + FusedAdam + the
``DynamicLossScale`` sentinel, as ``chip_smoke.train_phase`` builds them.

One compiled step with its state is built in set-up, driven from the seed
through its warm-up steps (the first three are what ``correct`` compares)
and handed to the window.  The window dispatches steps without blocking on
each: the statistics of a step are fetched ``steps_in_flight`` steps later,
as a trainer that logs every few steps does, so that a pause of the host
shorter than the queued work leaves the device busy; the one block that
counts is on the last step.  The window opens at the dispatch of the first
counted step and closes when the last dispatched step has finished; the
rate is all tokens of all counted steps over ``t1 - t0``.
"""

import collections
import time

import numpy as np

from lib import check, tracing


def batch_of(seed: int, step: int, traffic: dict, vocab: int) -> np.ndarray:
    """Batch ``step`` of a seed: rows of uniform token ids, all different."""
    rng = np.random.default_rng([seed, step])
    return rng.integers(0, vocab, (traffic["batch"], traffic["seq"]),
                        dtype=np.int32)


def run(cell):
    import jax
    import jax.numpy as jnp

    from apex_tpu import parallel
    from apex_tpu.amp.scaler import DynamicLossScale
    from apex_tpu.observability import MetricRegistry, TrainStatsLogger
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import sentinel_init
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    from drivers import gpt_program

    traffic, config, reference = cell.traffic, cell.config, cell.reference
    sz = reference.sizes_of(config)
    lay = traffic["layout"]
    dp, pp, tp = lay["dp"], lay["pp"], lay["tp"]
    if dp * pp * tp != len(cell.devices):
        raise SystemExit(f"layout {lay} does not cover {len(cell.devices)} "
                         "devices")
    vpp = sz["layers"] // pp
    hyper = traffic["adam"]
    tokens_per_step = traffic["batch"] * traffic["seq"]
    key = gpt_program.seed_key(cell.seed)

    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=tp, pipeline_model_parallel_size=pp,
        virtual_pipeline_model_parallel_size=vpp if pp > 1 else None,
        devices=cell.devices)
    cfg = gpt_program.transformer_config(
        config, tp, sequence_parallel=lay["sequence_parallel"])
    init_fn, _, make_train_step = build_gpt_3d(
        cfg, num_chunks=vpp, num_microbatches=traffic["microbatches"],
        mesh=mesh)
    sample = jnp.zeros((traffic["batch"], traffic["seq"]), jnp.int32)
    template, specs = init_fn(jax.random.PRNGKey(0), sample)
    cell.mark("program_init")
    make_weights = gpt_program.weights_maker(template, reference, sz)
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
    # trainer's loop does: the step then fits without rematerialisation
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
            gpt_program.to_reference_names(exp_avg), sz)
        return {k: x / (1.0 - hyper["beta1"]) for k, x in norms.items()}

    @jax.jit
    def change_norms(params, key):
        # the weights the steps began from are made again from the seed
        # inside this program: they are its temporaries and never an array
        # beside the trainer's state, so the memory peak is the trainer's
        return reference.delta_norms(
            gpt_program.to_reference_names(params),
            gpt_program.to_reference_names(make_weights(key)), sz)

    n_checked = traffic["checked_steps"]
    seen = {"losses": []}
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
            seen["losses"].append(stats_logger.fetch(stats)["loss"])
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
    memory = cell.read_memory()

    # -- the program's state goes, then the reference follows the first steps
    del params, state, sent, stats, compiled
    parallel.destroy_model_parallel()
    jax.clear_caches()
    batches = [jnp.asarray(batch_of(cell.seed, i, traffic, vocab))
               for i in range(n_checked)]
    t_ref = time.perf_counter()
    ref = reference.train(
        jax.jit(lambda k: reference.init_weights(k, sz),
                out_shardings=reference.weight_shardings(cell.devices, sz))(
                    key),
        batches, sz, hyper, traffic["reference_rows_per_block"])
    compared = check.training(seen, ref, cell.limits)
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
            "slowest_step_ms": max(step_ms),
            "tokens_per_step": tokens_per_step,
            "reference_s": time.perf_counter() - t_ref,
            "gc_pause_ms": cell.gc_pause_ms(t0, t1),
            "memory": memory, "sizes": sz,
            "losses": seen["losses"]},
        "trace": trace,
    }

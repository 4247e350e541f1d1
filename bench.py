"""apex_tpu benchmark suite over the BASELINE.json config matrix.

Headline (the ONE JSON line, driver contract): ResNet-50 mixed-precision
(O2) training throughput in images/sec/chip — the reference's flagship
config (``examples/imagenet/main_amp.py``: ResNet-50, amp O2, FusedSGD).
``vs_baseline`` normalizes against an adopted per-A100 figure for Apex RN50
AMP training (the repo publishes no numbers — BASELINE.md): NVIDIA NGC
PyTorch+Apex RN50 AMP convergence runs report ~2.5k images/sec per A100-80GB.

The ``extras`` field carries the rest of the BASELINE.json matrix, each
individually run in its own process:

- ``resnet50_lamb_syncbn``  — RN50 + FusedLAMB + SyncBatchNorm (32k-style)
- ``bert_large``            — BERT-large encoder train step (fused
                              LN/dense/Adam), tokens/sec
- ``gpt_flash``             — flagship GPT with Pallas flash attention,
                              tokens/sec and **MFU**
- ``gpt_flash_fp8``         — same with delayed-scaling fp8 GEMMs
                              (``vs_bf16`` stated when both rows share a
                              platform)
- ``gpt_long_context``      — the seq-8192 flash config
- ``tp_gpt``                — tensor-parallel GPT train step (shard_map over
                              the tp axis; tp=#devices); A/B-measures the
                              ring-decomposed collective matmul
                              (``overlap_comm`` on/off — ``vs_monolithic``
                              < 1 = the overlap schedule wins)
- ``fused_adam_step``       — optimizer step-time microbench (the
                              "fused-optimizer step time" BASELINE metric);
                              measures per-leaf AND chunked-flat configs
- ``zero_adam_step``        — ZeRO step-time over the dp mesh: flat-bucket
                              vs per-leaf ``DistributedFusedAdam`` vs
                              replicated ``FusedAdam`` (``vs_per_leaf``
                              < 1 = the bucketed exchange wins)
- ``ckpt_save_restore``     — checkpoint-path wall-time: save/verify/
                              restore for the flat vs sharded layouts
                              (``vs_sharded`` = flat/sharded total), so
                              crash-safety machinery (checksums, fsync,
                              manifest commit) shows regressions
- ``ckpt_reshard``          — restore-anywhere wall-time: the same
                              flat-bucket ZeRO checkpoint restored onto
                              the writing mesh vs reshard-restored onto
                              dp/2 (``vs_same_mesh`` = reshard/plain —
                              the measured cost of an elastic resume)
- ``telemetry_overhead``    — instrumented vs bare 3D GPT train step
                              (in-graph TrainStats, ``observability``):
                              ``vs_bare`` pins "telemetry is free"
                              numerically (gate: <= 1.05 on the CPU mesh)
- ``input_pipeline``        — host decode + packed decode-free loader rates
                              vs the chip's consumption rate
- ``real_data_rn50``        — end-to-end real-JPEG training through the
                              packed loader (``vs_synthetic`` vs the
                              same-run headline)

Processes: every row runs in its own child (``bench.py --one <name>``)
under a hard timeout, one at a time, so each holds the chip alone and one
failed or hung row cannot take the others with it.  The parent never
initializes a JAX backend.  A row runs on the TPU JAX reports, at the real
shapes; without a TPU the child fails.  ``JAX_PLATFORMS=cpu python
bench.py`` is the explicit dry run: tiny shapes on the CPU (eight virtual
devices for the mesh rows), useful to see that every row still executes
and never produced by the TPU path as a fallback.  Every row names its
``platform``, ``device_kind`` and ``device_count``.  The exit code is
non-zero when any row failed.

This file predates the chip tool and is due to be rebuilt as a list of
cells writing ``PERF_LEDGER.jsonl`` (ROADMAP S1); until then its numbers
are builder records, not the driver's.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from functools import partial


def _log(msg: str) -> None:
    print(f"bench[{time.strftime('%H:%M:%S')}]: {msg}", file=sys.stderr,
          flush=True)

_REPO = os.path.dirname(os.path.abspath(__file__))


def _env_int(name: str, default: int) -> int:
    """Positive-int env knob; warn and fall back on malformed values (an
    operator typo must not cost a bench row)."""
    try:
        val = int(os.environ.get(name, str(default)))
        if val <= 0:
            raise ValueError(val)
        return val
    except ValueError:
        _log(f"ignoring invalid {name}={os.environ.get(name)!r}; "
             f"using {default}")
        return default


def adopted_baseline() -> float:
    """The adopted reference number for ``vs_baseline`` — read from
    BASELINE.json ("adopted" section, provenance recorded there and in
    BASELINE.md) rather than hardcoded here."""
    try:
        with open(os.path.join(_REPO, "BASELINE.json")) as f:
            rec = json.load(f)
        return float(rec["adopted"]["rn50_amp_a100_images_per_sec"]["value"])
    except Exception as e:
        _log(f"BASELINE.json adopted baseline unreadable ({e!r}); "
             "using 2500.0")
        return 2500.0

# bf16 peak FLOP/s per chip: ONE table, owned by the observability
# subsystem (its MFU metric and the bench rows must never disagree).
from apex_tpu.observability.metrics import peak_flops_reason  # noqa: E402


def _peak_flops(device) -> float:
    """bf16 peak FLOP/s of ``device`` from the one sourced table; a device
    that is not in it is an error, never a default."""
    peak, reason = peak_flops_reason(device)
    if peak is None:
        raise RuntimeError(f"no MFU without a peak: {reason}")
    return peak


def _timeit(jax, step, state, steps):
    """Run ``state = step(*state)`` ``steps`` times; return (dt, state)."""
    t0 = time.perf_counter()
    for _ in range(steps):
        state = step(*state)
    jax.block_until_ready(state)
    return time.perf_counter() - t0, state


# ---------------------------------------------------------------------------
# ResNet-50 benches
# ---------------------------------------------------------------------------

def resnet_setup(jax, on_tpu, optimizer_name, sync_bn=False):
    """Build the RN50 train step — the ONE definition of the resnet50_*
    workloads, shared by the bench and ``examples/profile_resnet.py`` so
    a profile explains exactly the numbers the bench records.

    Returns ``(train_step, state0, meta)`` where ``state0 = (params,
    batch_stats, opt_state, sharded_batch)`` is the step's carry (the
    bench threads the batch through) and ``meta`` carries the record
    fields.  Call ``meta["mesh_cleanup"]()`` when done.
    """
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.models import ResNet50
    from apex_tpu.optimizers import FusedLAMB, FusedSGD
    from apex_tpu.parallel import (
        collectives as cc,
        dp_shard_batch,
        mesh as mesh_lib,
        replicate,
    )
    from apex_tpu.parallel.distributed import all_reduce_gradients

    n_chips = len(jax.devices())
    # APEX_TPU_RN50_BATCH: batch-per-chip sweep knob for chip runs
    # (the shipped default stays 128 = the reference recipe's per-GPU
    # batch; a sweep that finds a better point records it in
    # bench_results/ and the default is bumped by hand, keeping records
    # comparable)
    batch_per_chip = _env_int("APEX_TPU_RN50_BATCH", 128) if on_tpu else 4
    image_size = 224 if on_tpu else 32
    steps = 20 if on_tpu else 3
    batch = batch_per_chip * n_chips

    mesh = mesh_lib.initialize_model_parallel()
    try:
        policy = amp.policy("O2")
        dp_axes = ("dcn", "dp")
        model = ResNet50(num_classes=1000,
                         axis_name="dp" if sync_bn else None,
                         dtype=policy.compute_dtype)

        x0 = jnp.zeros((2, image_size, image_size, 3), jnp.float32)
        variables = model.init(jax.random.PRNGKey(0), x0, train=True)
        params = policy.cast_to_param(variables["params"])
        batch_stats = variables["batch_stats"]
        if optimizer_name == "lamb":
            # APEX_TPU_LAMB_FLAT=0 falls back to the per-leaf update for a
            # live A/B of the chunked flat-buffer path (the r4 weak-#3
            # diagnosis lever); the record carries which path ran
            opt = FusedLAMB(lr=1e-3, weight_decay=1e-2,
                            master_weights=policy.master_weights,
                            flat=os.environ.get(
                                "APEX_TPU_LAMB_FLAT", "1") != "0")
        else:
            opt = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4,
                           master_weights=policy.master_weights)
        opt_state = opt.init(params)

        def loss_fn(params, batch_stats, batch):
            x, y = batch
            logits, mutated = model.apply(
                {"params": params, "batch_stats": batch_stats},
                policy.cast_to_compute(x),
                train=True,
                mutable=["batch_stats"],
            )
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.mean(logp[jnp.arange(y.shape[0]), y])
            return loss, mutated["batch_stats"]

        def local_step(params, batch_stats, opt_state, batch):
            (loss, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch_stats, batch)
            if sync_bn:
                # shard_map path: explicit dp gradient reduction (the pjit
                # path gets it implicitly from the global-mean loss).
                grads = all_reduce_gradients(grads, dp_axes)
            params, opt_state = opt.step(grads, opt_state, params)
            return params, new_stats, opt_state, batch

        if sync_bn:
            rep = lambda tree: jax.tree_util.tree_map(lambda _: P(), tree)

            def sharded_step(params, batch_stats, opt_state, batch):
                bspec = jax.tree_util.tree_map(
                    lambda x: P(dp_axes, *([None] * (jnp.ndim(x) - 1))),
                    batch)
                return cc.shard_over(
                    local_step, mesh=mesh,
                    in_specs=(rep(params), rep(batch_stats),
                              rep(opt_state), bspec),
                    out_specs=(rep(params), rep(batch_stats),
                               rep(opt_state), bspec),
                )(params, batch_stats, opt_state, batch)

            train_step = jax.jit(sharded_step, donate_argnums=(0, 1, 2))
        else:
            train_step = partial(jax.jit, donate_argnums=(0, 1, 2))(
                local_step)

        params = replicate(params, mesh)
        batch_stats = replicate(batch_stats, mesh)
        opt_state = replicate(opt_state, mesh)

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(batch, image_size, image_size, 3),
                        jnp.float32)
        y = jnp.asarray(rng.randint(0, 1000, size=(batch,)))
        sharded = dp_shard_batch((x, y), mesh)
    except BaseException:
        mesh_lib.destroy_model_parallel()
        raise

    meta = {
        "n_chips": n_chips,
        "batch": batch,
        "batch_per_chip": batch_per_chip,
        "image_size": image_size,
        "steps": steps,
        "optimizer": optimizer_name,
        "sync_bn": sync_bn,
        "mesh_cleanup": mesh_lib.destroy_model_parallel,
    }
    if optimizer_name == "lamb":
        meta["lamb_flat"] = opt.flat
    return train_step, (params, batch_stats, opt_state, sharded), meta


def _resnet_bench(jax, on_tpu, optimizer_name, sync_bn=False):
    train_step, st0, meta = resnet_setup(jax, on_tpu, optimizer_name,
                                         sync_bn=sync_bn)
    try:
        batch, steps = meta["batch"], meta["steps"]
        _log(f"resnet50({optimizer_name}): compile start")
        t0 = time.perf_counter()
        state = train_step(*st0)
        jax.block_until_ready(state)
        _log(f"resnet50({optimizer_name}): compiled in "
             f"{time.perf_counter() - t0:.1f}s; timing {steps} steps")
        dt, _ = _timeit(jax, train_step, state, steps)

        ips_per_chip = batch * steps / dt / meta["n_chips"]
        rec = {
            "value": round(ips_per_chip, 1),
            "unit": "images/sec/chip",
            "n_chips": meta["n_chips"],
            "batch_per_chip": meta["batch_per_chip"],
            "image_size": meta["image_size"],
            "optimizer": optimizer_name,
        }
        if "lamb_flat" in meta:
            rec["lamb_flat"] = meta["lamb_flat"]
        return rec
    finally:
        meta["mesh_cleanup"]()


def bench_resnet50_o2(jax, on_tpu):
    return _resnet_bench(jax, on_tpu, "sgd")


def bench_resnet50_lamb_syncbn(jax, on_tpu):
    # BASELINE.json "RN50 FusedLAMB 32k+SyncBN": SyncBatchNorm with the dp
    # axis genuinely bound (shard_map), cross-replica Welford psum included
    # in the measured step (a single chip binds a size-1 axis).
    return _resnet_bench(jax, on_tpu, "lamb", sync_bn=True)


# ---------------------------------------------------------------------------
# Transformer benches
# ---------------------------------------------------------------------------

def _lm_train_flops(cfg, n_params, batch, seq):
    """fwd+bwd FLOPs per step: 6*N*tokens + attention 12*L*h*B*S^2."""
    return (6.0 * n_params * batch * seq
            + 12.0 * cfg.num_layers * cfg.hidden_size * batch * seq * seq)


def bench_bert_large(jax, on_tpu):
    import jax.numpy as jnp

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
    from apex_tpu.transformer.testing import BertModel, TransformerConfig

    # use_flash_attention: BERT's padding mask rides the flash kernels'
    # segment-id mechanism (round-2 addition); the bench previously ran
    # the unfused-softmax path and still hit 0.488 MFU on v5e.
    if on_tpu:
        cfg = TransformerConfig(
            hidden_size=1024, num_layers=24, num_attention_heads=16,
            padded_vocab_size=30592, max_position_embeddings=512,
            hidden_dropout=0.0, attention_dropout=0.0, tensor_axis=None,
            use_flash_attention=True, dtype=jnp.bfloat16,
        )
        batch, seq, steps = 8, 512, 10
    else:
        cfg = TransformerConfig(
            hidden_size=64, num_layers=2, num_attention_heads=4,
            padded_vocab_size=512, max_position_embeddings=64,
            hidden_dropout=0.0, attention_dropout=0.0, tensor_axis=None,
            use_flash_attention=True,
        )
        batch, seq, steps = 2, 32, 2

    model = BertModel(cfg)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    mask = jnp.ones((batch, seq), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens, mask)["params"]
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    opt = FusedAdam(lr=1e-4)
    state = opt.init(params)

    def loss_fn(p):
        lm_logits, bin_logits = model.apply({"params": p}, tokens, mask)
        # flatten the [s, b, v] logits in native order (transposing only
        # the tiny labels) and keep half logits half through the CE kernel
        # — the loss is a mean, so row order is irrelevant (the gpt_loss
        # bandwidth note, standalone_gpt.py)
        lm = softmax_cross_entropy_loss(
            lm_logits.reshape(-1, lm_logits.shape[-1]),
            tokens.T.reshape(-1), padding_idx=-1, half_to_float=True)
        sop = -jax.nn.log_softmax(bin_logits)[:, 0]
        return jnp.mean(lm) + jnp.mean(sop)

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, state = opt.step(grads, state, params)
        return params, state

    _log("compile start")
    t0 = time.perf_counter()
    st = step(params, state)
    jax.block_until_ready(st)
    _log(f"compiled in {time.perf_counter() - t0:.1f}s; timing %d steps"
         % steps)
    dt, _ = _timeit(jax, step, st, steps)

    tps = batch * seq * steps / dt
    flops = _lm_train_flops(cfg, n_params, batch, seq) * steps / dt
    return {
        "value": round(tps, 1),
        "unit": "tokens/sec/chip",
        "mfu": round(flops / _peak_flops(jax.devices()[0]), 4)
        if on_tpu else None,
        "params": int(n_params),
        "batch": batch,
        "seq": seq,
    }


def _tuned_gpt_batch(jax):
    """Per-chip batch from ``bench_results/gpt_batch_tuned.json`` (written
    by a TPU sweep of ``examples/tune_gpt_batch.py`` at the flagship seq),
    adopted only on a matching ``device_kind``."""
    from apex_tpu.utils.tuning import load_tuned_record

    rec = load_tuned_record("gpt_batch_tuned.json", jax)
    try:
        if rec and int(rec.get("base_batch", 0)) > 0:
            return int(rec["base_batch"])
    except (TypeError, ValueError):
        pass
    return None


def gpt_flash_setup(jax, on_tpu, seq=None, fp8=False):
    """Build the flagship GPT-124M flash train step — the ONE definition
    of the ``gpt_flash`` workload, shared by this bench, the block-size
    sweep (``examples/tune_flash_blocks.py``), and the profiler
    (``examples/profile_gpt.py``) so their configs cannot drift.

    Returns ``(cfg, step, st0, batch, seq, n_params)`` where ``step`` is
    the donated jitted train step and ``st0 = (params, opt_state,
    fp8_state)`` its initial carry (``fp8_state`` is ``{}`` when ``fp8``
    is off).  Batch policy: 8 up to seq 1024, token-budget-rescaled above.
    """
    import jax.numpy as jnp

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.testing import GPTModel, TransformerConfig

    if on_tpu:
        seq = seq or 1024
        # APEX_TPU_GPT_BATCH: per-chip batch sweep knob for chip runs.
        # Precedence: env > hardware-matched tuned file
        # (written by examples/tune_gpt_batch.py from a TPU sweep, the
        # flash-blocks auto-land pattern) > shipped 8.  The tuned file is
        # consulted only when the env knob is absent (sweep children set
        # it, so a stale tuned record can't contaminate a sweep).  The
        # record always carries the batch actually used.
        base_batch = (_env_int("APEX_TPU_GPT_BATCH", 8)
                      if "APEX_TPU_GPT_BATCH" in os.environ
                      else (_tuned_gpt_batch(jax) or 8))
        batch = base_batch if seq <= 1024 else max(
            1, base_batch * 1024 // seq)
        cfg = TransformerConfig(
            hidden_size=768, num_layers=12, num_attention_heads=12,
            padded_vocab_size=50304, max_position_embeddings=seq,
            hidden_dropout=0.0, attention_dropout=0.0, tensor_axis=None,
            use_flash_attention=True, dtype=jnp.bfloat16, fp8=fp8,
        )
    else:
        seq = min(seq or 128, 128)
        batch = 2
        cfg = TransformerConfig(
            hidden_size=64, num_layers=2, num_attention_heads=4,
            padded_vocab_size=512, max_position_embeddings=seq,
            hidden_dropout=0.0, attention_dropout=0.0, tensor_axis=None,
            use_flash_attention=True, fp8=fp8,
        )

    model = GPTModel(cfg)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    params = variables["params"]
    fp8_state = dict(variables.get("fp8_meta", {}))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    opt = FusedAdam(lr=1e-4)
    state = opt.init(params)

    def loss_fn(p, fp8_state):
        if not fp8_state:
            return jnp.mean(model.apply({"params": p}, tokens,
                                        labels=tokens)), fp8_state
        losses, mut = model.apply(
            {"params": p, "fp8_meta": fp8_state}, tokens, labels=tokens,
            mutable=["fp8_meta"])
        return jnp.mean(losses), dict(mut)["fp8_meta"]

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, state, fp8_state):
        (_, fp8_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, fp8_state)
        params, state = opt.step(grads, state, params)
        return params, state, fp8_state

    return cfg, step, (params, state, fp8_state), batch, seq, n_params


def _gpt_flash_bench(jax, on_tpu, fp8: bool):
    """Flagship GPT train-step bench; ``fp8=True`` threads the delayed-
    scaling ``fp8_meta`` collection through the step (e4m3 GEMMs for
    qkv/attn-out/fc1/fc2, e5m2 JIT cotangents — the fp8-vs-bf16 delta the
    r2 VERDICT asked to put in the bench extras)."""
    cfg, step, st, batch, seq, n_params = gpt_flash_setup(
        jax, on_tpu, fp8=fp8)
    steps = 10 if on_tpu else 2

    name = "gpt_flash_fp8" if fp8 else "gpt_flash"
    _log(f"{name}: compile start")
    t0 = time.perf_counter()
    st = step(*st)
    jax.block_until_ready(st)
    _log(f"{name}: compiled in {time.perf_counter() - t0:.1f}s; "
         f"timing {steps} steps")
    dt, _ = _timeit(jax, step, st, steps)

    tps = batch * seq * steps / dt
    flops = _lm_train_flops(cfg, n_params, batch, seq) * steps / dt
    rec = {
        "value": round(tps, 1),
        "unit": "tokens/sec/chip",
        "mfu": round(flops / _peak_flops(jax.devices()[0]), 4)
        if on_tpu else None,
        "params": int(n_params),
        "batch": batch,
        "seq": seq,
        "flash_attention": True,
    }
    if fp8:
        rec["fp8"] = True
    return rec


def bench_gpt_flash(jax, on_tpu):
    return _gpt_flash_bench(jax, on_tpu, fp8=False)


def bench_gpt_flash_fp8(jax, on_tpu):
    return _gpt_flash_bench(jax, on_tpu, fp8=True)


def bench_gpt_long_context(jax, on_tpu):
    """Long-context GPT train step: seq 8192 with the Pallas flash kernels.
    The unfused path would materialize [b, h, 8192, 8192] fp32 scores
    (3 GB/head-batch) — this config exists *because* of flash (SURVEY §5
    long-context; the reference caps at 16384 fused-softmax keys / 512
    fmha)."""
    import jax.numpy as jnp

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.transformer.testing import GPTModel, TransformerConfig

    if on_tpu:
        cfg = TransformerConfig(
            hidden_size=768, num_layers=12, num_attention_heads=12,
            padded_vocab_size=50304, max_position_embeddings=8192,
            hidden_dropout=0.0, attention_dropout=0.0, tensor_axis=None,
            use_flash_attention=True, dtype=jnp.bfloat16,
        )
        batch, seq, steps = 1, 8192, 5
    else:
        cfg = TransformerConfig(
            hidden_size=64, num_layers=2, num_attention_heads=4,
            padded_vocab_size=512, max_position_embeddings=512,
            hidden_dropout=0.0, attention_dropout=0.0, tensor_axis=None,
            use_flash_attention=True,
        )
        batch, seq, steps = 1, 512, 2

    model = GPTModel(cfg)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    opt = FusedAdam(lr=1e-4)
    state = opt.init(params)

    def loss_fn(p):
        return jnp.mean(model.apply({"params": p}, tokens, labels=tokens))

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, state):
        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, state = opt.step(grads, state, params)
        return params, state

    _log("long_context: compile start")
    t0 = time.perf_counter()
    st = step(params, state)
    jax.block_until_ready(st)
    _log(f"long_context: compiled in {time.perf_counter() - t0:.1f}s")
    dt, _ = _timeit(jax, step, st, steps)

    tps = batch * seq * steps / dt
    flops = _lm_train_flops(cfg, n_params, batch, seq) * steps / dt
    return {
        "value": round(tps, 1),
        "unit": "tokens/sec/chip",
        "mfu": round(flops / _peak_flops(jax.devices()[0]), 4)
        if on_tpu else None,
        "params": int(n_params),
        "batch": batch,
        "seq": seq,
        "flash_attention": True,
    }


def bench_tp_gpt(jax, on_tpu):
    """Tensor-parallel GPT train step via shard_map over the tp axis
    (tp = all attached devices; tp=1 on the single bench chip still
    exercises the TP code path)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu import parallel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import collectives as cc
    from apex_tpu.transformer import tensor_parallel as tp
    from apex_tpu.transformer.testing import GPTModel, TransformerConfig

    n = len(jax.devices())
    mesh = parallel.initialize_model_parallel(tensor_model_parallel_size=n)
    try:
        if on_tpu:
            cfg = TransformerConfig(
                hidden_size=1024, num_layers=4, num_attention_heads=16,
                padded_vocab_size=50304, max_position_embeddings=1024,
                hidden_dropout=0.0, attention_dropout=0.0,
                tensor_axis="tp", sequence_parallel=n > 1,
                dtype=jnp.bfloat16,
            )
            batch, seq, steps = 8, 1024, 10
        else:
            # heads/hidden must split over tp (8 on the virtual CPU mesh)
            cfg = TransformerConfig(
                hidden_size=128, num_layers=2, num_attention_heads=8,
                padded_vocab_size=512, max_position_embeddings=64,
                hidden_dropout=0.0, attention_dropout=0.0,
                tensor_axis="tp", sequence_parallel=n > 1,
            )
            batch, seq, steps = 2, 64, 2

        model = GPTModel(cfg)
        tokens = jnp.zeros((batch, seq), jnp.int32)

        def tp_init(tokens):
            return model.init(jax.random.PRNGKey(0), tokens)["params"]

        param_specs = tp.infer_param_specs(jax.eval_shape(tp_init, tokens))
        _log("tp_gpt: param specs inferred")

        def shardings_of(spec_tree):
            return jax.tree_util.tree_map(
                lambda s: cc.named_sharding(*s, mesh=mesh), spec_tree,
                is_leaf=lambda x: isinstance(x, P))

        # Init through plain jit with output shardings (the idiomatic
        # SPMD path) rather than shard_map: the r2/r4 900 s timeouts hung
        # before the step compile ever started, i.e. in this setup phase,
        # and a shard_map'd *initializer* is the one nonstandard compile
        # here.  The train step below still goes through shard_map — that
        # is the thing this row exists to measure.
        params = jax.jit(
            tp_init, out_shardings=shardings_of(param_specs))(tokens)
        jax.block_until_ready(params)
        _log("tp_gpt: params initialized")

        def tp_loss(p, t):
            losses = model.apply({"params": p}, t, labels=t)
            return jax.lax.pmean(jnp.mean(losses), "tp")

        opt = FusedAdam(lr=1e-4)
        state0 = jax.eval_shape(opt.init, params)
        state_specs = type(state0)(
            step=P(),
            slots={k: param_specs for k in state0.slots},
            master=param_specs if state0.master is not None else None,
        )
        state = jax.jit(
            opt.init, out_shardings=shardings_of(state_specs))(params)
        jax.block_until_ready(state)
        _log("tp_gpt: optimizer state initialized")

        @partial(jax.jit, donate_argnums=(0, 1))
        def step(params, state, tokens):
            def local(p, s, t):
                g = jax.grad(tp_loss)(p, t)
                return opt.step(g, s, p)
            return cc.shard_over(
                local,
                in_specs=(param_specs, state_specs, P()),
                out_specs=(param_specs, state_specs),
            )(params, state, tokens)

        _log("tp_gpt: compile start")
        t0 = time.perf_counter()
        st = step(params, state, tokens)
        jax.block_until_ready(st)
        _log(f"tp_gpt: compiled in {time.perf_counter() - t0:.1f}s")
        dt, st = _timeit(jax, lambda p, s: step(p, s, tokens), st, steps)

        # A/B: the same step with overlap_comm=True — the SP
        # all-gather/reduce-scatter ring-decomposed into collective-permute
        # hops pipelined under partial GEMMs (tensor_parallel/overlap.py).
        # Shares this child's expensive setup (params/opt state thread
        # through — the monolithic timing loop's final buffers are valid
        # inputs); only the step recompiles.  vs_monolithic < 1 = overlap
        # wins (same time-ratio convention as zero_adam_step's
        # vs_per_leaf).
        dt_overlap = None
        if n > 1:
            import dataclasses

            model_ov = GPTModel(dataclasses.replace(cfg, overlap_comm=True))

            def tp_loss_ov(p, t):
                losses = model_ov.apply({"params": p}, t, labels=t)
                return jax.lax.pmean(jnp.mean(losses), "tp")

            @partial(jax.jit, donate_argnums=(0, 1))
            def step_ov(params, state, tokens):
                def local(p, s, t):
                    g = jax.grad(tp_loss_ov)(p, t)
                    return opt.step(g, s, p)
                return cc.shard_over(
                    local,
                    in_specs=(param_specs, state_specs, P()),
                    out_specs=(param_specs, state_specs),
                )(params, state, tokens)

            _log("tp_gpt: overlap variant compile start")
            t0 = time.perf_counter()
            st = step_ov(*st, tokens)
            jax.block_until_ready(st)
            _log("tp_gpt: overlap variant compiled in "
                 f"{time.perf_counter() - t0:.1f}s")
            dt_overlap, _ = _timeit(
                jax, lambda p, s: step_ov(p, s, tokens), st, steps)

        tps = batch * seq * steps / dt
        on_cpu_mesh = jax.devices()[0].platform != "tpu" and n > 1
        rec = {
            "value": round(tps, 1),
            "unit": "tokens/sec",
            "tp": n,
            "sequence_parallel": n > 1,
            "batch": batch,
            "seq": seq,
            # exactly what this row measured (r3 VERDICT weak #5: no
            # headline row whose collectives never execute)
            "measured": (
                "tp=%d shard_map step on a virtual %d-device CPU host "
                "mesh: TP collectives (all-gather/reduce-scatter) "
                "genuinely execute; step-time *shape* only, not TPU perf"
                % (n, n) if on_cpu_mesh else
                "tp=1 on the single attached chip: TP code path only, "
                "zero TP collectives; multi-chip shardings validated by "
                "dryrun_multichip + virtual-mesh scaling records" if n == 1
                else "tp=%d on %d attached TPU chips" % (n, n)),
        }
        if dt_overlap is not None:
            rec["overlap_tokens_per_sec"] = round(
                batch * seq * steps / dt_overlap, 1)
            rec["vs_monolithic"] = round(dt_overlap / dt, 3)
        return rec
    finally:
        parallel.mesh.destroy_model_parallel()


def _make_synth_jpeg_tree(root, n_classes: int, per_class: int,
                          side: int) -> None:
    """Deterministic synthetic ImageFolder tree (RandomState(0), quality
    90) — shared by bench_input_pipeline and bench_real_data_rn50 so the
    two measurements stay apples-to-apples."""
    import numpy as np
    from PIL import Image

    rng = np.random.RandomState(0)
    for c in range(n_classes):
        d = os.path.join(root, f"class_{c}")
        os.makedirs(d, exist_ok=True)
        for i in range(per_class):
            arr = rng.randint(0, 256, (side, side, 3), dtype=np.uint8)
            Image.fromarray(arr).save(
                os.path.join(d, f"{i}.jpg"), quality=90)


def bench_input_pipeline(jax, on_tpu):
    """Host input-pipeline throughput: images decoded+augmented per second
    by ``ImageFolderLoader`` over a synthetic JPEG ImageFolder tree — the
    "can the loader feed the chip?" number (the reference's flagship
    recipe leans on DataLoader workers + DALI for this;
    ``examples/imagenet/main_amp.py:207-232``).

    ISSUE 8 shape: A/Bs the decode **backends** (process pool vs thread
    pool — ``loader_ips_per_backend``), measures the **overlapped stall
    per step** through the double-buffered device prefetcher for each
    path (``stall_ms_per_step``; ``stall_ms_single_buffer`` is the
    depth=0 synchronous-pull A/B — the pre-double-buffer shape), and
    cross-checks the bench-side stopwatch against the in-run
    ``data/stall_ms`` telemetry (``stall_ms_in_run_gauge`` — the two
    must agree within noise).  Also rates the decode-free packed image
    path and the packed-sequence **LM stream**
    (``packed_lm_tokens_per_sec``) — the GPT trainers' real-data input.

    Reported against an RN50 consumption rate (the adopted per-A100
    figure of BASELINE.json): ``vs_rn50_consumption > 1`` means the
    pipeline outpaces a consumer of that rate."""
    import shutil
    import tempfile

    from apex_tpu.data import ImageFolder, ImageFolderLoader
    from apex_tpu.data import prefetch_to_device
    from apex_tpu.observability.metrics import MetricRegistry

    # enough images that several batches fit per epoch: the pipeline
    # drains at epoch boundaries (by design), so a 1-batch epoch would
    # measure un-overlapped decode, not steady-state prefetch
    n_classes, per_class = 4, 128 if not on_tpu else 512
    side = 300  # ~typical resized ImageNet shard JPEG
    # consumption rate to beat: the adopted per-A100 RN50 figure — a
    # fixed, sourced pace (no RN50 rate of this code has been measured on
    # the chip since the July records; ROADMAP S3/S5)
    rn50_rate = adopted_baseline()
    rate_src = "BASELINE.json adopted"
    root = tempfile.mkdtemp(prefix="bench_jpegs_")
    try:
        _make_synth_jpeg_tree(root, n_classes, per_class, side)

        batch = 256 if on_tpu else 128  # >= 4 batches per epoch either way
        # effective quota, not raw core count (matches the host_cpus field)
        eff_cpus = (len(os.sched_getaffinity(0))
                    if hasattr(os, "sched_getaffinity")
                    else (os.cpu_count() or 8))
        workers = min(32, eff_cpus)
        ds = ImageFolder(root)

        # target + warm batch stays under the batches-per-epoch (8 on tpu
        # shapes, 4 on cpu) so neither loop times an epoch-boundary drain
        # + producer restart
        target = 6 if on_tpu else 2
        step_s = batch / rn50_rate  # an RN50 step's device time

        def measure_ips(make_loader):
            """Raw pipeline throughput: warm the POOL (worker spawn +
            imports — the one-time cost warm_up() exists for), then time
            from decode cold start and count every delivered batch, so
            prefetch's head start cannot credit undone work to the
            window."""
            with make_loader() as loader:
                if hasattr(loader, "warm_up"):
                    loader.warm_up()
                it = iter(loader)

                def batches():
                    nonlocal it
                    while True:  # re-iterating -> next epoch
                        for b in it:
                            yield b
                        it = iter(loader)

                src = batches()
                t0 = time.perf_counter()
                for _ in range(target + 1):
                    next(src)
                n = (target + 1) * batch
                return n / (time.perf_counter() - t0)

        def measure_stall(make_loader, depth=2):
            """Steady-state overlapped stall through the double-buffered
            device prefetcher: warm the pipeline, pace like the device
            (sleep an RN50 step), then time how long next() blocks.
            Returns (bench-side stall ms, in-run gauge-mean ms) — the
            agreement check for the data/stall_ms telemetry."""
            reg = MetricRegistry(rank=0, world=1)
            with make_loader() as loader:
                dev = prefetch_to_device(loader, depth=depth,
                                         place=lambda b: b, registry=reg)
                try:
                    next(dev)
                    # reset after warmup: the first pull pays cold decode
                    warm = reg.histogram("span_ms/data/next_wait")
                    warm_total, warm_count = warm.total, warm.count
                    stall = 0.0
                    for _ in range(target):
                        time.sleep(step_s)
                        s0 = time.perf_counter()
                        next(dev)
                        stall += time.perf_counter() - s0
                    hist = reg.histogram("span_ms/data/next_wait")
                    gauge_ms = ((hist.total - warm_total)
                                / max(hist.count - warm_count, 1))
                    return stall / target * 1e3, gauge_ms
                finally:
                    dev.close(close_source=False)

        def jpeg_loader(backend):
            return lambda: ImageFolderLoader(
                ds, local_batch=batch, image_size=224, workers=workers,
                prefetch=2, backend=backend)

        ips_per_backend = {}
        stall_per_path = {}
        gauge_per_path = {}
        for backend in ("thread", "process"):
            ips_per_backend[backend] = round(
                measure_ips(jpeg_loader(backend)), 1)
            stall_ms, gauge_ms = measure_stall(jpeg_loader(backend))
            stall_per_path[backend] = round(stall_ms, 2)
            gauge_per_path[backend] = round(gauge_ms, 2)
        best_backend = max(ips_per_backend, key=ips_per_backend.get)
        raw_ips = ips_per_backend[best_backend]
        # the pre-double-buffer A/B: depth=0 degenerates to the old
        # synchronous pull-at-next() shape on the winning backend
        single_ms, _ = measure_stall(jpeg_loader(best_backend), depth=0)

        # Packed (decode-free) image path: pack the same tree once, then
        # measure the memmap-gather loader the same two ways.  This is
        # the path that must feed the chip when per-core decode can't
        # (the DALI role; apex_tpu/data/packed.py module docstring).
        from apex_tpu.data import PackedLoader, pack_image_folder

        pds = pack_image_folder(
            ds, os.path.join(root, "packed"), side=232, workers=workers)

        def packed_loader():
            return PackedLoader(pds, local_batch=batch, prefetch=2)

        packed_ips = measure_ips(packed_loader)
        packed_stall_ms, packed_gauge_ms = measure_stall(packed_loader)
        stall_per_path["packed"] = round(packed_stall_ms, 2)
        gauge_per_path["packed"] = round(packed_gauge_ms, 2)

        # Packed-sequence LM stream (the GPT paths' real-data input):
        # synthetic pre-tokenized corpus -> pack once -> stream
        # (tokens, segment_ids) batches; rate in tokens/sec.
        from apex_tpu.data import (
            PackedSequenceLoader,
            pack_token_documents,
            synthetic_token_documents,
        )

        seq_len = 2048 if on_tpu else 512
        n_docs = 2048 if on_tpu else 256
        docs = synthetic_token_documents(n_docs, vocab=50_000,
                                         mean_len=seq_len // 2, seed=0)
        sds = pack_token_documents(
            docs, os.path.join(root, "lm", "train"), seq_len=seq_len,
            eos_id=0)
        lm_target = 4
        # size the batch so the lm_target+1 timed pulls stay INSIDE one
        # epoch — the same guard as the image legs: an epoch-boundary
        # drain + producer restart must not land in the timing window
        lm_batch = max(2, min(32, len(sds) // (lm_target + 2)))

        with PackedSequenceLoader(sds, local_batch=lm_batch,
                                  prefetch=2) as lm_loader:
            it = iter(lm_loader)

            def lm_batches():
                nonlocal it
                while True:
                    for b in it:
                        yield b
                    it = iter(lm_loader)

            src = lm_batches()
            t0 = time.perf_counter()
            for _ in range(lm_target + 1):
                next(src)
            lm_tps = ((lm_target + 1) * lm_batch * seq_len
                      / (time.perf_counter() - t0))

        return {
            "value": raw_ips,
            "unit": "images-decoded/sec",
            "vs_rn50_consumption": round(raw_ips / rn50_rate, 3),
            "rn50_rate_source": rate_src,
            # the ISSUE 8 backend A/B: process pool vs thread pool on the
            # same host/images (acceptance: process beats thread where
            # the GIL was the binding constraint)
            "loader_ips_per_backend": ips_per_backend,
            "decode_backend_used": best_backend,
            "per_worker_ips": round(raw_ips / workers, 1),
            # overlapped stall per step through the double-buffered
            # prefetcher, per input path; the in-run data/stall_ms gauge
            # must agree with the bench stopwatch within noise
            "stall_ms_per_step": stall_per_path,
            "stall_ms_in_run_gauge": gauge_per_path,
            "stall_ms_single_buffer": round(single_ms, 2),
            "rn50_step_ms": round(step_s * 1e3, 2),
            # decode-free packed shard (gather-memcpy + on-device augment)
            "packed_ips": round(packed_ips, 1),
            "packed_vs_rn50_consumption": round(packed_ips / rn50_rate, 3),
            # packed-sequence LM stream rate (tokens/sec incl. segments)
            "packed_lm_tokens_per_sec": round(lm_tps, 1),
            "lm_seq_len": seq_len,
            "batch": batch,
            "workers": workers,
            "jpeg_side": side,
            "n_images": n_classes * per_class,
            # host context: decode scales ~per core, so the same loader
            # reads very differently on a 1-core sandbox vs a TPU-VM host
            # (sched_getaffinity = the EFFECTIVE quota under cgroups)
            "host_cpus": eff_cpus,
            # which decode stage ran: the C kernel (_native/jpegdec.c,
            # DCT-scaled decode fused with crop+resize) or the PIL path
            "native_decode": _native_decode_available(),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _native_decode_available() -> bool:
    try:
        from apex_tpu.data import _jpeg_native
        return _jpeg_native.native_available()
    except Exception:
        return False


def bench_real_data_rn50(jax, on_tpu):
    """End-to-end REAL-DATA training throughput (VERDICT r4 missing #2):
    real JPEG files -> one-time pack -> ``PackedLoader`` host gather ->
    H2D prefetch -> jitted O2 train step with on-device crop/flip — the
    composition of the input_pipeline row (host side) with the
    resnet50_o2 row (device side), which had only ever been measured
    separately.  The reference capability is the flagship recipe's
    worker/prefetch loop feeding main_amp's step
    (``examples/imagenet/main_amp.py:207-232``).

    Drives ``examples/imagenet_amp.py`` itself (the user-facing recipe,
    not a bench-only path).  The JPEG tree and packed shard are cached
    under /tmp across runs, so only the first run pays dataset setup."""
    import sys as _sys

    examples_dir = os.path.join(_REPO, "examples")
    if examples_dir not in _sys.path:
        _sys.path.insert(0, examples_dir)
    import imagenet_amp

    n_classes, per_class = (8, 256) if on_tpu else (4, 16)
    # CPU dry-run shapes sized for a 1-CPU host (batch-16 RN50 steps
    # measured ~31 s each there)
    batch, steps = (128, 200) if on_tpu else (8, 3)
    side = 300
    cache = os.path.join("/tmp", "apex_tpu_bench_data",
                         f"synth_{n_classes}x{per_class}_{side}")
    done_marker = os.path.join(cache, ".complete")
    if not os.path.exists(done_marker):
        _make_synth_jpeg_tree(os.path.join(cache, "train"),
                              n_classes, per_class, side)
        with open(done_marker, "w") as f:
            f.write("ok")
    eff_cpus = (len(os.sched_getaffinity(0))
                if hasattr(os, "sched_getaffinity")
                else (os.cpu_count() or 8))
    # snapshot the in-run stall telemetry around the run: the example's
    # double-buffered prefetcher records every next() block into the
    # default registry (data/stall_ms gauge + span_ms/data/next_wait
    # histogram) — the stall lands in the record from the SAME run that
    # produced the throughput, not a separate bench-side loop
    from apex_tpu.observability import default_registry

    hist = default_registry().histogram("span_ms/data/next_wait")
    t0_count, t0_total = hist.count, hist.total
    ips = imagenet_amp.main([
        "--data", cache,
        "--packed", os.path.join(cache, "pack"),
        "--batch-size", str(batch),
        "--num-classes", str(n_classes),
        "--steps", str(steps),
        "--workers", str(min(32, eff_cpus)),
    ])
    stall_ms = ((hist.total - t0_total) / max(hist.count - t0_count, 1))
    return {
        "value": round(ips, 1),
        "unit": "images/sec/chip",
        "batch_per_chip": batch,
        "steps": steps,
        "image_size": 224,
        "n_images": n_classes * per_class,
        "data_path": "jpeg->packed-shard->PackedLoader->H2D prefetch",
        # in-run overlapped stall/step (the BENCH_r05 574 ms number,
        # re-measured through the rebuilt pipeline; the single- vs
        # double-buffer A/B lives in input_pipeline.stall_ms_single_buffer)
        "stall_ms_per_step": round(stall_ms, 2),
        "host_cpus": eff_cpus,
    }


def bench_fused_adam_step(jax, on_tpu):
    """Optimizer step-time microbench: FusedAdam over a resnet-sized tree
    vs the native-JAX baseline (optax.adamw) — the BASELINE
    "fused-optimizer step time <= native" metric (``vs_native`` < 1 means
    ours is faster)."""
    import jax.numpy as jnp

    from apex_tpu.optimizers import FusedAdam

    n_tensors = 161  # RN50-ish tree
    size = 160_000 if on_tpu else 1_000
    keys = [f"w{i}" for i in range(n_tensors)]
    steps = 50 if on_tpu else 5

    # One compiled program per tree instead of 161 eager jnp.full dispatches
    # (x4 trees), each of which compiles and dispatches by itself.
    @jax.jit
    def make_tree(fill):
        return {k: jnp.full((size,), fill, jnp.float32) for k in keys}

    grads = make_tree(1e-4)

    def fresh_params():
        # per-run trees: the jitted steps donate params/state, so each
        # optimizer needs its own buffers
        return make_tree(0.01)

    def timed(step, init):
        params = fresh_params()
        state = jax.jit(init)(params)  # one program, not 2x161 dispatches
        params, state = step(grads, state, params)  # compile
        jax.block_until_ready((params, state))
        t0 = time.perf_counter()
        for _ in range(steps):
            params, state = step(grads, state, params)
        jax.block_until_ready((params, state))
        return (time.perf_counter() - t0) / steps

    def time_fused(flat):
        opt = FusedAdam(lr=1e-3, weight_decay=1e-2, adam_w_mode=True,
                        flat=flat)

        @partial(jax.jit, donate_argnums=(1, 2))
        def fused_step(grads, state, params):
            return opt.step(grads, state, params)

        return timed(fused_step, opt.init)

    # both shipped configs: per-leaf (XLA fuses per tensor) and chunked
    # flat buffer (one wide kernel per op + pack/unpack copies) — which
    # wins depends on tree fragmentation and platform, and the update is
    # elementwise so the two agree to ~1 ulp; report the better one as
    # the headline with both measured
    dt_leaf = time_fused(flat=False)
    dt_flat = time_fused(flat=True)
    dt, config = ((dt_leaf, "per_leaf") if dt_leaf <= dt_flat
                  else (dt_flat, "flat"))

    dt_native = None
    try:
        import optax

        native = optax.adamw(1e-3, weight_decay=1e-2)

        @partial(jax.jit, donate_argnums=(1, 2))
        def native_step(grads, state, params):
            updates, state = native.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        dt_native = timed(native_step, native.init)
    except ImportError:
        pass

    return {
        "value": round(dt * 1e6, 1),
        "unit": "us/step",
        "config": config,
        "per_leaf_us": round(dt_leaf * 1e6, 1),
        "flat_us": round(dt_flat * 1e6, 1),
        "native_optax_us": round(dt_native * 1e6, 1) if dt_native else None,
        "vs_native": round(dt / dt_native, 3) if dt_native else None,
        "n_tensors": n_tensors,
        "n_elements": n_tensors * size,
    }


def bench_zero_adam_step(jax, on_tpu):
    """ZeRO optimizer step-time microbench over the dp mesh: flat-bucket
    ``DistributedFusedAdam`` (one reduce-scatter + one all-gather per
    dtype-group bucket) vs the per-leaf port (one collective pair per
    tensor) vs the replicated ``FusedAdam`` baseline, on a 161-leaf
    RN50-ish tree.  ``vs_per_leaf`` < 1 means the bucketed exchange wins —
    the point of the reference's StateBucket design
    (``apex/contrib/optimizers/distributed_fused_adam.py:397``).  On CPU
    the child runs with 8 virtual host devices (same as ``tp_gpt``)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu import parallel
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import collectives as cc

    n_tensors = 161  # RN50-ish tree; >= 100 leaves is where per-leaf drowns
    size = 160_000 if on_tpu else 1_000
    steps = 50 if on_tpu else 5
    mesh = parallel.initialize_model_parallel()  # all devices on dp
    dp = mesh.shape["dp"]
    keys = [f"w{i}" for i in range(n_tensors)]

    @jax.jit
    def make_tree(fill):
        return {k: jnp.full((size,), fill, jnp.float32) for k in keys}

    grads = make_tree(1e-4)

    def timed(step, init_params_state):
        params, state = init_params_state()
        params, state = step(grads, state, params)  # compile
        jax.block_until_ready((params, state))
        t0 = time.perf_counter()
        for _ in range(steps):
            params, state = step(grads, state, params)
        jax.block_until_ready((params, state))
        return (time.perf_counter() - t0) / steps

    def time_dist(opt):
        param_spec = {k: P() for k in keys}
        state_specs = opt.state_partition_specs(grads)
        init = jax.jit(cc.shard_over(
            opt.init, mesh=mesh, in_specs=(param_spec,),
            out_specs=state_specs))
        step = jax.jit(
            cc.shard_over(
                lambda g, s, p: opt.step(g, s, p), mesh=mesh,
                in_specs=(param_spec, state_specs, param_spec),
                out_specs=(param_spec, state_specs)),
            donate_argnums=(1, 2))
        return timed(step,
                     lambda: (make_tree(0.01), init(make_tree(0.01))))

    dt_flat = time_dist(DistributedFusedAdam(
        lr=1e-3, weight_decay=1e-2, flat_bucket=True))
    dt_leaf = time_dist(DistributedFusedAdam(
        lr=1e-3, weight_decay=1e-2, flat_bucket=False))

    # replicated baseline: every replica does the full FusedAdam update,
    # no sharded state, no collectives (grads pre-averaged upstream)
    rep = FusedAdam(lr=1e-3, weight_decay=1e-2)

    @partial(jax.jit, donate_argnums=(1, 2))
    def rep_step(g, s, p):
        return rep.step(g, s, p)

    dt_rep = timed(rep_step,
                   lambda: (make_tree(0.01), jax.jit(rep.init)(
                       make_tree(0.01))))

    return {
        "value": round(dt_flat * 1e6, 1),
        "unit": "us/step",
        "config": "flat_bucket",
        "flat_bucket_us": round(dt_flat * 1e6, 1),
        "per_leaf_us": round(dt_leaf * 1e6, 1),
        "replicated_us": round(dt_rep * 1e6, 1),
        "vs_per_leaf": round(dt_flat / dt_leaf, 3),
        "n_tensors": n_tensors,
        "n_elements": n_tensors * size,
        "dp": dp,
    }


def bench_ckpt_save_restore(jax, on_tpu):
    """Checkpoint-path wall-time (ISSUE 3): save / verify / restore for
    the flat (``save_checkpoint``) vs sharded (``save_checkpoint_sharded``)
    layouts on the same train-state-shaped tree, so checkpoint-path
    regressions (checksumming cost, fsync stalls, manifest overhead)
    show up in the perf trajectory like any compute row.  ``vs_sharded``
    = flat total / sharded total (< 1 = flat faster; sharded wins once
    per-process parallel writes matter, which a single host can't show).
    On CPU the child runs with 8 virtual devices so the sharded layout
    actually splits shards over a dp mesh."""
    import tempfile

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from apex_tpu import checkpoint as ckpt
    from apex_tpu import parallel

    n_tensors = 32
    size = 262_144 if on_tpu else 32_768  # fp32 elems per leaf
    reps = 3
    mesh = parallel.initialize_model_parallel()  # all devices on dp
    sharding = NamedSharding(mesh, P(("dcn", "dp")))
    tree = {
        f"w{i}": jax.device_put(
            jnp.full((size,), float(i % 7) + 0.5, jnp.float32), sharding)
        for i in range(n_tensors)
    }
    jax.block_until_ready(tree)
    nbytes = n_tensors * size * 4

    def timed(fn):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best * 1e3  # ms

    with tempfile.TemporaryDirectory() as d:
        flat = os.path.join(d, "flat.npz")
        flat_save = timed(lambda: ckpt.save_checkpoint(flat, tree, step=1))
        flat_verify = timed(lambda: ckpt.verify_checkpoint(flat))
        flat_restore = timed(lambda: ckpt.restore_checkpoint(flat, tree))

        shd = os.path.join(d, "sharded")
        shd_save = timed(
            lambda: ckpt.save_checkpoint_sharded(shd, tree, step=1))
        shd_verify = timed(lambda: ckpt.verify_checkpoint_sharded(shd))
        shd_restore = timed(
            lambda: ckpt.restore_checkpoint_sharded(shd, tree))

    parallel.destroy_model_parallel()
    flat_total = flat_save + flat_verify + flat_restore
    shd_total = shd_save + shd_verify + shd_restore
    return {
        "value": round(flat_total, 2),
        "unit": "ms/save+verify+restore",
        "config": "flat",
        "flat_save_ms": round(flat_save, 2),
        "flat_verify_ms": round(flat_verify, 2),
        "flat_restore_ms": round(flat_restore, 2),
        "sharded_save_ms": round(shd_save, 2),
        "sharded_verify_ms": round(shd_verify, 2),
        "sharded_restore_ms": round(shd_restore, 2),
        "vs_sharded": round(flat_total / max(shd_total, 1e-9), 3),
        "checkpoint_mb": round(nbytes / 2**20, 1),
        "dp": mesh.shape["dp"] if "dp" in mesh.shape else 1,
    }


def bench_ckpt_reshard(jax, on_tpu):
    """Restore-anywhere wall-time (ISSUE 6): the same committed
    flat-bucket ZeRO checkpoint restored onto the mesh that wrote it
    (the plain lazy path) vs onto a HALVED dp world
    (``resilience.reshard.restore_resharded`` — logical leaves
    reassembled on host, buckets re-chunked).  ``vs_same_mesh`` =
    reshard-restore / same-mesh-restore (> 1 expected: resharding
    materializes and re-packs every bucket on host); the row exists so
    the elastic-resume cost stays a measured number and host-path
    regressions (spec parsing, unflatten/re-chunk copies) show up in the
    perf trajectory."""
    import tempfile

    import numpy as np

    from apex_tpu import parallel
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.parallel.distributed import replicate, zero_init
    from apex_tpu.resilience import CheckpointManager, reshard

    n_tensors = 16
    size = 262_144 if on_tpu else 32_768  # fp32 elems per leaf
    reps = 3
    devices = jax.devices()
    if len(devices) < 2:
        return {"error": "needs >= 2 devices for a dp halving"}
    opt = DistributedFusedAdam(lr=1e-2, flat_bucket=True, n_buckets=4)
    host = {f"w{i}": jax.numpy.full((size,), float(i % 7) + 0.5)
            for i in range(n_tensors)}

    def build(devs):
        mesh = parallel.initialize_model_parallel(devices=devs)
        p = replicate(host, mesh)
        pack = {"params": p, "opt": zero_init(opt, p, mesh)}
        spec = reshard.build_spec(pack, mesh=mesh,
                                  zero_states=[("opt", opt, p)])
        return pack, spec

    def timed(fn):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best * 1e3  # ms

    with tempfile.TemporaryDirectory() as d:
        pack, spec = build(devices)
        writer = CheckpointManager(d, sharded=True, spec=spec)
        writer.save(pack, 0)
        same_ms = timed(lambda: writer.restore_latest(pack)[0])
        parallel.destroy_model_parallel()

        half, spec_half = build(devices[: len(devices) // 2])
        reader = CheckpointManager(d, sharded=True, spec=spec_half)
        reshard_ms = timed(lambda: reader.restore_latest(half)[0])
        dp_src, dp_dst = len(devices), len(devices) // 2
        parallel.destroy_model_parallel()

    nbytes = sum(np.asarray(x).nbytes
                 for x in jax.tree_util.tree_leaves(pack))
    return {
        "value": round(reshard_ms, 2),
        "unit": "ms/reshard-restore",
        "config": f"zero_flat_bucket dp{dp_src}->dp{dp_dst}",
        "same_mesh_restore_ms": round(same_ms, 2),
        "reshard_restore_ms": round(reshard_ms, 2),
        "vs_same_mesh": round(reshard_ms / max(same_ms, 1e-9), 3),
        "checkpoint_mb": round(nbytes / 2**20, 1),
        "measured": (
            "flat-bucket ZeRO train state: restore_latest onto the "
            "writing mesh (lazy slice assembly) vs restore_resharded "
            "onto dp/2 (host reassembly + re-chunk); verification on "
            "for both"),
    }


def bench_serving(jax, on_tpu):
    """Continuous-batching decode runtime (ISSUE 9): steady-state
    tokens/sec and p50/p99 time-per-output-token at several concurrent-
    request levels, plus the fused-vs-unfused decode A/B.

    ``tokens_per_sec_at`` / ``tpot_p50_ms_at`` / ``tpot_p99_ms_at`` are
    keyed by concurrency — the continuous-batching win IS the shape of
    that curve (a batched decode step costs ~the same wall time at c=1
    and c=max_batch, so tokens/sec should scale near-linearly until the
    chip saturates).  ``vs_unfused`` = fused tokens/sec over the
    unfused-XLA lowering's (paged-attention Pallas kernel + fused
    residual/norm epilogue vs gather + separate-HLO chain) at the top
    concurrency — > 1 means the fusions pay.  NB on the CPU mesh the
    Pallas kernels run in *interpret mode*, so the CPU ``vs_unfused``
    measures dispatch overhead, not the HBM-gather saving; the TPU
    window is where the ratio is meaningful (docs/serving.md)."""
    import numpy as np

    from apex_tpu import parallel
    from apex_tpu.observability.metrics import MetricRegistry
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    devices = jax.devices()
    tp = min(8, len(devices)) if not on_tpu else 1
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=devices[:tp])
    hidden, layers, heads, vocab = (
        (512, 4, 8, 2048) if on_tpu else (128, 2, 8, 512))
    max_batch, prompt_len, gen = 8, 16, 24
    cfg = TransformerConfig(
        hidden_size=hidden, num_layers=layers, num_attention_heads=heads,
        padded_vocab_size=vocab, max_position_embeddings=256,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis="tp",
        use_flash_attention=True)
    init_fn, _, _ = build_gpt_3d(cfg, num_chunks=layers,
                                 num_microbatches=1, mesh=mesh)
    params, _ = init_fn(jax.random.PRNGKey(0),
                        jax.numpy.zeros((2, 8), jax.numpy.int32))
    rng = np.random.RandomState(0)

    def run_level(concurrency, fused):
        eng = ServingEngine(
            cfg, ServingConfig(max_batch=max_batch, block_size=16,
                               max_seq=prompt_len + gen + 8,
                               prefill_len=128, fused_attention=fused,
                               fuse_epilogue=fused),
            params, mesh=mesh, registry=MetricRegistry(rank=0))
        # warmup: pay the prefill+decode compiles outside the window
        eng.submit(rng.randint(1, vocab - 1, size=prompt_len).tolist(), 2)
        eng.run_until_drained(max_steps=100)
        registry = MetricRegistry(rank=0)   # steady-state window only
        eng.registry = registry
        reqs = [eng.submit(rng.randint(1, vocab - 1,
                                       size=prompt_len).tolist(), gen)
                for _ in range(concurrency)]
        t0 = time.perf_counter()
        eng.run_until_drained(max_steps=5000)
        dt = time.perf_counter() - t0
        tokens = registry.counter("serving/tokens_generated").value
        assert all(len(r.output_tokens) == gen for r in reqs)
        assert eng.decode_compile_count() == 1
        tpot = registry.histogram("serving/tpot_ms")
        return (tokens / max(dt, 1e-9), tpot.percentile(50.0),
                tpot.percentile(99.0))

    levels = [1, 4, max_batch]
    tps, p50, p99 = {}, {}, {}
    for c in levels:
        rate, l50, l99 = run_level(c, fused=True)
        tps[str(c)] = round(rate, 1)
        p50[str(c)] = round(l50, 2) if l50 is not None else None
        p99[str(c)] = round(l99, 2) if l99 is not None else None
        _log(f"serving: c={c} {tps[str(c)]} tok/s "
             f"p50={p50[str(c)]}ms p99={p99[str(c)]}ms")
    unfused_rate, _, _ = run_level(max_batch, fused=False)
    parallel.destroy_model_parallel()
    top = str(max_batch)
    return {
        "value": tps[top],
        "unit": "tokens/sec",
        "config": (f"gpt h{hidden} L{layers} tp{tp} max_batch{max_batch} "
                   f"prompt{prompt_len} gen{gen}"),
        "tokens_per_sec_at": tps,
        "tpot_p50_ms_at": p50,
        "tpot_p99_ms_at": p99,
        "vs_unfused": round(tps[top] / max(unfused_rate, 1e-9), 3),
        "measured": (
            "continuous-batching greedy decode, paged KV cache, steady "
            "state after the compile step; tokens/sec at concurrency "
            f"{levels}; vs_unfused = fused (Pallas paged attention + "
            "fused epilogue) over unfused XLA lowering at "
            f"c={max_batch} (interpret-mode Pallas on CPU)"),
    }


def bench_serving_occupancy(jax, on_tpu):
    """Serving at production occupancy (ISSUE 12): throughput and p99
    TPOT as the KV pool is oversubscribed 1x/2x/4x against the
    steady-state worst-case demand, on a shared-template workload.

    PR 8 admitted by worst-case reservation, so the pool had to cover
    every admitted request's full horizon; occupancy admission
    (on-demand growth + prefix-cache eviction + preemption with
    recompute-on-readmit) keeps the batch full from a fraction of the
    pool.  ``tokens_per_sec_at``/``tpot_p99_ms_at`` are keyed by the
    oversubscription factor; every admitted request must FINISH at
    every factor (preempt + recompute, zero failures — asserted).
    ``vs_reserve`` = occupancy tokens/sec over the worst-case-
    reservation baseline at the SAME 2x pool — > 1 means occupancy
    admission pays.  ``ttft_cold_ms``/``ttft_hit_ms`` time the first
    token of a long-template prompt cold vs after the template's
    blocks are prefix-cached (``ttft_hit_vs_cold`` < 1 = sharing
    pays); NB on CPU the Pallas kernels run in interpret mode, so the
    absolute numbers are CPU-shaped — the curve and the ratios are the
    signal, the TPU window is the real magnitude (docs/serving.md)."""
    import numpy as np

    from apex_tpu import parallel
    from apex_tpu.observability.metrics import MetricRegistry
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    devices = jax.devices()
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=devices[:1])
    hidden, layers, heads, vocab = (
        (512, 4, 8, 2048) if on_tpu else (128, 2, 8, 512))
    max_batch, block = 8, 16
    template_len, suffix_len, gen = 96, 8, 24
    prompt_len = template_len + suffix_len
    max_seq = prompt_len + gen + block
    n_requests = 16
    cfg = TransformerConfig(
        hidden_size=hidden, num_layers=layers, num_attention_heads=heads,
        padded_vocab_size=vocab, max_position_embeddings=max_seq,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis="tp",
        use_flash_attention=True)
    init_fn, _, _ = build_gpt_3d(cfg, num_chunks=layers,
                                 num_microbatches=1, mesh=mesh)
    params, _ = init_fn(jax.random.PRNGKey(0),
                        jax.numpy.zeros((2, 8), jax.numpy.int32))
    rng = np.random.RandomState(0)
    template = rng.randint(1, vocab - 1, size=template_len).tolist()
    prompts = [template + rng.randint(1, vocab - 1,
                                      size=suffix_len).tolist()
               for _ in range(n_requests)]
    per_req = -(-min(prompt_len + gen, max_seq) // block)
    demand = max_batch * per_req          # steady worst-case working set

    def build(n_blocks, admission):
        eng = ServingEngine(
            cfg, ServingConfig(max_batch=max_batch, block_size=block,
                               max_seq=max_seq, n_blocks=n_blocks,
                               prefill_len=64, admission=admission),
            params, mesh=mesh, registry=MetricRegistry(rank=0))
        # warmup: pay the prefill+decode compiles outside every window
        eng.submit(rng.randint(1, vocab - 1, size=8).tolist(), 2)
        eng.run_until_drained(max_steps=200)
        return eng

    def throughput(eng):
        registry = MetricRegistry(rank=0)   # steady-state window only
        eng.registry = registry
        reqs = [eng.submit(p, gen) for p in prompts]
        t0 = time.perf_counter()
        eng.run_until_drained(max_steps=50_000)
        dt = time.perf_counter() - t0
        assert all(len(r.output_tokens) == gen for r in reqs), \
            "an admitted request failed to finish"
        assert eng.decode_compile_count() == 1
        tokens = registry.counter("serving/tokens_generated").value
        p99 = registry.histogram("serving/tpot_ms").percentile(99.0)
        return (tokens / max(dt, 1e-9),
                round(p99, 2) if p99 is not None else None)

    def ttft_ms(eng, prompt):
        req = eng.submit(prompt, 2)
        eng.run_until_drained(max_steps=5000)
        return (req.t_first_token - req.t_submit) * 1e3

    tps, p99s, preempts = {}, {}, {}
    for factor in (1, 2, 4):
        pool = max(-(-demand // factor), per_req)
        eng = build(pool, "occupancy")
        if factor == 1:
            # TTFT A/B on the 1x engine while its prefix cache is cold:
            # same template, different suffix -> the second prompt
            # shares the template's blocks and prefills only the tail
            cold = ttft_ms(eng, template
                           + rng.randint(1, vocab - 1, size=8).tolist())
            hit = ttft_ms(eng, template
                          + rng.randint(1, vocab - 1, size=8).tolist())
        rate, p99 = throughput(eng)
        key = f"{factor}x"
        tps[key], p99s[key] = round(rate, 1), p99
        preempts[key] = int(eng.scheduler.preemptions)
        _log(f"serving_occupancy: {key} pool={pool} {tps[key]} tok/s "
             f"p99={p99}ms preemptions={preempts[key]}")
    pool_2x = max(-(-demand // 2), per_req)
    reserve_rate, _ = throughput(build(pool_2x, "reserve"))
    parallel.destroy_model_parallel()
    return {
        "value": tps["2x"],
        "unit": "tokens/sec",
        "config": (f"gpt h{hidden} L{layers} max_batch{max_batch} "
                   f"block{block} template{template_len} gen{gen} "
                   f"n_req{n_requests} demand{demand}blk"),
        "tokens_per_sec_at": tps,
        "tpot_p99_ms_at": p99s,
        "preemptions_at": preempts,
        "vs_reserve": round(tps["2x"] / max(reserve_rate, 1e-9), 3),
        "ttft_cold_ms": round(cold, 2),
        "ttft_hit_ms": round(hit, 2),
        "ttft_hit_vs_cold": round(hit / max(cold, 1e-9), 3),
        "measured": (
            "occupancy admission (prefix caching + eviction + "
            "preemption/recompute) at pool oversubscription 1x/2x/4x "
            f"of the {demand}-block steady demand; every request "
            "finishes at every factor; vs_reserve = occupancy over "
            "worst-case reservation at the same 2x pool; ttft hit vs "
            "cold on a shared 96-token template (interpret-mode Pallas "
            "on CPU)"),
    }


def _replica_processes_need_cpu(jax, row: str) -> None:
    """The fleet rows spawn ``ReplicaProcess`` children that each
    initialize JAX.  A chip belongs to one process and this one already
    holds it, so on a TPU the children could only fail, or hang inside
    ``wait_ready``: fail at once instead.  Giving each replica its own
    device inside one process is ROADMAP R8."""
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"{row} spawns replica processes, and they cannot share the "
            f"{platform} this process holds: it runs only under "
            "JAX_PLATFORMS=cpu until ROADMAP R8 gives each replica its "
            "own device")


def bench_serving_fleet(jax, on_tpu):
    """Fleet serving (ISSUE 11): steady-state fleet tokens/sec over 3
    replica processes behind the router, and p99 TPOT during a
    staggered zero-downtime weight rollout vs steady state.

    ``value`` is fleet tokens/sec with all replicas up;
    ``p99_tpot_ms_steady`` / ``p99_tpot_ms_roll`` are router-observed
    inter-token p99s in the two windows, and ``roll_vs_steady`` their
    ratio — the SLO cost of rolling new weights through the fleet under
    load (the smoke gates it hard; here it is a tracked number).  Each
    replica is its own spawned process with its own mesh and compiled
    programs (CPU: 3x tp=1 on one host — measuring the router + process
    transport, not chip scaling; on a TPU host each replica needs its
    own device, ROADMAP R8).

    ISSUE 14: the same steady wave then runs over the framed-TCP
    transport (3 ``replica_serve`` daemons on loopback) —
    ``tokens_per_sec_socket`` and ``wire_vs_inproc`` (socket/in-proc
    ratio) track the wire cost instead of guessing it.  Measured
    surprise, stable across runs: ~15x ABOVE in-proc on the CPU host —
    the socket server batches a whole event backlog into each 64 KB
    send while mp.Queue pays a feeder-thread wakeup per put (GIL-
    starved while the child decodes); the socket wave runs at the
    fleet's compute-bound ceiling (~16 ticks x p99 TPOT).  ISSUE 15
    re-stamp: the worker now batches its event backlog into one queue
    put per relay turn (fleet/relay_batch), and the ratio BARELY moved
    (15.7x, was ~15x) — the verdict is that the feeder-thread wakeup
    latency dominates, not the per-event pickle count, so the socket
    transport stays the performance path even single-host.  Loopback
    bounds framing+session cost only; cross-host adds real NIC
    latency on top."""
    _replica_processes_need_cpu(jax, "serving_fleet")
    import os
    import shutil
    import tempfile

    import numpy as np

    from apex_tpu import parallel
    from apex_tpu.observability.metrics import MetricRegistry
    from apex_tpu.resilience import CheckpointManager, reshard
    from apex_tpu.serving import (
        FleetRouter, ReplicaProcess, ReplicaSpec, ServingConfig)
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import (
        build_gpt_3d, gpt3d_logical_folds)

    n_replicas = 3
    hidden, layers, heads, vocab = (
        (256, 2, 8, 1024) if on_tpu else (64, 2, 4, 256))
    prompt_len, gen, wave = 12, 16, 24
    max_seq = prompt_len + gen + 4
    cfg = TransformerConfig(
        hidden_size=hidden, num_layers=layers, num_attention_heads=heads,
        padded_vocab_size=vocab, max_position_embeddings=max_seq,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis="tp",
        use_flash_attention=True)
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])
    init_fn, _, _ = build_gpt_3d(cfg, num_chunks=layers,
                                 num_microbatches=1, mesh=mesh)
    params, _ = init_fn(jax.random.PRNGKey(0),
                        jax.numpy.zeros((2, 8), jax.numpy.int32))
    workdir = tempfile.mkdtemp(prefix="apex_bench_fleet_")
    ckpt_dir = os.path.join(workdir, "ckpt")
    tree = {"params": params, "step_count": np.asarray(1)}
    spec = reshard.build_spec(tree, mesh=mesh,
                              folds=gpt3d_logical_folds(tree))
    CheckpointManager(ckpt_dir, sharded=True, spec=spec).save(tree, 1)
    rng = np.random.RandomState(0)
    router = None
    sock_procs = []
    try:
        rspec = ReplicaSpec(
            config=cfg,
            serving=ServingConfig(max_batch=8, block_size=8,
                                  max_seq=max_seq, prefill_len=64),
            tp=1, ckpt_dir=ckpt_dir, debug_server=False)
        replicas = [ReplicaProcess(rspec, f"r{i}")
                    for i in range(n_replicas)]
        for r in replicas:
            r.wait_ready(timeout=500)
        registry = MetricRegistry(rank=0, world=1)
        router = FleetRouter(replicas, max_queue_depth=4 * wave,
                             replica_queue_limit=wave,
                             heartbeat_timeout_s=30.0,
                             registry=registry)

        def run_wave(n, budget):
            reqs = [router.submit(
                rng.randint(1, vocab - 1, size=prompt_len).tolist(),
                budget) for _ in range(n)]
            router.run_until_idle(timeout_s=500)
            assert all(len(r.output_tokens) == budget for r in reqs)
            return reqs

        run_wave(n_replicas, 2)   # warm the transport path
        t0 = time.perf_counter()
        reqs = run_wave(wave, gen)
        steady_dt = time.perf_counter() - t0
        tokens = sum(len(r.output_tokens) for r in reqs)
        p99_steady = registry.histogram("fleet/tpot_ms").percentile(99)

        roll_reg = MetricRegistry(rank=0, world=1)
        router.registry = roll_reg
        drip, budget_left = [], [wave]

        def on_tick():
            if budget_left[0] > 0 and router.total_queue_depth() < 8:
                drip.append(router.submit(
                    rng.randint(1, vocab - 1,
                                size=prompt_len).tolist(), gen // 2))
                budget_left[0] -= 1

        t1 = time.perf_counter()
        router.rollout(lambda name: ReplicaProcess(rspec, name),
                       on_tick=on_tick, drain_timeout_s=200,
                       ready_timeout_s=500)
        router.run_until_idle(timeout_s=500)
        roll_dt = time.perf_counter() - t1
        assert all(r.output_tokens for r in drip)
        p99_roll = roll_reg.histogram("fleet/tpot_ms").percentile(99)

        # socket-transport leg (ISSUE 14): the same steady wave over
        # framed loopback TCP through replica_serve daemons
        from apex_tpu.serving.transport import (
            SocketTransport, start_replica_server)

        router.close()                 # free the mp fleet first
        started = [start_replica_server(rspec, f"s{i}",
                                        addr_timeout_s=500)
                   for i in range(n_replicas)]
        sock_procs = [p for p, _ in started]
        sock_clients = [SocketTransport(f"s{i}", addr)
                        for i, (_, addr) in enumerate(started)]
        for c in sock_clients:
            c.wait_ready(timeout=500)
        router = FleetRouter(sock_clients, max_queue_depth=4 * wave,
                             replica_queue_limit=wave,
                             heartbeat_timeout_s=30.0,
                             registry=MetricRegistry(rank=0, world=1))
        run_wave(n_replicas, 2)        # warm the socket path
        t2 = time.perf_counter()
        sreqs = run_wave(wave, gen)
        sock_dt = time.perf_counter() - t2
        sock_tps = sum(len(r.output_tokens)
                       for r in sreqs) / max(sock_dt, 1e-9)
        steady_tps = tokens / max(steady_dt, 1e-9)
        _log(f"serving_fleet: {steady_tps:.1f} tok/s steady "
             f"(p99 TPOT {p99_steady}ms), roll {roll_dt:.1f}s "
             f"(p99 TPOT {p99_roll}ms, {len(drip)} drip requests), "
             f"socket {sock_tps:.1f} tok/s "
             f"({sock_tps / steady_tps:.3f}x in-proc)")
        return {
            "value": round(tokens / max(steady_dt, 1e-9), 1),
            "unit": "tokens/sec",
            "config": (f"gpt h{hidden} L{layers} {n_replicas}x tp1 "
                       f"replicas prompt{prompt_len} gen{gen} "
                       f"wave{wave}"),
            "replicas": n_replicas,
            "p99_tpot_ms_steady": (round(p99_steady, 2)
                                   if p99_steady is not None else None),
            "p99_tpot_ms_roll": (round(p99_roll, 2)
                                 if p99_roll is not None else None),
            "roll_vs_steady": (round(p99_roll / p99_steady, 3)
                               if p99_roll and p99_steady else None),
            "roll_wall_s": round(roll_dt, 1),
            "tokens_per_sec_socket": round(sock_tps, 1),
            "wire_vs_inproc": round(sock_tps / steady_tps, 3),
            "measured": (
                f"{wave} requests x {gen} greedy tokens across "
                f"{n_replicas} replica processes via the fleet router "
                "(steady window, post-warmup); then a staggered SIGTERM "
                "drain + restore-from-checkpoint roll of every replica "
                f"under a {wave}-request drip — p99 TPOT per window is "
                "router-observed inter-token latency; then the same "
                "steady wave over the framed-TCP socket transport "
                "(replica_serve daemons, loopback) — wire_vs_inproc = "
                "socket/in-proc tokens-per-sec (>1 on CPU: batched "
                "socket event relay beats mp.Queue's one-pickle-per-"
                "feeder-wakeup)"),
        }
    finally:
        if router is not None:
            router.close()
        from apex_tpu.data._producer import reap_process
        for p in sock_procs:
            try:
                p.terminate()
            except Exception:
                pass
            reap_process(p, 15.0, what="socket replica")
        shutil.rmtree(workdir, ignore_errors=True)
        parallel.destroy_model_parallel()


def bench_serving_spec(jax, on_tpu):
    """Speculative decoding (ISSUE 13): accepted-tokens/sec of the
    self-speculative engine (n-gram drafting + fused k+1 verify) vs the
    non-speculative baseline at concurrency 1/4/8, on a
    template-heavy workload where prompt-lookup drafting actually
    fires.

    ``tokens_per_sec_at`` is the speculative engine's emitted-token
    rate per concurrency (every emitted token is an *accepted* token —
    the verify never emits an unverified draft);
    ``baseline_tokens_per_sec_at`` the plain engine's on the same wave;
    ``vs_baseline_at`` their per-concurrency ratios and ``vs_baseline``
    the top-concurrency ratio (>= 1 means speculation pays — the
    acceptance bar demands it never regresses, even on CPU).
    ``mean_accept_len`` is emitted tokens per decode/verify call (1.0 =
    nothing accepted, k+1 = every draft accepted);
    ``acceptance_rate`` the drafted-token hit rate.  NB on CPU the
    verify's extra FLOPs are nearly free only relative to CPU dispatch
    overhead; the TPU window measures the real memory-bound win
    (docs/serving.md — the decode tick is HBM-bound there, so k extra
    query positions ride the same paged gather)."""
    import numpy as np

    from apex_tpu import parallel
    from apex_tpu.observability.metrics import MetricRegistry
    from apex_tpu.serving import (
        ServingConfig, ServingEngine, SpeculativeConfig)
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    devices = jax.devices()
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=devices[:1])
    hidden, layers, heads, vocab = (
        (512, 4, 8, 2048) if on_tpu else (128, 2, 8, 512))
    max_batch, block, gen, k = 8, 16, 32, 4
    motif_len, reps, suffix_len = 4, 8, 4
    prompt_len = motif_len * reps + suffix_len
    max_seq = prompt_len + gen + block
    cfg = TransformerConfig(
        hidden_size=hidden, num_layers=layers, num_attention_heads=heads,
        padded_vocab_size=vocab, max_position_embeddings=max_seq,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis="tp",
        use_flash_attention=True)
    init_fn, _, _ = build_gpt_3d(cfg, num_chunks=layers,
                                 num_microbatches=1, mesh=mesh)
    params, _ = init_fn(jax.random.PRNGKey(0),
                        jax.numpy.zeros((2, 8), jax.numpy.int32))
    rng = np.random.RandomState(0)
    # template-heavy prompts: a repeated motif plus a short unique
    # suffix — the workload shape (shared templates, quoted context,
    # structured output) prompt-lookup drafting exists for
    prompts = []
    for _ in range(max_batch):
        motif = rng.randint(1, vocab - 1, size=motif_len).tolist()
        prompts.append(motif * reps
                       + rng.randint(1, vocab - 1,
                                     size=suffix_len).tolist())

    def build(spec):
        eng = ServingEngine(
            cfg, ServingConfig(max_batch=max_batch, block_size=block,
                               max_seq=max_seq, prefill_len=64,
                               speculative=spec),
            params, mesh=mesh, registry=MetricRegistry(rank=0))
        # warmup: pay the prefill + decode/verify compiles outside
        # every timed window
        eng.submit(rng.randint(1, vocab - 1, size=8).tolist(), 2)
        eng.run_until_drained(max_steps=200)
        return eng

    def level(eng, c):
        registry = MetricRegistry(rank=0)   # steady-state window only
        eng.registry = registry
        acc0, slots0 = eng.spec_accepted, eng._slot_steps
        reqs = [eng.submit(p, gen) for p in prompts[:c]]
        t0 = time.perf_counter()
        eng.run_until_drained(max_steps=20_000)
        dt = time.perf_counter() - t0
        assert all(len(r.output_tokens) == gen for r in reqs)
        assert eng.decode_compile_count() == 1
        tokens = registry.counter("serving/tokens_generated").value
        # mean accept length: tokens one slot emits per verify step —
        # 1 (the always-emitted verified token) + accepted drafts per
        # slot-step; 1.0 = plain decode, k+1 = every draft accepted
        mean_len = 1.0 + ((eng.spec_accepted - acc0)
                          / max(eng._slot_steps - slots0, 1))
        return tokens / max(dt, 1e-9), mean_len

    spec_eng = build(SpeculativeConfig(k=k))
    base_eng = build(None)
    levels = [1, 4, max_batch]
    tps, base_tps, ratio, accept = {}, {}, {}, {}
    for c in levels:
        key = str(c)
        rate, mean_len = level(spec_eng, c)
        base_rate, _ = level(base_eng, c)
        tps[key] = round(rate, 1)
        base_tps[key] = round(base_rate, 1)
        ratio[key] = round(rate / max(base_rate, 1e-9), 3)
        accept[key] = round(mean_len, 2)
        _log(f"serving_spec: c={c} spec {tps[key]} vs base "
             f"{base_tps[key]} tok/s (x{ratio[key]}, mean accept len "
             f"{accept[key]})")
    acc_rate = (spec_eng.spec_accepted / spec_eng.spec_proposed
                if spec_eng.spec_proposed else None)
    parallel.destroy_model_parallel()
    top = str(max_batch)
    return {
        "value": tps[top],
        "unit": "tokens/sec",
        "config": (f"gpt h{hidden} L{layers} max_batch{max_batch} k{k} "
                   f"prompt{prompt_len} (motif{motif_len}x{reps}) "
                   f"gen{gen}"),
        "tokens_per_sec_at": tps,
        "baseline_tokens_per_sec_at": base_tps,
        "vs_baseline_at": ratio,
        "vs_baseline": ratio[top],
        "mean_accept_len": accept[top],
        "acceptance_rate": (round(acc_rate, 3)
                            if acc_rate is not None else None),
        "measured": (
            "self-speculative n-gram decode (fused [max_batch, k+1] "
            f"verify, k={k}) vs the non-speculative engine on a "
            "template-heavy greedy wave at concurrency "
            f"{levels}; emitted tokens are verified-accepted tokens, "
            "so vs_baseline is accepted-tokens/sec over baseline "
            "tokens/sec (interpret-mode Pallas on CPU — the TPU window "
            "measures the memory-bound win)"),
    }


def bench_serving_lora(jax, on_tpu):
    """Batched multi-LoRA serving (ISSUE 17): emitted-tokens/sec of the
    LoRA-enabled engine on waves tagged round-robin over 1 / 8 / 64
    concurrent adapters, vs the bare (``lora=None``) engine on the same
    untagged wave.

    Every request in the tagged wave carries an ``adapter_id`` through
    ``SamplingParams``, so every decode tick runs the per-slot gathered
    low-rank delta (the scalar-prefetch kernel indexes the paged
    adapter arena with the per-slot adapter-slot vector — data, never
    shape).  ``tokens_per_sec_at`` keys on the number of *distinct*
    concurrent adapters; ``vs_bare_at`` the per-level ratios; and
    ``vs_bare_1adapter`` — the single-tenant ratio, where the delta is
    pure overhead — is the floored acceptance signal (>= 0.9: one
    adapter must cost <= ~10%).  The decode compile count is asserted
    == 1 across all levels: 1 adapter and 64 adapters run the exact
    same jit program.  NB the CPU row runs the ``jnp.take`` unfused
    twin (``fused=False`` — same values): interpret-mode Pallas would
    gate interpreter dispatch, not the adapter math; the TPU window
    measures the real fused scalar-prefetch gather riding the decode
    tick."""
    import numpy as np

    from apex_tpu import parallel
    from apex_tpu.observability.metrics import MetricRegistry
    from apex_tpu.serving import (
        LoRAConfig, SamplingParams, ServingConfig, ServingEngine)
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    devices = jax.devices()
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=devices[:1])
    # rank deliberately small relative to hidden: the production regime
    # is r << h (16 vs 4096) — at the tiny-model r/h the delta's FLOPs
    # fraction stops representing what the floor gates.  max_batch is
    # the other half of that argument: the delta adds a fixed handful
    # of ops per layer, so a thin batch gates op-dispatch overhead
    # instead of the adapter math
    hidden, layers, heads, vocab, rank = (
        (512, 4, 8, 2048, 8) if on_tpu else (256, 2, 8, 512, 4))
    max_batch, block, gen = 32, 16, 32
    n_adapters, n_reqs, rounds = 64, 64, 3
    prompt_len = 16
    max_seq = prompt_len + gen + block
    cfg = TransformerConfig(
        hidden_size=hidden, num_layers=layers, num_attention_heads=heads,
        padded_vocab_size=vocab, max_position_embeddings=max_seq,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis="tp",
        use_flash_attention=True)
    init_fn, _, _ = build_gpt_3d(cfg, num_chunks=layers,
                                 num_microbatches=1, mesh=mesh)
    params, _ = init_fn(jax.random.PRNGKey(0),
                        jax.numpy.zeros((2, 8), jax.numpy.int32))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, vocab - 1, size=prompt_len).tolist()
               for _ in range(n_reqs)]

    def build(lora):
        eng = ServingEngine(
            cfg, ServingConfig(max_batch=max_batch, block_size=block,
                               max_seq=max_seq, prefill_len=64,
                               lora=lora),
            params, mesh=mesh, registry=MetricRegistry(rank=0))
        if lora is not None:
            # adapter registration (pack + device put) happens outside
            # every timed window — the steady state being measured is
            # decode with residents, not cold loads
            for i in range(n_adapters):
                eng.register_adapter(f"tenant-{i}", seed=i)
        # warmup: pay the prefill + decode compiles (including the
        # gathered-delta path) outside the timed windows
        warm = (SamplingParams(adapter_id="tenant-0")
                if lora is not None else None)
        eng.submit(rng.randint(1, vocab - 1, size=8).tolist(), 2,
                   sampling=warm)
        eng.run_until_drained(max_steps=500)
        return eng

    def level(eng, c):
        registry = MetricRegistry(rank=0)   # steady-state window only
        eng.registry = registry
        reqs = []
        for i, p in enumerate(prompts):
            sp = (SamplingParams(adapter_id=f"tenant-{i % c}")
                  if c else None)
            reqs.append(eng.submit(p, gen, sampling=sp))
        t0 = time.perf_counter()
        eng.run_until_drained(max_steps=50_000)
        dt = time.perf_counter() - t0
        assert all(len(r.output_tokens) == gen for r in reqs)
        # the jit-stability claim, measured where it matters: adapter
        # mix is data, so the whole sweep shares ONE decode program
        assert eng.decode_compile_count() == 1
        tokens = registry.counter("serving/tokens_generated").value
        return tokens / max(dt, 1e-9)

    # fused only where the kernel is real: the CPU dry-run row would
    # otherwise gate the Pallas interpreter's dispatch overhead (~4x)
    # instead of the adapter math the floor is about
    lora_eng = build(LoRAConfig(rank=rank, max_adapters=n_adapters,
                                fused=on_tpu))
    base_eng = build(None)
    levels = [1, 8, n_adapters]
    tps, base_tps, ratio = {}, {}, {}
    for c in levels:
        key = str(c)
        # paired rounds, median ratio: host drift cancels (the
        # serving_trace_overhead discipline — the gated signal is a
        # ratio near 1, so single-window noise would flip the floor)
        pairs = [(level(lora_eng, c), level(base_eng, 0))
                 for _ in range(rounds)]
        ratios = sorted(r / max(b, 1e-9) for r, b in pairs)
        rates = sorted(r for r, _ in pairs)
        base_rates = sorted(b for _, b in pairs)
        tps[key] = round(rates[rounds // 2], 1)
        base_tps[key] = round(base_rates[rounds // 2], 1)
        ratio[key] = round(ratios[rounds // 2], 3)
        _log(f"serving_lora: adapters={c} lora {tps[key]} vs bare "
             f"{base_tps[key]} tok/s (x{ratio[key]} median of "
             f"{[round(x, 3) for x in ratios]})")
    parallel.destroy_model_parallel()
    top = str(n_adapters)
    return {
        "value": tps[top],
        "unit": "tokens/sec",
        "config": (f"gpt h{hidden} L{layers} max_batch{max_batch} "
                   f"rank{rank} adapters{n_adapters} reqs{n_reqs} "
                   f"prompt{prompt_len} gen{gen}"),
        "tokens_per_sec_at": tps,
        "bare_tokens_per_sec_at": base_tps,
        "vs_bare_at": ratio,
        "vs_bare_1adapter": ratio["1"],
        "measured": (
            f"{n_reqs}-request greedy waves tagged round-robin over "
            f"{levels} distinct adapters (rank-{rank} deltas gathered "
            "per slot from the paged arena via scalar-prefetch) vs the "
            "bare lora=None engine on the same untagged wave — "
            f"median of {rounds} paired rounds per level, so host "
            "drift cancels out of the gated ratio; one decode program "
            "across the whole sweep (CPU runs the jnp.take unfused "
            "twin — the TPU window measures the fused HBM-bound "
            "gather)"),
    }


def bench_serving_disagg(jax, on_tpu):
    """Disaggregated prefill/decode fleets (ISSUE 16): decode p99 TPOT
    under a concurrent prefill flood, 1-prefill + 1-decode vs 2
    co-located ``role="both"`` replicas at EQUAL pool size, plus the
    cost of the handoff itself (``kv_migrate_ms_per_req``,
    ``kv_migrate_kb_per_req`` — blocks on the wire per migrated
    request).

    The workload: a wave of decode-heavy requests (the latency-
    sensitive traffic) decodes while prefill-heavy flood requests
    (long prompt, 2 tokens) drip in continuously.  Co-located, every
    flood's prefill chunk steals engine ticks from decode on BOTH
    replicas; disaggregated, floods stay on the prefill replica
    (2-token budgets never cross ``migrate_min_remaining``) while the
    decode wave migrates over and decodes undisturbed.

    ``vs_colocated`` = co-located p99 / disaggregated p99 of the
    steady decode TPOT (>= 1.0 is the acceptance floor: disaggregation
    must protect the decode tail).  Both sides read the same steady
    signal: co-located from the decode tenant's SLO histogram (no
    migrations happen there), disaggregated from the decode ROLE
    histogram, which excludes the one inter-token gap spanning the
    handoff — that gap is reported separately as
    ``kv_migrate_ms_per_req``, not hidden.  The tenant-side p99
    INCLUDING the handoff gap rides along as
    ``p99_tpot_ms_disagg_tenant``."""
    _replica_processes_need_cpu(jax, "serving_disagg")
    import dataclasses as dc

    import numpy as np

    from apex_tpu.observability.metrics import MetricRegistry
    from apex_tpu.serving import (
        FleetRouter, ReplicaProcess, ReplicaSpec, ServingConfig)
    from apex_tpu.transformer.testing import TransformerConfig

    # the flood chunk must be EXPENSIVE relative to a decode tick —
    # head-of-line blocking inside a co-located engine is the effect
    # disaggregation removes, and it only rises above host scheduling
    # noise when one prefill chunk costs many decode ticks
    hidden, layers, heads, vocab = (
        (256, 2, 8, 1024) if on_tpu else (128, 2, 4, 256))
    flood_len, dec_len, dec_gen = 64, 8, 48
    n_dec, flood_total, flood_inflight = 4, 24, 6
    max_seq = flood_len + dec_gen + 8
    cfg = TransformerConfig(
        hidden_size=hidden, num_layers=layers, num_attention_heads=heads,
        padded_vocab_size=vocab, max_position_embeddings=max_seq,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis="tp",
        use_flash_attention=True)
    rspec = ReplicaSpec(
        config=cfg,
        serving=ServingConfig(max_batch=8, block_size=8,
                              max_seq=max_seq, prefill_len=flood_len),
        tp=1, ckpt_dir=None, debug_server=False)
    # per-role engine tuning — the knob disaggregation unlocks: a
    # decode-pool replica only ever prefills one-token import re-dos
    # (and failover replays), so its chunk width shrinks to a block
    # and an import costs ~1/8th of a flood chunk.  A co-located
    # replica cannot do this: it needs the wide chunk for the floods.
    dspec = dc.replace(rspec, serving=dc.replace(
        rspec.serving, prefill_len=8))
    rng = np.random.RandomState(7)
    dec_prompts = [rng.randint(1, vocab - 1, size=dec_len).tolist()
                   for _ in range(n_dec)]

    def run_fleet(roles):
        replicas = [ReplicaProcess(
            dc.replace(dspec if role == "decode" else rspec,
                       role=role), f"{role[0]}{i}")
                    for i, role in enumerate(roles)]
        for r in replicas:
            r.wait_ready(timeout=500)
        router = FleetRouter(replicas, max_queue_depth=128,
                             replica_queue_limit=32,
                             heartbeat_timeout_s=60.0,
                             registry=MetricRegistry(rank=0, world=1))
        frng = np.random.RandomState(11)
        try:
            # warm every shape on every engine, including the handoff
            # path (gen 6 crosses migrate_min_remaining, so the decode
            # replica compiles its import re-prefill here, not in the
            # measured window)
            warm = [router.submit(
                frng.randint(1, vocab - 1, size=dec_len).tolist(), 6)
                for _ in range(len(roles) * 2)]
            warm += [router.submit(
                frng.randint(1, vocab - 1, size=flood_len).tolist(), 2)
                for _ in range(len(roles))]
            router.run_until_idle(timeout_s=500)
            assert all(r.output_tokens for r in warm)
            # fresh registry for the measured window: the warm wave's
            # samples (compiles, its own migrations) must not ride
            # into the histograms this bench reads
            registry = MetricRegistry(rank=0, world=1)
            router.registry = registry
            # decode arrivals staggered 250ms apart — real latency-
            # sensitive streams start at independent times; back-to-
            # back submission would pile all the handoff imports into
            # one burst and measure the pileup, not the steady state
            dec, t0 = [], time.monotonic()
            budget, inflight = [flood_total], []
            deadline = t0 + 500
            while len(dec) < n_dec or not all(r.done for r in dec):
                router.pump()
                now = time.monotonic()
                if len(dec) < n_dec and now >= t0 + 0.25 * len(dec):
                    dec.append(router.submit(
                        dec_prompts[len(dec)], dec_gen, tenant="decode"))
                inflight[:] = [r for r in inflight if not r.done]
                while budget[0] > 0 and len(inflight) < flood_inflight:
                    inflight.append(router.submit(
                        frng.randint(1, vocab - 1,
                                     size=flood_len).tolist(),
                        2, tenant="flood"))
                    budget[0] -= 1
                if now > deadline:
                    raise RuntimeError("decode wave not terminal")
                time.sleep(0.0005)
            router.run_until_idle(timeout_s=500)
            status = router.fleet_statusz()
            snap = registry.snapshot()
            tenant_p99 = (status["slo"]["tenants"]["decode"]
                          ["tpot_ms"]["p99"])
            role_p99 = registry.histogram(
                "fleet/role/decode/tpot_ms").percentile(99)
            return {
                "streams": [list(r.output_tokens) for r in dec],
                "tenant_p99": tenant_p99,
                "role_p99": role_p99,
                "migrations": snap.get("fleet/kv_migrate_completed",
                                       0.0),
                "migrate_failed": snap.get("fleet/kv_migrate_failed",
                                           0.0),
                "migrate_ms_p50": registry.histogram(
                    "fleet/kv_migrate_ms").percentile(50),
                "migrate_bytes": snap.get("fleet/kv_migrate_bytes",
                                          0.0),
                "failovers": snap.get("fleet/failovers", 0.0),
            }
        finally:
            router.close()

    coloc = run_fleet(["both", "both"])
    disagg = run_fleet(["prefill", "decode"])
    # equal pool, same prompts, greedy: the decode streams must be
    # bitwise identical however the fleet is carved up
    assert coloc["streams"] == disagg["streams"], \
        "disaggregated decode streams diverged from co-located"
    assert coloc["failovers"] == 0 and disagg["failovers"] == 0
    assert disagg["migrations"] >= n_dec, \
        (f"only {disagg['migrations']} of {n_dec} decode requests "
         "migrated")
    p99_coloc = coloc["tenant_p99"]
    p99_disagg = disagg["role_p99"]
    mig_ms = disagg["migrate_ms_p50"]
    mig_kb = (disagg["migrate_bytes"] / disagg["migrations"] / 1024.0
              if disagg["migrations"] else None)
    vs = (round(p99_coloc / p99_disagg, 3)
          if p99_coloc and p99_disagg else None)
    _log(f"serving_disagg: decode p99 TPOT {p99_disagg:.1f}ms "
         f"disaggregated vs {p99_coloc:.1f}ms co-located "
         f"(x{vs}), {disagg['migrations']:.0f} migrations "
         f"({mig_ms:.0f}ms p50, {mig_kb:.1f} KiB/req on the wire)")
    return {
        "value": round(p99_disagg, 2),
        "unit": "ms",
        "config": (f"gpt h{hidden} L{layers} pool2 "
                   f"(1 prefill + 1 decode vs 2x both) "
                   f"dec {n_dec}x{dec_gen}tok prompt{dec_len}, flood "
                   f"{flood_total}x prompt{flood_len} gen2 "
                   f"({flood_inflight} in flight)"),
        "p99_tpot_ms_colocated": (round(p99_coloc, 2)
                                  if p99_coloc is not None else None),
        "p99_tpot_ms_disagg_tenant": (
            round(disagg["tenant_p99"], 2)
            if disagg["tenant_p99"] is not None else None),
        "vs_colocated": vs,
        "kv_migrate_ms_per_req": (round(mig_ms, 2)
                                  if mig_ms is not None else None),
        "kv_migrate_kb_per_req": (round(mig_kb, 2)
                                  if mig_kb is not None else None),
        "migrations": disagg["migrations"],
        "measured": (
            f"p99 inter-token latency of {n_dec} decode-heavy requests "
            f"under a continuous {flood_total}-request prefill flood, "
            "2-replica pool either co-located (both role=both; decode-"
            "tenant SLO histogram) or disaggregated (1 prefill + 1 "
            "decode; decode-ROLE histogram, which excludes the one "
            "handoff gap — reported separately as kv_migrate_ms_per_"
            "req).  vs_colocated = coloc p99 / disagg p99 (>= 1.0: "
            "disaggregation protects the decode tail); decode streams "
            "asserted bitwise identical across both fleet shapes"),
    }


def bench_telemetry_overhead(jax, on_tpu):
    """Instrumented vs bare 3D GPT train step (ISSUE 5): the same
    ``build_gpt_3d`` step compiled with and without
    ``collect_stats=True`` (in-graph TrainStats riding the existing
    collectives, ``apex_tpu.observability``), timed back-to-back so the
    "observability is free" claim is a number, not prose.  ``vs_bare``
    = instrumented/bare step time; the steady-state (non-logging) step
    fetches nothing, so the honest expectation is ~1.0 — the acceptance
    gate is <= 1.05 on the CPU mesh.  Runs dp=2 x pp=2 x tp=2(+sp) on 8
    virtual devices (CPU) or whatever the attached chips factor into.

    ISSUE 10: the instrumented variant additionally runs with the
    FLIGHT RECORDER armed (per-step timeline events spilled to JSONL),
    so the ``vs_bare <= 1.05`` gate now also covers the run-timeline
    layer's host cost — the recorder must ride inside the same
    free-telemetry budget, not get its own."""
    import tempfile

    import jax.numpy as jnp

    from apex_tpu.observability import timeline as tl
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    n = len(jax.devices())
    tp = 2 if n % 2 == 0 else 1
    pp = 2 if (n // tp) % 2 == 0 else 1
    dp = n // tp // pp
    tl_dir = None
    mesh = mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=tp, pipeline_model_parallel_size=pp)
    try:
        if on_tpu:
            hidden, heads, vocab, seq, steps = 512, 8, 50304, 512, 10
        else:
            hidden, heads, vocab, seq, steps = 64, 4, 128, 32, 6
        cfg = TransformerConfig(
            hidden_size=hidden, num_layers=pp, num_attention_heads=heads,
            padded_vocab_size=vocab, max_position_embeddings=seq,
            hidden_dropout=0.0, attention_dropout=0.0,
            tensor_axis="tp" if tp > 1 else None,
            sequence_parallel=tp > 1,
        )
        num_microbatches = 2
        init_fn, _, make_train_step = build_gpt_3d(
            cfg, num_chunks=1, num_microbatches=num_microbatches,
            mesh=mesh)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (dp * num_microbatches * 2, seq), 0,
            vocab)
        params, specs = init_fn(jax.random.PRNGKey(0), tokens)
        opt = FusedAdam(lr=1e-3)
        state = opt.init(params)

        bare = jax.jit(make_train_step(opt, specs))
        instr = jax.jit(make_train_step(opt, specs, collect_stats=True))
        # the recorder spills to a tempdir (removed in the finally);
        # only the INSTRUMENTED passes emit step events, so dt_instr
        # carries the full armed-recorder host cost and dt_bare none
        tl_dir = tempfile.mkdtemp(prefix="apex_bench_tl_")
        recorder = tl.arm(os.path.join(tl_dir, "timeline.jsonl"))

        def one_pass(step_fn):
            p, s = params, state
            armed = step_fn is instr
            t0 = time.perf_counter()
            for k in range(steps):
                if armed:
                    with tl.scope("step", step=k):
                        res = step_fn(p, s, tokens)
                else:
                    res = step_fn(p, s, tokens)
                p, s = res[0], res[1]
            jax.block_until_ready((p, s))
            return (time.perf_counter() - t0) / steps
        # Compile + warm BOTH before timing either, then interleave the
        # timed passes and take per-variant minima: back-to-back A-then-B
        # timing on the shared-thread CPU mesh hands whichever variant
        # runs second a warmed allocator/thread pool and skews the ratio
        # either way.
        _log("telemetry_overhead: compiling bare + instrumented steps")
        for fn in (bare, instr):
            jax.block_until_ready(fn(params, state, tokens))
        dt_bare, dt_instr = float("inf"), float("inf")
        for r in range(4):
            order = ((bare, instr) if r % 2 == 0 else (instr, bare))
            for fn in order:
                dt = one_pass(fn)
                if fn is bare:
                    dt_bare = min(dt_bare, dt)
                else:
                    dt_instr = min(dt_instr, dt)
        _log(f"telemetry_overhead: bare {dt_bare * 1e3:.1f}ms "
             f"instr {dt_instr * 1e3:.1f}ms "
             f"({recorder.events_emitted} timeline events)")

        return {
            "value": round(dt_instr * 1e6, 1),
            "unit": "us/step",
            "config": "instrumented",
            "bare_us": round(dt_bare * 1e6, 1),
            "instrumented_us": round(dt_instr * 1e6, 1),
            "vs_bare": round(dt_instr / dt_bare, 3),
            "timeline_events": recorder.events_emitted,
            "dp": dp, "pp": pp, "tp": tp,
            "measured": (
                "gpt_3d train step (dp=%d,pp=%d,tp=%d%s) A/B: TrainStats "
                "in-graph telemetry + armed flight recorder (per-step "
                "JSONL timeline spill) on vs off, steady-state (no host "
                "fetch); vs_bare ~1.0 = telemetry is free"
                % (dp, pp, tp, "+sp" if tp > 1 else "")),
        }
    finally:
        tl.disarm()
        if tl_dir is not None:
            shutil.rmtree(tl_dir, ignore_errors=True)
        mesh_lib.destroy_model_parallel()


def bench_serving_trace_overhead(jax, on_tpu):
    """Distributed tracing on the serving hot path (ISSUE 15): the same
    continuous-batching wave with the flight recorder DISARMED vs ARMED
    with per-request trace contexts (request lifecycle events + decode
    ticks spilled to JSONL, trace ids stamped on every event — exactly
    what a traced fleet replica pays).  ``vs_bare`` = traced/bare wave
    wall time at the SHIPPED default tick sampling (every 8th token —
    what a production replica arms); the standing free-telemetry
    acceptance gate is <= 1.05 (scripts/bench_regress.py, beside the
    PR 9 telemetry gate) — tracing must ride inside the existing
    telemetry budget, not get its own.  ``vs_bare_tick1`` additionally
    reports the every-token worst case (what the trace smoke arms for
    exact hop boundaries) — tracked, not gated: on this tiny CPU
    config a decode tick is ~5 ms, so even a ~20µs spill per token
    reads as whole percent; on a real chip serving real shapes it
    vanishes into the step.  Unarmed tracing is a None check and is
    not measured here because it is the bare leg."""
    import tempfile

    import numpy as np

    from apex_tpu import parallel
    from apex_tpu.observability import timeline as tl
    from apex_tpu.observability.metrics import MetricRegistry
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])
    tl_dir = tempfile.mkdtemp(prefix="apex_bench_trace_")
    try:
        # hidden 256 (vs the serving row's 128): a realistically-heavy
        # decode tick, so the gate measures the tracing plane against a
        # step that does real work — on the 128-wide toy the ~20µs
        # per-event spill reads as whole percent of a ~4ms tick and
        # host jitter dominates the ratio
        hidden, layers, heads, vocab = (
            (512, 4, 8, 2048) if on_tpu else (256, 2, 8, 512))
        max_batch, prompt_len, gen = 8, 12, 24
        cfg = TransformerConfig(
            hidden_size=hidden, num_layers=layers,
            num_attention_heads=heads, padded_vocab_size=vocab,
            max_position_embeddings=256, hidden_dropout=0.0,
            attention_dropout=0.0, tensor_axis="tp",
            use_flash_attention=True)
        init_fn, _, _ = build_gpt_3d(cfg, num_chunks=layers,
                                     num_microbatches=1, mesh=mesh)
        params, _ = init_fn(jax.random.PRNGKey(0),
                            jax.numpy.zeros((2, 8), jax.numpy.int32))
        engine = ServingEngine(
            cfg, ServingConfig(max_batch=max_batch, block_size=16,
                               max_seq=prompt_len + gen + 8,
                               prefill_len=128),
            params, mesh=mesh, registry=MetricRegistry(rank=0, world=1))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, vocab - 1, size=prompt_len).tolist()
                   for _ in range(max_batch)]
        recorder = tl.FlightRecorder(
            os.path.join(tl_dir, "timeline.jsonl"))

        def wave(traced: bool, wave_id: int) -> float:
            t0 = time.perf_counter()
            for i, p in enumerate(prompts):
                trace = ({"trace_id": f"w{wave_id}r{i}", "attempt": 1}
                         if traced else None)
                engine.submit(p, gen, trace=trace)
            engine.run_until_drained(max_steps=5000)
            return time.perf_counter() - t0

        wave(False, 0)                 # compile + warm both programs
        # interleave timed passes, per-variant minima (the
        # telemetry_overhead discipline: back-to-back A-then-B on a
        # shared CPU host skews the ratio either way)
        # PAIRED rounds, median-of-ratios: on the shared CPU host the
        # wave-to-wave jitter is whole percent while the true armed
        # overhead is ~1-2% — minima of independent samples let drift
        # trip a 5% gate (observed: the same build measured 1.005 and
        # 1.065 in consecutive runs).  Pairing each traced wave with
        # an adjacent bare wave cancels the drift; the median ratio is
        # the gated number.
        import statistics

        def traced_wave(wid, tick_every):
            engine.timeline_tick_every = tick_every
            tl.arm(recorder)
            try:
                return wave(True, wid)
            finally:
                engine.timeline_tick_every = 8
                tl.disarm()

        def paired(n, tick_every, base):
            out = []
            for r in range(1, n + 1):
                if r % 2:
                    b = wave(False, base + 2 * r)
                    t = traced_wave(base + 2 * r + 1, tick_every)
                else:
                    t = traced_wave(base + 2 * r, tick_every)
                    b = wave(False, base + 2 * r + 1)
                out.append((t, b))
            return out

        pairs = paired(10, 8, 0)
        pairs_tick1 = paired(4, 1, 100)
        vs_bare = statistics.median(t / b for t, b in pairs)
        vs_bare_tick1 = statistics.median(t / b for t, b in pairs_tick1)
        dt_bare = min(b for _, b in pairs)
        dt_traced = min(t for t, _ in pairs)
        tokens = max_batch * gen
        _log(f"serving_trace_overhead: bare {dt_bare * 1e3:.1f}ms "
             f"traced {dt_traced * 1e3:.1f}ms, paired vs_bare "
             f"{vs_bare:.3f} (tick_every=1: {vs_bare_tick1:.3f}) over "
             f"{len(pairs)}+{len(pairs_tick1)} rounds "
             f"({recorder.events_emitted} timeline events)")
        return {
            "value": round(tokens / max(dt_traced, 1e-9), 1),
            "unit": "tokens/sec",
            "config": (f"gpt h{hidden} L{layers} c={max_batch} "
                       f"gen{gen}, default tick sampling"),
            "bare_tokens_per_sec": round(tokens / max(dt_bare, 1e-9), 1),
            "vs_bare": round(vs_bare, 3),
            "vs_bare_tick1": round(vs_bare_tick1, 3),
            "timeline_events": recorder.events_emitted,
            "measured": (
                "continuous-batching wave A/B: flight recorder armed "
                "with per-request trace contexts (lifecycle events + "
                "sampled decode ticks, JSONL spill) vs disarmed; "
                "vs_bare (median of per-round paired ratios — host "
                "drift cancels) at the shipped tick_every=8 default "
                "is the <= 1.05 hard gate, vs_bare_tick1 tracks the "
                "every-token worst case ungated"),
        }
    finally:
        tl.disarm()
        shutil.rmtree(tl_dir, ignore_errors=True)
        parallel.destroy_model_parallel()


def bench_serving_slo_overhead(jax, on_tpu):
    """Longitudinal history + SLO burn-rate evaluation on the serving
    hot path (ISSUE 20): the same continuous-batching wave BARE vs
    ARMED with a :class:`MetricHistory` sampling the engine registry
    and an :class:`SLOEvaluator` walking its burn-rate state machine
    every 4th step — a far hotter cadence than the shipped per-second
    default, so the gate bounds a deliberate worst case.  Both legs
    drive the engine through an identical manual step loop (only the
    sample/evaluate calls differ), paired rounds, median-of-ratios —
    the serving_trace_overhead discipline.  ``vs_bare`` <= 1.05 is the
    standing free-telemetry acceptance gate (scripts/bench_regress.py):
    the history plane must ride inside the existing telemetry budget.
    A disarmed fleet is a single None check and is the bare leg."""
    import numpy as np

    from apex_tpu import parallel
    from apex_tpu.observability.metrics import MetricRegistry
    from apex_tpu.observability.slo import SLOEvaluator, SLOPolicy
    from apex_tpu.observability.timeseries import MetricHistory
    from apex_tpu.serving import ServingConfig, ServingEngine
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])
    try:
        hidden, layers, heads, vocab = (
            (512, 4, 8, 2048) if on_tpu else (256, 2, 8, 512))
        max_batch, prompt_len, gen = 8, 12, 24
        cfg = TransformerConfig(
            hidden_size=hidden, num_layers=layers,
            num_attention_heads=heads, padded_vocab_size=vocab,
            max_position_embeddings=256, hidden_dropout=0.0,
            attention_dropout=0.0, tensor_axis="tp",
            use_flash_attention=True)
        init_fn, _, _ = build_gpt_3d(cfg, num_chunks=layers,
                                     num_microbatches=1, mesh=mesh)
        params, _ = init_fn(jax.random.PRNGKey(0),
                            jax.numpy.zeros((2, 8), jax.numpy.int32))
        registry = MetricRegistry(rank=0, world=1)
        engine = ServingEngine(
            cfg, ServingConfig(max_batch=max_batch, block_size=16,
                               max_seq=prompt_len + gen + 8,
                               prefill_len=128),
            params, mesh=mesh, registry=registry)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, vocab - 1, size=prompt_len).tolist()
                   for _ in range(max_batch)]
        history = MetricHistory(registry)
        evaluator = SLOEvaluator(history, [
            SLOPolicy(name="ttft", metric="serving/ttft_ms:p99",
                      objective=50.0, fast_window_s=5.0,
                      slow_window_s=30.0, compliance_window_s=300.0),
            SLOPolicy(name="tpot", metric="serving/tpot_ms:p99",
                      objective=20.0, fast_window_s=5.0,
                      slow_window_s=30.0, compliance_window_s=300.0),
        ])

        def wave(armed: bool) -> float:
            t0 = time.perf_counter()
            for p in prompts:
                engine.submit(p, gen)
            steps = 0
            for _ in range(5000):
                if engine.scheduler.idle:
                    break
                engine.step()
                steps += 1
                if armed and steps % 4 == 0:
                    history.sample()
                    evaluator.evaluate()
            return time.perf_counter() - t0

        wave(False)                    # compile + warm both programs
        import statistics

        pairs = []
        for r in range(16):
            if r % 2:
                b = wave(False)
                t = wave(True)
            else:
                t = wave(True)
                b = wave(False)
            pairs.append((t, b))
        vs_bare = statistics.median(t / b for t, b in pairs)
        dt_bare = min(b for _, b in pairs)
        dt_armed = min(t for t, _ in pairs)
        tokens = max_batch * gen
        _log(f"serving_slo_overhead: bare {dt_bare * 1e3:.1f}ms armed "
             f"{dt_armed * 1e3:.1f}ms, paired vs_bare {vs_bare:.3f} "
             f"over {len(pairs)} rounds "
             f"({history.introspect()['samples']} history samples, "
             f"{len(evaluator.last_rows)} slo rows)")
        return {
            "value": round(tokens / max(dt_armed, 1e-9), 1),
            "unit": "tokens/sec",
            "config": (f"gpt h{hidden} L{layers} c={max_batch} "
                       f"gen{gen}, sample+evaluate every 4th step"),
            "bare_tokens_per_sec": round(tokens / max(dt_bare, 1e-9), 1),
            "vs_bare": round(vs_bare, 3),
            "history_samples": history.introspect()["samples"],
            "measured": (
                "continuous-batching wave A/B: MetricHistory registry "
                "sampling + SLOEvaluator burn-rate evaluation every "
                "4th engine step vs the identical bare loop; vs_bare "
                "(median of per-round paired ratios — host drift "
                "cancels) is the <= 1.05 hard gate: the longitudinal "
                "plane rides inside the telemetry budget"),
        }
    finally:
        parallel.destroy_model_parallel()


def bench_serving_autopilot(jax, on_tpu):
    """SLO autopilot (ISSUE 18): a tenant burst against a one-replica
    fleet with the autopilot closing the scale loop (warm-standby
    spawn, ready-handshake join) vs the same burst on the static
    single-replica fleet.

    ``vs_static`` is the paired median-of-ratios of burst p99 TTFT
    (static / autopilot) — the SLO the scale loop exists to protect:
    the static replica queues the burst behind ``max_batch`` so the
    tail requests wait out whole decode generations before their first
    token, while the scaled pool admits the burst immediately.  The
    floor is >= 1.0 (scripts/bench_regress.py): an autopilot that does
    not beat the fleet it operates is a regression.  TTFT (not wall
    tokens/sec) is the judged metric because it holds on a single-core
    CPU host too, where three timesharing replica processes add no
    throughput — the win is admission, not FLOPs.  ``recover_s`` is
    the drain-back: wall seconds from quiesce until the autopilot has
    SIGTERM-drained the pool back to one replica (includes the trend
    window settling to flat — quiesce *detection* is part of the
    loop's cost).  ``actions`` counts autopilot actuations
    (``fleet/autopilot/actions``)."""
    _replica_processes_need_cpu(jax, "serving_autopilot")
    import os
    import shutil
    import statistics
    import tempfile

    import numpy as np

    from apex_tpu import parallel
    from apex_tpu.observability.metrics import MetricRegistry
    from apex_tpu.resilience import CheckpointManager, reshard
    from apex_tpu.serving import (
        AutopilotConfig, FleetAutopilot, FleetRouter, ReplicaProcess,
        ReplicaSpec, ServingConfig)
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import (
        build_gpt_3d, gpt3d_logical_folds)

    hidden, layers, heads, vocab = (
        (256, 2, 8, 1024) if on_tpu else (64, 2, 4, 256))
    prompt_len, gen, wave, rounds = 12, 16, 24, 3
    max_seq = prompt_len + gen + 4
    cfg = TransformerConfig(
        hidden_size=hidden, num_layers=layers, num_attention_heads=heads,
        padded_vocab_size=vocab, max_position_embeddings=max_seq,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis="tp",
        use_flash_attention=True)
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=1, devices=jax.devices()[:1])
    init_fn, _, _ = build_gpt_3d(cfg, num_chunks=layers,
                                 num_microbatches=1, mesh=mesh)
    params, _ = init_fn(jax.random.PRNGKey(0),
                        jax.numpy.zeros((2, 8), jax.numpy.int32))
    workdir = tempfile.mkdtemp(prefix="apex_bench_autopilot_")
    ckpt_dir = os.path.join(workdir, "ckpt")
    tree = {"params": params, "step_count": np.asarray(1)}
    spec = reshard.build_spec(tree, mesh=mesh,
                              folds=gpt3d_logical_folds(tree))
    CheckpointManager(ckpt_dir, sharded=True, spec=spec).save(tree, 1)
    rng = np.random.RandomState(0)
    routers, pool = [], []
    try:
        rspec = ReplicaSpec(
            config=cfg,
            serving=ServingConfig(max_batch=8, block_size=8,
                                  max_seq=max_seq, prefill_len=64),
            tp=1, ckpt_dir=ckpt_dir, debug_server=False)
        # static fleet: one replica, no controller.  autopilot fleet:
        # one primary + a warm standby pool the spawn actuator draws
        # from (scale-up from standby — the join is the ordinary ready
        # handshake, just without a cold compile in the middle)
        static_rep = ReplicaProcess(rspec, "s0")
        primary = ReplicaProcess(rspec, "a0")
        pool = [ReplicaProcess(rspec, f"auto{i}") for i in (1, 2)]
        for r in [static_rep, primary] + pool:
            r.wait_ready(timeout=500)

        def spawn(name):
            if not pool:
                raise RuntimeError("standby pool exhausted")
            client = pool.pop(0)
            assert client.name == name, (client.name, name)
            return client

        # replica_queue_limit == max_batch: the router keeps the burst
        # backlog on its own queue instead of stuffing one replica's —
        # identical admission policy for both fleets, so the only
        # difference the pairing sees is the capacity the autopilot adds
        static_router = FleetRouter(
            [static_rep], max_queue_depth=4 * wave,
            replica_queue_limit=8, heartbeat_timeout_s=30.0,
            registry=MetricRegistry(rank=0, world=1))
        auto_router = FleetRouter(
            [primary], max_queue_depth=4 * wave,
            replica_queue_limit=8, heartbeat_timeout_s=30.0,
            registry=MetricRegistry(rank=0, world=1))
        routers = [static_router, auto_router]
        # burst-phase policy: grow eagerly (no cool-down gate between
        # the two standby joins), never drain mid-burst (min==max) —
        # the drain-back phase swaps in the quiesce policy below
        ap = FleetAutopilot(auto_router, spawn=spawn,
                            config=AutopilotConfig(
                                min_replicas=3, max_replicas=3,
                                scale_up_queue_depth=8,
                                scale_cooldown_s=0.0))

        def burst(router, prompts, autopilot=None, budget=gen):
            reg = MetricRegistry(rank=0, world=1)
            router.registry = reg
            t0 = time.perf_counter()
            reqs = [router.submit(p, budget) for p in prompts]
            while not router.idle():
                router.pump()
                if autopilot is not None:
                    autopilot.tick()
                if time.perf_counter() - t0 > 500:
                    raise RuntimeError("autopilot bench burst wedged")
                time.sleep(0.002)
            dt = time.perf_counter() - t0
            assert all(len(r.output_tokens) == budget for r in reqs)
            return {"dt": dt,
                    "p99_ttft": reg.histogram("fleet/ttft_ms")
                    .percentile(99),
                    "p99_tpot": reg.histogram("fleet/tpot_ms")
                    .percentile(99)}

        warm = [rng.randint(1, vocab - 1, size=prompt_len).tolist()
                for _ in range(3)]
        burst(static_router, warm, budget=2)
        burst(auto_router, warm, budget=2)     # no scale: depth < 8
        stat_rows, auto_rows = [], []
        for _ in range(rounds):
            prompts = [rng.randint(1, vocab - 1,
                                   size=prompt_len).tolist()
                       for _ in range(wave)]
            stat_rows.append(burst(static_router, prompts))
            auto_rows.append(burst(auto_router, prompts,
                                   autopilot=ap))
        def live():
            return sum(1 for v in auto_router._views.values()
                       if not v.down and v.client.alive())

        assert live() == 3, "autopilot never grew the pool"
        vs_static = statistics.median(
            s["p99_ttft"] / max(a["p99_ttft"], 1e-9)
            for s, a in zip(stat_rows, auto_rows))
        # quiesce: swap in the drain-back policy and measure the wall
        # time until the pool is back to one replica (the spawned
        # replicas leave via the ordinary SIGTERM-drain path)
        ap.config = AutopilotConfig(min_replicas=1, max_replicas=3,
                                    scale_down_queue_depth=2,
                                    scale_cooldown_s=0.0)
        t0 = time.perf_counter()
        while live() > 1:
            auto_router.pump()
            ap.tick()
            if time.perf_counter() - t0 > 200:
                raise RuntimeError("drain-back wedged")
            time.sleep(0.01)
        recover_s = time.perf_counter() - t0
        actions = int(ap.registry.counter(
            "fleet/autopilot/actions").value)
        p99_burst = statistics.median(a["p99_ttft"] for a in auto_rows)
        p99_static = statistics.median(s["p99_ttft"] for s in stat_rows)
        tokens = wave * gen
        tps = statistics.median(tokens / a["dt"] for a in auto_rows)
        _log(f"serving_autopilot: burst p99 TTFT {p99_burst:.1f}ms "
             f"autopilot vs {p99_static:.1f}ms static "
             f"(vs_static {vs_static:.2f}x, {actions} actions, "
             f"drain-back {recover_s:.1f}s)")
        return {
            "value": round(tps, 1),
            "unit": "tokens/sec",
            "config": (f"gpt h{hidden} L{layers} 1+2-standby tp1 "
                       f"replicas prompt{prompt_len} gen{gen} "
                       f"wave{wave} x{rounds} rounds"),
            "p99_ttft_ms_burst": round(p99_burst, 2),
            "p99_ttft_ms_static": round(p99_static, 2),
            "p99_tpot_ms_burst": round(statistics.median(
                a["p99_tpot"] for a in auto_rows), 2),
            "vs_static": round(vs_static, 3),
            "actions": actions,
            "recover_s": round(recover_s, 1),
            "measured": (
                f"{rounds} paired rounds of a {wave}-request tenant "
                f"burst x {gen} greedy tokens: static one-replica "
                "fleet vs the same fleet with the autopilot scaling "
                "onto 2 warm standbys through the ready handshake; "
                "vs_static = median per-round (static p99 TTFT / "
                "autopilot p99 TTFT) — admission latency, the metric "
                "the scale loop protects; recover_s = quiesce-policy "
                "drain back to one replica (includes trend-flat "
                "detection)"),
        }
    finally:
        for router in routers:
            router.close()
        for r in pool:
            try:
                r.close()
            except Exception:
                pass
        shutil.rmtree(workdir, ignore_errors=True)
        parallel.destroy_model_parallel()


# ---------------------------------------------------------------------------

BENCHES = {
    "resnet50_o2": bench_resnet50_o2,
    "resnet50_lamb_syncbn": bench_resnet50_lamb_syncbn,
    "bert_large": bench_bert_large,
    "gpt_flash": bench_gpt_flash,
    "gpt_flash_fp8": bench_gpt_flash_fp8,
    "gpt_long_context": bench_gpt_long_context,
    "tp_gpt": bench_tp_gpt,
    "fused_adam_step": bench_fused_adam_step,
    "zero_adam_step": bench_zero_adam_step,
    "ckpt_save_restore": bench_ckpt_save_restore,
    "ckpt_reshard": bench_ckpt_reshard,
    "telemetry_overhead": bench_telemetry_overhead,
    "serving": bench_serving,
    "serving_occupancy": bench_serving_occupancy,
    "serving_fleet": bench_serving_fleet,
    "serving_spec": bench_serving_spec,
    "serving_disagg": bench_serving_disagg,
    "serving_trace_overhead": bench_serving_trace_overhead,
    "serving_slo_overhead": bench_serving_slo_overhead,
    "serving_lora": bench_serving_lora,
    "serving_autopilot": bench_serving_autopilot,
    "input_pipeline": bench_input_pipeline,
    "real_data_rn50": bench_real_data_rn50,
    # Diagnostic-only combos (run via ``--one``, not in BENCH_ORDER):
    # isolate which factor of the lamb+syncbn row costs what — the r4
    # first window measured resnet50_o2 (sgd, plain BN, pjit) 3.4x faster
    # than resnet50_lamb_syncbn (lamb, SyncBN, shard_map) on one chip.
    "resnet50_sgd_syncbn": lambda jax, on_tpu: _resnet_bench(
        jax, on_tpu, "sgd", sync_bn=True),
    "resnet50_lamb_nosync": lambda jax, on_tpu: _resnet_bench(
        jax, on_tpu, "lamb"),
}
# headline first; tp_gpt last (the one row with a history of hanging in
# setup: a hang there costs its own timeout and nothing behind it).
BENCH_ORDER = ["resnet50_o2", "gpt_flash", "bert_large",
               "resnet50_lamb_syncbn", "fused_adam_step",
               "zero_adam_step", "ckpt_save_restore", "ckpt_reshard",
               "telemetry_overhead", "serving", "serving_occupancy",
               "serving_fleet", "serving_spec", "serving_disagg",
               "serving_trace_overhead", "serving_slo_overhead",
               "serving_lora", "serving_autopilot",
               "gpt_flash_fp8", "gpt_long_context", "input_pipeline",
               "real_data_rn50", "tp_gpt"]


def run_one(name: str) -> None:
    """Child mode: init the backend, run one bench, print its JSON."""
    import jax

    from apex_tpu.utils import platform

    cpu_dry_run = os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
    if cpu_dry_run:
        platform.pin_cpu()
    else:
        platform.enable_compilation_cache()
    _log(f"{name}: initializing backend")
    t0 = time.perf_counter()
    dev = jax.devices()[0]
    _log(f"{name}: backend up in {time.perf_counter() - t0:.1f}s "
         f"({dev.platform} {dev.device_kind})")
    if dev.platform != "tpu" and not cpu_dry_run:
        # JAX carries on on the CPU when it finds no chip; a row measured
        # there must be asked for, not fallen into
        raise SystemExit(
            f"{name}: no TPU (platform={dev.platform!r}); set "
            "JAX_PLATFORMS=cpu for the tiny-shape dry run")
    rec = BENCHES[name](jax, dev.platform == "tpu")
    rec["platform"] = dev.platform
    rec["device_kind"] = dev.device_kind
    rec["device_count"] = len(jax.devices())
    _log(f"{name}: done -> {rec.get('value')} {rec.get('unit')}")
    print(json.dumps(rec), flush=True)


# Rows that need a mesh: in the CPU dry run they get eight virtual devices
# (tp_gpt a real tp=8 shard_map, zero_adam_step and the ckpt rows a dp=8
# mesh to shard over).  On the TPU they use the devices there are.
_CPU_MESH_ROWS = ("tp_gpt", "zero_adam_step", "ckpt_save_restore",
                  "ckpt_reshard", "telemetry_overhead", "serving")

_CHILD_TIMEOUT_S = 900.0


def _run_child(name: str) -> dict:
    env = dict(os.environ)
    if env.get("JAX_PLATFORMS", "").lower() == "cpu" \
            and name in _CPU_MESH_ROWS:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
    _log(f"launching {name} (timeout {_CHILD_TIMEOUT_S:.0f}s)")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--one", name],
            timeout=_CHILD_TIMEOUT_S, capture_output=True, env=env,
        )
    except subprocess.TimeoutExpired as e:
        # Partial stderr attributes the loss: no "backend up" line means
        # backend init hung; "compile start" without "compiled" means a
        # compile blowup; otherwise the bench itself was too slow.
        tail = (e.stderr or b"").decode(errors="replace")[-600:]
        _log(f"{name}: TIMEOUT after {_CHILD_TIMEOUT_S:.0f}s; partial "
             f"stderr:\n{tail}")
        return {"error": f"timeout after {_CHILD_TIMEOUT_S:.0f}s",
                "stderr_tail": tail[-300:]}
    err_tail = proc.stderr.decode(errors="replace")[-1500:]
    if proc.returncode != 0:
        _log(f"{name}: rc={proc.returncode}\n{err_tail}")
        return {"error": f"rc={proc.returncode}: {err_tail[-300:]}"}
    try:
        return json.loads(proc.stdout.decode().strip().splitlines()[-1])
    except (IndexError, json.JSONDecodeError) as e:
        _log(f"{name}: unparseable output ({e!r})\n{err_tail}")
        return {"error": f"unparseable output: {e!r}"}


def build_record(results) -> dict:
    """Assemble the record from the rows: the RN50 O2 headline plus every
    other row under ``extras``.  Rows that did not run appear as errors."""
    headline = results.get("resnet50_o2", {"error": "unrun"})
    ok = "error" not in headline
    baseline = adopted_baseline()
    record = {
        "metric": "resnet50_o2_train_throughput",
        "value": headline.get("value", 0.0) if ok else 0.0,
        "unit": "images/sec/chip",
        # the adopted A100 figure is a device number: only a TPU headline
        # is compared with it
        "vs_baseline": (round(headline["value"] / baseline, 3)
                        if ok and headline.get("platform") == "tpu"
                        else None),
        "platform": headline.get("platform"),
        "device_kind": headline.get("device_kind"),
        "device_count": headline.get("device_count"),
        "headline": headline,
        "extras": {k: v for k, v in results.items() if k != "resnet50_o2"},
    }
    # State the fp8-vs-bf16 delta plainly when both rows ran on the same
    # platform (the fp8 path is a storage/numerics capability on this chip
    # generation — the honest expectation is ~1.0x, not a win).
    bf16, fp8 = results.get("gpt_flash", {}), results.get("gpt_flash_fp8", {})
    if ("error" not in bf16 and "error" not in fp8
            and bf16.get("platform") == fp8.get("platform")
            and bf16.get("value")):
        record["extras"]["gpt_flash_fp8"] = dict(
            fp8, vs_bf16=round(fp8["value"] / bf16["value"], 3))
    # Real-data vs synthetic RN50: how much of the device rate survives
    # feeding the step from actual files (1.0 = the input path costs
    # nothing; VERDICT r4 missing #2 asks for this composition).
    real = results.get("real_data_rn50", {})
    if ("error" not in real and ok and real.get("value")
            and headline.get("platform") == real.get("platform")
            and headline.get("value")):
        record["extras"]["real_data_rn50"] = dict(
            real, vs_synthetic=round(real["value"] / headline["value"], 3))
    return record


def main() -> int:
    """Run ``BENCH_ORDER`` one child at a time; print the record as one
    JSON line and keep it in ``bench_results/latest_record.json``.
    Returns non-zero when any row failed."""
    results = {}
    for name in BENCH_ORDER:
        results[name] = _run_child(name)
    record = build_record(results)
    path = os.path.join(_REPO, "bench_results", "latest_record.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".tmp", "w") as f:
        json.dump(record, f)
    os.replace(path + ".tmp", path)
    print(json.dumps(record), flush=True)
    failed = sorted(n for n, r in results.items() if "error" in r)
    if failed:
        _log(f"{len(failed)} of {len(results)} rows failed: {failed}")
    return 1 if failed else 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--one":
        run_one(sys.argv[2])
    else:
        sys.exit(main())

"""Crash/resume smoke trainer — the end-to-end resilience proof.

A tiny but *real* run of the 3D-parallel GPT trainer
(:mod:`apex_tpu.transformer.testing.gpt_parallel_train`, sentinel armed)
— or, with ``--zero``, the flat-bucket ZeRO data-parallel trainer
(:func:`apex_tpu.parallel.distributed.zero_data_parallel_train_step`
with ``DistributedFusedAdam(flat_bucket=True)``) — on a virtual CPU
mesh, checkpointing every step through
:class:`apex_tpu.resilience.CheckpointManager` (async sharded saves —
the pod-scale path).  ``scripts/crash_resume_smoke.sh`` runs it three
ways: uninterrupted, SIGKILLed mid-run, and resumed — and asserts the
resumed loss curve is byte-identical to the uninterrupted one
(``tests/test_crash_resume.py`` drives the script in the fast tier).

**Elastic resume (ISSUE 6)**: every save embeds the
:class:`apex_tpu.resilience.reshard.ShardingSpec` logical-state
description, and the mesh shape is a command-line choice (``--tp``,
``--pp``, ``--devices`` for dp, ``--global-batch`` to keep the input
stream mesh-independent), so a ``--resume`` may run on a DIFFERENT
dp/tp/pp layout than the run that saved: ``restore_latest`` then
reshards — layer stacks re-factored across ``[vpp, pp]``, ZeRO flat
buckets re-chunked for the new world size — bit-losslessly.
``scripts/elastic_resume_smoke.sh`` drives the kill-at-mesh-N /
resume-at-mesh-M matrix; ``--fingerprint`` writes the canonical
mesh-independent state digest (:func:`apex_tpu.resilience.reshard.
load_logical` of the newest committed checkpoint, one
``"{leaf} {sha256}"`` line each) that the harness compares bitwise
across mesh shapes.

Per-step losses are appended to ``--losses`` as ``"{step} {fp32 bits as
hex}"`` lines (flushed + fsynced per line, so a SIGKILL loses at most
the in-flight line): hex bits make the bit-exact-resume comparison a
string equality, immune to repr rounding.

SIGTERM (preemption) is handled by
:class:`apex_tpu.resilience.PreemptionGuard`: drain the in-flight async
save, take a final synchronous checkpoint, exit 0.

Determinism: tokens for step ``i`` are ``fold_in(data_key, i)`` over the
GLOBAL batch, so any resume point replays the identical input stream on
any mesh shape; CPU XLA + bit-exact checkpoint round trips make the
whole curve reproducible bit-for-bit on a FIXED mesh.  Across a mesh
change the replayed *state* is bit-identical but the step arithmetic
re-associates (different dp reduction widths, tp matmul splits), so the
elastic harness compares a killed N→M run against a clean N→M reference
rather than against a single-mesh curve.
"""

from __future__ import annotations

import argparse
import os
import sys

VOCAB = 64
SEQ = 16


def _append_loss(path: str, step: int, loss) -> None:
    import numpy as np

    with open(path, "a") as f:
        f.write(f"{step} {np.float32(loss).tobytes().hex()}\n")
        f.flush()
        os.fsync(f.fileno())


def _truncate_losses(path: str, last_step: int) -> None:
    """Keep loss lines for steps <= ``last_step`` (a crash may have
    logged steps newer than the newest durable checkpoint)."""
    if not os.path.exists(path):
        return
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines()
                 if ln and int(ln.split()[0]) <= last_step]
    with open(path, "w") as f:
        f.write("".join(ln + "\n" for ln in lines))
        f.flush()
        os.fsync(f.fileno())


def _write_fingerprint(out_path: str, mgr) -> None:
    """Canonical mesh-independent digest of the newest committed
    checkpoint: one ``"{logical leaf} {sha256 of bytes}"`` line per
    leaf, sorted — two checkpoints of the same training state saved
    under different mesh shapes must produce identical files."""
    import hashlib

    import numpy as np

    from apex_tpu.resilience import reshard

    step = next((s for s in reversed(mgr.all_steps())
                 if mgr._is_committed(s)), None)
    if step is None:
        raise SystemExit("fingerprint: no committed checkpoint")
    leaves, at = reshard.load_logical(mgr._path(step))
    lines = [f"step {at}\n"]
    for key in sorted(leaves):
        arr = np.ascontiguousarray(leaves[key])
        digest = hashlib.sha256(arr.tobytes()).hexdigest()
        lines.append(f"{key} {arr.dtype} {list(arr.shape)} {digest}\n")
    with open(out_path, "w") as f:
        f.writelines(lines)
        f.flush()
        os.fsync(f.fileno())


def _build_gpt(args, mesh, jax):
    """The 3D GPT trainer legs: returns (pack, step_fn, data_fn, spec).

    With ``--tp``/``--pp`` > 1 the model grows to 2 layers / 4 heads so
    the same logical network factors as (pp=2, vpp=1) or (pp=1, vpp=2)
    and tp in {1, 2, 4} — the elastic transitions of the ISSUE 6 matrix.
    """
    from apex_tpu.amp.scaler import DynamicLossScale
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel.distributed import replicate
    from apex_tpu.resilience import reshard, sentinel_init
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import (
        build_gpt_3d,
        gpt3d_logical_folds,
    )

    dp = mesh.shape["dp"]
    model_parallel = args.tp > 1 or args.pp > 1
    num_layers = 2 if model_parallel else 1
    pp = mesh.shape["pp"]
    if num_layers % pp:
        raise SystemExit(f"num_layers {num_layers} not divisible by "
                         f"pp {pp}")
    cfg = TransformerConfig(
        hidden_size=32, num_layers=num_layers,
        num_attention_heads=4 if model_parallel else 2,
        padded_vocab_size=VOCAB, max_position_embeddings=SEQ,
        hidden_dropout=0.0, attention_dropout=0.0,
        tensor_axis="tp" if args.tp > 1 else None,
        sequence_parallel=args.tp > 1)
    num_microbatches = 2
    init_fn, _, make_train_step = build_gpt_3d(
        cfg, num_chunks=num_layers // pp,
        num_microbatches=num_microbatches, mesh=mesh)

    batch = args.global_batch or dp * num_microbatches
    if batch % (dp * num_microbatches):
        raise SystemExit(f"global batch {batch} not divisible by "
                         f"dp*microbatches {dp * num_microbatches}")
    data_key = jax.random.PRNGKey(7)

    def data_fn(i):
        return jax.random.randint(jax.random.fold_in(data_key, i),
                                  (batch, SEQ), 0, VOCAB)

    params, specs = init_fn(jax.random.PRNGKey(0), data_fn(0))
    opt = FusedAdam(lr=1e-2)
    scaler = DynamicLossScale()
    # Commit optimizer/sentinel state to the mesh (replicated): restore
    # places leaves by the template's sharding, and a resumed step must
    # see the same device layout as the uninterrupted run.
    opt_state = replicate(opt.init(params), mesh)
    sent = replicate(sentinel_init(scaler), mesh)
    step_fn = jax.jit(make_train_step(opt, specs, scaler=scaler))

    pack = {"params": params, "opt": opt_state, "sent": sent}
    spec = reshard.build_spec(pack, mesh=mesh,
                              folds=gpt3d_logical_folds(pack))
    return pack, step_fn, data_fn, spec


def _build_zero(args, mesh, jax):
    """The flat-bucket ZeRO leg: a small dp-sharded regression whose
    optimizer state — per-(dtype-group, bucket) ``(rows, chunk)``
    buffers — is mesh-shape-DEPENDENT, the hard case of restore-anywhere
    (the buffers must be unflattened to logical leaves and re-chunked
    for the new dp world on resume)."""
    import jax.numpy as jnp

    from apex_tpu.amp.scaler import DynamicLossScale
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.parallel.distributed import (
        dp_shard_batch,
        replicate,
        zero_data_parallel_train_step,
        zero_init,
    )
    from apex_tpu.resilience import reshard, sentinel_init

    dp = mesh.shape["dp"]
    batch = args.global_batch or 8
    if batch % dp:
        raise SystemExit(f"global batch {batch} not divisible by dp {dp}")

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    params = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (13, 7)),
        "b": jnp.zeros((7,)),
    }
    opt = DistributedFusedAdam(lr=1e-2, flat_bucket=True, n_buckets=2)
    scaler = DynamicLossScale()
    params = replicate(params, mesh)
    opt_state = zero_init(opt, params, mesh)
    sent = replicate(sentinel_init(scaler), mesh)
    inner = zero_data_parallel_train_step(loss_fn, opt, mesh=mesh,
                                          scaler=scaler)
    data_key = jax.random.PRNGKey(11)

    def data_fn(i):
        kx, ky = jax.random.split(jax.random.fold_in(data_key, i))
        return dp_shard_batch(
            (jax.random.normal(kx, (batch, 13)),
             jax.random.normal(ky, (batch, 7))), mesh)

    def step_fn(params, opt_state, batch, sent):
        return inner(params, opt_state, batch, sent)

    pack = {"params": params, "opt": opt_state, "sent": sent}
    spec = reshard.build_spec(
        pack, mesh=mesh, zero_states=[("opt", opt, params)])
    return pack, step_fn, data_fn, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ckpt-dir", required=True)
    ap.add_argument("--losses", required=True)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--devices", type=int, default=2)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel size (gpt mode)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel size (gpt mode)")
    ap.add_argument("--global-batch", type=int, default=0,
                    help="fixed global batch so the input stream is "
                         "identical on every mesh shape (0 = the legacy "
                         "dp-dependent default)")
    ap.add_argument("--zero", action="store_true",
                    help="flat-bucket ZeRO trainer instead of the 3D "
                         "GPT (dp-only mesh; optimizer buffers are "
                         "mesh-shape-dependent)")
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest intact checkpoint — "
                         "resharding it onto THIS run's mesh shape if "
                         "it was saved under another — and continue "
                         "from the step after it")
    ap.add_argument("--flat", action="store_true",
                    help="flat single-file layout instead of sharded")
    ap.add_argument("--fingerprint", default=None,
                    help="after the run, write the mesh-independent "
                         "logical digest of the newest committed "
                         "checkpoint to this path")
    ap.add_argument("--step-delay", type=float, default=0.0,
                    help="sleep this many seconds per step while the "
                         "async save is in flight — gives an external "
                         "killer a deterministic window (a warm "
                         "compilation cache can otherwise finish the "
                         "whole run between two poll ticks)")
    args = ap.parse_args(argv)

    # Platform pinning must precede any backend use (same contract as
    # __graft_entry__.dryrun_multichip).
    from apex_tpu.utils.platform import (
        enable_compilation_cache, force_host_device_count, pin_cpu)

    force_host_device_count(max(args.devices, 1))
    pin_cpu()
    import jax
    import numpy as np

    # The smoke scripts launch this trainer several times (reference,
    # crash, resume) with identical programs: the persistent compilation
    # cache keeps later runs warm, which is what keeps the whole
    # save->SIGKILL->resume proof in the fast tier.
    enable_compilation_cache()

    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.resilience import CheckpointManager, PreemptionGuard

    devices = jax.devices("cpu")[: args.devices]
    if args.zero and (args.tp > 1 or args.pp > 1):
        raise SystemExit("--zero is dp-only (tp/pp must be 1)")
    mesh = mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=args.tp,
        pipeline_model_parallel_size=args.pp, devices=devices)

    build = _build_zero if args.zero else _build_gpt
    pack, step_fn, data_fn, spec = build(args, mesh, jax)

    mgr = CheckpointManager(args.ckpt_dir, keep=args.keep,
                            sharded=not args.flat, spec=spec)

    # Flight recorder (ISSUE 10): both trainer legs (3D GPT / flat-bucket
    # ZeRO) emit the run timeline when APEX_TPU_TIMELINE_DIR is set —
    # step intervals, sentinel skips, the checkpoint save/verify/restore
    # events from the manager, and the preemption/drain tail; the spill
    # survives the SIGKILL this harness exists to inject (torn-tail-only
    # loss).  Armed before the resume path so restores are on the
    # timeline too.
    from apex_tpu.observability import timeline

    recorder = timeline.arm_from_env()

    start = 0
    if args.resume:
        try:
            restored, at = mgr.restore_latest(pack)
            pack = restored
            start = at + 1
            _truncate_losses(args.losses, at)
            print(f"crash_resume: resumed from step {at}", file=sys.stderr)
        except FileNotFoundError as e:
            # Every checkpoint was lost (e.g. the crash plus injected
            # corruption destroyed the only save): restart from scratch —
            # determinism makes even this resume bit-exact.
            _truncate_losses(args.losses, -1)
            print(f"crash_resume: no intact checkpoint ({e}); "
                  "restarting from step 0", file=sys.stderr)

    params, opt_state, sent = pack["params"], pack["opt"], pack["sent"]

    def packed(p, s, z):
        return {"params": p, "opt": s, "sent": z}

    prev_skips = int(np.asarray(sent.skipped_steps))

    import time

    guard = PreemptionGuard()
    try:
        for i in range(start, args.steps):
            t_step = time.monotonic()
            params, opt_state, sent, loss = step_fn(params, opt_state,
                                                    data_fn(i), sent)
            loss = jax.block_until_ready(loss)
            step_s = time.monotonic() - t_step
            # No finiteness assert: the armed sentinel SKIPS an overflow
            # step rather than dying, and a non-finite reported loss is
            # deterministic, so the bit-exact curve comparison still
            # holds across resume.
            if not bool(np.isfinite(np.asarray(loss))):
                print(f"crash_resume: step {i} overflowed (skipped "
                      f"by sentinel)", file=sys.stderr)
            if recorder is not None:
                # the step event can only be emitted AFTER the skip
                # verdict is known — a sentinel-skipped step must land
                # in the goodput `skipped_step` bucket, not `compute`
                skips = int(np.asarray(sent.skipped_steps))
                skipped = skips > prev_skips
                recorder.emit("step", dur_s=step_s, step=i,
                              **({"skipped": True} if skipped else {}))
                if skipped:
                    recorder.sentinel_skip(i, skips)
                prev_skips = skips
            _append_loss(args.losses, i, loss)
            mgr.save_async(packed(params, opt_state, sent), i)
            if args.step_delay > 0:
                # sleep WHILE the async writer is in flight, so an
                # external SIGKILL can land mid-save
                time.sleep(args.step_delay)
            if guard.triggered:
                # drain the in-flight async save: step i is durable once
                # wait() returns (no redundant re-save in the grace
                # window)
                if recorder is not None:
                    recorder.preemption(step=i)
                with timeline.scope("drain", step=i):
                    mgr.wait()
                if recorder is not None:
                    recorder.flush()
                print(f"crash_resume: preempted, drained at step {i}, "
                      "clean exit", file=sys.stderr)
                return 0
        mgr.wait()
        if recorder is not None:
            recorder.flush()
    finally:
        guard.uninstall()
    if args.fingerprint:
        _write_fingerprint(args.fingerprint, mgr)
    print(f"crash_resume: completed {args.steps} steps "
          f"(skipped_steps={int(sent.skipped_steps)})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Observability subsystem (ISSUE 5): telemetry must be FREE and SAFE.

Three contract groups:

1. **Instrumentation adds nothing** (analyzer satellite): the
   instrumented (``collect_stats=True``) 3D GPT and ZeRO train steps
   compile to HLO with exactly the bare step's collective opcode counts
   and zero host-transfer ops — cross-rank stats ride widened existing
   reductions, never new ones (:mod:`apex_tpu.analysis.hlo` does the
   counting, async pairs folded).
2. **Instrumentation changes nothing**: params/optimizer state (and the
   sentinel) of the instrumented step are bit-identical to the bare
   step over multiple steps — observation never feeds back.
3. **The host pipeline survives its failure modes** (PR 3 fault
   harness): the JSONL writer retries transient I/O and its reader
   drops torn tails; the heartbeat monitor detects a hung checkpoint
   write (``faults.hung_writes``) and flags
   ``resilience.PreemptionGuard``; the stats logger fetches only on its
   ``every_n`` schedule; the trace window state machine opens/closes
   captures correctly.

Plus the end-to-end smoke: ``scripts/telemetry_smoke.sh`` runs the
driver dryrun with telemetry armed on a small virtual mesh and asserts
the JSONL metric catalog (fast tier, subprocess — the same idiom as
``tests/test_entry_dryrun.py``).
"""

import functools
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.analysis.hlo import compiled_hlo, hlo_op_counts
from apex_tpu.observability import (
    HeartbeatMonitor,
    JsonlWriter,
    MetricRegistry,
    TraceWindow,
    TrainStats,
    TrainStatsLogger,
    compiled_flops,
    mfu,
    peak_flops_for,
    read_jsonl,
    train_stats,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
HOST_TRANSFER = ("outfeed", "infeed", "send", "recv")


def _bits_equal(a, b):
    eq = jax.tree_util.tree_map(
        lambda x, y: np.asarray(x).tobytes() == np.asarray(y).tobytes(),
        a, b)
    return all(jax.tree_util.tree_leaves(eq))


def _collective_counts(counts):
    return {op: counts[op] for op in COLLECTIVES}


def _assert_no_host_transfers(counts, what):
    for op in HOST_TRANSFER:
        assert counts[op] == 0, (
            f"{what}: instrumentation must not add host transfers, found "
            f"{counts[op]} x {op}")


# ---------------------------------------------------------------------------
# 3D GPT: dp=2 x pp=2 x tp=2(+sp) on the virtual 8-device mesh
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _gpt3d_setup():
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    if len(jax.devices()) < 8:
        return None
    cfg = TransformerConfig(
        hidden_size=32, num_layers=2, num_attention_heads=2,
        padded_vocab_size=64, max_position_embeddings=16,
        hidden_dropout=0.0, attention_dropout=0.0,
        tensor_axis="tp", sequence_parallel=True)
    mesh = mesh_lib.initialize_model_parallel(
        tensor_model_parallel_size=2, pipeline_model_parallel_size=2)
    init_fn, _, make_train_step = build_gpt_3d(
        cfg, num_chunks=1, num_microbatches=2, mesh=mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
    params, specs = init_fn(jax.random.PRNGKey(0), tokens)
    opt = FusedAdam(lr=1e-3)
    state = opt.init(params)
    # The mesh object stays captured in the step closures, so the
    # conftest teardown clearing the global registry is harmless.
    mesh_lib.destroy_model_parallel()
    return {
        "bare": jax.jit(make_train_step(opt, specs)),
        "instr": jax.jit(make_train_step(opt, specs, collect_stats=True)),
        "params": params, "state": state, "tokens": tokens,
    }


def _gpt3d_or_skip():
    s = _gpt3d_setup()
    if s is None:
        pytest.skip("needs 8 virtual devices")
    return s


class TestInstrumentationAddsNothing:
    """The analyzer satellite: HLO opcode-count compare, bare vs
    instrumented, on the steady-state (non-logging) step — which IS the
    only compiled step; logging is a host-side fetch decision."""

    def test_gpt_3d_same_collectives_no_host_transfers(self):
        s = _gpt3d_or_skip()
        args = (s["params"], s["state"], s["tokens"])
        bare = hlo_op_counts(compiled_hlo(s["bare"], *args))
        instr = hlo_op_counts(compiled_hlo(s["instr"], *args))
        assert _collective_counts(instr) == _collective_counts(bare), (
            "TrainStats must ride existing collectives on the 3D step")
        _assert_no_host_transfers(instr, "gpt_3d instrumented")
        _assert_no_host_transfers(bare, "gpt_3d bare")
        # Sanity: this program really is collective-heavy (pipeline
        # ppermutes + dp/tp reductions) — the compare is not vacuous.
        assert bare["collective-permute"] > 0
        assert bare["all-reduce"] > 0

    def test_zero_same_collectives_no_host_transfers(self, devices8):
        z = _zero_setup()
        for name in ("plain", "scaler"):
            b, i, args = z[name]
            bare = hlo_op_counts(compiled_hlo(b, *args))
            instr = hlo_op_counts(compiled_hlo(i, *args))
            assert _collective_counts(instr) == _collective_counts(bare), (
                f"zero {name}: stats must ride the existing loss reduce")
            _assert_no_host_transfers(instr, f"zero {name} instrumented")
            assert bare["reduce-scatter"] > 0  # the ZeRO exchange is live


# ---------------------------------------------------------------------------
# ZeRO flat-bucket step over dp=8
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _zero_setup():
    from apex_tpu import parallel
    from apex_tpu.amp.scaler import DynamicLossScale
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.parallel.distributed import (
        dp_shard_batch, replicate, zero_data_parallel_train_step,
        zero_init)
    from apex_tpu.resilience import sentinel_init

    mesh = parallel.initialize_model_parallel()  # all 8 devices on dp
    params = replicate({"w": jnp.ones((16, 8)), "b": jnp.zeros((8,))},
                       mesh)

    def loss_fn(p, batch):
        x, y = batch
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    opt = DistributedFusedAdam(lr=1e-3, flat_bucket=True)
    state = zero_init(opt, params, mesh)
    x = jnp.arange(16 * 16, dtype=jnp.float32).reshape(16, 16) / 100.0
    batch = dp_shard_batch((x, jnp.ones((16, 8))), mesh)
    scaler = DynamicLossScale()
    sent = sentinel_init(scaler)

    def build(**kw):
        return zero_data_parallel_train_step(
            loss_fn, opt, mesh=mesh, donate=False, **kw)

    from apex_tpu.parallel import mesh as mesh_lib

    mesh_lib.destroy_model_parallel()
    return {
        "plain": (build(microbatches=2),
                  build(microbatches=2, collect_stats=True),
                  (params, state, batch)),
        "scaler": (build(scaler=scaler),
                   build(scaler=scaler, collect_stats=True),
                   (params, state, batch, sent)),
    }


class TestInstrumentationChangesNothing:
    """Bit-identical params/state: observation never feeds back."""

    def test_gpt_3d_parity_two_steps(self):
        s = _gpt3d_or_skip()
        p1, st1 = s["params"], s["state"]
        p2, st2 = p1, st1
        for step in range(2):
            p1, st1, l1 = s["bare"](p1, st1, s["tokens"])
            p2, st2, l2, stats = s["instr"](p2, st2, s["tokens"])
            assert _bits_equal(p1, p2), f"params diverged at step {step}"
            assert _bits_equal(st1, st2), f"state diverged at step {step}"
            assert np.float32(l1).tobytes() == np.float32(l2).tobytes()
        # The 3D step emits device-partial norms (zero extra
        # collectives); the host finalizes them at fetch time.
        host = jax.device_get(stats).finalize()
        assert np.isfinite(host.loss) and np.isfinite(host.grad_norm)
        assert host.param_norm > 0
        assert int(host.nonfinite_leaves) == 0
        assert float(host.loss_scale) == 1.0
        assert int(host.skipped_steps) == 0
        assert host.moe_aux.shape == (2,)  # per-microbatch (dense: zeros)

    def test_zero_parity_plain_and_scaler(self, devices8):
        z = _zero_setup()
        bare, instr, args = z["plain"]
        p1, s1, _ = bare(*args)
        p2, s2, _, stats = instr(*args)
        assert _bits_equal(p1, p2) and _bits_equal(s1, s2)
        host = jax.device_get(stats)
        assert host.grad_norm > 0 and int(host.nonfinite_leaves) == 0

        bare_s, instr_s, args_s = z["scaler"]
        p1, s1, se1, l1 = bare_s(*args_s)
        p2, s2, se2, l2, stats = instr_s(*args_s)
        assert _bits_equal(p1, p2) and _bits_equal(s1, s2)
        assert _bits_equal(se1, se2), "sentinel state must match too"
        assert np.float32(l1).tobytes() == np.float32(l2).tobytes()
        host = jax.device_get(stats)
        assert float(host.loss_scale) == 2.0 ** 16  # the scale used
        assert int(host.skipped_steps) == 0

    def test_zero_stats_see_poisoned_grads(self, devices8):
        """The sentinel path's stats report the overflow the sentinel
        acted on: NaN batch -> nonfinite_leaves > 0, skipped_steps 1,
        params bit-unchanged (the lax.cond skip)."""
        z = _zero_setup()
        _, instr_s, (params, state, batch, sent) = z["scaler"]
        bad_batch = jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, jnp.nan)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, batch)
        p2, s2, se2, l2, stats = instr_s(params, state, bad_batch, sent)
        host = jax.device_get(stats)
        assert int(host.nonfinite_leaves) > 0
        assert int(host.skipped_steps) == 1
        assert _bits_equal(params, p2), "skipped step must not move params"


# ---------------------------------------------------------------------------
# Host pipeline: writer crash-safety, heartbeat, logger cadence, traces
# ---------------------------------------------------------------------------


class TestJsonlCrashSafety:
    def test_writer_retries_transient_os_errors(self, tmp_path):
        from apex_tpu.testing.faults import transient_os_errors

        path = str(tmp_path / "m.jsonl")
        w = JsonlWriter(path, backoff_s=0.01)
        with transient_os_errors(2, path_prefix=str(tmp_path),
                                 op="open") as counter:
            w.write({"step": 0, "loss": 1.5})
        assert counter.failed == 2, "the blips must actually have fired"
        assert read_jsonl(path) == [{"step": 0, "loss": 1.5}]

    def test_writer_gives_up_after_retry_budget(self, tmp_path):
        from apex_tpu.testing.faults import transient_os_errors

        path = str(tmp_path / "m.jsonl")
        w = JsonlWriter(path, retries=1, backoff_s=0.01)
        with transient_os_errors(5, path_prefix=str(tmp_path), op="open"):
            with pytest.raises(OSError):
                w.write({"step": 0})

    def test_reader_drops_torn_tail(self, tmp_path):
        from apex_tpu.testing.faults import truncate_file

        path = str(tmp_path / "m.jsonl")
        w = JsonlWriter(path)
        for i in range(3):
            w.write({"step": i, "loss": 1.0 / (i + 1)})
        size = os.path.getsize(path)
        # Tear mid-way into the LAST record (the crashed-writer shape).
        truncate_file(path, keep_frac=(size - 5) / size)
        records = read_jsonl(path)
        assert [r["step"] for r in records] == [0, 1]
        # strict mode still accepts a torn TAIL (expected crash artifact)
        assert len(read_jsonl(path, strict=True)) == 2

    def test_reader_interior_corruption(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        w = JsonlWriter(path)
        w.write({"step": 0})
        with open(path, "a") as f:
            f.write("{torn interior garbage\n")
        w.write({"step": 2})
        assert [r["step"] for r in read_jsonl(path)] == [0, 2]
        with pytest.raises(ValueError):
            read_jsonl(path, strict=True)

    def test_registry_flush_is_rank_aware(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        w = JsonlWriter(path)
        r1 = MetricRegistry(rank=1, world=2)
        r1.gauge("x").set(1.0)
        assert r1.flush(w, step=0) is None
        assert not os.path.exists(path), "rank 1 must not write"
        r0 = MetricRegistry(rank=0, world=2)
        r0.gauge("x").set(2.0)
        assert r0.flush(w, step=0)["metrics"]["x"] == 2.0
        assert len(read_jsonl(path)) == 1

    def test_histogram_percentiles(self):
        """keep_samples histograms (the serving latency metrics) expose
        nearest-rank percentiles over a BOUNDED window; plain
        histograms stay sample-free and answer None."""
        reg = MetricRegistry(rank=0)
        h = reg.histogram("serving/tpot_ms", keep_samples=100)
        assert h.percentile(50) is None        # nothing observed yet
        for v in range(1, 101):                # 1..100
            h.observe(float(v))
        assert h.percentile(50) == 50.0
        assert h.percentile(99) == 99.0
        assert h.percentile(0) == 1.0 and h.percentile(100) == 100.0
        s = h.summary()
        assert s["p50"] == 50.0 and s["p99"] == 99.0 and s["count"] == 100
        # window is bounded: 100 more observations evict the old ones
        for v in range(1000, 1100):
            h.observe(float(v))
        assert h.percentile(0) == 1000.0 and h.count == 200
        # keep_samples applies on first creation only (no silent
        # truncation of someone else's window)
        assert reg.histogram("serving/tpot_ms") is h
        plain = reg.histogram("plain")
        plain.observe(1.0)
        assert plain.percentile(50) is None
        assert "p50" not in plain.summary()

    def test_histogram_empty_and_single_sample_windows(self):
        """ISSUE 10 satellite: percentile() edge cases.  Empty window —
        every q answers None (never a fabricated 0); one sample — every
        q is that sample (nearest-rank with n=1); summary() mirrors."""
        from apex_tpu.observability.metrics import Histogram

        h = Histogram(keep_samples=8)
        for q in (0, 50, 99, 100):
            assert h.percentile(q) is None
        s = h.summary()
        assert s == {"count": 0, "total": 0.0, "mean": None, "min": None,
                     "max": None, "last": None, "p50": None, "p99": None}
        h.observe(7.25)
        for q in (0, 50, 99, 100):
            assert h.percentile(q) == 7.25
        s = h.summary()
        assert s["p50"] == s["p99"] == 7.25
        assert s["mean"] == 7.25 and s["count"] == 1

    def test_histogram_ring_wraparound_exact(self):
        """keep_samples ring wrap must retain EXACTLY the newest N
        observations — off-by-one here silently shifts every
        percentile."""
        from apex_tpu.observability.metrics import Histogram

        h = Histogram(keep_samples=4)
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        assert h.percentile(0) == 1.0 and h.percentile(100) == 4.0
        h.observe(5.0)  # evicts exactly 1.0
        assert h.percentile(0) == 2.0 and h.percentile(100) == 5.0
        assert sorted(h._samples) == [2.0, 3.0, 4.0, 5.0]
        for v in (6.0, 7.0, 8.0, 9.0):  # full wrap
            h.observe(v)
        assert sorted(h._samples) == [6.0, 7.0, 8.0, 9.0]
        assert h.percentile(50) == 7.0  # nearest-rank over the window

    def test_histogram_summary_mean_vs_percentile_semantics(self):
        """summary() keys answer over two documented domains: count/
        total/mean/min/max are LIFETIME moments, p50/p99 cover the
        bounded sample window — after a wrap they may legitimately
        disagree, and before one they must agree."""
        from apex_tpu.observability.metrics import Histogram

        h = Histogram(keep_samples=4)
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        s = h.summary()
        assert s["mean"] == pytest.approx(2.5)
        assert s["p50"] == 2.0  # nearest-rank(50%, n=4) = 2nd
        for v in (100.0, 100.0, 100.0, 100.0):
            h.observe(v)
        s = h.summary()
        # lifetime mean remembers the evicted small values...
        assert s["mean"] == pytest.approx((1 + 2 + 3 + 4 + 400) / 8)
        assert s["count"] == 8 and s["min"] == 1.0 and s["max"] == 100.0
        # ...while the windowed percentiles describe only the window
        assert s["p50"] == 100.0 and s["p99"] == 100.0

    def test_registry_per_rank_flush_opt_in(self, tmp_path):
        """ISSUE 10 satellite: host-local metrics (data/stall_ms,
        span_ms/*) are per-host facts — all_ranks=True lets every rank
        write its own rank-stamped record instead of rank 0's values
        silently standing in for the fleet."""
        from apex_tpu.observability.metrics import is_host_local

        w1 = JsonlWriter(str(tmp_path / "m.rank1.jsonl"))
        r1 = MetricRegistry(rank=1, world=2)
        r1.gauge("data/stall_ms").set(42.0)
        rec = r1.flush(w1, step=3, all_ranks=True)
        assert rec is not None and rec["rank"] == 1
        back = read_jsonl(str(tmp_path / "m.rank1.jsonl"))
        assert back[0]["rank"] == 1
        assert back[0]["metrics"]["data/stall_ms"] == 42.0
        # default stays rank-gated
        assert r1.flush(w1, step=4) is None
        # the catalog split the docs table is generated from
        assert is_host_local("data/stall_ms")
        assert is_host_local("span_ms/checkpoint/save")
        assert is_host_local("serving/ttft_ms")
        assert is_host_local("heartbeat/hangs")
        assert not is_host_local("train/loss")
        assert not is_host_local("train/grad_norm")


class TestHeartbeat:
    def test_flags_hung_checkpoint_write_to_preemption_guard(
            self, tmp_path):
        """faults.hung_writes parks the save mid-flight; no beat can
        arrive; the monitor flags the hang to the guard — the drain
        path a preemption would take."""
        from apex_tpu.resilience import CheckpointManager, PreemptionGuard
        from apex_tpu.testing.faults import hung_writes

        guard = PreemptionGuard(signals=())  # flag-only, no handlers
        reg = MetricRegistry(rank=0)
        hb = HeartbeatMonitor(timeout_s=0.15, on_hang=guard, registry=reg)
        mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
        hb.beat(0)
        tree = {"w": np.arange(4.0, dtype=np.float32)}
        with hung_writes(path_prefix=str(tmp_path)) as h:
            t = threading.Thread(target=mgr.save, args=(tree, 1),
                                 daemon=True)
            t.start()
            assert h.entered.wait(10), "writer never reached the gate"
            time.sleep(0.2)  # step 1 cannot complete -> no beat
            assert hb.check_now() is True
            assert hb.hung and guard.triggered
            h.release()
            t.join(10)
        assert reg.snapshot()["heartbeat/hangs"] == 1
        # The next completed step re-arms the monitor.
        hb.beat(1)
        assert not hb.hung
        assert hb.check_now() is False

    def test_fires_once_per_episode(self):
        calls = []
        hb = HeartbeatMonitor(timeout_s=0.05, on_hang=lambda: calls.append(1))
        hb.beat(0)
        time.sleep(0.1)
        assert hb.check_now() and hb.check_now() and hb.check_now()
        assert calls == [1], "one hang episode -> one flag"

    def test_background_thread_detects(self):
        hb = HeartbeatMonitor(timeout_s=0.08, poll_s=0.02)
        with hb:
            hb.beat(0)
            time.sleep(0.3)
            assert hb.hung


class TestStatsLoggerCadence:
    def _stats(self):
        return train_stats(
            jnp.float32(2.5), {"g": jnp.ones((3,))}, {"p": jnp.ones((2,))})

    def test_log_every_n(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        logger = TrainStatsLogger(
            MetricRegistry(rank=0), every_n=3, writer=JsonlWriter(path))
        stats = self._stats()
        logged = [step for step in range(7)
                  if logger.maybe_log(step, stats) is not None]
        assert logged == [0, 3, 6], "fetch only on the every_n schedule"
        records = read_jsonl(path)
        assert len(records) == 3
        for rec in records:
            assert rec["loss"] == 2.5
            assert rec["nonfinite_leaves"] == 0
            assert rec["metrics"]["train/loss"] == 2.5
        assert [r["step"] for r in records] == [0, 3, 6]

    def test_fetch_flattens_trainstats(self):
        logger = TrainStatsLogger(MetricRegistry(rank=0), every_n=1)
        values = logger.fetch(self._stats())
        optional = {"moe_aux", "moe_pairs", "moe_choices"}
        assert set(TrainStats._fields) - optional <= set(values)
        assert isinstance(values["skipped_steps"], int)
        assert isinstance(values["loss"], float)

    def test_fetch_and_log_of_the_expert_layers_pairs(self):
        """``moe_pairs`` comes back as nested lists of ints and the logger
        publishes their sum and the load peak; ``moe_choices`` is left on
        the device, out of what is fetched, the gauges and the record."""
        import numpy as np

        pairs = np.asarray([[[4, 0, 2, 2]], [[1, 1, 1, 5]]], np.int32)
        stats = self._stats()._replace(
            moe_pairs=jnp.asarray(pairs),
            moe_choices=jnp.zeros((2, 1, 3, 2), jnp.int32))
        registry = MetricRegistry(rank=0)
        logger = TrainStatsLogger(registry, every_n=1)
        values = logger.fetch(stats)
        assert values["moe_pairs"] == pairs.tolist()
        assert "moe_choices" not in values
        logger.log(0, stats)
        assert registry.gauge("train/moe_pairs").value == 16
        assert registry.gauge("train/moe_expert_load_peak").value == \
            pytest.approx((4 / 2 + 5 / 2) / 2)
        assert "train/moe_choices" not in registry.snapshot()


class _FakeProfiler:
    def __init__(self, fail_start=False):
        self.started, self.stops, self.fail_start = [], 0, fail_start

    def start_trace(self, path):
        if self.fail_start:
            raise RuntimeError("profiler unavailable")
        self.started.append(path)

    def stop_trace(self):
        self.stops += 1


class TestTraceWindow:
    def test_windowed_capture_state_machine(self, tmp_path):
        fp = _FakeProfiler()
        with TraceWindow(str(tmp_path), every_n=4, capture_steps=2,
                         _profiler=fp) as tw:
            for step in range(10):
                tw.on_step(step)
        # Windows at steps 0-2, 4-6, 8-(close).
        assert [os.path.basename(p) for p in fp.started] == [
            "step_00000000", "step_00000004", "step_00000008"]
        assert fp.stops == 3
        assert tw.windows_captured == 3
        assert os.path.isdir(os.path.join(str(tmp_path), "step_00000000"))

    def test_profiler_failure_disables_not_raises(self, tmp_path):
        tw = TraceWindow(str(tmp_path), every_n=1, capture_steps=1,
                         _profiler=_FakeProfiler(fail_start=True))
        tw.on_step(0)  # must not raise
        assert not tw.enabled
        tw.on_step(1)  # disabled: no-op


class TestMfu:
    def test_compiled_flops_handles_both_shapes(self):
        class L:
            def cost_analysis(self):
                return [{"flops": 123.0}]

        class D:
            def cost_analysis(self):
                return {"flops": 456.0}

        class N:
            def cost_analysis(self):
                raise NotImplementedError

        assert compiled_flops(L()) == 123.0
        assert compiled_flops(D()) == 456.0
        assert compiled_flops(N()) is None

    def test_mfu_math_and_unknown_peak(self):
        assert mfu(1e9, 0.01, peak_flops=1e12) == pytest.approx(0.1)
        assert mfu(1e9, 0.01, peak_flops=1e12, n_devices=2) == \
            pytest.approx(0.05)
        assert mfu(None, 0.01, peak_flops=1e12) is None
        assert mfu(1e9, 0.01) is None  # no peak, no device
        assert peak_flops_for(jax.devices()[0]) is None  # cpu: undefined

    def test_real_compiled_cost_analysis(self):
        compiled = jax.jit(lambda x: x @ x).lower(
            jnp.ones((64, 64))).compile()
        flops = compiled_flops(compiled)
        if flops is not None:  # backend-dependent; math must hold when set
            assert flops > 0
            assert mfu(flops, 1.0, peak_flops=1e12) > 0

    def test_mfu_none_carries_a_reason(self):
        """ISSUE 10 satellite: the two silently-conflated None cases
        (unknown device peak vs missing cost analysis) now name
        themselves, and exactly one of (value, reason) is None."""
        from apex_tpu.observability.metrics import (
            mfu_or_reason, peak_flops_reason)

        value, reason = mfu_or_reason(None, 0.01, peak_flops=1e12)
        assert value is None and "cost-analysis" in reason
        value, reason = mfu_or_reason(1e9, 0.01,
                                      device=jax.devices()[0])
        assert value is None and "'cpu'" in reason
        value, reason = mfu_or_reason(1e9, 0.01)
        assert value is None and "no device" in reason
        value, reason = mfu_or_reason(1e9, 0.0, peak_flops=1e12)
        assert value is None and "step time" in reason
        value, reason = mfu_or_reason(1e9, 0.01, peak_flops=1e12)
        assert reason is None and value == pytest.approx(0.1)
        # mfu() stays the value-only projection
        assert mfu(1e9, 0.01, peak_flops=1e12) == pytest.approx(0.1)
        peak, reason = peak_flops_reason(jax.devices()[0])
        assert peak is None and "platform 'cpu'" in reason
        peak, reason = peak_flops_reason(None)
        assert peak is None and "no device" in reason

        class _TpuDevice:
            platform = "tpu"
            device_kind = "TPU v4"

        peak, reason = peak_flops_reason(_TpuDevice())
        assert peak == 275e12 and reason is None
        # the table is keyed by the exact device_kind: what the v5e chip
        # reports is in it, and an unknown TPU gets a reason, not a guess
        _TpuDevice.device_kind = "TPU v5 lite"
        assert peak_flops_reason(_TpuDevice()) == (197e12, None)
        _TpuDevice.device_kind = "TPU v9 nano"
        peak, reason = peak_flops_reason(_TpuDevice())
        assert peak is None and "'TPU v9 nano'" in reason


# ---------------------------------------------------------------------------
# End-to-end smoke: the dryrun entry with telemetry armed
# ---------------------------------------------------------------------------


def test_telemetry_smoke_script(tmp_path):
    """scripts/telemetry_smoke.sh on a 2-device virtual mesh: the full
    TrainStats -> TrainStatsLogger -> MetricRegistry -> JsonlWriter
    pipeline through the real driver entry, asserted against the metric
    catalog (the subprocess idiom of tests/test_entry_dryrun.py — the
    child must own its XLA flags)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        ["bash", os.path.join(_REPO, "scripts", "telemetry_smoke.sh"),
         "2", str(tmp_path)],
        cwd=_REPO, env=env, capture_output=True, timeout=540,
    )
    assert proc.returncode == 0, (
        f"telemetry_smoke rc={proc.returncode}\n"
        f"stderr tail:\n{proc.stderr.decode(errors='replace')[-2000:]}")
    records = read_jsonl(str(tmp_path / "metrics.jsonl"), strict=True)
    assert records and records[-1]["nonfinite_leaves"] == 0

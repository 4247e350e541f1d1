"""The flight-recorder / goodput / debug-server layer (ISSUE 10).

Pins the tentpole contracts:

- event log semantics: monotonic clock, bounded ring, typed helpers,
  JSONL spill readable under the strict torn-tail rules;
- crash safety: a SIGKILL'd emitter loses at most the torn tail (the
  fault-injection acceptance);
- goodput: buckets exhaustive + disjoint, online (incremental) ==
  offline (recompute over the spilled file), serving per-request
  attribution;
- free telemetry: arming the recorder changes NOTHING in the compiled
  step — identical optimized HLO (zero extra collectives or host
  transfers, the PR 5 property extended to the timeline layer);
- instrumented subsystems: CheckpointManager and DevicePrefetcher emit
  the documented events, with disjoint attribution;
- the debug server: /metrics Prometheus text, /statusz timeline tail +
  goodput + engine state.
"""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import jax

from apex_tpu.observability import (
    DebugServer,
    FlightRecorder,
    MetricRegistry,
    read_jsonl,
)
from apex_tpu.observability import timeline
from apex_tpu.observability.goodput import (
    TRAIN_BUCKETS,
    classify_event,
    goodput_report,
    serving_goodput_report,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm():
    """No test leaks an armed process-global recorder into the next."""
    yield
    timeline.disarm()


# ---------------------------------------------------------------------------
# recorder core
# ---------------------------------------------------------------------------


class TestFlightRecorder:
    def test_events_monotonic_and_typed(self):
        rec = FlightRecorder()
        with rec.step(0):
            pass
        rec.data_stall(0.01)
        rec.sentinel_skip(3, skipped_steps=1)
        evs = rec.events()
        kinds = [e["kind"] for e in evs]
        assert kinds == ["run_begin", "step", "data_stall",
                         "sentinel_skip"]
        ts = [e["t"] for e in evs]
        assert ts == sorted(ts) and all(t >= 0 for t in ts)
        assert evs[1]["step"] == 0 and "dur_s" in evs[1]
        assert evs[3]["skipped_steps"] == 1

    def test_ring_bounded_but_accounting_exact(self):
        rec = FlightRecorder(ring=8)
        for i in range(50):
            rec.emit("step", dur_s=0.001, step=i)
        assert len(rec.events()) == 8
        assert rec.events_emitted == 51  # + run_begin
        # goodput survived the wrap: all 50 steps still attributed
        assert rec.report()["buckets"]["compute"] == pytest.approx(
            0.05, abs=1e-9)

    def test_tail(self):
        rec = FlightRecorder()
        for i in range(10):
            rec.emit("step", step=i)
        tail = rec.tail(3)
        assert [e["step"] for e in tail] == [7, 8, 9]

    def test_scope_emits_on_exception(self):
        rec = FlightRecorder()
        with pytest.raises(RuntimeError):
            with rec.scope("compile", what="x"):
                raise RuntimeError("boom")
        assert rec.events()[-1]["kind"] == "compile"

    def test_ring_validation(self):
        with pytest.raises(ValueError):
            FlightRecorder(ring=0)

    def test_spill_round_trip_strict(self, tmp_path):
        path = str(tmp_path / "tl.jsonl")
        rec = FlightRecorder(path)
        with rec.step(0):
            pass
        rec.flush()
        back = read_jsonl(path, strict=True)
        assert [e["kind"] for e in back] == ["run_begin", "step",
                                            "run_end"]
        assert back == rec.events()

    def test_flush_writes_goodput_json(self, tmp_path):
        rec = FlightRecorder()
        rec.emit("step", dur_s=0.01, step=0)
        gp = str(tmp_path / "sub" / "goodput.json")
        report = rec.flush(gp)
        with open(gp) as f:
            assert json.load(f) == report

    def test_module_level_arming(self, tmp_path):
        assert timeline.active() is None
        assert timeline.emit("step", step=0) is None  # unarmed no-op
        with timeline.scope("step", step=0):
            pass
        rec = timeline.arm(str(tmp_path / "tl.jsonl"))
        assert timeline.active() is rec
        timeline.emit("compile", dur_s=0.1, what="x")
        with timeline.scope("step", step=1):
            pass
        assert [e["kind"] for e in rec.events()] == [
            "run_begin", "compile", "step"]
        assert timeline.disarm() is rec
        assert timeline.active() is None

    def test_arm_from_env(self, tmp_path, monkeypatch):
        monkeypatch.delenv(timeline.TIMELINE_ENV_VAR, raising=False)
        assert timeline.arm_from_env() is None
        monkeypatch.setenv(timeline.TIMELINE_ENV_VAR, str(tmp_path))
        rec = timeline.arm_from_env()
        assert rec is not None and timeline.active() is rec
        rec.emit("step", step=0)
        assert os.path.exists(tmp_path / "timeline.jsonl")


# ---------------------------------------------------------------------------
# crash safety (the fault-injection acceptance)
# ---------------------------------------------------------------------------


_EMITTER = r"""
import sys
from apex_tpu.observability.timeline import FlightRecorder
rec = FlightRecorder(sys.argv[1])
print("armed", flush=True)
i = 0
while True:
    rec.emit("step", dur_s=0.0001, step=i)
    i += 1
"""


class TestCrashSafety:
    def test_sigkill_loses_at_most_the_torn_tail(self, tmp_path):
        """A SIGKILL'd emitter leaves a timeline whose intact prefix
        parses under strict semantics, with a contiguous step sequence
        — the reuse of the read_jsonl torn-tail contract."""
        path = str(tmp_path / "tl.jsonl")
        proc = subprocess.Popen(
            [sys.executable, "-c", _EMITTER, path],
            stdout=subprocess.PIPE, cwd=REPO)
        assert proc.stdout.readline().strip() == b"armed"
        # let it write enough to make the kill land mid-stream
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if os.path.exists(path) and os.path.getsize(path) > 4096:
                break
            time.sleep(0.01)
        proc.send_signal(signal.SIGKILL)
        proc.wait()
        events = read_jsonl(path, strict=True)  # strict: no interior tears
        steps = [e["step"] for e in events if e["kind"] == "step"]
        assert len(steps) > 10
        assert steps == list(range(len(steps))), "lost interior events"

        # and even a genuinely torn tail (truncate mid-final-line) still
        # yields the intact prefix under strict
        from apex_tpu.testing.faults import truncate_file

        truncate_file(path, keep_frac=0.9)
        again = read_jsonl(path, strict=True)
        assert [e["step"] for e in again if e["kind"] == "step"] == \
            list(range(len(again) - 1))


# ---------------------------------------------------------------------------
# goodput
# ---------------------------------------------------------------------------


class TestGoodput:
    def test_classification(self):
        assert classify_event({"kind": "step"}) == "compute"
        assert classify_event({"kind": "step", "skipped": True}) == \
            "skipped_step"
        assert classify_event({"kind": "compile"}) == "compile"
        assert classify_event({"kind": "checkpoint_save"}) == "checkpoint"
        assert classify_event(
            {"kind": "checkpoint_save_async_submit"}) == "checkpoint"
        assert classify_event({"kind": "checkpoint_verify"}) == "checkpoint"
        assert classify_event({"kind": "checkpoint_restore"}) == "restore"
        assert classify_event({"kind": "data_stall"}) == "data_stall"
        assert classify_event({"kind": "drain"}) == "drain"
        # markers and serving lifecycle carry no training attribution
        for kind in ("run_begin", "run_end", "preemption", "sentinel_skip",
                     "request_submit", "decode_tick", "prefill"):
            assert classify_event({"kind": kind}) is None

    def test_buckets_exhaustive_and_disjoint(self):
        events = [
            {"t": 0.0, "kind": "run_begin"},
            {"t": 1.0, "kind": "compile", "dur_s": 1.0},
            {"t": 1.2, "kind": "data_stall", "dur_s": 0.2},
            {"t": 2.2, "kind": "step", "dur_s": 1.0, "step": 0},
            {"t": 2.7, "kind": "checkpoint_save", "dur_s": 0.5},
            {"t": 3.2, "kind": "step", "dur_s": 0.5, "step": 1,
             "skipped": True},
            {"t": 3.4, "kind": "drain", "dur_s": 0.2},
            {"t": 4.0, "kind": "run_end", "wall_s": 4.0},
        ]
        rep = goodput_report(events)
        assert rep["wall_s"] == 4.0
        assert set(rep["buckets"]) == set(TRAIN_BUCKETS)
        assert rep["buckets"]["compute"] == 1.0
        assert rep["buckets"]["skipped_step"] == 0.5
        assert rep["buckets"]["other"] == pytest.approx(0.6)
        assert sum(rep["buckets"].values()) == pytest.approx(4.0)
        assert rep["goodput_fraction"] == pytest.approx(0.25)
        assert rep["overcommit_s"] == 0.0

    def test_overcommit_surfaces_not_hides(self):
        """Attributed time beyond wall-clock (nested instrumentation
        bug) is reported, never silently clamped into the fractions."""
        rep = goodput_report([
            {"t": 1.0, "kind": "step", "dur_s": 5.0, "step": 0}],
            wall_s=1.0)
        assert rep["overcommit_s"] == pytest.approx(4.0)
        assert rep["buckets"]["other"] == 0.0

    def test_crash_wall_clock_from_last_event(self):
        """No run_end (the crash case): wall is the newest event's t —
        the unknowable post-crash tail is not attributed."""
        rep = goodput_report([
            {"t": 0.0, "kind": "run_begin"},
            {"t": 2.5, "kind": "step", "dur_s": 1.0, "step": 0}])
        assert rep["wall_s"] == 2.5

    def test_multi_run_spill_reports_newest_segment(self, tmp_path):
        """A spill path reused across restarts (crash -> resume)
        appends runs with restarting clocks; the offline report covers
        the NEWEST run and split_runs exposes the history."""
        from apex_tpu.observability.goodput import split_runs

        path = str(tmp_path / "tl.jsonl")
        first = FlightRecorder(path)
        first.emit("step", dur_s=1.0, step=0)
        first.flush()
        second = FlightRecorder(path)  # the resumed process re-arms
        second.emit("step", dur_s=0.25, step=1)
        second.flush()
        events = read_jsonl(path, strict=True)
        runs = split_runs(events)
        assert len(runs) == 2
        assert [e["kind"] for e in runs[0]][0] == "run_begin"
        rep = goodput_report(events)
        assert rep["buckets"]["compute"] == pytest.approx(0.25)
        assert goodput_report(runs[0])["buckets"]["compute"] == \
            pytest.approx(1.0)

    def test_online_equals_offline(self, tmp_path):
        path = str(tmp_path / "tl.jsonl")
        rec = FlightRecorder(path)
        for i in range(5):
            with rec.step(i):
                time.sleep(0.002)
        rec.data_stall(0.004)
        with rec.scope("checkpoint_save", step=4):
            time.sleep(0.002)
        online = rec.report()
        offline = goodput_report(read_jsonl(path, strict=True),
                                 wall_s=online["wall_s"])
        for name in TRAIN_BUCKETS:
            # the spill rounds dur_s to 6 dp per event; the online path
            # accumulates unrounded floats — agreement is to ~n*5e-7
            assert online["buckets"][name] == pytest.approx(
                offline["buckets"][name], abs=1e-5), name

    def test_serving_attribution(self):
        events = [
            {"t": 0.0, "kind": "request_submit", "rid": 1,
             "prompt_tokens": 4, "max_new_tokens": 8},
            {"t": 0.5, "kind": "request_admit", "rid": 1, "slot": 0},
            {"t": 1.0, "kind": "decode_tick", "rid": 1, "tokens": 8},
            {"t": 1.5, "kind": "request_finish", "rid": 1, "tokens": 10},
            {"t": 0.2, "kind": "request_submit", "rid": 2,
             "prompt_tokens": 2, "max_new_tokens": 4},
            {"t": 0.9, "kind": "request_cancel", "rid": 2},
            {"t": 1.0, "kind": "request_submit", "rid": 3,
             "prompt_tokens": 2, "max_new_tokens": 4},
            # rid 4: refused at submit (drain window / overload shed,
            # ISSUE 11) — a typed terminal state holding ~zero seconds
            {"t": 1.2, "kind": "request_submit", "rid": 4,
             "prompt_tokens": 2, "max_new_tokens": 4},
            {"t": 1.2, "kind": "request_reject", "rid": 4},
        ]
        rep = serving_goodput_report(events)
        assert rep["requests"][1] == {
            "state": "finished", "tokens": 10, "queue_wait_s": 0.5,
            "active_s": 1.0}
        assert rep["requests"][2]["state"] == "cancelled"
        assert rep["requests"][2]["drained_s"] == pytest.approx(0.7)
        assert rep["requests"][3]["state"] == "open"
        assert rep["requests"][4]["state"] == "rejected"
        assert rep["requests"][4]["drained_s"] == pytest.approx(0.0)
        assert rep["totals"] == {
            "finished": 1, "cancelled": 1, "rejected": 1, "open": 1,
            "queue_wait_s": 0.5, "active_s": 1.0,
            "drained_s": pytest.approx(0.7)}
        assert rep["goodput_fraction"] == pytest.approx(1.0 / 2.2,
                                                        abs=1e-6)

    def test_serving_attribution_survives_ring_wrap(self):
        """A terminal request whose submit event was evicted by the
        bounded ring still counts toward finished/cancelled (totals
        must never contradict per-request states); it just contributes
        no seconds to the fraction."""
        events = [
            # rid 1: submit evicted — only the finish survived
            {"t": 5.0, "kind": "request_finish", "rid": 1, "tokens": 9},
            # rid 2: fully observed
            {"t": 5.2, "kind": "request_submit", "rid": 2,
             "prompt_tokens": 2, "max_new_tokens": 4},
            {"t": 5.3, "kind": "request_admit", "rid": 2, "slot": 0},
            {"t": 6.3, "kind": "request_finish", "rid": 2, "tokens": 4},
            # rid 3: submit evicted, cancel survived
            {"t": 6.4, "kind": "request_cancel", "rid": 3},
        ]
        rep = serving_goodput_report(events)
        assert rep["requests"][1] == {"state": "finished", "tokens": 9}
        assert rep["totals"]["finished"] == 2
        assert rep["totals"]["cancelled"] == 1
        assert rep["totals"]["open"] == 0
        assert rep["totals"]["active_s"] == pytest.approx(1.0)
        assert rep["goodput_fraction"] == pytest.approx(1.0 / 1.1,
                                                        abs=1e-6)


# ---------------------------------------------------------------------------
# free telemetry: arming changes nothing in the compiled program
# ---------------------------------------------------------------------------


class TestArmedRecorderIsFree:
    def test_identical_optimized_hlo_with_recorder_armed(self, devices8):
        """The recorder is host-side by construction; this pins it —
        tracing and compiling the SAME sharded step under an armed
        recorder (scopes wrapping the trace AND the dispatch) yields
        identical optimized HLO: zero extra collectives, zero host
        transfers, zero anything.  Source metadata is stripped before
        the compare: it carries the line and column of each call site,
        and the two ``make_step()`` calls sit on different lines."""
        import re

        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(devices8[:4]), ("dp",))

        def program(compiled):
            text = re.sub(r",? ?metadata=\{[^}]*\}", "", compiled.as_text())
            # the file/line tables the metadata ids point into
            return "\n".join(
                line for line in text.splitlines()
                if not re.match(r"\s*(\d+ |FileNames|FunctionNames|"
                                r"FileLocations|StackFrames)", line))

        def make_step():
            def local(x):
                return jax.lax.pmean(x * 2.0, "dp")

            return jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))

        x = np.arange(8.0, dtype=np.float32)
        bare = program(make_step().lower(x).compile())

        timeline.arm(FlightRecorder())
        with timeline.scope("compile", what="step"):
            armed_fn = make_step()
            armed = program(armed_fn.lower(x).compile())
        with timeline.scope("step", step=0):
            armed_fn(x)
        assert armed == bare
        assert timeline.active().events_emitted >= 3


# ---------------------------------------------------------------------------
# instrumented subsystems
# ---------------------------------------------------------------------------


class TestSubsystemEvents:
    def test_checkpoint_manager_events_disjoint(self, tmp_path):
        """save / save_async_submit / verify / restore land as their
        own intervals; the restore_latest wrapper is NOT an event (it
        contains verify+restore — counting it would double-attribute)."""
        from apex_tpu.resilience import CheckpointManager

        rec = timeline.arm(FlightRecorder())
        tree = {"w": np.arange(6.0, dtype=np.float32)}
        mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
        mgr.save(tree, 0)
        mgr.save_async(tree, 1)
        mgr.wait()
        restored, at = mgr.restore_latest(tree)
        assert at == 1 and _bits(restored["w"]) == _bits(tree["w"])
        kinds = [e["kind"] for e in rec.events()]
        assert "checkpoint_save" in kinds
        assert "checkpoint_save_async_submit" in kinds
        assert "checkpoint_verify" in kinds
        assert "checkpoint_restore" in kinds
        assert "restore_latest" not in " ".join(kinds)
        ev = [e for e in rec.events()
              if e["kind"] == "checkpoint_restore"][0]
        assert ev["step"] == 1 and ev["resharded"] is False
        # every interval is attributable
        rep = rec.report()
        assert rep["buckets"]["checkpoint"] > 0
        assert rep["buckets"]["restore"] > 0
        assert rep["overcommit_s"] == 0.0

    def test_prefetcher_emits_data_stall(self):
        from apex_tpu.data.prefetch import prefetch_to_device

        rec = timeline.arm(FlightRecorder())
        batches = [np.ones((2, 2)) * i for i in range(4)]
        pf = prefetch_to_device(iter(batches), depth=1,
                                place=lambda b: b)
        got = list(pf)
        pf.close()
        assert len(got) == 4
        stalls = [e for e in rec.events() if e["kind"] == "data_stall"]
        # one per delivered batch + one for the exhaustion pull (the
        # wait for the end marker is real blocking time too)
        assert len(stalls) == 5
        assert all(e["dur_s"] >= 0 for e in stalls)


def _bits(a):
    return np.asarray(a).tobytes()


# ---------------------------------------------------------------------------
# debug server
# ---------------------------------------------------------------------------


class _FakeEngine:
    def introspect(self):
        return {"active_slots": 2, "free_blocks": 7, "queue_depth": 1,
                "draining": False, "mfu": None,
                "mfu_reason": "no peak-FLOPs table entry"}


class TestDebugServer:
    def _get(self, srv, path):
        return urllib.request.urlopen(srv.url(path), timeout=10)

    def test_metrics_prometheus_format(self):
        reg = MetricRegistry(rank=0, world=1)
        reg.counter("serving/tokens_generated").inc(42)
        reg.gauge("data/stall_ms").set(1.5)
        reg.gauge("unset/gauge")  # None: must be omitted, not NaN
        h = reg.histogram("serving/tpot_ms", keep_samples=16)
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        with DebugServer(registry=reg) as srv:
            body = self._get(srv, "/metrics").read().decode()
        assert '# TYPE apex_serving_tokens_generated counter' in body
        assert 'apex_serving_tokens_generated{rank="0"} 42.0' in body
        assert 'apex_data_stall_ms{rank="0"} 1.5' in body
        assert "apex_unset_gauge" not in body
        assert 'apex_serving_tpot_ms_count{rank="0"} 3.0' in body
        assert 'quantile="0.5"' in body and 'quantile="0.99"' in body

    def test_statusz_carries_timeline_goodput_and_engine(self):
        rec = FlightRecorder()
        with rec.step(0):
            time.sleep(0.001)
        with DebugServer(registry=MetricRegistry(rank=0, world=1),
                         recorder=rec, engine=_FakeEngine()) as srv:
            body = json.loads(self._get(srv, "/statusz").read())
        assert body["timeline"][-1]["kind"] == "step"
        assert body["goodput"]["buckets"]["compute"] > 0
        assert body["serving"]["free_blocks"] == 7
        assert "no peak-FLOPs" in body["serving"]["mfu_reason"]

    def test_statusz_uses_armed_recorder_by_default(self):
        rec = timeline.arm(FlightRecorder())
        rec.emit("compile", dur_s=0.5, what="x")
        with DebugServer(registry=MetricRegistry(rank=0, world=1)) as srv:
            body = json.loads(self._get(srv, "/statusz").read())
        assert body["goodput"]["buckets"]["compile"] == pytest.approx(0.5)

    def test_unknown_path_404(self):
        with DebugServer(registry=MetricRegistry(rank=0, world=1)) as srv:
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(srv, "/nope")
            assert ei.value.code == 404

    def test_healthz_ok_draining_down(self):
        """ISSUE 11 satellite: the one health contract router and
        external probes share — ok is HTTP 200, draining/down are 503
        with the status named, so both a stock prober (code only) and
        the fleet router (JSON) read the same endpoint."""

        class Engine:
            draining = False
            broken = False

            def introspect(self):
                if self.broken:
                    raise RuntimeError("decode wedged")
                return {"draining": self.draining}

        eng = Engine()
        with DebugServer(registry=MetricRegistry(rank=0, world=1),
                         engine=eng) as srv:
            body = json.loads(self._get(srv, "/healthz").read())
            assert body["status"] == "ok" and body["engine"] is True
            eng.draining = True
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(srv, "/healthz")
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["status"] == "draining"
            eng.broken = True
            with pytest.raises(urllib.error.HTTPError) as ei:
                self._get(srv, "/healthz")
            assert ei.value.code == 503
            payload = json.loads(ei.value.read())
            assert payload["status"] == "down"
            assert "decode wedged" in payload["error"]

    def test_healthz_without_engine_is_liveness_only(self):
        with DebugServer(registry=MetricRegistry(rank=0, world=1)) as srv:
            body = json.loads(self._get(srv, "/healthz").read())
        assert body == {"status": "ok", "engine": False}

    def test_ephemeral_port_and_close(self):
        srv = DebugServer(registry=MetricRegistry(rank=0, world=1)).start()
        assert srv.port > 0
        srv.close()
        with pytest.raises(Exception):
            urllib.request.urlopen(srv.url("/metrics"), timeout=1)


# The obs_smoke.sh end-to-end run is wired fast-tier in
# tests/test_aux_subsystems.py alongside the data/serving/telemetry
# smokes (ISSUE 10 CI satellite).

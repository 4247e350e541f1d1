"""Flight recorder — the run-timeline layer (ISSUE 10 tentpole).

PR 5 gave every subsystem numbers (gauges, histograms, spans); what no
subsystem had is a shared **timeline**: one monotonic-clock event log a
human (or :mod:`.goodput`) can replay to answer "where did this run's
wall-clock actually go?".  TorchTitan (PAPERS.md, arxiv 2410.06511)
treats exactly this — per-phase time attribution over always-on
lightweight tracing — as table stakes for a production stack.

One object, :class:`FlightRecorder`, owns the log:

- **events** are flat JSON dicts ``{"t": <monotonic seconds since the
  recorder armed>, "kind": <type>, ...}``; interval kinds additionally
  carry ``dur_s`` (the event is emitted at the interval's *end*, so a
  crash loses at most the in-flight interval — there are no dangling
  ``begin`` markers to repair);
- a **bounded in-memory ring** keeps the newest events for live
  introspection (``/statusz`` tail, :meth:`FlightRecorder.tail`) — a
  weeks-long run cannot leak memory through its own telemetry;
- an optional **JSONL spill** writes every event through
  :class:`~apex_tpu.observability.writers.JsonlWriter` — one
  ``O_APPEND`` single-shot line per event, so a SIGKILL tears at most
  the final line and :func:`~apex_tpu.observability.writers.read_jsonl`
  (strict) recovers the intact prefix — the PR 3/PR 5 crash-safety
  contract applied to the timeline (``fsync=False`` by default: process
  death cannot tear a buffered line, only power loss can, and an fsync
  per decode tick would tax the serving hot loop);
- **goodput buckets accumulate incrementally** at emit time (see
  :mod:`.goodput` for the classification), so goodput-so-far is O(1)
  to read at any instant even after the ring has wrapped.

Event schema (the full catalog is documented in
``docs/observability.md``):

=====================  ====================================================
kind                   payload (beyond ``t`` / ``dur_s``)
=====================  ====================================================
``run_begin``          ``wall_ts`` (epoch seconds) + caller metadata
``run_end``            ``wall_s`` — total armed wall-clock
``step``               ``step``; ``skipped=True`` for sentinel skips
``compile``            ``what`` — program name
``checkpoint_save``    (also ``checkpoint_save_async_submit``) ``step``
``checkpoint_verify``  ``step``
``checkpoint_restore`` ``step``
``data_stall``         blocking input wait (``data/prefetch.py``)
``sentinel_skip``      ``step``, ``skipped_steps`` (cumulative)
``preemption``         ``wall_ts``
``drain``              serving/trainer drain window
``request_submit``     ``rid``, ``prompt_tokens``, ``max_new_tokens``
``request_admit``      ``rid``, ``slot``, ``blocks``
``prefill``            ``rids`` (packed row), ``tokens``
``decode_tick``        ``rid``, ``tokens`` — every N generated tokens
``request_finish``     ``rid``, ``tokens``
``request_cancel``     ``rid``
``request_reject``     ``rid`` — refused at submit (drain window /
                       overload shed), never queued
``autopilot_observe``  ``decision_id``, ``loop`` + the signal snapshot
                       (queue depth, p99 trend, attribution, ...) the
                       decision was made on (ISSUE 18)
``autopilot_decide``   ``decision_id``, ``loop``, ``action``,
                       ``reason`` — what the autopilot chose and why
``autopilot_act``      ``decision_id``, ``action`` + actuation detail
                       (``replica`` spawned/drained/quarantined, knob
                       ``payload`` + ``canary`` host, ...)
``autopilot_verdict``  ``decision_id``, ``verdict`` — how the decision
                       resolved: ``joined`` / ``drained`` / ``reaped``
                       / ``quarantined`` / ``commit`` / ``rollback`` /
                       ``inconclusive`` / ``no action`` (+ ``ratio``,
                       ``rounds`` for canary judges)
=====================  ====================================================

The four ``autopilot_*`` kinds share one ``decision_id`` per decision
(observe → decide → act → verdict), so ``scripts/trace_report.py`` can
reconstruct *why* the fleet changed shape next to the request traces.

Arming is process-global and **opt-in**: the module-level
:func:`emit`/:func:`scope` used by the instrumented subsystems
(trainer drivers, ``CheckpointManager``, ``DevicePrefetcher``, the
serving engine) are a single ``is None`` check when no recorder is
armed — the free-telemetry property (zero HLO difference) is pinned by
``tests/test_timeline.py``; the armed recorder's cost on a train step is
not measured on the chip (ROADMAP W11).
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Any, Dict, List, Optional

from apex_tpu.observability.goodput import assemble_report, classify_event

__all__ = [
    "FlightRecorder",
    "arm",
    "arm_from_env",
    "disarm",
    "active",
    "emit",
    "scope",
    "TIMELINE_ENV_VAR",
]

TIMELINE_ENV_VAR = "APEX_TPU_TIMELINE_DIR"


class FlightRecorder:
    """Crash-safe structured event log on one process-local monotonic
    clock.

    ``path``   — optional JSONL spill; every event is durably appended
                 (torn-tail-only loss under SIGKILL).  ``None`` keeps
                 the ring only (unit tests, pure introspection).
    ``ring``   — in-memory tail size for live introspection.
    ``fsync``  — per-event fsync on the spill.  Off by default: the
                 single ``os.write`` of a full line already survives
                 process death; fsync only buys power-loss durability
                 at a syscall per event.
    ``meta``   — extra fields stamped onto the ``run_begin`` event
                 (run name, mesh shape, ...).
    """

    def __init__(self, path: Optional[str] = None, *, ring: int = 4096,
                 fsync: bool = False, meta: Optional[dict] = None):
        from apex_tpu.observability.writers import JsonlWriter

        if ring < 1:
            raise ValueError(f"ring must be >= 1, got {ring}")
        self.path = path
        # keep_open: the spill is per-process (a traced fleet child
        # arms its own recorder post-spawn, never inheriting this
        # descriptor), and at traced-serving event rates the
        # open-per-record cycle would be the dominant cost of the
        # armed path (the vs_bare <= 1.05 gate); durability is
        # unchanged — one O_APPEND write per event, torn-tail-only
        self._writer = (JsonlWriter(path, fsync=fsync, keep_open=True)
                        if path else None)
        self._ring: "collections.deque[dict]" = collections.deque(maxlen=ring)
        self._lock = threading.Lock()
        self._t0 = time.monotonic()
        self.events_emitted = 0
        # incremental goodput accounting: bucket -> attributed seconds
        # (classification lives in goodput.py; accumulating here keeps
        # goodput-so-far exact after the ring wraps)
        self._bucket_s: Dict[str, float] = {}
        # mono_t0 anchors this spill on the process's monotonic clock:
        # cross-process trace stitching (observability/trace.py) maps an
        # event's relative ``t`` back to raw monotonic time as
        # ``mono_t0 + t``, then onto the router clock via the per-link
        # offset samples — relative-only spills could never be merged
        self.emit("run_begin", wall_ts=time.time(),
                  mono_t0=round(self._t0, 6), **(meta or {}))

    # ------------------------------------------------------------ clock

    @property
    def elapsed_s(self) -> float:
        return time.monotonic() - self._t0

    # ------------------------------------------------------------- emit

    def emit(self, kind: str, *, dur_s: Optional[float] = None,
             **fields: Any) -> dict:
        """Record one event now.  Interval events pass ``dur_s`` (the
        caller measured it; the event lands at the interval's end)."""
        ev: Dict[str, Any] = {"t": round(self.elapsed_s, 6), "kind": kind}
        if dur_s is not None:
            ev["dur_s"] = round(float(dur_s), 6)
        ev.update(fields)
        bucket = classify_event(ev)
        with self._lock:
            self._ring.append(ev)
            self.events_emitted += 1
            if bucket is not None and dur_s is not None:
                self._bucket_s[bucket] = (
                    self._bucket_s.get(bucket, 0.0) + float(dur_s))
        if self._writer is not None:
            self._writer.write(ev)
        return ev

    @contextlib.contextmanager
    def scope(self, kind: str, **fields: Any):
        """Time a block and emit one ``kind`` event with its ``dur_s``
        when it exits (even on exception — the crash-visible shape is a
        *missing* final event, never a dangling half-interval)."""
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.emit(kind, dur_s=time.monotonic() - t0, **fields)

    # ----------------------------------------------------- typed helpers

    def step(self, step: int, **fields: Any):
        """Scope for one training step's host dispatch+sync window."""
        return self.scope("step", step=step, **fields)

    def compile(self, what: str):
        return self.scope("compile", what=what)

    def data_stall(self, dur_s: float, **fields: Any) -> dict:
        return self.emit("data_stall", dur_s=dur_s, **fields)

    def sentinel_skip(self, step: int, skipped_steps: int) -> dict:
        return self.emit("sentinel_skip", step=step,
                         skipped_steps=skipped_steps)

    def preemption(self, **fields: Any) -> dict:
        return self.emit("preemption", wall_ts=time.time(), **fields)

    # ------------------------------------------------------ introspection

    def events(self) -> List[dict]:
        """Snapshot of the in-memory ring (oldest retained first)."""
        with self._lock:
            return list(self._ring)

    def tail(self, n: int = 32) -> List[dict]:
        with self._lock:
            if n >= len(self._ring):
                return list(self._ring)
            return list(self._ring)[-n:]

    def report(self) -> dict:
        """Goodput-so-far from the incremental bucket accounting (exact
        even after the ring wrapped) — see :func:`goodput.goodput_report`
        for the offline recompute over a spilled timeline."""
        with self._lock:
            buckets = dict(self._bucket_s)
        return assemble_report(buckets, wall_s=self.elapsed_s)

    # ------------------------------------------------------------- flush

    def flush(self, goodput_path: Optional[str] = None) -> dict:
        """Emit ``run_end``, compute the final goodput report, and
        optionally write it as JSON.  Idempotent-ish: callable once per
        run end (a second call emits a second ``run_end``)."""
        wall = self.elapsed_s
        self.emit("run_end", wall_s=round(wall, 6))
        report = self.report()
        if goodput_path:
            import json

            parent = os.path.dirname(goodput_path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            tmp = goodput_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(report, f, indent=1)
            os.replace(tmp, goodput_path)
        return report


# --- process-global arming ------------------------------------------------

_ACTIVE: Optional[FlightRecorder] = None
_ARM_LOCK = threading.Lock()


def arm(recorder_or_path) -> FlightRecorder:
    """Install the process-wide recorder (a :class:`FlightRecorder`, or
    a path string to spill to).  Instrumented subsystems pick it up via
    the module-level :func:`emit`/:func:`scope`."""
    global _ACTIVE
    rec = (recorder_or_path if isinstance(recorder_or_path, FlightRecorder)
           else FlightRecorder(recorder_or_path))
    with _ARM_LOCK:
        _ACTIVE = rec
    return rec


def arm_from_env() -> Optional[FlightRecorder]:
    """Arm from ``APEX_TPU_TIMELINE_DIR`` (spill to
    ``<dir>/timeline.jsonl``); ``None`` when the variable is unset —
    the zero-cost default."""
    d = os.environ.get(TIMELINE_ENV_VAR)
    if not d:
        return None
    return arm(os.path.join(d, "timeline.jsonl"))


def disarm() -> Optional[FlightRecorder]:
    """Remove (and return) the process recorder."""
    global _ACTIVE
    with _ARM_LOCK:
        rec, _ACTIVE = _ACTIVE, None
    return rec


def active() -> Optional[FlightRecorder]:
    return _ACTIVE


def emit(kind: str, *, dur_s: Optional[float] = None,
         **fields: Any) -> Optional[dict]:
    """Emit into the armed recorder; a single ``None`` check when
    unarmed — safe on any hot host path."""
    rec = _ACTIVE
    if rec is None:
        return None
    return rec.emit(kind, dur_s=dur_s, **fields)


@contextlib.contextmanager
def scope(kind: str, **fields: Any):
    """Module-level :meth:`FlightRecorder.scope`; no-op (no clock read,
    no allocation beyond the generator) when unarmed."""
    rec = _ACTIVE
    if rec is None:
        yield
        return
    with rec.scope(kind, **fields):
        yield

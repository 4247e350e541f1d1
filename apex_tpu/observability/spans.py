"""Span timers + profiler annotations — the NVTX/xprof layer.

Two kinds of instrumentation, deliberately distinct because they see
different clocks:

- :func:`named_span` — for **traced** code (inside jit/shard_map): a
  ``jax.named_scope`` that stamps the emitted ops' metadata so xprof
  groups the ring-matmul chunk GEMMs, bucket reduce-scatters, and
  pipeline ticks under readable names.  Adds ZERO HLO operations (pure
  metadata — the instrumented/bare HLO-parity test in
  ``tests/test_observability.py`` depends on this), so it is safe on any
  hot path.
- :func:`span` — for **host** code (checkpoint save/verify/restore,
  data loading, the step dispatch loop): wall-clock timing recorded into
  a :class:`~apex_tpu.observability.metrics.MetricRegistry` histogram
  plus a ``jax.profiler.TraceAnnotation`` so the same interval shows up
  as a range in a captured trace (the ``nvtx.range_push`` analog,
  ``apex/parallel/distributed.py:363``).

Plus the two step-level tools the real-TPU ``overlap_comm`` A/B needs
(ROADMAP S8/D7):

- :func:`step_trace` — ``jax.profiler.StepTraceAnnotation`` wrapper, so
  xprof's step-time view segments by training step;
- :class:`TraceWindow` — windowed programmatic capture: every
  ``every_n`` steps, ``jax.profiler.start_trace`` for ``capture_steps``
  steps then stop, so a long run continuously produces *small* trace
  windows instead of one giant (or zero) capture — the per-step timing
  evidence the overlap A/B must land with.

The span catalog (which names instrument which subsystem) is documented
in ``docs/observability.md``.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time
from typing import Optional

import jax

__all__ = ["named_span", "span", "step_trace", "TraceWindow"]

logger = logging.getLogger(__name__)

# One shared prefix so apex spans are greppable in an xprof trace among
# the framework-emitted scopes.
_PREFIX = "apex"


def named_span(name: str):
    """Trace-time scope for jitted code: ``with named_span("zero/rs")``.

    Pure op-metadata (``jax.named_scope``) — compiles to the identical
    HLO program, only with attributable op names.  Use this inside any
    traced function; use :func:`span` for host-side intervals.
    """
    return jax.named_scope(f"{_PREFIX}/{name}")


@contextlib.contextmanager
def span(name: str, *, registry=None):
    """Host wall-clock span: times the block, records
    ``span_ms/<name>`` into the registry's histogram, and opens a
    ``jax.profiler.TraceAnnotation`` so captured traces carry the range.

    NOTE: host spans measure *dispatch* unless the block itself blocks
    (``jax.block_until_ready``, file I/O) — time jitted work with
    :func:`step_trace` + a trace window, not with a host span around an
    async dispatch.
    """
    if registry is None:
        from apex_tpu.observability.metrics import default_registry

        registry = default_registry()
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(f"{_PREFIX}/{name}"):
            yield
    finally:
        registry.histogram(f"span_ms/{name}").observe(
            (time.perf_counter() - t0) * 1e3)


def step_trace(step_num: int, name: str = "train_step"):
    """``jax.profiler.StepTraceAnnotation`` for one training step — wrap
    the step dispatch so xprof's step-time view segments correctly::

        with step_trace(step):
            state = train_step(*state)
    """
    return jax.profiler.StepTraceAnnotation(name, step_num=step_num)


class TraceWindow:
    """Windowed programmatic profiler capture.

    ``on_step(step)`` is called once per training step (before or after
    the dispatch — it only manages capture state): at every
    ``every_n``-th step a trace starts into
    ``<logdir>/step_<step>``, and after ``capture_steps`` more calls it
    stops — so a week-long run leaves a trail of small, per-window xprof
    captures instead of requiring a human to attach at the right moment.
    This is how the real-TPU ``overlap_comm`` A/B run collects its
    comm/compute-overlap evidence for free (ROADMAP).

    Profiler failures (already-active sessions, missing profiler plugin)
    are logged and disable the window rather than killing the run —
    telemetry must never take down training.  ``_profiler`` is
    injectable for tests.
    """

    def __init__(self, logdir: str, *, every_n: int = 100,
                 capture_steps: int = 3, enabled: bool = True,
                 _profiler=None):
        if every_n < 1 or capture_steps < 1:
            raise ValueError(
                f"every_n ({every_n}) and capture_steps ({capture_steps}) "
                "must be >= 1")
        self.logdir = logdir
        self.every_n = every_n
        self.capture_steps = capture_steps
        self.enabled = enabled
        self.windows_captured = 0
        self._active_until: Optional[int] = None
        self._profiler = _profiler if _profiler is not None else jax.profiler

    @property
    def active(self) -> bool:
        return self._active_until is not None

    def on_step(self, step: int) -> None:
        if not self.enabled:
            return
        if self._active_until is not None:
            if step >= self._active_until:
                self._stop()
            return
        if step % self.every_n == 0:
            path = os.path.join(self.logdir, f"step_{step:08d}")
            try:
                os.makedirs(path, exist_ok=True)
                self._profiler.start_trace(path)
            except Exception as e:  # profiler unavailable / double-start
                logger.warning(
                    "TraceWindow disabled: start_trace failed (%r)", e)
                self.enabled = False
                return
            self._active_until = step + self.capture_steps

    def _stop(self) -> None:
        try:
            self._profiler.stop_trace()
            self.windows_captured += 1
        except Exception as e:
            logger.warning("TraceWindow stop_trace failed (%r)", e)
            self.enabled = False
        self._active_until = None

    def close(self) -> None:
        """Stop any in-flight capture (call at shutdown so the last
        window is flushed rather than torn)."""
        if self._active_until is not None:
            self._stop()

    def __enter__(self) -> "TraceWindow":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

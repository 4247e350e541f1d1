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
- :class:`span` — for **host** code (checkpoint save/verify/restore,
  data loading, the phases of a serving tick): wall-clock timing recorded
  into a :class:`~apex_tpu.observability.metrics.MetricRegistry` histogram
  plus a ``jax.profiler.TraceAnnotation`` so the same interval shows up
  as a range in a captured trace (the ``nvtx.range_push`` analog,
  ``apex/parallel/distributed.py:363``).  Every span is also kept, with
  its start, end, parent and integer fields, in one bounded process-wide
  ring that :func:`recorded` reads and :func:`self_ms` reduces.

Traced scopes become **device time by layer** through the scope tables:
:func:`register_program` keeps a lowered program by name,
:func:`program_scopes` compiles it on the first ask and reduces its
optimized HLO with :func:`scopes_of` to ``{instruction: (scope, text)}``,
which is what names each operation of a captured profile (the profiler's
events carry the instruction's text and no metadata).

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

import collections
import itertools
import logging
import os
import re
import threading
import time
import weakref
from typing import Callable, Dict, Iterable, List, Optional, Tuple, Union

import jax

from apex_tpu.observability.metrics import default_registry

__all__ = ["named_span", "span", "recorded", "self_ms", "scopes_of",
           "register_program", "program_scopes", "step_trace",
           "TraceWindow"]

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


# Every finished host span of the process, oldest first.  Bounded, so a
# server that runs for weeks keeps the last few hundred ticks and no more;
# ``deque.append`` is atomic, so threads share it without a lock.
_RING: "collections.deque[span]" = collections.deque(maxlen=8192)
_IDS = itertools.count(1)
_LOCAL = threading.local()          # .stack: the thread's open spans
# registry -> {span name: its ``span_ms/<name>`` histogram}: a span runs
# about nine times a serving tick, so it does not pay the registry's lock
# and a formatted name each time
_HISTOGRAMS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


class span:
    """Host wall-clock span: ``with span("checkpoint/save", step=7) as s``.

    Times the block on ``time.perf_counter``, observes ``span_ms/<name>``
    in the registry, opens a ``jax.profiler.TraceAnnotation("apex/<name>")``
    (a flag test while no profiler session runs; a range on the profiler's
    host plane while one does) and, on exit, joins the process-wide ring
    with ``name``, ``start``, ``end``, ``id``, ``parent`` (the ``id`` of the
    span open round it on the same thread, 0 at the top) and ``fields``
    (integers known at entry as keywords, later ones through :meth:`note`).

    NOTE: host spans measure *dispatch* unless the block itself blocks
    (``jax.block_until_ready``, ``np.asarray``, file I/O) — time jitted
    work with :func:`step_trace` + a trace window, not with a host span
    around an async dispatch.
    """

    __slots__ = ("name", "fields", "start", "end", "id", "parent",
                 "_histogram", "_annotation")

    def __init__(self, name: str, *, registry=None, **fields: int):
        self.name = name
        self.fields = fields
        self.start = self.end = 0.0
        self.id = self.parent = 0
        if registry is None:
            registry = default_registry()
        try:
            self._histogram = _HISTOGRAMS[registry][name]
        except KeyError:
            self._histogram = _HISTOGRAMS.setdefault(registry, {})[name] = \
                registry.histogram(f"span_ms/{name}")
        self._annotation = jax.profiler.TraceAnnotation(f"{_PREFIX}/{name}")

    def note(self, **fields: int) -> None:
        """Add fields that are known only once the block has run."""
        self.fields.update(fields)

    @property
    def ms(self) -> float:
        return (self.end - self.start) * 1e3

    def __enter__(self) -> "span":
        try:
            stack = _LOCAL.stack
        except AttributeError:
            stack = _LOCAL.stack = []
        self.id = next(_IDS)
        self.parent = stack[-1].id if stack else 0
        stack.append(self)
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._annotation = None
        _LOCAL.stack.pop()
        _RING.append(self)
        self._histogram.observe(self.ms)

    def __repr__(self) -> str:
        return (f"span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"ms={self.ms:.3f}, fields={self.fields})")


def recorded(since: Optional[float] = None) -> List[span]:
    """The ring's finished spans, oldest first; with ``since`` (a
    ``time.perf_counter`` reading) only those that started at or after
    it."""
    records = list(_RING)
    if since is None:
        return records
    return [s for s in records if s.start >= since]


def self_ms(records: Iterable[span]) -> Dict[int, float]:
    """Each span's own milliseconds by ``id``: its duration less what its
    children among ``records`` cover."""
    records = list(records)
    own = {s.id: s.ms for s in records}
    for s in records:
        if s.parent in own:
            own[s.parent] -= s.ms
    return own


# ------------------------------------------------- device time by layer

# the first part of a traced scope's name, wherever the scope sits in an
# ``op_name``: at its start, after a ``/``, or inside a transform's
# brackets (``transpose(jvp(apex/flash_full))``)
_SCOPE = re.compile(rf"(?:^|[/(]){_PREFIX}/([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_OPERANDS = re.compile(r"\s[a-z][a-z0-9\-]*\(")
# an attribute that names one computation the instruction calls, or several
_CALLED = re.compile(
    r"\b(calls|to_apply|body|condition|true_computation"
    r"|false_computation)=([^,\s]+)"
    r"|\b(branch_computations|called_computations)=\{([^}]*)\}")


def _without(line: str, attribute: str) -> Tuple[str, str]:
    """``line`` less its ``, <attribute>=<value>``, and the value: a
    ``{...}`` (braces nest, quoted strings may hold any) or a ``"..."``;
    ``(line, "")`` where the line has none."""
    at = line.find(f", {attribute}=")
    if at < 0:
        return line, ""
    i = start = at + len(attribute) + 3
    depth, quoted = 0, False
    while i < len(line):
        c = line[i]
        if quoted:
            if c == "\\":
                i += 1
            elif c == '"':
                quoted = False
        elif c == '"':
            quoted = True
        elif c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        if not quoted and depth == 0:
            break
        i += 1
    return line[:at] + line[i + 1:], line[start:i + 1]


def instruction_text(line: str) -> str:
    """What tells one program's ``fusion.12`` from another's, from the
    instruction's line in a module's text or from the name the profiler
    gives its event: ``name = result opcode(operand names)``.  The event
    prints each operand's shape and the module's text does not, and their
    attributes differ, so neither is kept."""
    line = line.strip().removeprefix("ROOT ").replace("%", "")
    head = _OPERANDS.search(line, line.find(" = ") + 2)
    if head is None:
        return line
    # an operand is the last word of its entry (``bf16[8,128]{1,0} copy.3``)
    # between the commas that lie inside no further bracket
    operands, depth, at = [], 1, head.end()
    for i in range(at, len(line)):
        c = line[i]
        depth += (c in "([{") - (c in ")]}")
        if depth == 0 or (depth == 1 and c == ","):
            operands += line[at:i].split()[-1:]
            at = i + 1
            if depth == 0:
                break
    return f"{line[:head.end()]}{', '.join(operands)})"


def scopes_of(hlo_text: str) -> Dict[str, Tuple[Optional[str], str]]:
    """``{instruction name: (scope, text)}`` of an optimized HLO module's
    text (``compiled.as_text()``), fused computations' own instructions
    included.

    ``scope`` is the innermost :func:`named_span` round the primitive the
    instruction came from: the last ``apex/<name>`` in its ``op_name``,
    also inside ``transpose(jvp(..))``, ``checkpoint`` or a nested
    ``jit(..)``.  A name of several parts reads as its first
    (``zero/reduce_scatter/bucket3`` is ``zero``): a rendered path does
    not say where a name ends.  XLA gives a fusion its root's ``op_name``,
    so a fusion that spans two scopes counts under its root's.  An
    instruction whose path has no scope takes one from its neighbours: a
    fusion (XLA's own scatter, with no path at all) the scope most of the
    instructions fused into it carry; then any instruction (a copy XLA
    put into a loop's body, a reduction's adder) that of the instruction
    that calls its computation; ``None`` where that has none either.
    ``text`` is :func:`instruction_text` of the line."""
    # [(computation, [(instruction, scope, text, callees, fused into it)])]
    computations = []
    for line in hlo_text.splitlines():
        header = _COMPUTATION.match(line)
        if header is not None:
            computations.append((header.group(1), []))
            continue
        found = _INSTRUCTION.match(line)
        if found is None or not computations:
            continue
        line, metadata = _without(line, "metadata")
        line, _ = _without(line, "backend_config")
        op_name = _OP_NAME.search(metadata)
        inside = _SCOPE.findall(op_name.group(1)) if op_name else ()
        called = [(one or several, name.strip().lstrip("%"))
                  for one, name, several, names in _CALLED.findall(line)
                  for name in (name or names).split(",")]
        computations[-1][1].append((
            found.group(1), inside[-1] if inside else None,
            instruction_text(line), [name for _, name in called],
            next((name for how, name in called if how == "calls"), None)))
    most = {}               # computation -> the scope most of it carries
    for name, instructions in computations:
        own = collections.Counter(
            scope for _, scope, _, _, _ in instructions if scope)
        most[name] = own.most_common(1)[0][0] if own else None
    table, inherited = {}, {}
    # a module's text lists a computation before the ones that call it
    for name, instructions in reversed(computations):
        outer = inherited.get(name)
        for instruction, scope, text, callees, fused in instructions:
            scope = scope or most.get(fused) or outer
            table[instruction] = (scope, text)
            for callee in callees:
                inherited.setdefault(callee, scope)
    return table


# name -> a ``Lowered``, a callable that makes one, or the table built from
# it.  Process-wide like the ring: a second engine's programs take the
# names over (two replicas of one model compile the same programs).
_PROGRAMS: Dict[str, object] = {}


def register_program(name: str,
                     lowered: Union[object, Callable[[], object]]) -> None:
    """Keep a program for :func:`program_scopes`: ``jitted.lower(...)``'s
    result, or a callable that returns it (where lowering is better left
    to whoever asks).  Costs nothing until someone asks.  What is handed
    in must hold no device array and no owner of one: a ``Lowered`` holds
    the module's text, avals and shardings; a callable should close over
    the jitted function and ``jax.ShapeDtypeStruct`` arguments."""
    _PROGRAMS[name] = lowered


def program_scopes() -> Dict[str, Dict[str, Tuple[Optional[str], str]]]:
    """``{program name: scopes_of(its optimized HLO)}`` of every registered
    program.  The first ask compiles each (a hit where JAX's persistent
    compile cache is on: the program ran before) and keeps the table in
    the program's place (an empty one, and a warning, where a program no
    longer lowers or compiles).  For whoever reads a capture of this
    process by layer; not for a status page, which must never start a
    compilation."""
    for name, kept in list(_PROGRAMS.items()):
        if isinstance(kept, dict):
            continue
        try:
            lowered = kept() if callable(kept) else kept
            _PROGRAMS[name] = scopes_of(lowered.compile().as_text())
        except Exception as e:  # telemetry never takes the process down
            logger.warning("no scope table of %s: %r", name, e)
            _PROGRAMS[name] = {}
    return dict(_PROGRAMS)


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

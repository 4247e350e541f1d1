"""The program's own host spans of a serving window.

``apex_tpu.observability.spans`` keeps every finished host span of the
process in a bounded ring, on ``time.perf_counter``: the clock of ``Tick.t``.
``ServingEngine.step`` records one ``serving/tick`` span per call and one
child per phase that ran (``serving/tick/admit``, ``.../prefill_dispatch``,
``.../decode_fetch``, ...; the span map is in ``docs/observability.md``).
The readers take the ticks that lie inside the window of
``view["observed"]["ticks_seen"]``, from its first tick's start to its last
tick's end, so that warm-up and the first, compiling tick stay out.

A program from before the ring records none: every reader then finds
nothing to read and returns ``None``.
"""

import statistics

from apex_tpu.observability import spans

TICK = "serving/tick"


def window_ticks(view):
    """``[(tick, {phase: span})]`` of the window, oldest first: each
    ``serving/tick`` span with its children by the last part of their
    name."""
    seen = view["observed"]["ticks_seen"]
    recorded = getattr(spans, "recorded", None)
    if not seen or recorded is None:
        return []
    lo, hi = seen[0].t - seen[0].ms / 1e3, seen[-1].t
    records = [s for s in recorded(since=lo) if s.end <= hi]
    phases = {s.id: {} for s in records if s.name == TICK}
    for s in records:
        if s.parent in phases:
            phases[s.parent][s.name.rpartition("/")[2]] = s
    return [(s, phases[s.id]) for s in records if s.name == TICK]


def _call_ms(phases, kind):
    return phases[kind + "_dispatch"].ms + phases[kind + "_fetch"].ms


def plain_decode_ticks(view):
    """``[(host ms, call ms)]`` of the ticks that ran the decode program
    and no prefill chunk: the call is ``decode_dispatch`` + ``decode_fetch``
    (argument transfer, enqueue, and the host blocked on the device), the
    host's share is the rest of the tick (admit, plan, deliver: the Python
    an engine that dispatched ahead could overlap)."""
    return [(tick.ms - _call_ms(phases, "decode"), _call_ms(phases, "decode"))
            for tick, phases in window_ticks(view)
            if "prefill_dispatch" not in phases and "decode_dispatch" in phases]


def prefill_call_ms(view):
    """``prefill_dispatch`` + ``prefill_fetch`` of each tick with a chunk."""
    return [_call_ms(phases, "prefill") for _, phases in window_ticks(view)
            if "prefill_dispatch" in phases]


def median(values):
    return statistics.median(values) if values else None

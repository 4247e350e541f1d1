"""Reduction of the profiler's ``.xplane.pb`` to what the metrics read:
device busy and idle time, each operation's own time, the operations that
took most time, and the idle gaps by what the host was doing.

What a trace of this chip holds (see ``tests/record_trace.py``): one plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` has one event per
executed HLO instruction, named by the instruction's whole text
(``%fusion.4 = bf16[..] fusion(...)``), with start and duration in
nanoseconds on the device's clock; and a plane ``/host:CPU`` whose lines
are threads, where ``TraceAnnotation`` spans appear by name on the host's
clock.  The two clocks differ by a millisecond or so; device times are
shifted so that the window's first operation starts with the first host
span of the window, which is right to the dispatch latency.
"""

import re

WINDOW_SPAN = "bench/window"
SPAN_PREFIX = "bench/"
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")


def instruction(name: str) -> str:
    """``%fusion.4 = ...`` -> ``fusion.4``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def opcode(name: str) -> str:
    """The HLO opcode of an event named by its instruction's text: the first
    lower-case word directly before a ``(`` after the ``=`` (tile
    annotations in shapes, ``T(8,128)``, are upper-case)."""
    found = _OPCODE.search(name.split(" = ", 1)[-1] if " = " in name
                           else " " + name)
    return found.group(1) if found else ""


def is_collective(name: str) -> bool:
    return opcode(name).removesuffix("-start").removesuffix(
        "-done") in COLLECTIVES


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals, in their unit."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def self_times(events):
    """Own time of each event of one line, ``events`` being
    ``(start, end, name)``: an event's duration less what the events nested
    in it cover (a ``while`` holds its body's operations)."""
    out, stack = [], []           # stack of [start, end, name, child_time]

    def close(until):
        while stack and stack[-1][1] <= until:
            a, b, name, child = stack.pop()
            out.append((name, max(0.0, (b - a) - child)))
            if stack:
                stack[-1][3] += b - a

    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        close(a)
        stack.append([a, b, name, 0.0])
    close(float("inf"))
    return out


def gaps_of(intervals, lo, hi):
    """The idle gaps inside ``[lo, hi]`` left by ``(start, end)`` intervals."""
    gaps, at = [], lo
    for a, b in sorted(intervals):
        if a > at:
            gaps.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(a, b) for a, b in gaps if b > a]


def _host_spans(data):
    spans = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    return spans


def _device_lines(data):
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                yield plane.name, [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events]


def reduce(path: str, top: int = 10) -> dict:
    """Reduce one ``.xplane.pb``.  Seconds throughout.

    ``devices``: per chip ``busy_s`` (union of the intervals in which an
    operation ran), ``ops`` (``{instruction text: [own seconds, count]}``)
    and ``collective_s`` (own time of collective operations).
    ``busy_s`` is the mean over the chips and ``window_s`` the length of the
    ``bench/window`` span.  ``breakdown`` is the contract's: the operations
    that took most time on the busiest chip and the idle seconds by host
    span.
    """
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    spans = _host_spans(data)
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(windows)}")
    w_lo, w_hi, _ = windows[0]
    inner = sorted(s for s in spans if s[2] != WINDOW_SPAN
                   and s[0] >= w_lo and s[1] <= w_hi)

    devices = []
    for name, events in _device_lines(data):
        events = [e for e in events if e[1] > e[0]]
        if not events:
            continue
        ops = {}
        for text, own in self_times(events):
            rec = ops.setdefault(text, [0.0, 0])
            rec[0] += own * 1e-9
            rec[1] += 1
        intervals = [(a, b) for a, b, _ in events]
        devices.append({
            "plane": name, "busy_s": union_seconds(intervals) * 1e-9,
            "ops": ops, "intervals": intervals,
            "collective_s": sum(s for t, (s, _) in ops.items()
                                if is_collective(t))})
    if not devices:
        raise RuntimeError("the trace holds no operation on any device")

    top_chip = max(devices, key=lambda d: d["busy_s"])
    window_s = (w_hi - w_lo) * 1e-9
    # the device's clock against the host's
    first_op = min(a for a, _ in top_chip["intervals"])
    shift = (inner[0][0] if inner else w_lo) - first_op
    idle = {}
    for a, b in gaps_of([(a + shift, b + shift)
                         for a, b in top_chip["intervals"]], w_lo, w_hi):
        mid = 0.5 * (a + b)
        covering = [s for s in inner if s[0] <= mid <= s[1]]
        label = (min(covering, key=lambda s: s[1] - s[0])[2] if covering
                 else "between spans")
        idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
    by_instruction = {}
    for text, (s, _) in top_chip["ops"].items():
        key = f"{instruction(text)}:{opcode(text)}"
        by_instruction[key] = by_instruction.get(key, 0.0) + s
    for d in devices:
        del d["intervals"]
    return {
        "devices": devices,
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "window_s": window_s,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                by_instruction.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])[:top]]},
    }


def busiest(reduced: dict) -> dict:
    return max(reduced["devices"], key=lambda d: d["busy_s"])


def op_seconds(reduced: dict, match) -> tuple:
    """Own seconds and count, on the busiest chip, of the operations whose
    instruction text ``match`` accepts."""
    hits = [(s, n) for t, (s, n) in busiest(reduced)["ops"].items()
            if match(t)]
    return sum(s for s, _ in hits), sum(n for _, n in hits)

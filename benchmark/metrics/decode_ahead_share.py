"""Share of the window's decode calls that the engine dispatched while the
call before was still unfetched (the field ``ahead`` of each
``decode_dispatch`` span): the ticks in which the host's plan, the way back
and the delivery ran under the device's work."""

from metrics import _hybrid


def read(view):
    calls = _hybrid.phase_fields(view, "decode_dispatch", "ahead")
    if not calls:
        return None
    return 100.0 * sum(c["ahead"] for c in calls) / len(calls)

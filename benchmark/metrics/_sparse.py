"""What the readers of the sparse latent layers share: the rows the window's
decode calls scanned and selected (the ``decode_plan`` span's
``dsa_rows_scanned`` and ``dsa_rows_selected``, summed over the sparse
layers), and the seconds of their scopes.  A program without them gives
nothing to read."""

from lib import peaks, xplane
from metrics import _hybrid, _scopes

FIELDS = ("dsa_rows_scanned", "dsa_rows_selected")


def rows(view):
    """``(scanned, selected)`` rows of the window's decode calls, or
    ``None``."""
    ticks = _hybrid.phase_fields(view, "decode_plan", *FIELDS)
    if not ticks:
        return None
    return tuple(sum(t[f] for t in ticks) for f in FIELDS)


def scope_seconds(view, *names):
    """Seconds of the busiest chip's operations under any of ``names``, or
    ``None`` where the program has no scope tables or none of them ran."""
    seconds = _scopes.by_scope(view)
    if seconds is None:
        return None
    total = sum(seconds.get(name, 0.0) for name in names)
    return total or None


def roofline(view, seconds, what):
    """``what(scanned, selected, kind) -> (flops, bytes)`` against
    ``seconds``, as a share of the roofline."""
    counted = rows(view)
    if not seconds or counted is None or view["peaks"] is None:
        return None
    flops, nbytes = what(*counted, view["observed"]["sizes"]["kinds"][0])
    return 100.0 * peaks.roofline_seconds(
        flops, nbytes, view["peaks"], view["chips"]) / seconds


def index_kernel_seconds(view):
    seconds, count = xplane.op_seconds(view["trace"],
                                       _hybrid.named("paged_index_decode"))
    return seconds if count else None

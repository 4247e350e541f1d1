"""Blocks the window cache group holds over the blocks the same histories
would hold with nothing handed back, over the window's decode ticks."""

from metrics import _hybrid


def read(view):
    ticks = _hybrid.phase_fields(view, "decode_plan", "window_blocks_held",
                                 "kv_pages")
    unfreed = sum(t["kv_pages"] for t in ticks)
    if not unfreed:
        return None
    return 100.0 * sum(t["window_blocks_held"] for t in ticks) / unfreed

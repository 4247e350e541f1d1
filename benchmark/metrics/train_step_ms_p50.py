"""Median time of one train step, each blocked on in the traced window."""

import statistics


def read(view):
    steps = view["observed"].get("step_ms")
    return statistics.median(steps) if steps else None

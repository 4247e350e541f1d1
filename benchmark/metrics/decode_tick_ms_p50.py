"""Median time of an engine tick that carried no prefill chunk."""

import statistics


def read(view):
    plain = [t.ms for t in view["observed"]["ticks_seen"] if not t.prefill]
    return statistics.median(plain) if plain else None

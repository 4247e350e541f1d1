"""Median host time of a plain decode tick, from the program's own spans:
the ``serving/tick`` span less its ``decode_dispatch`` and ``decode_fetch``
children."""

from lib import program_spans


def read(view):
    return program_spans.median(
        [host for host, _ in program_spans.plain_decode_ticks(view)])

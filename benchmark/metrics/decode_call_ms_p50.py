"""Median time of the decode call in a plain decode tick, from the program's
own spans: ``decode_dispatch`` + ``decode_fetch``."""

from lib import program_spans


def read(view):
    return program_spans.median(
        [call for _, call in program_spans.plain_decode_ticks(view)])

"""Median time of the fixed-shape prefill call, from the program's own
spans: ``prefill_dispatch`` + ``prefill_fetch`` of the ticks with a chunk."""

from lib import program_spans


def read(view):
    return program_spans.median(program_spans.prefill_call_ms(view))

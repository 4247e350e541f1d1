"""Prompt tokens the window's prefill calls advanced over the tokens their
fixed ``[max_batch, prefill_len]`` shape has room for, from the fields of
the program's ``serving/tick`` spans."""

from lib import program_spans


def read(view):
    ticks = [tick for tick, _ in program_spans.window_ticks(view)]
    capacity = sum(t.fields["prefill_capacity"] for t in ticks)
    if not capacity:
        return None
    return 100.0 * sum(t.fields["prefill_tokens"] for t in ticks) / capacity

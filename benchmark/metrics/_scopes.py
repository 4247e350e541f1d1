"""What the readers of device time by layer share: each operation of the
traced window put down to the scope of the program that made it.

The program names its layers with ``spans.named_span`` and registers its
compiled programs (``spans.register_program``); ``spans.program_scopes()``
gives ``{program: {instruction name: (scope, text)}}``, read from each
program's optimized HLO.  The profiler's event carries the instruction's
text and no scope, so the join is by the instruction's name, and by
``spans.instruction_text`` where two programs share the name with
different scopes.  A program from before the tables registers nothing:
every reader then returns ``None``.
"""

from apex_tpu.observability import spans
from lib import xplane

AMBIGUOUS = "ambiguous"
# the weight-streaming matmuls XLA compiles
DENSE = ("embed", "attn_proj", "mla_absorb_q", "mla_expand_o", "dense_ffn",
         "moe_shared", "lm_head")


def scope_of(text, tables):
    """The scope of the operation whose event is named ``text``: ``None``
    where no program lists it or the one that does gives it none,
    ``AMBIGUOUS`` where programs that disagree list it and the text
    settles on none or on several of them."""
    name = xplane.instruction(text)
    found = [table[name] for table in tables.values() if name in table]
    if len({scope for scope, _ in found}) <= 1:
        return found[0][0] if found else None
    same = spans.instruction_text(text)
    scopes = {scope for scope, kept in found if kept == same}
    return scopes.pop() if len(scopes) == 1 else AMBIGUOUS


def by_scope(view):
    """``{scope or None or "ambiguous": own seconds}`` of the busiest
    chip's operations, or ``None`` where the program has no tables."""
    tables = getattr(spans, "program_scopes", dict)()
    if not tables:
        return None
    seconds = {}
    for text, (own, _) in xplane.busiest(view["trace"])["ops"].items():
        scope = scope_of(text, tables)
        seconds[scope] = seconds.get(scope, 0.0) + own
    return seconds


def share(view, pick):
    """The seconds ``pick(by_scope)`` gives as a share of the busiest
    chip's busy time."""
    seconds = by_scope(view)
    if seconds is None:
        return None
    return 100.0 * pick(seconds) / xplane.busiest(view["trace"])["busy_s"]

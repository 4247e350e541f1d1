"""What the readers of the hybrid model's per-layer metrics share: the
fields of the program's ``serving/tick`` phase spans inside the traced
window, and matchers of its kernels by the names the program gives them.
A program without those spans, fields or names gives nothing to read."""

from lib import program_spans, xplane


def phase_fields(view, phase, *names):
    """``[{name: value}]`` of the window's ticks whose ``phase`` span has
    every field of ``names``."""
    out = []
    for _, phases in program_spans.window_ticks(view):
        fields = getattr(phases.get(phase), "fields", None) or {}
        if all(n in fields for n in names):
            out.append({n: fields[n] for n in names})
    return out


def named(prefix):
    """A matcher of the Mosaic custom calls whose instruction's name starts
    with ``prefix`` (``%paged_decode_window.3``, ``%moe_experts.12``)."""
    def match(text):
        return (xplane.opcode(text) == "custom-call"
                and xplane.instruction(text).startswith(prefix))
    return match


def routed_calls(view):
    """Pairs and (layer, expert) entries hit, summed over the decode and
    prefill calls of the traced window, or ``None`` where no call says."""
    rows = (phase_fields(view, "decode_fetch", "moe_pairs", "moe_experts_hit")
            + phase_fields(view, "prefill_fetch", "moe_pairs",
                           "moe_experts_hit"))
    if not rows:
        return None
    return (sum(r["moe_pairs"] for r in rows),
            sum(r["moe_experts_hit"] for r in rows))


def decode_roofline(view, kernel, rows_field, kind_index):
    """Least time to read ``rows_field`` rows in every layer of attention
    kind ``kind_index`` over the time of the kernel named ``kernel``."""
    from kernels import paged_attention_groups
    from lib import peaks

    seconds, count = xplane.op_seconds(view["trace"], named(kernel))
    ticks = phase_fields(view, "decode_plan", rows_field)
    if not count or not ticks or view["peaks"] is None:
        return None
    sz = view["observed"]["sizes"]
    layers = sum(1 for k in sz["pattern"] if k == kind_index)
    flops, nbytes = paged_attention_groups.decode_rows(
        sum(t[rows_field] for t in ticks), sz["kinds"][kind_index], layers)
    return 100.0 * peaks.roofline_seconds(
        flops, nbytes, view["peaks"], view["chips"]) / seconds

"""What several readers share.  A reader takes the run's ``view`` and
returns its number, or ``None`` where it finds nothing to read."""

from lib import xplane


def is_flash_kernel(text: str) -> bool:
    """The flash attention kernels in a train step's trace: Mosaic custom
    calls under the ``core_attention`` module of a layer."""
    return (xplane.opcode(text) == "custom-call"
            and "tpu_custom_call" in text
            and "core_attention" in text)


def share_of_busy(view, match):
    """Own time of the operations ``match`` accepts as a share of the
    busiest chip's busy time, or ``None`` where the trace holds none."""
    seconds, count = xplane.op_seconds(view["trace"], match)
    if not count:
        return None
    return 100.0 * seconds / xplane.busiest(view["trace"])["busy_s"]


def idle_share(view):
    trace = view["trace"]
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def paged_decode_kernel(view):
    """A matcher of the paged decode attention kernel in a serving trace:
    the Mosaic custom call that reads the two KV arenas
    (``[n_blocks, block, kv_heads, d]``) and writes one row of attention per
    slot (``[slots, heads, d]``; the multi-query prefill kernel writes a
    chunk per slot).  Shapes, not names: the program gives its kernels no
    stable name yet."""
    sz, eng = view["observed"]["sizes"], view["traffic"]["engine"]
    d = sz["hidden"] // sz["heads"]
    block = view["observed"]["block_size"]
    arena = f"[{eng['n_blocks']},{block},{sz['heads']},{d}]"
    out = f"[{eng['max_batch']},{sz['heads']},{d}]"

    def match(text):
        if xplane.opcode(text) != "custom-call" or \
                "tpu_custom_call" not in text:
            return False
        result = text.split(" = ", 1)[-1].split(" custom-call(", 1)[0]
        return text.count(arena) >= 2 and out + "{" in result

    return match

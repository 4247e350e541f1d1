"""Operations and bytes of a top-k expert feed-forward over the experts a
chip holds, from counts, whatever implements it
(``apex_tpu/transformer/moe.held_experts_ffn`` today).

Each ``(token, expert)`` pair routed to a held expert meets the expert's
three matrices (gate, up, down: ``hidden x f`` each): ``6 * hidden * f`` FLOP.
An expert that any pair of a call hits has its three matrices read once in
that call, however many pairs hit it; an expert no pair hits is not read.
The tokens' rows in and out are small beside the weights and are counted.
"""


def expert_bytes(hidden, ffn, itemsize=2):
    """Bytes of one expert's gate, up and down matrices."""
    return 3 * hidden * ffn * itemsize


def routed(pairs, experts_hit, hidden, ffn, itemsize=2):
    """FLOP and bytes of the held experts' part of the calls that routed
    ``pairs`` pairs in all and hit ``experts_hit`` (layer, expert) entries
    in all (both summed over the calls)."""
    flops = pairs * 6 * hidden * ffn
    nbytes = (experts_hit * expert_bytes(hidden, ffn, itemsize)
              + 2 * pairs * hidden * itemsize)
    return flops, nbytes

"""Operations and bytes of training a top-k expert feed-forward over the
experts a chip holds, from counts, whatever implements it
(``apex_tpu/transformer/moe.held_experts_ffn`` and its VJP today).

Each ``(token, expert)`` pair routed to a held expert meets the expert's
three matrices (gate, up, down: ``hidden x f`` each) once forward (``6 *
hidden * f`` FLOP) and twice backward (the rows' gradient and the weights'
gradient): ``18 * hidden * f`` FLOP a pair.  An expert that any pair of a
microbatch hits has its three matrices read once forward and once backward
and its gradient written once; an expert no pair hits is not touched.  The
pairs' rows in and out (read forward, read and written backward) are small
beside the weights and are counted.  Recomputing the forward products under
a checkpoint is the trainer's choice and is not counted.
"""

from kernels import moe


def routed(pairs, experts_hit, hidden, ffn, itemsize=2):
    """FLOP and bytes of the held experts' part of the microbatches that
    routed ``pairs`` pairs in all and hit ``experts_hit`` (microbatch,
    layer, expert) entries in all."""
    flops = pairs * 18 * hidden * ffn
    nbytes = (3 * experts_hit * moe.expert_bytes(hidden, ffn, itemsize)
              + 6 * pairs * hidden * itemsize)
    return flops, nbytes

"""Operations and bytes that causal softmax attention needs, from shapes,
whatever implements it (``apex_tpu/ops/flash_attention`` today).

Forward: ``QK^T`` and ``PV``, each ``2*b*n*s*s*d`` FLOP over the full
square; a causal mask needs half of it.  Backward: ``dV = P^T dO``,
``dP = dO V^T``, ``dQ = dS K`` and ``dK = dS^T Q``, four such products.
Recomputing ``QK^T`` in the backward pass is the kernel's choice and is not
counted.  Bytes are each operand read once and each result written once.
"""


def forward_flops(b, n, s, d, causal=True):
    return 2 * (2 * b * n * s * s * d) * (0.5 if causal else 1.0)


def backward_flops(b, n, s, d, causal=True):
    return 4 * (2 * b * n * s * s * d) * (0.5 if causal else 1.0)


def forward_bytes(b, n, s, d, itemsize=2):
    """q, k, v read; out written; the row log-sum-exp (float32) written."""
    return 4 * b * n * s * d * itemsize + b * n * s * 4


def backward_bytes(b, n, s, d, itemsize=2):
    """q, k, v, out, d_out read; dq, dk, dv written; log-sum-exp read."""
    return 8 * b * n * s * d * itemsize + b * n * s * 4


def train_step(sz, batch, seq, itemsize=2):
    """FLOP and bytes of attention, forward and backward, in one training
    step of ``batch`` sequences of ``seq`` through every layer."""
    n, d, L = sz["heads"], sz["hidden"] // sz["heads"], sz["layers"]
    flops = L * (forward_flops(batch, n, seq, d)
                 + backward_flops(batch, n, seq, d))
    nbytes = L * (forward_bytes(batch, n, seq, d, itemsize)
                  + backward_bytes(batch, n, seq, d, itemsize))
    return flops, nbytes

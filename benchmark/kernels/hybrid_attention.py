"""Operations and bytes that causal softmax attention with grouped-query
heads and an optional sliding window needs, from shapes, whatever implements
it (``apex_tpu/ops/flash_attention`` with ``window`` today).

Position ``i`` of a sequence of ``s`` sees ``min(i + 1, window)`` keys
(``i + 1`` with no window).  Forward: ``QK^T`` and ``PV``, each ``2 * d``
FLOP a (query, key) pair seen, for each of the ``n`` query heads.
Backward: ``dV = P^T dO``, ``dP = dO V^T``, ``dQ = dS K`` and ``dK = dS^T
Q``, four such products.  Recomputing ``QK^T`` in the backward pass is the
kernel's choice and is not counted, nor is a layer recomputed under a
checkpoint.  Bytes are each operand read once and each result written once,
K and V (and their gradients) at their own ``g`` heads: a kernel that
repeats them for their query heads moves more and reads lower.
"""


def pairs_seen(s, window=None):
    """(query, key) pairs one head of one sequence of ``s`` attends."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def forward_flops(b, n, s, d, window=None):
    return 2 * 2 * d * b * n * pairs_seen(s, window)


def backward_flops(b, n, s, d, window=None):
    return 4 * 2 * d * b * n * pairs_seen(s, window)


def forward_bytes(b, n, g, s, d, itemsize=2):
    """q read, out written (``n`` heads); k, v read (``g`` heads); the row
    log-sum-exp (float32) written."""
    return (2 * n + 2 * g) * b * s * d * itemsize + b * n * s * 4


def backward_bytes(b, n, g, s, d, itemsize=2):
    """q, out, d_out read and dq written (``n`` heads); k, v read and dk,
    dv written (``g`` heads); log-sum-exp read."""
    return (4 * n + 4 * g) * b * s * d * itemsize + b * n * s * 4


def train_step(sz, sliding: bool, batch, seq, itemsize=2):
    """FLOP and bytes of attention, forward and backward, in one training
    step of ``batch`` sequences of ``seq`` through every layer of one kind
    (``sliding`` or full) of the reference's sizes ``sz``."""
    n, g, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    layers = sum(1 for s in sz["sliding"] if s == sliding)
    window = sz["window"] if sliding else None
    flops = layers * (forward_flops(batch, n, seq, d, window)
                      + backward_flops(batch, n, seq, d, window))
    nbytes = layers * (forward_bytes(batch, n, g, seq, d, itemsize)
                       + backward_bytes(batch, n, g, seq, d, itemsize))
    return flops, nbytes

"""Operations the passes of a model whose layers are of more than one kind
require in one training step, from its sizes and the pairs its routers
counted.

``6 * N`` FLOP a token for the matrix multiplications of the forward and
backward passes, ``N`` the parameters a token's matmuls always meet
(attention, the dense layers, the routers, the shared experts, the head; the
token table is a gather); ``18 * hidden * f`` FLOP for each ``(token,
expert)`` pair the routers sent to a held expert, as counted; attention's
products by layer kind, a sliding layer's over ``sum_i min(i + 1, window)``
keys (``kernels/hybrid_attention``).  Recomputed operations are not
counted.
"""

from kernels import hybrid_attention


def always_met(sz) -> int:
    """Matmul parameters every token meets on this chip."""
    h, n, g, d = sz["hidden"], sz["heads"], sz["kv_heads"], sz["head_dim"]
    total = sz["vocab_padded"] * h
    for layer in range(sz["layers"]):
        total += h * d * (3 * n + 2 * g)
        if layer >= sz["dense_layers"]:
            total += h * sz["n_experts"] + sz["shared"] * 3 * h * \
                sz["expert_ffn"]
        else:
            total += 3 * h * sz["dense_ffn"]
    return total


def train_step_flops(sz, batch, seq, pairs):
    """FLOP one step of ``batch`` sequences of ``seq`` requires when its
    routers sent ``pairs`` pairs in all to held experts."""
    attention = sum(hybrid_attention.train_step(sz, sliding, batch, seq)[0]
                    for sliding in (True, False))
    return (6 * always_met(sz) * batch * seq
            + 18 * sz["hidden"] * sz["expert_ffn"] * pairs + attention)

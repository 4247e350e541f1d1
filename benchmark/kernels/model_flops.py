"""Operations a GPT's passes require, from its sizes.

Training: ``6 * N`` FLOP per token for the matrix multiplications of the
forward and backward passes (``N`` the parameters without the position
table, the token table counted once: it is the output head's matrix), plus
attention's products as ``kernels/flash_attention`` counts them (causal:
half of the square).  ``bench._lm_train_flops`` of the repo counts the full
square, ``12*L*h*B*S^2``; the causal half is what the model needs, so this
count is the smaller.  Recomputed operations are not counted.

Decoding: ``2 * N`` FLOP per delivered token.
"""

from kernels import flash_attention


def train_step_flops(reference, sz, batch, seq):
    n = reference.count_params(sz, positions=False)
    attention, _ = flash_attention.train_step(sz, batch, seq)
    return 6 * n * batch * seq + attention


def decode_token_flops(reference, sz):
    return 2 * reference.count_params(sz, positions=False)

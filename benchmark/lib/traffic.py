"""The one generator of serving traffic.  A mix is a file of parameters;
this reads it.

A closed loop of ``callers``: each sends its next request when its last one
finishes.  Request ``j`` of a caller has a prompt length and an answer
length drawn uniformly from the mix's ranges; the caller's first answer is
cut to a length drawn uniformly from ``first_answer_min`` to its own, so
that the callers are out of phase from the first tick.

The lengths come from the mix's own ``shape_seed``, not from ``--seed``:
every seed of a run meets the same set of callers' length sequences, dealt
to the callers in another order, with other token ids.  Else the number of
requests that finish in a window, and with it the share of ticks that carry
a prompt, would change with the seed and the work with it.
"""

import numpy as np


class ClosedLoop:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.callers = mix["callers"]
        rng = np.random.default_rng([seed, 0])
        self.deal = rng.permutation(self.callers)    # caller -> length row
        sampled = mix.get("sampled_callers", 0)
        self.sampled = set(rng.choice(self.callers, sampled,
                                      replace=False).tolist())

    def lengths(self, caller: int, j: int):
        """(prompt tokens, answer tokens) of a caller's request ``j``."""
        rng = np.random.default_rng(
            [self.mix["shape_seed"], int(self.deal[caller]), j])
        lo, hi = self.mix["prompt_tokens"]
        prompt = int(rng.integers(lo, hi + 1))
        lo, hi = self.mix["answer_tokens"]
        answer = int(rng.integers(lo, hi + 1))
        if j == 0:
            answer = int(rng.integers(self.mix["first_answer_min"],
                                      answer + 1))
        return prompt, answer

    def request(self, caller: int, j: int):
        """(prompt token ids, answer tokens, sampled or greedy)."""
        n_prompt, n_answer = self.lengths(caller, j)
        rng = np.random.default_rng([self.seed, 1, caller, j])
        prompt = rng.integers(0, self.vocab, n_prompt, dtype=np.int32)
        return prompt, n_answer, caller in self.sampled

"""Jit-stable sampling policies: temperature / top-k / top-p, per-request
seeds.

The continuous-batching contract extends to sampling: every request can
carry its own policy, but the decode step compiles ONCE — so the
policies are ``[max_batch]`` *data* arrays (temperature, k, p, seed,
step counter), never shapes or Python branches.  A slot's policy
changing between ticks (request churn) re-runs the same executable.

Determinism is load-bearing twice over:

- **greedy** (``temperature == 0``, the default) must be the exact
  fp32 ``argmax`` the fleet's failover replay and the smoke's
  token-identity checks rest on;
- **seeded sampling** keys each draw with
  ``fold_in(PRNGKey(seed), step)`` where ``step`` is the request's
  output-token index.  A preempted request replayed through prefill
  resumes at the same counter, so recompute-on-readmit (and the fleet's
  failover replay) reproduces the *same stochastic stream* — sampling
  does not break the bitwise-stitched-stream story, it joins it.

What a call pays (:func:`sample_tokens`): one ``argmax`` over the
batch, always.  The draw sits under one ``lax.cond`` on
``any(temperature > 0)``, so an all-greedy batch runs nothing else.
Where any row is sampled, every row goes through :func:`_sample_one`
— one sort of the row and elementwise passes, nothing gathered from or
scattered into a ``[vocab]`` row — and a ``where`` hands the greedy
rows their ``argmax`` back.

Filter order is the conventional temperature -> top-k -> top-p (p
renormalizes over the k survivors).  ``top_k <= 0`` and
``top_p >= 1`` disable their filters; ``top_k == 1`` degenerates to
greedy by construction (only the argmax survives).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["SamplingParams", "sample_tokens"]

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """One request's sampling policy (host-side; packed to device as
    ``[max_batch]`` data by the engine).

    ``temperature == 0`` is exact greedy argmax (the default — and what
    every token-identity contract in the serving stack assumes);
    ``top_k <= 0`` / ``top_p >= 1`` leave those filters off. ``seed``
    plus the request's output-token counter key every draw, so the same
    request replayed (preemption recompute, fleet failover) redraws the
    same stream.

    ``step_offset`` rebases that counter: the engine keys draw i of a
    request at ``fold_in(PRNGKey(seed), step_offset + i)``.  In-process
    it stays 0 — a preempted request keeps its ``output_tokens``, so the
    counter continues by itself.  Across the fleet wire a failover
    replay re-submits ``prompt + emitted`` as a *new* engine request
    whose counter restarts at 0; the router sets ``step_offset`` to the
    emitted count so the survivor redraws the continuation of the SAME
    stream (the stitched sampled stream is bitwise the uninterrupted
    one — pinned in ``tests/test_fleet.py``).

    ``adapter_id`` names the LoRA adapter the request decodes under
    (:mod:`.lora`): ``None`` — the default — gathers the permanent zero
    adapter and is bitwise the bare engine.  It rides the wire inside
    this dataclass, so both transports, failover replay and preemption
    readmit carry it for free; the engine resolves it to an arena slot
    at admission (unknown id -> typed REJECTED) and the slot index is
    per-tick ``[max_batch]`` data, never shape.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    step_offset: int = 0
    adapter_id: Optional[str] = None

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.step_offset < 0:
            raise ValueError(
                f"step_offset must be >= 0, got {self.step_offset}")


def _sample_one(logits, temperature, top_k, top_p, seed, step):
    """One slot's draw; vmapped over the batch.

    The row is ordered once (values descending, equal values by token
    id) and both filters are read off that order.  Top-k cuts at the
    order's entry ``k - 1``.  Top-p keeps the entries whose exclusive
    running probability is below ``p``: a prefix of the same order (what
    top-k cut is its tail), so the kept set is the entries at or before
    the prefix's last one, which every token id can tell by comparing
    its own (value, id) with that entry's.  The draw itself runs over
    the row in vocabulary order, where the noise is indexed by token id.
    """
    vocab = logits.shape[0]
    ids = jax.lax.iota(jnp.int32, vocab)
    x = logits / jnp.maximum(temperature, 1e-6)
    neg, order = jax.lax.sort((-x, ids), num_keys=1, is_stable=True)
    desc = -neg
    # top-k: threshold at the kth-largest logit (k <= 0 disables)
    kth = desc[jnp.clip(top_k - 1, 0, vocab - 1)]
    x, desc = (jnp.where((top_k > 0) & (v < kth), _NEG, v)
               for v in (x, desc))
    # top-p (nucleus): keep the smallest prefix of the sorted
    # distribution whose mass reaches p; the argmax always survives
    # (cumsum - own prob < p holds for the head token whenever p > 0).
    # The sorted probabilities are the row's softmax, entry for entry:
    # same maximum, same sum, taken over the row as it lies
    top = jnp.max(x)
    probs = jnp.exp(desc - top) / jnp.sum(jnp.exp(x - top))
    kept = jnp.sum((jnp.cumsum(probs) - probs) < top_p)
    last = jnp.maximum(kept - 1, 0)
    edge, edge_id = desc[last], order[last]
    keep = (kept > 0) & ((x > edge) | ((x == edge) & (ids <= edge_id)))
    x = jnp.where(keep, x, _NEG)
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    return jax.random.categorical(key, x).astype(jnp.int32)


def sample_tokens(logits, temperature, top_k, top_p, seeds, steps):
    """Sample one token per slot from ``logits [max_batch, vocab]``.

    All policy arguments are ``[max_batch]`` arrays (data, never
    shape); fp32 throughout.  Slots with ``temperature == 0`` return
    the exact fp32 argmax.  What a call pays is in the module
    docstring; the predicate of the one ``lax.cond`` is data, so there
    is still one compile.  The branch holds no collectives (the logits
    arrive tp-gathered), so the cond is APX102-clean by construction.
    """
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def drawn(_):
        sampled = jax.vmap(_sample_one)(
            logits, temperature.astype(jnp.float32),
            top_k.astype(jnp.int32), top_p.astype(jnp.float32),
            seeds.astype(jnp.uint32), steps.astype(jnp.int32))
        return jnp.where(temperature <= 0.0, greedy, sampled)

    return jax.lax.cond(jnp.any(temperature > 0.0), drawn,
                        lambda _: greedy, None)

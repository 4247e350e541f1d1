"""Rotary position embeddings (RoPE), TPU-native.

Parity-plus beyond the reference: apex's testing GPT uses learned absolute
positions only (``apex/transformer/testing/standalone_transformer_lm.py``
Embedding), while its production lineage (Megatron-LM
``rotary_pos_embedding``) moved to RoPE; this module brings the framework's
transformer stack to that modern baseline.  Selected via
``TransformerConfig(position_embedding_type="rope")``.

Design notes (TPU/XLA):

- The cos/sin tables are built inside the traced function from a
  ``positions`` vector — no host-side cache to invalidate, XLA constant-
  folds them for static shapes and fuses the rotation into the
  surrounding elementwise region of the QKV projection.
- Half-rotation ("NeoX"/Megatron) layout: the first ``rotary_dim``
  channels are rotated as two contiguous halves — contiguous lane slices,
  which vectorize on the VPU, unlike the interleaved even/odd ("GPT-J")
  layout which would gather alternating lanes.
- Context parallelism composes by construction: callers pass this rank's
  *global* ``positions`` (shard offset + local arange — see
  ``ParallelAttention``), and each rank rotates its local q/k shard
  before ring/all-to-all exchange, so rotated keys travel the ring
  already position-stamped.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp

__all__ = ["rotary_cos_sin", "apply_rotary", "apply_rotary_decode",
           "apply_rotary_packed", "apply_rotary_interleaved", "YarnScaling",
           "yarn_inv_freq", "yarn_mscale"]


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN's stretch of a rotary base trained at ``original_max_position``
    to ``factor`` times that context (arXiv:2309.00071, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` has it): channel pairs that turn more
    than ``beta_fast`` times over the original context keep their frequency,
    those that turn fewer than ``beta_slow`` times are slowed by ``factor``,
    the pairs between blend linearly.  ``mscale`` scales cos and sin against
    ``mscale_all_dim`` (their quotient; 1 where they are equal), and a model
    multiplies its softmax scale by ``yarn_mscale(factor, mscale_all_dim)``
    squared."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 for no stretch)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(rotary_dim: int, base: float, scaling: YarnScaling):
    """The ``rotary_dim / 2`` blended inverse frequencies (float32)."""
    def correction_dim(rotations):
        # the channel pair that turns ``rotations`` times over the
        # original context
        return (rotary_dim * math.log(scaling.original_max_position
                                      / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), rotary_dim - 1)
    extra = 1.0 / (base ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                            / rotary_dim))
    inter = extra / scaling.factor
    span = 0.001 if low == high else high - low
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / span, 0.0, 1.0)
    keep = 1.0 - ramp                   # 1: the trained frequency is kept
    return inter * (1.0 - keep) + extra * keep


def rotary_cos_sin(positions, rotary_dim: int, base: float = 10000.0,
                   dtype=jnp.float32, scaling: Optional[YarnScaling] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for :func:`apply_rotary`.

    ``positions`` ``[s]`` (ints; global token indices), ``rotary_dim`` the
    even number of leading head channels to rotate -> ``(cos, sin)`` each
    ``[s, rotary_dim/2]``.  Computed in fp32 regardless of ``dtype``
    (bf16 angles visibly wobble at long context), then cast.  ``scaling``
    (a :class:`YarnScaling`) blends the frequencies and scales the tables.
    """
    if rotary_dim % 2:
        raise ValueError(f"rotary_dim must be even, got {rotary_dim}")
    if scaling is not None:
        inv_freq = yarn_inv_freq(rotary_dim, base, scaling)
        gain = (yarn_mscale(scaling.factor, scaling.mscale)
                / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
        return ((jnp.cos(angles) * gain).astype(dtype),
                (jnp.sin(angles) * gain).astype(dtype))
    inv_freq = 1.0 / (
        base ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                 / rotary_dim))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def _rotate(x, cos, sin):
    """Half-rotation with pre-broadcast cos/sin (shaped to x's rank)."""
    half = cos.shape[-1]
    rotary_dim = 2 * half
    x1 = x[..., :half]
    x2 = x[..., half:rotary_dim]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if rotary_dim == x.shape[-1]:
        return rotated
    return jnp.concatenate([rotated, x[..., rotary_dim:]], axis=-1)


def apply_rotary(x, cos, sin):
    """Rotate the leading ``2 * cos.shape[-1]`` channels of ``x``
    ``[s, b, n, d]`` (Megatron's ``[sq, b, np, hn]`` layout); channels
    past ``rotary_dim`` pass through (``rotary_percent < 1``)."""
    # cos/sin [s, half]: broadcast over [b, n]
    return _rotate(x, cos[:, None, None, :], sin[:, None, None, :])


def apply_rotary_packed(x, cos, sin):
    """Chunked-prefill rotation: ``x [s, b, n, d]`` where every
    ``(position, slot)`` pair sits at its own sequence index —
    ``cos``/``sin`` ``[s, b, half]`` from
    ``rotary_cos_sin(positions.reshape(-1), ...)`` reshaped back.  The
    serving runtime's batched-chunk prefill form: each slot's chunk
    starts at that request's own absolute offset, so the tables vary
    along both the position and the batch dim and broadcast only over
    heads."""
    return _rotate(x, cos[:, :, None, :], sin[:, :, None, :])


def apply_rotary_interleaved(x, cos, sin):
    """Rotation of all of ``x [tokens, heads, d]`` in which channel ``2 t``
    pairs with ``2 t + 1`` (the layout DeepSeek's checkpoints keep), ``cos``
    / ``sin [tokens, d / 2]``.  The pairs are pulled apart first, so the
    result lies in half-rotation order (the ``d / 2`` first members, then
    the second ones): a fixed permutation of the channels that q and k
    share, which no score sees."""
    return _rotate(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1),
                   cos[:, None, :], sin[:, None, :])


def apply_rotary_decode(x, cos, sin):
    """Decode-step rotation: ``x [1, b, n, d]`` (one token per batch
    slot) with **per-slot** positions — ``cos``/``sin`` ``[b, half]``
    from ``rotary_cos_sin(positions[b], ...)``.  The serving runtime's
    form of the same half-rotation: in a continuously-batched decode
    step every slot sits at a different sequence position, so the
    tables broadcast over the head dim but vary along batch."""
    return _rotate(x, cos[None, :, None, :], sin[None, :, None, :])

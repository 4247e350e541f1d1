"""Flash attention — Pallas TPU kernels with a custom VJP.

Capability parity target: ``apex/contrib/fmha`` (fixed-shape fp16 fused MHA,
seqlens ≤512, varlen via cu_seqlens, dropout —
``apex/contrib/csrc/fmha/fmha_api.cpp``) and the fused softmax-attention
core of ``apex/contrib/multihead_attn`` — rebuilt as a *blockwise
online-softmax* kernel family with none of the shape limits (any seqlen,
any head dim that tiles to the MXU, fp32/bf16).

Design (the standard flash decomposition, mapped to TPU):

- forward: grid ``(batch*heads, q_blocks, k_blocks)`` with the k-block index
  innermost; the running row-max ``m``, row-sum ``l`` and output accumulator
  live in VMEM scratch that persists across the k sweep, so K/V *stream*
  through VMEM one block at a time (Pallas double-buffers the HBM→VMEM
  copies against the MXU work) and VMEM holds O(block) state regardless of
  sequence length — the softmax never materialises the ``[sq, sk]`` score
  matrix (the reason apex's fused softmax caps at 16384 keys disappears).
- saves ``(out, lse)`` only — the activation-memory profile of the fused
  kernels (``fmha`` saves the same) rather than O(s²) probabilities.
- backward: one kernel recomputes scores per (q-block, k-block) pair to form
  ``dq`` (k innermost, dq in scratch), a second forms ``dk/dv`` over the
  transposed blocking (q innermost), both seeded with
  ``delta = rowsum(do * o)`` computed in plain XLA.
- **causal block skipping**: fully-masked (q-block, k-block) pairs are
  skipped with ``pl.when`` (no MXU work) and their K/V block index maps are
  clamped to the last live block so Pallas elides the HBM→VMEM copy —
  the ~2× FLOP saving of a production causal kernel.
- **segment masking / varlen**: optional per-token integer segment ids
  (must be ≥ 0) mask attention across segment boundaries — the TPU-native
  form of fmha's ``cu_seqlens`` packed-varlen API (a packed batch is one
  row with increasing segment ids; padding = any position whose id differs).
  Non-multiple-of-block sequence lengths are handled by padding to the
  block grid with sentinel segment ids, so any length compiles.
- **attention dropout**: counter-based (seed, batch·head, row, col) hash →
  keep mask, regenerated bit-identically in the backward kernels, so no
  dropout mask is ever materialised in HBM.  Matches the reference's
  "dropout after softmax" semantics: the row normaliser ``l`` accumulates
  *undropped* probabilities.
- ``q_offset``/``kv_offset`` place a q/k shard at its global sequence
  position so causal masking stays correct when the sequence is sharded —
  the hook ring attention (context parallelism,
  :mod:`apex_tpu.transformer.context_parallel`) builds on.  The backward
  entry points (:func:`dq_chunk`, :func:`dkv_chunk`) are exposed for the
  same reason: ring backward re-drives them per visiting chunk with the
  *global* lse.
- fully-masked q rows (reachable with offset combinations or segment ids)
  produce **zero** output and ``lse = -1e30``: the running max is clamped
  before the exp so masked-out scores can never contribute unit mass
  (the ``exp(NEG_INF - NEG_INF) = 1`` failure mode).
- ``interpret=True`` is selected on the CPU backend
  (:func:`apex_tpu.utils.platform.pallas_interpret`) so the same code runs
  in the CPU test mesh.

Layouts: ``q, k, v: [batch, heads, seq, head_dim]`` (BHSD).  ``lse`` rides
as ``[b, h, s, 1]`` inside kernels (trailing singleton keeps the TPU
(sublane, lane) tiling rule satisfied for any block) and is squeezed at the
API boundary.  Segment ids ride as ``[b, s, 1]`` for the same reason.
"""

from __future__ import annotations

import functools
import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils import platform

__all__ = [
    "flash_attention",
    "flash_attention_with_lse",
    "dq_chunk",
    "dkv_chunk",
]


def _env_block(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        val = int(raw)
        if val <= 0:
            raise ValueError(f"must be positive, got {val}")
        return val
    except ValueError as e:
        warnings.warn(f"ignoring {name}={raw!r} ({e}); "
                      f"using default {default}")
        return default


def resolve_default_blocks(block_q=None, block_k=None):
    """Fill unset block sizes: the explicit argument, else
    ``APEX_TPU_FLASH_BLOCK_Q/K`` (the handle a block sweep on the chip has
    on a whole train step, ROADMAP S1), else 256/512."""
    if block_q is None:
        block_q = _env_block("APEX_TPU_FLASH_BLOCK_Q", 256)
    if block_k is None:
        block_k = _env_block("APEX_TPU_FLASH_BLOCK_K", 512)
    return block_q, block_k


NEG_INF = -1e30
_LANES = 128   # TPU lane count: minor-dim tile
_SUBLANES = 8  # fp32 sublane tile


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


def _flash_compiler_params():
    """All three kernels iterate grid (batch*heads, outer-block, inner-block)
    and accumulate scratch only over the *innermost* dim — dims 0/1 are
    independent, so tell Mosaic: it may split them across cores (megacore
    on v4/v5p) and reorder for pipelining; the innermost stays sequential
    (init-at-0 / finalize-at-last scratch carry)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _pick_blocks(sq, sk, block_q, block_k):
    """Block sizes + padded lengths.  Blocks shrink to the (tile-aligned)
    sequence length; sequences pad up to a whole number of blocks, so
    non-power-of-two lengths never degrade to ``block = s`` VMEM blowups."""
    bq = min(block_q, _round_up(sq, _SUBLANES))
    bk = min(block_k, _round_up(sk, _LANES))
    return bq, bk, _round_up(sq, bq), _round_up(sk, bk)


def _pad_dim2(x, target):
    s = x.shape[2]
    if s == target:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, target - s), (0, 0)))


def _prep_segments(seg_q, seg_k, b, sq, sk, sq_p, sk_p, need):
    """Pad/create ``[b, s, 1]`` int32 segment-id arrays.  Pad sentinels
    differ on the q (-1) and k (-2) side so padded q rows attend nothing
    and real rows never attend padded keys."""
    if not need:
        return None, None
    if seg_q is None:
        seg_q = jnp.zeros((b, sq), jnp.int32)
    if seg_k is None:
        seg_k = jnp.zeros((b, sk), jnp.int32)
    seg_q = jnp.pad(seg_q.astype(jnp.int32), ((0, 0), (0, sq_p - sq)),
                    constant_values=-1)
    seg_k = jnp.pad(seg_k.astype(jnp.int32), ((0, 0), (0, sk_p - sk)),
                    constant_values=-2)
    return seg_q[..., None], seg_k[..., None]


# ---------------------------------------------------------------------------
# dropout: counter-based keep mask, regenerated identically in fwd and bwd
# ---------------------------------------------------------------------------


def _mix32(x):
    """murmur3 finalizer — full-avalanche 32-bit mix."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _keep_mask(seed, bh, rows, cols, rate):
    """Boolean keep mask over global (row, col) coordinates.

    Pure uint32 arithmetic (no pltpu PRNG) so the identical mask is
    produced on TPU and in interpret mode, and the backward kernels can
    regenerate it from the same (seed, coords) regardless of grid order.
    """
    h = _mix32(seed.astype(jnp.uint32) ^ jnp.uint32(0x9E3779B9))
    h = _mix32(h + jnp.uint32(bh))
    h = _mix32(h + rows.astype(jnp.uint32))  # (bq, 1)
    h = _mix32(h + cols.astype(jnp.uint32))  # (bq, bk)
    thresh = jnp.uint32(min(int(rate * 4294967296.0), 4294967295))
    return h >= thresh


def _coords(iq, jk, bq, bk, q_offset, kv_offset):
    rows = (q_offset + iq * bq
            + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0))
    cols = (kv_offset + jk * bk
            + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1))
    return rows, cols


def _block_mask(iq, jk, bq, bk, causal, q_offset, kv_offset,
                seg_q, seg_k):
    """Combined causal+segment mask for one (q-block, k-block), or None."""
    mask = None
    if causal:
        rows, cols = _coords(iq, jk, bq, bk, q_offset, kv_offset)
        mask = rows >= cols
    if seg_q is not None:
        sm = seg_q[:, None] == seg_k[None, :]
        mask = sm if mask is None else mask & sm
    return mask


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale, causal, q_offset, kv_offset, has_segments,
                dropout_rate):
    i = 3
    q_ref, k_ref, v_ref = refs[:3]
    seg_q_ref = seg_k_ref = seed_ref = None
    if has_segments:
        seg_q_ref, seg_k_ref = refs[i], refs[i + 1]
        i += 2
    if dropout_rate > 0.0:
        seed_ref = refs[i]
        i += 1
    o_ref, lse_ref, m_sc, l_sc, acc_sc = refs[i:i + 5]

    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def _body():
        # Matmuls run in the INPUT dtype with fp32 accumulation: a
        # bf16xbf16->f32 MXU pass is ~2x the fp32 rate, and upcasting
        # the operands first forfeits that (r4 finding; the softmax/
        # rescale math stays fp32 below).  fp32 inputs are unaffected.
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        seg_q = seg_q_ref[0, :, 0] if has_segments else None
        seg_k = seg_k_ref[0, :, 0] if has_segments else None
        mask = _block_mask(iq, jk, bq, bk, causal, q_offset, kv_offset,
                           seg_q, seg_k)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m = m_sc[:, 0]
        l = l_sc[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        # Guard the all-masked row: with m_new == NEG_INF, exp(s - m_new)
        # would be exp(0) = 1 per masked entry (phantom mean(V) mass);
        # exp(s - 0) = exp(NEG_INF) = 0 is what we want.
        m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
        p = jnp.exp(s - m_safe[:, None])
        alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
        l_new = l * alpha + jnp.sum(p, axis=1)
        if dropout_rate > 0.0:
            rows, cols = _coords(iq, jk, bq, bk, q_offset, kv_offset)
            keep = _keep_mask(seed_ref[0], bh, rows, cols, dropout_rate)
            p_acc = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        else:
            p_acc = p
        # p quantized to V's dtype for the PV matmul (the fmha/flash
        # convention — the reference kernel holds P in fp16)
        acc_new = acc_sc[...] * alpha[:, None] + jax.lax.dot_general(
            p_acc.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_sc[...] = jnp.broadcast_to(m_new[:, None], m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new[:, None], l_sc.shape)
        acc_sc[...] = acc_new

    if causal:
        # Causal block skipping: a block whose max row < min col is fully
        # masked — no MXU work (its K/V copy is also elided via the index
        # map clamp in _fwd_call).
        run = (q_offset + (iq + 1) * bq - 1) >= (kv_offset + jk * bk)
        pl.when(run)(_body)
    else:
        _body()

    @pl.when(jk == num_kb - 1)
    def _finalize():
        l_fin = l_sc[:, 0]
        m_fin = m_sc[:, 0]
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0, 0] = (acc_sc[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.where(l_fin == 0.0, NEG_INF,
                                  m_fin + jnp.log(l_safe))[:, None]


# ---------------------------------------------------------------------------
# backward: dq (k innermost) and dk/dv (q innermost)
# ---------------------------------------------------------------------------


def _bwd_p(s, lse, mask):
    """exp(s - lse) with the fully-masked-row guard (lse == NEG_INF)."""
    lse_safe = jnp.where(lse <= NEG_INF * 0.5, 0.0, lse)
    p = jnp.exp(s - lse_safe[:, None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    return p


def _dq_kernel(*refs, scale, causal, q_offset, kv_offset, has_segments,
               dropout_rate):
    i = 6
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    seg_q_ref = seg_k_ref = seed_ref = None
    if has_segments:
        seg_q_ref, seg_k_ref = refs[i], refs[i + 1]
        i += 2
    if dropout_rate > 0.0:
        seed_ref = refs[i]
        i += 1
    dq_ref, dq_sc = refs[i:i + 2]

    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    num_kb = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def _body():
        # input-dtype matmuls, fp32 accumulate (see _fwd_kernel note)
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        seg_q = seg_q_ref[0, :, 0] if has_segments else None
        seg_k = seg_k_ref[0, :, 0] if has_segments else None
        mask = _block_mask(iq, jk, bq, bk, causal, q_offset, kv_offset,
                           seg_q, seg_k)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = _bwd_p(s, lse, mask)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if dropout_rate > 0.0:
            rows, cols = _coords(iq, jk, bq, bk, q_offset, kv_offset)
            keep = _keep_mask(seed_ref[0], bh, rows, cols, dropout_rate)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        ds = p * (dp - delta[:, None]) * scale
        dq_sc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        run = (q_offset + (iq + 1) * bq - 1) >= (kv_offset + jk * bk)
        pl.when(run)(_body)
    else:
        _body()

    @pl.when(jk == num_kb - 1)
    def _finalize():
        dq_ref[0, 0] = dq_sc[...].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, q_offset, kv_offset, has_segments,
                dropout_rate):
    i = 6
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = refs[:6]
    seg_q_ref = seg_k_ref = seed_ref = None
    if has_segments:
        seg_q_ref, seg_k_ref = refs[i], refs[i + 1]
        i += 2
    if dropout_rate > 0.0:
        seed_ref = refs[i]
        i += 1
    dk_ref, dv_ref, dk_sc, dv_sc = refs[i:i + 4]

    bk = k_ref.shape[2]
    bq = q_ref.shape[2]
    bh = pl.program_id(0)
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    num_qb = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def _body():
        # input-dtype matmuls, fp32 accumulate (see _fwd_kernel note)
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        seg_q = seg_q_ref[0, :, 0] if has_segments else None
        seg_k = seg_k_ref[0, :, 0] if has_segments else None
        mask = _block_mask(iq, jk, bq, bk, causal, q_offset, kv_offset,
                           seg_q, seg_k)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = _bwd_p(s, lse, mask)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if dropout_rate > 0.0:
            rows, cols = _coords(iq, jk, bq, bk, q_offset, kv_offset)
            keep = _keep_mask(seed_ref[0], bh, rows, cols, dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            p_drop = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            p_drop = p
        dv_sc[...] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale
        dk_sc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        run = (q_offset + (iq + 1) * bq - 1) >= (kv_offset + jk * bk)
        pl.when(run)(_body)
    else:
        _body()

    @pl.when(iq == num_qb - 1)
    def _finalize():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _causal_jmax(i, bq, bk, q_offset, kv_offset, num_kb):
    """Last k-block index with any live (unmasked) column for q-block i."""
    jmax = (q_offset + (i + 1) * bq - 1 - kv_offset) // bk
    return jnp.clip(jmax, 0, num_kb - 1)


def _causal_imin(j, bq, bk, q_offset, kv_offset, num_qb):
    """First q-block index with any live row for k-block j."""
    imin = -((-(kv_offset + j * bk - q_offset - bq + 1)) // bq)
    return jnp.clip(imin, 0, num_qb - 1)


def _specs_fwd(h, bq, bk, d, causal, q_offset, kv_offset, num_kb):
    """Block specs for the (bh, i, j) grid (k innermost).  Under causal the
    k/v (and seg_k) index maps clamp j into the live range so skipped
    blocks re-reference the previous block and Pallas elides the copy."""

    def q_idx(bh_, i, j):
        return (bh_ // h, bh_ % h, i, 0)

    def k_idx(bh_, i, j):
        if causal:
            j = jnp.minimum(j, _causal_jmax(i, bq, bk, q_offset, kv_offset,
                                            num_kb))
        return (bh_ // h, bh_ % h, j, 0)

    def segq_idx(bh_, i, j):
        return (bh_ // h, i, 0)

    def segk_idx(bh_, i, j):
        if causal:
            j = jnp.minimum(j, _causal_jmax(i, bq, bk, q_offset, kv_offset,
                                            num_kb))
        return (bh_ // h, j, 0)

    return {
        "q": pl.BlockSpec((1, 1, bq, d), q_idx),
        "k": pl.BlockSpec((1, 1, bk, d), k_idx),
        "q_lse": pl.BlockSpec((1, 1, bq, 1), q_idx),
        "seg_q": pl.BlockSpec((1, bq, 1), segq_idx),
        "seg_k": pl.BlockSpec((1, bk, 1), segk_idx),
        "seed": pl.BlockSpec(memory_space=pltpu.SMEM),
    }


def _specs_dkv(h, bq, bk, d, causal, q_offset, kv_offset, num_qb):
    """Block specs for the transposed (bh, j, i) grid (q innermost)."""

    def q_idx(bh_, j, i):
        if causal:
            i = jnp.maximum(i, _causal_imin(j, bq, bk, q_offset, kv_offset,
                                            num_qb))
        return (bh_ // h, bh_ % h, i, 0)

    def k_idx(bh_, j, i):
        return (bh_ // h, bh_ % h, j, 0)

    def segq_idx(bh_, j, i):
        if causal:
            i = jnp.maximum(i, _causal_imin(j, bq, bk, q_offset, kv_offset,
                                            num_qb))
        return (bh_ // h, i, 0)

    def segk_idx(bh_, j, i):
        return (bh_ // h, j, 0)

    return {
        "q": pl.BlockSpec((1, 1, bq, d), q_idx),
        "k": pl.BlockSpec((1, 1, bk, d), k_idx),
        "q_lse": pl.BlockSpec((1, 1, bq, 1), q_idx),
        "seg_q": pl.BlockSpec((1, bq, 1), segq_idx),
        "seg_k": pl.BlockSpec((1, bk, 1), segk_idx),
        "seed": pl.BlockSpec(memory_space=pltpu.SMEM),
    }


def _resolve(scale, d):
    return (1.0 / (d ** 0.5)) if scale is None else scale


def _seed_array(dropout_seed):
    if dropout_seed is None:
        # Reachable only via the chunk entry points / vjp residuals, whose
        # public callers have already validated (rate > 0) => seed given.
        raise ValueError(
            "dropout_rate > 0 requires an explicit dropout_seed (vary it "
            "per training step; a silent constant seed would drop the same "
            "attention entries forever)")
    return jnp.asarray(dropout_seed, jnp.int32).reshape((1,))


def _fwd_call(q, k, v, seg_q, seg_k, seed, causal, scale, block_q, block_k,
              q_offset, kv_offset, dropout_rate):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk, sq_p, sk_p = _pick_blocks(sq, sk, block_q, block_k)
    seg_q, seg_k = _prep_segments(
        seg_q, seg_k, b, sq, sk, sq_p, sk_p,
        need=(seg_q is not None or seg_k is not None
              or sq_p != sq or sk_p != sk))
    has_segments = seg_q is not None
    qp, kp, vp = _pad_dim2(q, sq_p), _pad_dim2(k, sk_p), _pad_dim2(v, sk_p)
    num_kb = sk_p // bk
    sp = _specs_fwd(h, bq, bk, d, causal, q_offset, kv_offset, num_kb)

    kernel = functools.partial(
        _fwd_kernel, scale=_resolve(scale, d), causal=causal,
        q_offset=q_offset, kv_offset=kv_offset, has_segments=has_segments,
        dropout_rate=dropout_rate,
    )
    in_specs = [sp["q"], sp["k"], sp["k"]]
    args = [qp, kp, vp]
    if has_segments:
        in_specs += [sp["seg_q"], sp["seg_k"]]
        args += [seg_q, seg_k]
    if dropout_rate > 0.0:
        in_specs += [sp["seed"]]
        args += [_seed_array(seed)]

    out, lse4 = pl.pallas_call(
        kernel,
        grid=(b * h, sq_p // bq, num_kb),
        in_specs=in_specs,
        out_specs=[sp["q"], sp["q_lse"]],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((bq, _LANES)),
            _scratch((bq, _LANES)),
            _scratch((bq, d)),
        ],
        compiler_params=_flash_compiler_params(),
        interpret=platform.pallas_interpret(),
    )(*args)
    return out[:, :, :sq], lse4[:, :, :sq, 0]


def dq_chunk(q, k, v, do, lse, delta, *, causal, scale=None,
             block_q=None, block_k=None,
             q_offset=0, kv_offset=0, segment_ids_q=None,
             segment_ids_kv=None, dropout_rate=0.0, dropout_seed=None):
    """dq contribution of one K/V chunk given the *global* ``lse``/``delta``.

    The flash-backward identity: each (q-block, k-block) pair's gradient
    depends on other blocks only through (lse, delta), so ring backward can
    re-drive this per visiting chunk.
    """
    block_q, block_k = resolve_default_blocks(block_q, block_k)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk, sq_p, sk_p = _pick_blocks(sq, sk, block_q, block_k)
    seg_q, seg_k = _prep_segments(
        segment_ids_q, segment_ids_kv, b, sq, sk, sq_p, sk_p,
        need=(segment_ids_q is not None or segment_ids_kv is not None
              or sq_p != sq or sk_p != sk))
    has_segments = seg_q is not None
    num_kb = sk_p // bk
    sp = _specs_fwd(h, bq, bk, d, causal, q_offset, kv_offset, num_kb)

    kernel = functools.partial(
        _dq_kernel, scale=_resolve(scale, d), causal=causal,
        q_offset=q_offset, kv_offset=kv_offset, has_segments=has_segments,
        dropout_rate=dropout_rate,
    )
    in_specs = [sp["q"], sp["k"], sp["k"], sp["q"], sp["q_lse"],
                sp["q_lse"]]
    args = [_pad_dim2(q, sq_p), _pad_dim2(k, sk_p), _pad_dim2(v, sk_p),
            _pad_dim2(do, sq_p),
            _pad_dim2(lse[..., None], sq_p),
            _pad_dim2(delta[..., None], sq_p)]
    if has_segments:
        in_specs += [sp["seg_q"], sp["seg_k"]]
        args += [seg_q, seg_k]
    if dropout_rate > 0.0:
        in_specs += [sp["seed"]]
        args += [_seed_array(dropout_seed)]

    dq = pl.pallas_call(
        kernel,
        grid=(b * h, sq_p // bq, num_kb),
        in_specs=in_specs,
        out_specs=sp["q"],
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[_scratch((bq, d))],
        compiler_params=_flash_compiler_params(),
        interpret=platform.pallas_interpret(),
    )(*args)
    return dq[:, :, :sq]


def dkv_chunk(q, k, v, do, lse, delta, *, causal, scale=None,
              block_q=None, block_k=None,
              q_offset=0, kv_offset=0, segment_ids_q=None,
              segment_ids_kv=None, dropout_rate=0.0, dropout_seed=None):
    """(dk, dv) of one K/V chunk given the global ``lse``/``delta``."""
    block_q, block_k = resolve_default_blocks(block_q, block_k)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk, sq_p, sk_p = _pick_blocks(sq, sk, block_q, block_k)
    seg_q, seg_k = _prep_segments(
        segment_ids_q, segment_ids_kv, b, sq, sk, sq_p, sk_p,
        need=(segment_ids_q is not None or segment_ids_kv is not None
              or sq_p != sq or sk_p != sk))
    has_segments = seg_q is not None
    num_qb = sq_p // bq
    sp = _specs_dkv(h, bq, bk, d, causal, q_offset, kv_offset, num_qb)

    kernel = functools.partial(
        _dkv_kernel, scale=_resolve(scale, d), causal=causal,
        q_offset=q_offset, kv_offset=kv_offset, has_segments=has_segments,
        dropout_rate=dropout_rate,
    )
    in_specs = [sp["q"], sp["k"], sp["k"], sp["q"], sp["q_lse"],
                sp["q_lse"]]
    args = [_pad_dim2(q, sq_p), _pad_dim2(k, sk_p), _pad_dim2(v, sk_p),
            _pad_dim2(do, sq_p),
            _pad_dim2(lse[..., None], sq_p),
            _pad_dim2(delta[..., None], sq_p)]
    if has_segments:
        in_specs += [sp["seg_q"], sp["seg_k"]]
        args += [seg_q, seg_k]
    if dropout_rate > 0.0:
        in_specs += [sp["seed"]]
        args += [_seed_array(dropout_seed)]

    dk, dv = pl.pallas_call(
        kernel,
        grid=(b * h, sk_p // bk, num_qb),
        in_specs=in_specs,
        out_specs=[sp["k"], sp["k"]],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk_p, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk_p, d), v.dtype),
        ],
        scratch_shapes=[_scratch((bk, d)), _scratch((bk, d))],
        compiler_params=_flash_compiler_params(),
        interpret=platform.pallas_interpret(),
    )(*args)
    return dk[:, :, :sk], dv[:, :, :sk]


# ---------------------------------------------------------------------------
# custom VJP + public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash_core(q, k, v, seg_q, seg_k, seed,
                causal, scale, block_q, block_k, q_offset, kv_offset,
                dropout_rate):
    return _fwd_call(q, k, v, seg_q, seg_k, seed, causal, scale, block_q,
                     block_k, q_offset, kv_offset, dropout_rate)


def _flash_vjp_fwd(q, k, v, seg_q, seg_k, seed, causal, scale, block_q,
                   block_k, q_offset, kv_offset, dropout_rate):
    out, lse = _fwd_call(q, k, v, seg_q, seg_k, seed, causal, scale,
                         block_q, block_k, q_offset, kv_offset,
                         dropout_rate)
    return (out, lse), (q, k, v, seg_q, seg_k, seed, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, q_offset, kv_offset,
                   dropout_rate, res, cts):
    q, k, v, seg_q, seg_k, seed, out, lse = res
    do, _ = cts
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    kw = dict(causal=causal, scale=scale, block_q=block_q, block_k=block_k,
              q_offset=q_offset, kv_offset=kv_offset,
              segment_ids_q=seg_q, segment_ids_kv=seg_k,
              dropout_rate=dropout_rate, dropout_seed=seed)
    dq = dq_chunk(q, k, v, do, lse, delta, **kw)
    dk, dv = dkv_chunk(q, k, v, do, lse, delta, **kw)
    return dq, dk, dv, None, None, None


_flash_core.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_with_lse(
    q, k, v,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    q_offset: int = 0,
    kv_offset: int = 0,
    *,
    segment_ids_q=None,
    segment_ids_kv=None,
    dropout_rate: float = 0.0,
    dropout_seed=None,
):
    """Attention returning ``(out, lse)``.

    ``segment_ids_q/kv`` (int ≥ 0, ``[b, s]``) mask attention across
    segment boundaries — packed-varlen (fmha cu_seqlens) and padding masks
    in one mechanism.  ``dropout_rate``/``dropout_seed`` apply attention
    dropout after softmax (seed may be a traced scalar; vary it per step).

    NB: the VJP propagates the cotangent of ``out`` only; ``lse`` is a
    by-product for sharded-softmax composition (ring attention defines its
    own VJP at the ring level for exactly that reason).
    """
    block_q, block_k = resolve_default_blocks(block_q, block_k)
    seed = _seed_array(dropout_seed) if dropout_rate > 0.0 else None
    return _flash_core(q, k, v, segment_ids_q, segment_ids_kv, seed,
                       causal, scale, block_q, block_k, q_offset, kv_offset,
                       float(dropout_rate))


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    *,
                    segment_ids_q=None,
                    segment_ids_kv=None,
                    dropout_rate: float = 0.0,
                    dropout_seed=None):
    """``softmax(q k^T * scale [+ masks]) v`` without materialising the
    score matrix.  ``q,k,v: [batch, heads, seq, head_dim]``."""
    out, _ = flash_attention_with_lse(
        q, k, v, causal, scale, block_q, block_k, 0, 0,
        segment_ids_q=segment_ids_q, segment_ids_kv=segment_ids_kv,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed)
    return out

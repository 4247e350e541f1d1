"""Flash attention — Pallas TPU kernels with a custom VJP.

Capability parity target: ``apex/contrib/fmha`` (fixed-shape fp16 fused MHA,
seqlens ≤512, varlen via cu_seqlens, dropout —
``apex/contrib/csrc/fmha/fmha_api.cpp``) and the fused softmax-attention
core of ``apex/contrib/multihead_attn`` — rebuilt as a *blockwise
online-softmax* kernel family with none of the shape limits (any seqlen,
any head dim that tiles to the MXU, fp32/bf16).

Design (the standard flash decomposition, mapped to TPU), with two tile
sizes, the tile a grid step *fetches* and the tile a loop trip *computes*:

- **fetched tile** (``block_q`` x ``block_k`` rows, the two knobs of
  :func:`resolve_default_blocks`): what a grid step has in VMEM.  Where a
  head's Q, K and V fit the byte cap (``_TILE_BYTES`` per operand: 2048
  positions at head 128 in bf16, 4096 at head 64) the tile is the head, the
  grid is ``(batch*heads, 1, 1)`` and no grid step is dead; longer
  sequences keep grid ``(batch*heads, q_blocks, k_blocks)`` (k innermost;
  q innermost for dk/dv) with the running row-max ``m``, row-sum ``l`` and
  accumulators in VMEM scratch across the inner sweep, and under causal
  the inner side's index maps clamp into the live range so Pallas elides
  the HBM→VMEM copy of a tile with nothing to visit.  The softmax never
  materialises the ``[sq, sk]`` score matrix in HBM (the reason apex's
  fused softmax caps at 16384 keys disappears).
- **computed tile** (up to 1024 x 1024 scores, by ``_SUB_BYTES``):
  inside a step, rolled ``lax.fori_loop``s over the computed tiles of the
  fetched one, their **trip counts bounded by causality** from the offsets
  and program ids, so a tile wholly above the diagonal is never visited
  and a tile wholly under it compares nothing.  A tile is straight-line
  code in strips of q lanes (512 forward, 256 backward); in a tile the
  diagonal crosses, a strip computes only the k rows at or under its
  diagonal block and compares positions on that block alone.  Scores are
  computed transposed (``[k, q]``), so the per-row statistics are
  lane-dense rows and ``lse``/``delta`` travel as rows (a ``[.., s, 1]``
  column costs 128 times its bytes in HBM).  The tile is this large
  because the MXU, not the VPU, bounds these kernels at head 64 (every
  product is half a pass wide) and only a large basic block keeps it fed:
  loops over 128 x 128 sub-tiles measured 3 times slower (PERF.md §6).
- the softmax scale is folded into q (k, for dk/dv) before the product
  **where that is exact**, a power of two such as 1/8 at head 64; else it
  multiplies the float32 scores; backward leaves it out of ``ds`` and
  applies it to ``dq``/``dk`` once at the end.
- saves ``(out, lse)`` only — the activation-memory profile of the fused
  kernels (``fmha`` saves the same) rather than O(s²) probabilities.
- backward: one kernel recomputes the scores of a q block against the k
  tiles to form ``dq``, a second recomputes them per k block against the q
  tiles to form ``dk/dv``, both seeded with ``delta = rowsum(do * o)``
  computed in plain XLA; backward masks once (``p``, after the exp).
- at trace time each call records the gauges ``flash/fetch_tile_rows``,
  ``flash/sub_tiles`` (128 x 128 sub-tiles computed per head),
  ``flash/sub_tiles_masked`` (those that take a compare) and
  ``flash/live_score_share`` on ``observability.default_registry()``.
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

Layouts: ``q, k, v: [batch, heads, seq, head_dim]`` (BHSD).  ``lse`` and
``delta`` ride as ``[b, h, s/sub_q, 1, sub_q]`` inside kernels (one
lane-dense row per computed q block) and are reshaped at the API boundary;
q segment ids likewise as ``[b, s/sub_q, 1, sub_q]``, k segment ids as a
``[b, s, 1]`` column (the two sides of a ``[k, q]`` score tile).
"""

from __future__ import annotations

import collections
import functools
import math
import os
import types
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.observability.metrics import default_registry
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
    """Fill unset sizes of the fetched tiles: the explicit argument, else
    ``APEX_TPU_FLASH_BLOCK_Q/K`` (the handle a block sweep on the chip has
    on a whole train step, ROADMAP S1), else 2048/2048: with the byte cap
    of ``_pick_blocks``, a head of up to 2048 positions in one grid step."""
    if block_q is None:
        block_q = _env_block("APEX_TPU_FLASH_BLOCK_Q", 2048)
    if block_k is None:
        block_k = _env_block("APEX_TPU_FLASH_BLOCK_K", 2048)
    return block_q, block_k


NEG_INF = -1e30
_SUB = 128                 # one lane tile: the grain of fetched tiles
_TILE_BYTES = 512 * 1024   # most one fetched operand tile may take of VMEM
_SUB_BYTES = 128 * 1024    # most the rows of it that one loop trip computes
_STRIP_FWD = 512           # q lanes of one strip of a computed tile: forward,
_STRIP_BWD = 256           # dq and dkv (PERF.md §6 has the sweep)


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


def _pick_blocks(sq, sk, d, itemsize, block_q, block_k):
    """``(bq, bk, sub_q, sub_k, sq_p, sk_p)``: the fetched tiles, the edges
    of the tile one loop trip computes (q rides the lanes of a score tile)
    and the padded lengths.

    A fetched tile is ``block_q``/``block_k`` rows in whole lane tiles,
    capped by ``_TILE_BYTES`` for this ``d`` and dtype and by the sequence:
    where a head fits, the tile is the head and that grid dimension is 1.
    The computed tile is the largest whole number of lane tiles that
    divides the fetched one within ``_SUB_BYTES`` (1024 rows at head 64 in
    bf16, 512 at head 128: with its temporaries that fits Mosaic's default
    VMEM scope).  Sequences pad up to a whole number of fetched tiles, so
    any length compiles."""
    row = d * itemsize

    def side(s, block):
        fetched = min(_round_up(block, _SUB), max(_SUB, _TILE_BYTES // row),
                      _round_up(s, _SUB))
        most = max(_SUB_BYTES // row // _SUB, 1)
        computed = _SUB * max(i for i in range(1, most + 1)
                              if fetched // _SUB % i == 0)
        return fetched, computed, _round_up(s, fetched)

    bq, sub_q, sq_p = side(sq, block_q)
    if sk <= _SUB:
        bk = sub_k = sk_p = _round_up(sk, 16)
    else:
        bk, sub_k, sk_p = side(sk, block_k)
    return bq, bk, sub_q, sub_k, sq_p, sk_p


def _pad_dim2(x, target):
    s = x.shape[2]
    if s == target:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, target - s), (0, 0)))


def _q_rows(x, sq_p, sub_q):
    """``[b, h, sq]`` per-row statistics -> ``[b, h, sq_p/sub_q, 1, sub_q]``:
    one lane-dense row per computed q block (a ``[.., sq, 1]`` column pads
    every value to a lane tile in HBM, 128 times its bytes)."""
    b, h, s = x.shape
    x = jnp.pad(x, ((0, 0), (0, 0), (0, sq_p - s)))
    return x.reshape(b, h, sq_p // sub_q, 1, sub_q)


def _prep_segments(seg_q, seg_k, b, sq, sk, sq_p, sk_p, sub_q, need):
    """Pad/create the int32 segment ids: q as ``[b, sq_p/sub_q, 1, sub_q]``
    rows, k as a ``[b, sk_p, 1]`` column (the two sides of a score sub-tile
    ``[k, q]``).  Pad sentinels differ on the q (-1) and k (-2) side so
    padded q rows attend nothing and real rows never attend padded keys."""
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
    return seg_q.reshape(b, sq_p // sub_q, 1, sub_q), seg_k[..., None]


def _record_tiling(sq, sk, bq, sub_q, sub_k, strip, sq_p, sk_p, causal,
                   window, q_offset, kv_offset, every_masked):
    """Trace-time gauges of the tiling one call runs (host only): rows of the
    fetched q tile, the 128 x 128 sub-tiles computed and those that take a
    compare, per head, and the scores that count over the scores computed
    (the kernels' own plans walked over the head, a window's tiles skipped
    and compared as the kernels skip and compare them)."""
    rows = np.arange(sq) + q_offset - kv_offset
    live = (int((np.clip(rows + 1, 0, sk)
                 - np.clip(rows + 1 - (window or sk + sq), 0, sk)).sum())
            if causal else sq * sk)
    plans = _plans(causal, window, q_offset, kv_offset, sub_q, sub_k, strip)
    blk = min(_SUB, sub_k)
    visited = masked = 0
    for r0 in range(q_offset, q_offset + sq_p, sub_q):
        for c0 in range(kv_offset, kv_offset + sk_p, sub_k):
            kind = _tile_kind(causal, window, r0, c0, sub_q, sub_k)
            if kind is None:
                continue
            for st in plans[kind]:
                n_rows = st.r1 - st.r0
                compared = ((n_rows - st.diag if st.diag is not None else 0)
                            + (st.edge if st.edge is not None else 0))
                if every_masked:
                    compared = n_rows
                visited += st.lanes // _SUB * (n_rows // blk)
                masked += st.lanes // _SUB * (min(compared, n_rows) // blk)
    reg = default_registry()
    reg.gauge("flash/fetch_tile_rows").set(bq)
    reg.gauge("flash/window").set(window or 0)
    reg.gauge("flash/sub_tiles").set(visited)
    reg.gauge("flash/sub_tiles_masked").set(masked)
    reg.gauge("flash/live_score_share").set(
        live / (visited * _SUB * blk) if visited else 0.0)


def _tile_kind(causal, window, r0, c0, sub_q, sub_k):
    """Which plan of :func:`_plans` the tile of q rows from ``r0`` and k
    columns from ``c0`` is computed by (0 free, 1 the diagonal crosses it,
    2 the window's edge does, 3 both), or None where it is not visited: the
    rule the kernels' loop ranges follow, in plain integers."""
    if not causal:
        return 0
    if c0 > r0 + sub_q - 1:
        return None
    diag = c0 + sub_k - 1 > r0
    if window is None:
        return 1 if diag else 0
    if c0 + sub_k - 1 <= r0 - window:
        return None
    edge = c0 <= r0 + sub_q - 1 - window
    return (3 if diag else 2) if edge else (1 if diag else 0)


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
    """Boolean keep mask over global (row, col) coordinates: q positions
    ``rows`` against k positions ``cols``, broadcast against each other
    (the kernels pass a ``[1, q]`` row of q positions and a ``[k, 1]``
    column of k positions; the bit of a (row, col) pair is the same
    whichever way the two are laid out).

    Pure uint32 arithmetic (no pltpu PRNG) so the identical mask is
    produced on TPU and in interpret mode, and the backward kernels can
    regenerate it from the same (seed, coords) regardless of grid order.
    """
    h = _mix32(seed.astype(jnp.uint32) ^ jnp.uint32(0x9E3779B9))
    h = _mix32(h + jnp.uint32(bh))
    h = _mix32(h + rows.astype(jnp.uint32))
    h = _mix32(h + cols.astype(jnp.uint32))
    thresh = jnp.uint32(min(int(rate * 4294967296.0), 4294967295))
    return h >= thresh


# ---------------------------------------------------------------------------
# the score tile, shared by the three kernels
# ---------------------------------------------------------------------------
#
# Every kernel computes scores transposed, ``s[k, q] = k . q^T``: q rides the
# lanes, so the per-row statistics (``m``, ``l``, ``lse``, ``delta``) are
# lane-dense rows that broadcast along sublanes for free, the row max and
# sum are elementwise over vregs, and ``dk``/``dv`` need no transposed
# operand.  One loop trip computes a *tile* of ``[sub_k, sub_q]`` scores as
# straight-line code: the MXU takes a row of an operand a cycle whatever
# the tile, and only a basic block of this size gives the scheduler enough
# independent pushes, pops and exps to keep it fed (a loop over single
# 128 x 128 sub-tiles runs one dependent chain at a time, PERF.md §6).
# A tile is computed in strips of ``_STRIP_FWD``/``_STRIP_BWD`` q lanes: a
# tile under the diagonal compares nothing; in a tile that the diagonal
# crosses corner to corner a strip computes the k rows at or under its
# diagonal block only and compares on that block only.  What is computed
# is counted in 128 x 128 sub-tiles (the gauges of ``_record_tiling``).


def _folds(scale):
    """Whether ``scale`` is a power of two, so that multiplying an operand
    by it before the product is exact in any float dtype."""
    return math.frexp(scale)[0] == 0.5


def _scores(k, q, scale):
    s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return s if _folds(scale) else s * scale


def _prescale(x, scale):
    return x * scale if _folds(scale) else x


def _q_pos(first, n):
    return first + jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)


def _k_pos(first, n):
    return first + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)


_Strip = collections.namedtuple("_Strip", "lane0 lanes r0 r1 diag edge")
_Strip.__doc__ = """One strip of a computed tile: its ``lanes`` q lanes from
``lane0``, the k rows ``[r0, r1)`` it computes, and among those rows (counted
from ``r0``) the row ``diag`` from which it compares positions against the
diagonal and the row ``edge`` up to which it compares them against the
window's edge (None: nowhere)."""


def _plans(causal, window, q_offset, kv_offset, sub_q, sub_k, strip):
    """How a tile is computed, by what crosses it: ``(free, diagonal,
    window's edge, both)``, each a list of :class:`_Strip` (None where no
    tile of that kind can occur).  Where every tile that the diagonal
    crosses has it corner to corner (square tiles on a grid the offsets do
    not shift) a strip stops at its diagonal block and compares there; else
    such a tile computes and compares everything.  Likewise the window's
    edge: a strip starts at its edge block (and is no wider than a backward
    strip, so that the forward skips what lies before the edge too).  A
    tile that both cross computes and compares everything."""
    w = math.gcd(strip, sub_q)
    lanes = range(0, sub_q, w)
    free = [_Strip(lane0, w, 0, sub_k, None, None) for lane0 in lanes]
    if not causal:
        return free, None, None, None
    square = sub_q == sub_k
    if square and (q_offset - kv_offset) % sub_q == 0:
        diag = [_Strip(lane0, w, 0, lane0 + w, lane0, None)
                for lane0 in lanes]
    else:
        diag = [_Strip(lane0, w, 0, sub_k, 0, None) for lane0 in lanes]
    if window is None:
        return free, diag, None, None
    if square and (q_offset - kv_offset - window) % sub_q == 0:
        we = math.gcd(w, _STRIP_BWD)
        edge = [_Strip(lane0, we, lane0, sub_k, None, we)
                for lane0 in range(0, sub_q, we)]
    else:
        edge = [_Strip(lane0, w, 0, sub_k, None, sub_k) for lane0 in lanes]
    both = [_Strip(lane0, w, 0, sub_k, 0, sub_k) for lane0 in lanes]
    return free, diag, edge, both


def _masked(x, fill, st, window, q_pos, k_pos, seg_q, seg_k):
    """``x`` (the ``[rows, lanes]`` scores of strip ``st``, ``k_pos`` the
    positions of its rows) with ``fill`` where a score does not count:
    other segments anywhere, q positions before k positions on the rows
    from ``st.diag`` on, and k positions before the window on the rows up
    to ``st.edge``."""
    if seg_q is not None:
        keep = seg_q == seg_k
        if st.diag is not None:
            keep &= q_pos >= k_pos
        if st.edge is not None:
            keep &= k_pos > q_pos - window
        return jnp.where(keep, x, fill)
    if st.edge is not None:
        n = st.edge
        keep = k_pos[:n] > q_pos - window
        if st.diag is not None:       # a tile both cross: every row, both
            keep &= q_pos >= k_pos
        top = jnp.where(keep, x[:n], fill)
        return top if n == x.shape[0] else jnp.concatenate([top, x[n:]],
                                                           axis=0)
    compare_from = st.diag
    if compare_from is None:
        return x
    low = jnp.where(q_pos >= k_pos[compare_from:], x[compare_from:], fill)
    if compare_from == 0:
        return low
    return jnp.concatenate([x[:compare_from], low], axis=0)


def _visit(tile, load, store, plans, ranges):
    """Run ``tile(index, carry, plan=...)`` over each plan's range of tiles
    (``ranges``: one ``(lo, hi)`` per plan of :func:`_plans`); tiles wholly
    above the diagonal or before the window are in no range.
    ``load(plan)`` reads the carry of a plan's strips from scratch and
    ``store(plan, carry)`` puts it back.  The loops stay rolled."""
    for plan, (lo, hi) in zip(plans, ranges):
        if plan is not None:
            store(plan, jax.lax.fori_loop(
                lo, hi, functools.partial(tile, plan=plan), load(plan)))


def _ceil_div(x, m):
    return (jnp.maximum(x, 0) + m - 1) // m


def _k_ranges(causal, window, row0, col0, sub_q, sub_k, n):
    """For the q block whose first global row is ``row0``: the ranges of the
    ``n`` k tiles from global column ``col0`` that it sees whole, that cross
    its diagonal, that the window's edge crosses and that both cross (the
    order of :func:`_plans`)."""
    if not causal:
        return (0, n), (n, n)
    free = jnp.minimum(jnp.maximum(row0 - col0 + 1, 0) // sub_k, n)
    live = jnp.minimum(
        (jnp.maximum(row0 + sub_q - col0, 0) + sub_k - 1) // sub_k, n)
    if window is None:
        return (0, free), (free, live)
    # tiles wholly before the window, and the first wholly inside it
    dead = jnp.minimum(jnp.maximum(row0 - window - col0 + 1, 0) // sub_k,
                       live)
    whole = jnp.minimum(
        _ceil_div(row0 + sub_q - window - col0, sub_k), live)
    return ((whole, free), (jnp.maximum(whole, free), live),
            (dead, jnp.minimum(whole, free)), (jnp.maximum(free, dead), whole))


def _q_ranges(causal, window, row0, col0, sub_q, sub_k, n):
    """For the k block whose first global column is ``col0``: the ranges of
    the ``n`` q tiles from global row ``row0`` that see it whole, that cross
    the diagonal over it, that see it across the window's edge and that do
    both (the order of :func:`_plans`)."""
    if not causal:
        return (0, n), (n, n)
    dead = jnp.minimum(jnp.maximum(col0 - row0, 0) // sub_q, n)
    free = jnp.minimum(
        (jnp.maximum(col0 + sub_k - 1 - row0, 0) + sub_q - 1) // sub_q, n)
    if window is None:
        return (free, n), (dead, free)
    # the first q tile that meets the window's edge, and the first past it
    whole = jnp.minimum(
        _ceil_div(col0 + window - sub_q + 1 - row0, sub_q), n)
    last = jnp.minimum(
        _ceil_div(col0 + sub_k - 1 + window - row0, sub_q), n)
    whole = jnp.minimum(whole, last)
    return ((free, whole), (dead, jnp.minimum(free, whole)),
            (jnp.maximum(free, whole), last),
            (jnp.maximum(dead, whole), jnp.minimum(free, last)))


def _unpack(refs, n_in, has_segments, dropout_rate):
    """Split a kernel's refs: the ``n_in`` dense inputs, the optional
    segment-id pair and dropout seed, and whatever follows."""
    i = n_in
    seg_q_ref = seg_k_ref = seed_ref = None
    if has_segments:
        seg_q_ref, seg_k_ref = refs[i], refs[i + 1]
        i += 2
    if dropout_rate > 0.0:
        seed_ref = refs[i]
        i += 1
    return refs[:n_in], seg_q_ref, seg_k_ref, seed_ref, refs[i:]


def _at(first, n):
    """``n`` rows from the traced ``first``, a multiple of ``n``."""
    return pl.ds(pl.multiple_of(first, n), n)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, scale, causal, window, q_offset, kv_offset,
                has_segments, dropout_rate, sub_q, sub_k, strip):
    (q_ref, k_ref, v_ref), seg_q_ref, seg_k_ref, seed_ref, rest = _unpack(
        refs, 3, has_segments, dropout_rate)
    o_ref, lse_ref, m_sc, l_sc, acc_sc = rest

    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    num_kb = pl.num_programs(2)
    col0 = kv_offset + jk * bk
    plans = _plans(causal, window, q_offset, kv_offset, sub_q, sub_k, strip)

    @pl.when(jk == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def q_block(qi, _):
        # Matmuls run in the INPUT dtype with fp32 accumulation: a
        # bf16xbf16->f32 MXU pass is ~2x the fp32 rate, and upcasting
        # the operands first forfeits that (r4 finding; the softmax/
        # rescale math stays fp32 below).  fp32 inputs are unaffected.
        q = _prescale(q_ref[0, 0, _at(qi * sub_q, sub_q), :], scale)
        row0 = q_offset + iq * bq + qi * sub_q

        def tile(j, carry, plan):
            at = _at(j * sub_k, sub_k)
            k = k_ref[0, 0, at, :]
            v = v_ref[0, 0, at, :]
            k_pos = _k_pos(col0 + j * sub_k, sub_k)
            seg_k = seg_k_ref[0, at, :] if has_segments else None
            out = []
            for st, (m, l, acc) in zip(plan, carry):
                lane0, lanes = st.lane0, st.lanes
                rows = slice(st.r0, st.r1)
                q_pos = _q_pos(row0 + lane0, lanes)
                s = _masked(
                    _scores(k[rows], q[lane0:lane0 + lanes], scale),
                    NEG_INF, st, window, q_pos, k_pos[rows],
                    (seg_q_ref[0, qi, :, lane0:lane0 + lanes]
                     if has_segments else None),
                    seg_k[rows] if has_segments else None)
                m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
                # Guard the all-masked row: with m_new == NEG_INF,
                # exp(s - m_new) would be exp(0) = 1 per masked entry
                # (phantom mean(V) mass); exp(s - 0) = exp(NEG_INF) = 0 is
                # what we want.
                m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
                p = jnp.exp(s - m_safe)
                alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
                l_new = l * alpha + jnp.sum(p, axis=0, keepdims=True)
                if dropout_rate > 0.0:
                    keep = _keep_mask(seed_ref[0], bh, q_pos, k_pos[rows],
                                      dropout_rate)
                    p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)),
                                  0.0)
                # p quantized to V's dtype for the PV matmul (the
                # fmha/flash convention — the reference kernel holds P in
                # fp16)
                acc_new = acc * alpha + jax.lax.dot_general(
                    v[rows], p.astype(v.dtype), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                out.append((m_new, l_new, acc_new))
            return tuple(out)

        def load(plan):
            return tuple(
                tuple(sc[qi, :, st.lane0:st.lane0 + st.lanes]
                      for sc in (m_sc, l_sc, acc_sc))
                for st in plan)

        def store(plan, carry):
            for st, strip in zip(plan, carry):
                for sc, x in zip((m_sc, l_sc, acc_sc), strip):
                    sc[qi, :, st.lane0:st.lane0 + st.lanes] = x

        _visit(tile, load, store, plans,
               _k_ranges(causal, window, row0, col0, sub_q, sub_k,
                         bk // sub_k))

    jax.lax.fori_loop(0, bq // sub_q, q_block, None)

    @pl.when(jk == num_kb - 1)
    def _finalize():
        def q_block_out(qi, _):
            l_fin = l_sc[qi]
            l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
            o_ref[0, 0, _at(qi * sub_q, sub_q), :] = (
                (acc_sc[qi] / l_safe).T.astype(o_ref.dtype))
            lse_ref[0, 0, qi] = jnp.where(l_fin == 0.0, NEG_INF,
                                          m_sc[qi] + jnp.log(l_safe))

        jax.lax.fori_loop(0, bq // sub_q, q_block_out, None)


# ---------------------------------------------------------------------------
# backward: dq (k innermost) and dk/dv (q innermost)
# ---------------------------------------------------------------------------


def _bwd_p(s, lse):
    """exp(s - lse) with the fully-masked-row guard (lse == NEG_INF).  The
    backward pass masks ``p``, once: an unmasked ``s`` may overflow the exp
    and the select drops it."""
    return jnp.exp(s - jnp.where(lse <= NEG_INF * 0.5, 0.0, lse))


def _dq_kernel(*refs, scale, causal, window, q_offset, kv_offset,
               has_segments, dropout_rate, sub_q, sub_k, strip):
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), seg_q_ref, seg_k_ref,
     seed_ref, rest) = _unpack(refs, 6, has_segments, dropout_rate)
    dq_ref, dq_sc = rest

    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    num_kb = pl.num_programs(2)
    col0 = kv_offset + jk * bk
    plans = _plans(causal, window, q_offset, kv_offset, sub_q, sub_k, strip)

    @pl.when(jk == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    def q_block(qi, _):
        # input-dtype matmuls, fp32 accumulate (see _fwd_kernel note)
        at_q = _at(qi * sub_q, sub_q)
        q = _prescale(q_ref[0, 0, at_q, :], scale)
        do = do_ref[0, 0, at_q, :]
        row0 = q_offset + iq * bq + qi * sub_q

        def tile(j, carry, plan):
            at = _at(j * sub_k, sub_k)
            k = k_ref[0, 0, at, :]
            v = v_ref[0, 0, at, :]
            k_pos = _k_pos(col0 + j * sub_k, sub_k)
            seg_k = seg_k_ref[0, at, :] if has_segments else None
            out = []
            for st, dq in zip(plan, carry):
                strip = slice(st.lane0, st.lane0 + st.lanes)
                rows = slice(st.r0, st.r1)
                q_pos = _q_pos(row0 + st.lane0, st.lanes)
                p = _masked(
                    _bwd_p(_scores(k[rows], q[strip], scale),
                           lse_ref[0, 0, qi, :, strip]),
                    0.0, st, window, q_pos, k_pos[rows],
                    seg_q_ref[0, qi, :, strip] if has_segments else None,
                    seg_k[rows] if has_segments else None)
                dp = jax.lax.dot_general(
                    v[rows], do[strip], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if dropout_rate > 0.0:
                    keep = _keep_mask(seed_ref[0], bh, q_pos, k_pos[rows],
                                      dropout_rate)
                    dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)),
                                   0.0)
                # the softmax scale waits for _finalize
                ds = p * (dp - delta_ref[0, 0, qi, :, strip])
                out.append(dq + jax.lax.dot_general(
                    k[rows], ds.astype(k.dtype), (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            return tuple(out)

        def load(plan):
            return tuple(dq_sc[qi, :, st.lane0:st.lane0 + st.lanes]
                         for st in plan)

        def store(plan, carry):
            for st, dq in zip(plan, carry):
                dq_sc[qi, :, st.lane0:st.lane0 + st.lanes] = dq

        _visit(tile, load, store, plans,
               _k_ranges(causal, window, row0, col0, sub_q, sub_k,
                         bk // sub_k))

    jax.lax.fori_loop(0, bq // sub_q, q_block, None)

    @pl.when(jk == num_kb - 1)
    def _finalize():
        def q_block_out(qi, _):
            dq_ref[0, 0, _at(qi * sub_q, sub_q), :] = (
                (dq_sc[qi] * scale).T.astype(dq_ref.dtype))

        jax.lax.fori_loop(0, bq // sub_q, q_block_out, None)


def _dkv_kernel(*refs, scale, causal, window, q_offset, kv_offset,
                has_segments, dropout_rate, sub_q, sub_k, strip):
    ((q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), seg_q_ref, seg_k_ref,
     seed_ref, rest) = _unpack(refs, 6, has_segments, dropout_rate)
    dk_ref, dv_ref, dk_sc, dv_sc = rest

    bk = k_ref.shape[2]
    bq = q_ref.shape[2]
    bh = pl.program_id(0)
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    num_qb = pl.num_programs(2)
    row0 = q_offset + iq * bq
    plans = _plans(causal, window, q_offset, kv_offset, sub_q, sub_k, strip)
    # dk and dv of a k block are carried in row blocks that every strip's
    # rows begin and end on, so that a strip of a crossed tile adds to the
    # rows it computed and to no others
    blk = math.gcd(*(r for plan in plans if plan for st in plan
                     for r in (st.r0, st.r1)))
    parts = [slice(r, r + blk) for r in range(0, sub_k, blk)]

    @pl.when(iq == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def k_block(kj, _):
        # input-dtype matmuls, fp32 accumulate (see _fwd_kernel note)
        at = _at(kj * sub_k, sub_k)
        k = _prescale(k_ref[0, 0, at, :], scale)
        v = v_ref[0, 0, at, :]
        col0 = kv_offset + jk * bk + kj * sub_k
        k_pos = _k_pos(col0, sub_k)
        seg_k = seg_k_ref[0, at, :] if has_segments else None

        def tile(i, carry, plan):
            dk, dv = (list(x) for x in carry)
            at_q = _at(i * sub_q, sub_q)
            q = q_ref[0, 0, at_q, :]
            do = do_ref[0, 0, at_q, :]
            for st in plan:
                strip = slice(st.lane0, st.lane0 + st.lanes)
                rows = slice(st.r0, st.r1)
                q_pos = _q_pos(row0 + i * sub_q + st.lane0, st.lanes)
                p = _masked(
                    _bwd_p(_scores(k[rows], q[strip], scale),
                           lse_ref[0, 0, i, :, strip]),
                    0.0, st, window, q_pos, k_pos[rows],
                    seg_q_ref[0, i, :, strip] if has_segments else None,
                    seg_k[rows] if has_segments else None)
                dp = jax.lax.dot_general(
                    v[rows], do[strip], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                if dropout_rate > 0.0:
                    keep = _keep_mask(seed_ref[0], bh, q_pos, k_pos[rows],
                                      dropout_rate)
                    inv = 1.0 / (1.0 - dropout_rate)
                    p_drop = jnp.where(keep, p * inv, 0.0)
                    dp = jnp.where(keep, dp * inv, 0.0)
                else:
                    p_drop = p
                dv_add = jnp.dot(p_drop.astype(do.dtype), do[strip],
                                 preferred_element_type=jnp.float32)
                # the softmax scale waits for _finalize
                ds = p * (dp - delta_ref[0, 0, i, :, strip])
                dk_add = jnp.dot(ds.astype(q.dtype), q[strip],
                                 preferred_element_type=jnp.float32)
                for r in range(st.r0 // blk, st.r1 // blk):
                    part = slice(parts[r].start - st.r0,
                                 parts[r].stop - st.r0)
                    dk[r] = dk[r] + dk_add[part]
                    dv[r] = dv[r] + dv_add[part]
            return tuple(dk), tuple(dv)

        def load(plan):
            return tuple(tuple(sc[_at(kj * sub_k + part.start, blk), :]
                               for part in parts) for sc in (dk_sc, dv_sc))

        def store(plan, carry):
            for sc, blocks in zip((dk_sc, dv_sc), carry):
                for part, x in zip(parts, blocks):
                    sc[_at(kj * sub_k + part.start, blk), :] = x

        _visit(tile, load, store, plans,
               _q_ranges(causal, window, row0, col0, sub_q, sub_k,
                         bq // sub_q))

    jax.lax.fori_loop(0, bk // sub_k, k_block, None)

    @pl.when(iq == num_qb - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_sc[...] * scale).astype(dk_ref.dtype)
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


def _window_jmin(i, bq, bk, window, q_offset, kv_offset, num_kb):
    """First k-block index with any column inside the window of q-block i."""
    jmin = (q_offset + i * bq - window + 1 - kv_offset) // bk
    return jnp.clip(jmin, 0, num_kb - 1)


def _window_imax(j, bq, bk, window, q_offset, kv_offset, num_qb):
    """Last q-block index with any row whose window reaches k-block j."""
    imax = (kv_offset + (j + 1) * bk + window - 2 - q_offset) // bq
    return jnp.clip(imax, 0, num_qb - 1)


def _specs(h, rep, bq, bk, d, sub_q, q_inner, causal, window, q_offset,
           kv_offset, n_inner):
    """Block specs of the fetched tiles for the ``(bh, i, j)`` grid of the
    forward and dq calls (k innermost) or, with ``q_inner``, the
    ``(bh, j, i)`` grid of the dkv call.  Under causal the index maps of
    the inner side clamp into the live range (from the window's far side
    too), so a fetched tile with nothing to visit re-references its
    neighbour and Pallas elides the copy (only where a head does not fit
    one tile: else ``n_inner`` is 1).  ``rep`` query heads read one K/V
    head (grouped-query attention: K and V are fetched, never repeated in
    HBM); ``"dk"`` is the dkv call's result, one per query head."""

    def ij(a, b_):
        i, j = (b_, a) if q_inner else (a, b_)
        if causal and q_inner:
            i = jnp.maximum(i, _causal_imin(j, bq, bk, q_offset, kv_offset,
                                            n_inner))
            if window is not None:
                i = jnp.minimum(i, _window_imax(j, bq, bk, window, q_offset,
                                                kv_offset, n_inner))
        elif causal:
            j = jnp.minimum(j, _causal_jmax(i, bq, bk, q_offset, kv_offset,
                                            n_inner))
            if window is not None:
                j = jnp.maximum(j, _window_jmin(i, bq, bk, window, q_offset,
                                                kv_offset, n_inner))
        return i, j

    def q_idx(bh_, a, b_):
        return (bh_ // h, bh_ % h, ij(a, b_)[0], 0)

    def k_idx(bh_, a, b_):
        return (bh_ // h, bh_ % h if rep == 1 else bh_ % h // rep,
                ij(a, b_)[1], 0)

    return {
        "q": pl.BlockSpec((1, 1, bq, d), q_idx),
        "k": pl.BlockSpec((1, 1, bk, d), k_idx),
        "dk": pl.BlockSpec(
            (1, 1, bk, d),
            lambda bh_, a, b_: (bh_ // h, bh_ % h, ij(a, b_)[1], 0)),
        "q_row": pl.BlockSpec(
            (1, 1, bq // sub_q, 1, sub_q),
            lambda bh_, a, b_: (bh_ // h, bh_ % h, ij(a, b_)[0], 0, 0)),
        "seg_q": pl.BlockSpec(
            (1, bq // sub_q, 1, sub_q),
            lambda bh_, a, b_: (bh_ // h, ij(a, b_)[0], 0, 0)),
        "seg_k": pl.BlockSpec(
            (1, bk, 1), lambda bh_, a, b_: (bh_ // h, ij(a, b_)[1], 0)),
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


def _plan(kernel, strip, q, k, seg_q, seg_k, seed, causal, window, scale,
          block_q, block_k, q_offset, kv_offset, dropout_rate, q_inner=False):
    """What the three calls share: tiles, padded segment ids, block specs,
    the kernel closed over its static arguments, the optional inputs with
    their specs, the tiling's gauges, and the ``pallas_call`` itself as
    ``plan.call(in_specs, out_specs, out_shape, scratch_shapes, *args)``."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if h % k.shape[1]:
        raise ValueError(f"K/V heads ({k.shape[1]}) must divide the query "
                         f"heads ({h})")
    if window is not None and (not causal or window < 1):
        raise ValueError("a window (position i sees keys i - window < j <= "
                         "i) needs causal=True and window >= 1")
    bq, bk, sub_q, sub_k, sq_p, sk_p = _pick_blocks(
        sq, sk, d, q.dtype.itemsize, block_q, block_k)
    seg_q, seg_k = _prep_segments(
        seg_q, seg_k, b, sq, sk, sq_p, sk_p, sub_q,
        need=(seg_q is not None or seg_k is not None
              or sq_p != sq or sk_p != sk))
    has_segments = seg_q is not None
    grid = ((b * h, sk_p // bk, sq_p // bq) if q_inner
            else (b * h, sq_p // bq, sk_p // bk))
    sp = _specs(h, h // k.shape[1], bq, bk, d, sub_q, q_inner, causal,
                window, q_offset, kv_offset, grid[2])
    kernel = functools.partial(
        kernel, scale=_resolve(scale, d), causal=causal, window=window,
        q_offset=q_offset, kv_offset=kv_offset, has_segments=has_segments,
        dropout_rate=dropout_rate, sub_q=sub_q, sub_k=sub_k, strip=strip)
    extra_specs, extra = [], []
    if has_segments:
        extra_specs += [sp["seg_q"], sp["seg_k"]]
        extra += [seg_q, seg_k]
    if dropout_rate > 0.0:
        extra_specs += [sp["seed"]]
        extra += [_seed_array(seed)]
    _record_tiling(sq, sk, bq, sub_q, sub_k, strip, sq_p, sk_p, causal,
                   window, q_offset, kv_offset,
                   has_segments or dropout_rate > 0.0)

    def call(in_specs, out_specs, out_shape, scratch_shapes, *args):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[sp[name] for name in in_specs] + extra_specs,
            out_specs=jax.tree.map(lambda name: sp[name], out_specs),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM(shape, jnp.float32)
                            for shape in scratch_shapes],
            compiler_params=_flash_compiler_params(),
            interpret=platform.pallas_interpret(),
        )(*args, *extra)

    return types.SimpleNamespace(call=call, bq=bq, bk=bk, sub_q=sub_q,
                                 sq_p=sq_p, sk_p=sk_p)


def _fwd_call(q, k, v, seg_q, seg_k, seed, causal, scale, block_q, block_k,
              q_offset, kv_offset, dropout_rate, window=None):
    b, h, sq, d = q.shape
    plan = _plan(_fwd_kernel, _STRIP_FWD, q, k, seg_q, seg_k, seed, causal,
                 window, scale, block_q, block_k, q_offset, kv_offset,
                 dropout_rate)
    sub_q, sq_p, sk_p = plan.sub_q, plan.sq_p, plan.sk_p
    nq = plan.bq // sub_q
    out, lse = plan.call(
        ["q", "k", "k"], ["q", "q_row"],
        [jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
         jax.ShapeDtypeStruct((b, h, sq_p // sub_q, 1, sub_q), jnp.float32)],
        [(nq, 1, sub_q), (nq, 1, sub_q), (nq, d, sub_q)],
        _pad_dim2(q, sq_p), _pad_dim2(k, sk_p), _pad_dim2(v, sk_p))
    return out[:, :, :sq], lse.reshape(b, h, sq_p)[:, :, :sq]


_BWD_IN = ["q", "k", "k", "q", "q_row", "q_row"]


def _bwd_args(plan, q, k, v, do, lse, delta):
    sub_q, sq_p, sk_p = plan.sub_q, plan.sq_p, plan.sk_p
    return [_pad_dim2(q, sq_p), _pad_dim2(k, sk_p), _pad_dim2(v, sk_p),
            _pad_dim2(do, sq_p), _q_rows(lse, sq_p, sub_q),
            _q_rows(delta, sq_p, sub_q)]


def dq_chunk(q, k, v, do, lse, delta, *, causal, scale=None,
             block_q=None, block_k=None,
             q_offset=0, kv_offset=0, segment_ids_q=None,
             segment_ids_kv=None, dropout_rate=0.0, dropout_seed=None,
             window=None):
    """dq contribution of one K/V chunk given the *global* ``lse``/``delta``.

    The flash-backward identity: each (q-block, k-block) pair's gradient
    depends on other blocks only through (lse, delta), so ring backward can
    re-drive this per visiting chunk.
    """
    block_q, block_k = resolve_default_blocks(block_q, block_k)
    b, h, sq, d = q.shape
    plan = _plan(_dq_kernel, _STRIP_BWD, q, k, segment_ids_q, segment_ids_kv,
                 dropout_seed, causal, window, scale, block_q, block_k,
                 q_offset, kv_offset, dropout_rate)
    dq = plan.call(
        _BWD_IN, "q", jax.ShapeDtypeStruct((b, h, plan.sq_p, d), q.dtype),
        [(plan.bq // plan.sub_q, d, plan.sub_q)],
        *_bwd_args(plan, q, k, v, do, lse, delta))
    return dq[:, :, :sq]


def dkv_chunk(q, k, v, do, lse, delta, *, causal, scale=None,
              block_q=None, block_k=None,
              q_offset=0, kv_offset=0, segment_ids_q=None,
              segment_ids_kv=None, dropout_rate=0.0, dropout_seed=None,
              window=None):
    """(dk, dv) of one K/V chunk given the global ``lse``/``delta``.  Under
    grouped-query attention the kernel writes one (dk, dv) per query head
    and the heads of a group are summed here, in float32."""
    block_q, block_k = resolve_default_blocks(block_q, block_k)
    b, h, _, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    plan = _plan(_dkv_kernel, _STRIP_BWD, q, k, segment_ids_q,
                 segment_ids_kv, dropout_seed, causal, window, scale, block_q,
                 block_k, q_offset, kv_offset, dropout_rate, q_inner=True)
    dk, dv = plan.call(
        _BWD_IN, ["dk", "dk"],
        [jax.ShapeDtypeStruct((b, h, plan.sk_p, d), k.dtype),
         jax.ShapeDtypeStruct((b, h, plan.sk_p, d), v.dtype)],
        [(plan.bk, d), (plan.bk, d)],
        *_bwd_args(plan, q, k, v, do, lse, delta))
    dk, dv = dk[:, :, :sk], dv[:, :, :sk]
    if hk != h:
        dk, dv = (x.reshape(b, hk, h // hk, sk, d).sum(
            axis=2, dtype=jnp.float32).astype(x.dtype) for x in (dk, dv))
    return dk, dv


# ---------------------------------------------------------------------------
# custom VJP + public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13))
def _flash_core(q, k, v, seg_q, seg_k, seed,
                causal, scale, block_q, block_k, q_offset, kv_offset,
                dropout_rate, window):
    return _fwd_call(q, k, v, seg_q, seg_k, seed, causal, scale, block_q,
                     block_k, q_offset, kv_offset, dropout_rate, window)


def _flash_vjp_fwd(q, k, v, seg_q, seg_k, seed, causal, scale, block_q,
                   block_k, q_offset, kv_offset, dropout_rate, window):
    out, lse = _fwd_call(q, k, v, seg_q, seg_k, seed, causal, scale,
                         block_q, block_k, q_offset, kv_offset,
                         dropout_rate, window)
    return (out, lse), (q, k, v, seg_q, seg_k, seed, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, q_offset, kv_offset,
                   dropout_rate, window, res, cts):
    q, k, v, seg_q, seg_k, seed, out, lse = res
    do, _ = cts
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    kw = dict(causal=causal, scale=scale, block_q=block_q, block_k=block_k,
              q_offset=q_offset, kv_offset=kv_offset,
              segment_ids_q=seg_q, segment_ids_kv=seg_k,
              dropout_rate=dropout_rate, dropout_seed=seed, window=window)
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
    window: Optional[int] = None,
):
    """Attention returning ``(out, lse)``.

    ``k`` and ``v`` may carry fewer heads than ``q`` (grouped-query
    attention: a divisor of ``q``'s; query head ``n`` reads K/V head ``n //
    (heads / kv_heads)``).  ``window`` (with ``causal``): position ``i``
    sees keys ``i - window < j <= i``, the token itself counts; tiles
    wholly before the window are not visited.

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
                       float(dropout_rate), window)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    *,
                    segment_ids_q=None,
                    segment_ids_kv=None,
                    dropout_rate: float = 0.0,
                    dropout_seed=None,
                    window: Optional[int] = None):
    """``softmax(q k^T * scale [+ masks]) v`` without materialising the
    score matrix.  ``q: [batch, heads, seq, head_dim]``, ``k, v: [batch,
    kv_heads, seq, head_dim]``; ``window`` as in
    :func:`flash_attention_with_lse`."""
    out, _ = flash_attention_with_lse(
        q, k, v, causal, scale, block_q, block_k, 0, 0,
        segment_ids_q=segment_ids_q, segment_ids_kv=segment_ids_kv,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed, window=window)
    return out

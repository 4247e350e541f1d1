"""Learned sparse attention over a latent cache (ISSUE 37; DeepSeek-V3.2's
DSA as GLM-5 serves it): an indexer scores every cached token, each query
keeps the ``k`` tokens of largest score, and the absorbed latent attention
reads those rows and no other.

Three steps, each in a scope of its own so that a device trace splits them:

- ``dsa_index`` — :func:`paged_index_scores`: the index queries ``q [b, Q,
  H, D]`` (``Q`` tokens a slot: 1 at decode, a chunk at prefill) against
  the index keys cached beside the latent rows (``[n_blocks, block,
  D]``, one key a token shared by every index head), ``I = sum_h w_h
  ReLU(q_h . k)`` in float32 over bfloat16 products, ``-inf`` past each
  query's causal horizon.  A Pallas kernel that copies each slot's live
  pages of keys (``D`` elements a token: 256 B at 128 channels in
  bfloat16) and writes one row of scores a query; its innermost scope
  names its Mosaic call, ``paged_index_decode`` for one query a slot and
  ``paged_index_prefill`` for a chunk, so that a trace tells them apart.
- ``dsa_select`` — :func:`select_topk`: the exact ``k`` largest of each
  row, ties to the lower position (the set ``lax.top_k`` gives), as
  positions in ascending order beside whether each is a live token.  No
  sort: the ``k``-th largest score is found by bisection on the
  order-preserving unsigned image of the float32 bits (32 counting passes
  over the row), the rows above it and the first ties are the set, and a
  two-level prefix count of sums and 0/1 matrix products, with no gather,
  turns the set into positions.
- ``sparse_latent`` — :func:`gather_rows` and
  :func:`sparse_latent_attention`: the selected rows gathered through the
  block table (``k`` rows of the latent arena, not the pages round them)
  and the absorbed attention over them, the softmax in float32.

Each has an unfused twin or is plain XLA, for the CPU tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.observability.spans import named_span
from apex_tpu.serving.paged_attention import (
    NEG_INF,
    _LANES,
    _mxu,
    _step_plan,
)
from apex_tpu.utils import platform

__all__ = ["gather_rows", "index_weights_scale", "paged_index_scores",
           "paged_index_scores_unfused", "select_topk", "select_topk_mask",
           "sparse_latent_attention"]

# the float32 score tile of one grid step of the index kernel, at most, and
# the most pages (of ``D`` lanes) a step copies
_SCORE_TILE_BYTES = 4 * 1024 * 1024
_MAX_INDEX_PAGES = 128
# lanes a position chunk of the selection's prefix count spans
_CHUNK = _LANES


def index_weights_scale(heads: int, dim: int) -> float:
    """``heads ** -0.5 * dim ** -0.5``: the indexer's head weights' scale
    (the source folds its ``softmax_scale`` into them)."""
    return heads ** -0.5 * dim ** -0.5


def _masked(scores, limits):
    cols = jnp.arange(scores.shape[-1])
    return jnp.where(cols < limits[..., None], scores, -jnp.inf)


def _index_pages(q_rows: int, block: int, max_blocks: int) -> int:
    """Pages a grid step of the index kernel copies: as many as keep its
    ``[q_rows, tile]`` float32 scores within ``_SCORE_TILE_BYTES``, at most
    ``_MAX_INDEX_PAGES``; a tile narrower than a slot's table is whole
    lane tiles wide."""
    pages = max(1, min(_MAX_INDEX_PAGES, max_blocks,
                       _SCORE_TILE_BYTES // (4 * q_rows * block)))
    if pages < max_blocks:
        per_tile = _LANES // block if _LANES % block == 0 else 1
        pages = max(per_tile, pages // per_tile * per_tile)
    return min(pages, max_blocks)


def _index_kernel(steps_ref, plan_ref, slot_ref, group_ref, len_ref, q_ref,
                  w_ref, k_hbm, o_ref, buf, sems, *, block_size: int,
                  pages: int, queries: int, heads: int, exact: bool):
    """One grid step = one tile of ``pages`` pages of one slot's index
    keys against all of the slot's ``queries x heads`` index queries."""
    step = pl.program_id(0)
    cur = step % 2

    def for_live_pages(s, into, act):
        length = len_ref[slot_ref[s]]
        n_live = jnp.clip(pl.cdiv(length, block_size) - group_ref[s] * pages,
                          0, pages)
        for p in range(pages):
            @pl.when(p < n_live)
            def _page(p=p):
                act(pltpu.make_async_copy(
                    k_hbm.at[plan_ref[s * pages + p]],
                    buf.at[into, pl.ds(p * block_size, block_size)],
                    sems.at[into]))

    @pl.when(step == 0)
    def _first():
        # rows no copy reaches are past the horizon and masked afterwards;
        # zeros keep them finite
        buf[...] = jnp.zeros_like(buf)
        for_live_pages(0, 0, lambda copy: copy.start())

    @pl.when(step + 1 < steps_ref[0])
    def _next():
        for_live_pages(step + 1, 1 - cur, lambda copy: copy.start())

    for_live_pages(step, cur, lambda copy: copy.wait())
    s = _mxu(q_ref[0], buf[cur], ((1,), (1,)), exact)   # [Q H, tile]
    r = jnp.maximum(s, 0.0) * w_ref[0]
    tile = r.shape[-1]
    o_ref[0] = jnp.sum(r.reshape(queries, heads, tile), axis=1)


def paged_index_scores(q, w, arena, block_tables, limits):
    """Index scores of ``q [b, Q, H, D]`` (weights ``w [b, Q, H]``
    float32) against the index keys ``arena [n_blocks, block, D]`` of each
    slot's table: ``[b, Q, max_blocks * block]`` float32, ``-inf`` at and
    past each query's horizon ``limits [b, Q]`` (0: a padding query).  The
    kernel reads a slot's pages up to its farthest horizon and no further;
    in a device trace the call is ``paged_index_decode`` (``Q`` 1) or
    ``paged_index_prefill``."""
    b, nq, h, d = q.shape
    bs = arena.shape[1]
    max_blocks = block_tables.shape[1]
    if arena.ndim != 3 or arena.shape[-1] != d:
        raise ValueError(f"an index arena {arena.shape} holds no keys of "
                         f"the queries' width {d}")
    limits = limits.astype(jnp.int32)
    lengths = jnp.max(limits, axis=1)
    pages = _index_pages(nq * h, bs, max_blocks)
    tile = pages * bs
    n_groups = pl.cdiv(max_blocks, pages)
    n_steps, plan, slot, group = _step_plan(block_tables, lengths, bs, pages)
    plan = jnp.clip(plan, 0, arena.shape[0] - 1)

    def row_idx(s, steps_ref, plan_ref, slot_ref, *refs):
        return (slot_ref[s], 0, 0)

    def out_idx(s, steps_ref, plan_ref, slot_ref, group_ref, *refs):
        return (slot_ref[s], 0, group_ref[s])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_steps,),
        in_specs=[pl.BlockSpec((1, nq * h, d), row_idx),
                  pl.BlockSpec((1, nq * h, 1), row_idx),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, nq, tile), out_idx),
        scratch_shapes=[pltpu.VMEM((2, tile, d), arena.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    kernel = functools.partial(
        _index_kernel, block_size=bs, pages=pages, queries=nq, heads=h,
        exact=arena.dtype != jnp.bfloat16)
    name = "paged_index_decode" if nq == 1 else "paged_index_prefill"
    with named_span(name):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, nq, n_groups * tile),
                                           jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                disable_bounds_checks=True,
                vmem_limit_bytes=48 << 20),
            interpret=platform.pallas_interpret(),
            name=name,
        )(n_steps.reshape(1), plan, slot, group, lengths,
          q.reshape(b, nq * h, d),
          w.astype(jnp.float32).reshape(b, nq * h, 1), arena)
    return _masked(out[..., :max_blocks * bs], limits)


def paged_index_scores_unfused(q, w, arena, block_tables, limits):
    """Plain-XLA twin of :func:`paged_index_scores`: gather each slot's
    whole table of keys."""
    b, max_blocks = block_tables.shape
    keys = arena[jnp.maximum(block_tables, 0)].reshape(
        b, max_blocks * arena.shape[1], -1)
    exact = arena.dtype != jnp.bfloat16
    s = jnp.einsum("bqhd,bsd->bqhs",
                   q.astype(jnp.float32) if exact else q,
                   keys.astype(jnp.float32) if exact else keys,
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST if exact else None)
    scores = jnp.einsum("bqhs,bqh->bqs", jnp.maximum(s, 0.0),
                        w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    return _masked(scores, limits.astype(jnp.int32))


def _ordered(scores):
    """The unsigned image of float32 ``scores`` whose order is theirs."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    sign = jnp.uint32(0x80000000)
    return jnp.where(bits >= sign, ~bits, bits | sign)


def _chosen(x, k: int):
    """The ``k`` largest of each row of ``x [rows, L]`` (``L > k``), ties to
    the lower position, as a mask ``[rows, L]``: the ``k``-th largest image
    found a bit at a time from the top (the largest ``t`` with at least
    ``k`` images at or above it), the rows above it and the first ties at
    it."""
    key = _ordered(x)
    rows = x.shape[0]

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, t)

    cut = jax.lax.fori_loop(0, 32, bit, jnp.zeros((rows,), jnp.uint32),
                            unroll=True)
    above = key > cut[:, None]
    tied = key == cut[:, None]
    room = k - jnp.sum(above, axis=1, keepdims=True)
    # ties at the cut past the room are rare: count them in order only
    # where a row has them
    return jax.lax.cond(
        jnp.any(jnp.sum(tied, axis=1, keepdims=True) > room),
        lambda: above | (tied & (jnp.cumsum(tied, axis=1) <= room)),
        lambda: above | tied)


def select_topk_mask(scores, k: int):
    """:func:`select_topk`'s set as a mask ``[..., L]`` int8, 1 at a chosen
    position whose score is finite: what a kernel that visits every key
    and masks the rest takes (the prefill's)."""
    lead, n = scores.shape[:-1], scores.shape[-1]
    x = scores.reshape(-1, n)
    live = jnp.isfinite(x)
    if n > k:
        live = live & _chosen(x, k)
    return live.astype(jnp.int8).reshape(scores.shape)


def select_topk(scores, k: int):
    """The ``k`` largest of each row of ``scores [..., L]`` (float32,
    ``-inf`` where nothing may be chosen), ties to the lower position:
    ``(positions [..., k] int32 ascending, live [..., k] bool)``, ``live``
    false where the position's score is ``-inf`` (a row with fewer than
    ``k`` finite scores keeps them all)."""
    lead, n = scores.shape[:-1], scores.shape[-1]
    x = scores.reshape(-1, n)
    rows = x.shape[0]
    if n <= k:
        pos = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (rows, k))
        live = (pos < n) & jnp.isfinite(
            jnp.pad(x, ((0, 0), (0, k - n)), constant_values=-jnp.inf))
        return pos.reshape(lead + (k,)), live.reshape(lead + (k,))
    chosen = _chosen(x, k)
    # 1 at a chosen position, 2 at a chosen one whose score is finite
    codes = chosen.astype(jnp.int8) + (chosen & jnp.isfinite(x)).astype(
        jnp.int8)
    pad = -n % _CHUNK
    codes = jnp.pad(codes, ((0, 0), (0, pad))).reshape(rows, -1, _CHUNK)
    counts = jnp.sum(codes > 0, axis=2, dtype=jnp.int32)     # [rows, C]
    # positions of the chosen, in order: the chunk of the j-th and the
    # chosen before that chunk, then its place among the chunk's chosen
    # lanes; as sums and products of 0/1 matrices (exact in bf16 with f32
    # sums), not gathers (an XLA gather of ``k`` scalars a row is slow on
    # the TPU) and not scans
    ends = jnp.cumsum(counts, axis=1)
    j = jnp.arange(k, dtype=jnp.int32)
    done = ends[:, None, :] <= j[None, :, None]                # [r, k, C]
    chunk = jnp.sum(done, axis=2, dtype=jnp.int32)             # [rows, k]
    before = jnp.sum(jnp.where(done, counts[:, None, :], 0), axis=2,
                     dtype=jnp.int32)
    pick = (chunk[..., None] == jnp.arange(codes.shape[1])).astype(
        jnp.bfloat16)                                          # [r, k, C]
    lanes = jnp.einsum("rkc,rcl->rkl", pick, codes.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)    # the chunk's
    upper = (jnp.arange(_CHUNK)[:, None] <= jnp.arange(_CHUNK)[None, :])
    seen = jnp.einsum("rkl,lm->rkm", (lanes > 0).astype(jnp.bfloat16),
                      upper.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    lane = jnp.sum(seen <= (j - before)[..., None].astype(jnp.float32),
                   axis=2, dtype=jnp.int32)
    pos = chunk * _CHUNK + lane
    # the code the one-hot product carried to the position: 2 where live
    live = jnp.sum(jnp.where(jnp.arange(_CHUNK) == lane[..., None], lanes,
                             0.0), axis=2) == 2.0
    return pos.reshape(lead + (k,)), live.reshape(lead + (k,))


def gather_rows(arena, block_tables, positions):
    """The rows at ``positions [b, ..., k]`` (logical, each slot's) of
    ``arena [n_blocks, block, d]`` through ``block_tables [b, max_blocks]``:
    ``[b, ..., k, d]``, each a row and not the page round it."""
    bs = arena.shape[1]
    b = block_tables.shape[0]
    flat = positions.reshape(b, -1)
    blocks = jnp.take_along_axis(
        block_tables, jnp.clip(flat // bs, 0, block_tables.shape[1] - 1),
        axis=1)
    phys = jnp.maximum(blocks, 0) * bs + flat % bs
    rows = arena.reshape(-1, arena.shape[-1])[phys]
    return rows.reshape(positions.shape + (arena.shape[-1],))


def sparse_latent_attention(q, rows, live, *, v_dim: int, scale: float):
    """Absorbed latent attention of ``q [..., heads, d]`` over its own
    selected rows ``rows [..., k, d]`` (``live [..., k]``: the rows that
    are tokens): scores against the whole row, values its leading
    ``v_dim`` lanes, the softmax in float32.  A bfloat16 cache is
    multiplied as it lies (bf16 operands, float32 accumulation, the
    probabilities rounded to bf16 for the values, as the paged kernels
    do); any other dtype in float32 at ``highest`` precision.  Returns
    ``[..., heads, v_dim]`` in ``q``'s dtype."""
    exact = rows.dtype != jnp.bfloat16
    prec = jax.lax.Precision.HIGHEST if exact else None
    cast = (lambda a: a.astype(jnp.float32)) if exact else (
        lambda a: a.astype(jnp.bfloat16))
    s = jnp.einsum("...hd,...kd->...hk", cast(q), cast(rows),
                   preferred_element_type=jnp.float32, precision=prec)
    mask = live[..., None, :]
    s = jnp.where(mask, s * scale, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - jnp.where(m <= NEG_INF * 0.5, 0.0, m)),
                  0.0)
    total = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("...hk,...kd->...hd", cast(p), cast(rows[..., :v_dim]),
                     preferred_element_type=jnp.float32, precision=prec)
    return (out / jnp.where(total == 0.0, 1.0, total)).astype(q.dtype)

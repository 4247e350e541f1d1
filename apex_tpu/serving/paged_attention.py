"""Fused paged-attention kernels (Pallas) + their unfused XLA twins.

The attention of the serving runtime over the paged KV cache
(:mod:`apex_tpu.serving.kv_cache`), in two shapes:

- **decode** (:func:`paged_attention_decode`) — one query token per
  active slot attends over that request's cached blocks; with a 4-D
  ``q`` the same entry point is the **speculative k+1 verify step**
  (ISSUE 13): ``k + 1`` query positions per slot — the slot's real
  last token plus k drafted continuations — attend with per-position
  causal ``limits`` riding the same scalar-prefetch block-table index
  maps, so draft and verify never bounce through HBM between proposal
  and check (the operation-fusion finding, PAPERS.md 2502.17728);
- **chunked prefill** (:func:`paged_prefill_attention`) — a
  ``[chunk]``-token slice of each slot's prompt attends over the
  request's *whole* context so far: the already-cached history blocks
  (earlier chunks, shared prefix-cache blocks) AND the chunk's own
  tokens, which the caller scatters into the arena *before* the call —
  so one block sweep with a per-token causal ``limit`` covers history
  and in-chunk causality with no second kernel and no softmax merge.

The k+1 verify and the chunked prefill are the *same* multi-query
block sweep (``_multi_query_attention``): a verify step is a
self-proposed chunk whose per-token limits happen to be consecutive.

The unfused XLA lowering of either needs a big gather (materialising
``[batch, max_seq, heads, head_dim]`` K/V copies in HBM) followed by an
unfused chain of elementwise/reduction ops — exactly the decode profile
the operation-fusion paper (PAPERS.md, arxiv 2502.17728) measures as
the dominant cost.  The fused kernels do **gather + online-softmax
attention in one pass**:

- the K/V **index maps read scalar-prefetched tables**
  (``pltpu.PrefetchScalarGridSpec``), so each HBM→VMEM copy pulls the
  right physical block directly.  No gathered K/V copy ever exists in
  HBM.
- **decode**: a grid step is a *group of P pages of one slot and all
  heads in one pass* (ISSUE 26), and the grid is as long as the lengths
  need (a dynamic bound): one step per ``P`` live pages of each slot, one
  for an empty slot's zero row, at most ``batch * ceil(max_blocks / P)``.
  Each arena is bound to the call ``P`` times, page ``p`` of the group
  each, through an index map into a **step plan** (``_step_plan``): a
  live page is its block-table entry, any other page names the block
  its operand needs at its next live step (or held at its last).  So a
  dead page is neither copied (the pipeline sees an unchanged block
  index) nor computed (``pl.when``), a live page's copy starts as soon
  as its buffer is free, groups past a slot's length have no step, and
  table columns past the live range never reach a DMA.  ``P`` is
  derived, not set (``_pages_per_step``): the VMEM bytes of a page
  against a fixed budget for the double-buffered K and V tiles, at most
  ``_MAX_PAGES`` operands per arena and at most ``max_blocks``.  (The
  pooled arena cannot stay in HBM with the kernel issuing the page
  copies itself: Mosaic refuses to slice a ``pl.ANY`` ref whose minor
  dimension, ``head_dim`` 64, is not a multiple of 128.  So it stays on
  page operands, and each page is computed as it lies.)
- **decode over a cache group's flat arenas** (ISSUE 32): the same step
  plan and grid, but a step's ``P`` pages are **one key tile**.  The
  arenas' rows are whole lane tiles (``kv_heads * d``: 768 / 512 and
  1536 / 1024 lanes at the widths that brought them), so they stay in
  HBM (``pl.ANY``) and the kernel copies each live page of the plan into
  its 16 rows of a ``[P * block, kv_heads * d]`` VMEM tile, double
  buffered: the next step's pages travel under this step's products, a
  dead page of a slot's last step is not copied and its rows are masked.
  Per step: one ``q k^T`` a KV head over the whole tile (scores ``P *
  block`` lanes wide), one mask, one update of the running max, sum and
  accumulator for all heads, one ``p v`` a KV head.  ``P`` is derived
  (``_flat_pages_per_step``): with a window the pages a window can touch
  (``ceil(window / block) + 1``: a slot is one step), without one
  ``_MAX_FLAT_PAGES``; at most what the same VMEM budget holds.
- **multi-query** (prefill, verify): grid ``(batch, max_blocks)``, one
  page a step; blocks past the request's length are skipped with
  ``pl.when`` and their index maps **clamp to the last live block**, so
  Pallas elides the HBM copy too — the paged analog of the flash
  kernel's causal block skipping (``ops/flash_attention.py``).
- running ``(m, l, acc)`` online-softmax state lives in VMEM scratch
  across the block sweep (the flash decomposition), so VMEM holds
  O(block) state however long the context.
- K/V are read in their **storage dtype** and upcast to fp32 inside
  the kernel (the fused-dequant convention — a bf16 cache moves half
  the HBM bytes and the dequant rides the same VMEM residency).  An
  **int8 cache** passes the per-vector scale arenas
  (``k_scales``/``v_scales``, one fp32 scale per cached row, stored
  block-major beside the block): the scale blocks ride the same
  index maps and the dequant is a VMEM multiply — quarter the HBM
  bytes of fp32, half of bf16, for one extra ``1/head_dim``-sized read.
- grouped-query attention: the arena stores the compact ``kv_heads``
  (= query groups); the kernel reads each KV head once *in VMEM* for
  its whole group of query heads — the GQA bandwidth saving is
  precisely the point of storing groups, not heads.
- shaped for the TPU compiler.  Decode with one query head per KV head
  is a matrix-vector product per head: all heads of a page at once on
  the VPU in exact fp32 (a multiply of the ``[block, heads, dim]`` page
  by ``q [heads, dim]`` and a reduction over ``dim``; ``p v`` is a
  multiply and a reduction over the page's rows), one update of the
  running max, sum and accumulator per page.  With GQA, and in the
  multi-query sweep, a static loop over KV heads of 2-D fp32
  contractions on the MXU (Mosaic refuses a ``dot_general`` with a
  batch dimension in the middle, which a per-head einsum over the
  arena block is); the multi-query sweep carries q/out head-major and
  ``limits`` as ``[b, T, 1]`` inside, behind unchanged signatures.

Layouts::

    decode   q:   [batch, n_heads, head_dim]      (one token per slot)
    verify   q:   [batch, k+1, n_heads, head_dim] (+ per-token limits)
    prefill  q:   [batch, chunk, n_heads, head_dim]
    k/v arena:    [n_blocks, block_size, kv_heads, head_dim]
    k/v scales:   [n_blocks, block_size, kv_heads]  fp32 (int8 cache)
    block_tables: [batch, max_blocks]  int32  (entries past the live
                  range are never used to fetch: decode takes them for
                  nothing at all, the multi-query sweep clamps them)
    lengths:      [batch] int32  (tokens in cache; 0 = inactive slot)
    limits:       [batch, chunk] int32 (prefill: each token attends
                  cache positions < limit; 0 = padding token)
    out:          same leading shape as q  (zeros for length/limit 0)

``interpret=True`` is selected on the CPU backend
(:func:`apex_tpu.utils.platform.pallas_interpret`) so the same code runs
on the CPU test mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.observability.metrics import default_registry
from apex_tpu.observability.spans import named_span
from apex_tpu.utils import platform

__all__ = [
    "paged_attention_decode",
    "paged_attention_decode_unfused",
    "paged_prefill_attention",
    "paged_prefill_attention_unfused",
    "paged_decode_latent",
    "paged_decode_latent_unfused",
    "paged_prefill_latent",
    "paged_prefill_latent_unfused",
]

NEG_INF = -1e30
_LANES = 128


def _resolve(scale: Optional[float], d: int) -> float:
    return (1.0 / (d ** 0.5)) if scale is None else scale


def _head_rows(ref, scale_ref, h: int):
    """KV head ``h`` of one block: storage dtype -> fp32 in VMEM
    (``[bs, d]``); an int8 cache multiplies its per-row scales."""
    x = ref[0, :, h, :].astype(jnp.float32)
    if scale_ref is not None:
        x = x * scale_ref[0, :, h:h + 1]
    return x


def _dot(a, b, contract):
    """2-D fp32 matmul on the MXU.  Mosaic only takes a ``dot_general``
    whose batch dimensions lead, so the kernels loop over heads with
    plain 2-D contractions; ``HIGHEST`` pins the fp32 contraction the
    module promises (and that interpret mode on the CPU computes)
    instead of leaving the precision to the compiler's default."""
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _online_softmax(s, m_prev, l_prev):
    """One block of the flash recurrence.  ``s [rows, bs]`` are the
    masked scores, ``m_prev``/``l_prev [rows, 1]`` the running max and
    normaliser.  Returns ``(p, alpha, m_new, l_new)``."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # all-masked-row guard (flash convention): exp against a NEG_INF
    # max must yield 0 mass, not exp(0)=1 per masked entry
    m_safe = jnp.where(m_new <= NEG_INF * 0.5, 0.0, m_new)
    p = jnp.exp(s - m_safe)
    alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    return p, alpha, m_new, l_new


# VMEM the decode kernels give to the double-buffered K and V pages of one
# grid step (scale pages included), and the most pages the pooled arena's
# kernel binds: each is one operand of the call and one unrolled page of
# the kernel body.  On a v5e the pipeline's bookkeeping costs 0.04 us per
# operand per step, so a partly filled group costs with its width: 8 pages
# measured 4-6% faster than 16 at gpt2-medium's shapes, and no slower than
# 4 (PERF.md §6).  The cache groups' kernel copies its pages itself and
# has its own cap (``_MAX_FLAT_PAGES``, below).
_KV_TILE_BYTES = 4 * 1024 * 1024
_MAX_PAGES = 8


def _pages_per_step(page_bytes: int, max_blocks: int) -> int:
    """How many pages one grid step of the decode kernel handles (``P``):
    as many as fit the VMEM budget twice over for K and for V, at least
    one, at most ``_MAX_PAGES`` and a slot's whole table.  ``page_bytes``
    is what one page (with its scale page, for an int8 cache) occupies in
    VMEM."""
    return max(1, min(_KV_TILE_BYTES // (4 * page_bytes), _MAX_PAGES,
                      max_blocks))


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM array: the last two dimensions are padded to the
    dtype's ``(sublanes, 128)`` tile (8 rows of 32 bits, packed rows for
    narrower types)."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * (4 // itemsize)
    *lead, rows, cols = shape
    n = -(-rows // sublanes) * sublanes * -(-cols // _LANES) * _LANES
    for dim in lead:
        n *= dim
    return n * itemsize


def _step_plan(block_tables, lengths, block_size: int, pages: int,
               first_page=None):
    """The decode kernel's sweep, from the table and the lengths: which
    ``(slot, group)`` each grid step is, how many steps there are, and
    which arena block each of the ``pages`` page operands holds at each.

    A slot takes one step per ``pages`` live pages (an empty slot one, to
    write its zero row), so the grid has no step for a group past a
    slot's length.  In ``plan``, flat ``[step * pages + page]``, a live
    page (``group * pages + page`` below the slot's page count) is its
    table entry.  Any other page names the block the operand needs at its
    *next* live step (the pipeline copies a block when its index changes,
    so that copy starts as early as the buffer is free, under the compute
    of the steps between), or, after its last, the block it held: table
    columns past the live range never reach a DMA, and a dead page costs
    no copy.

    ``first_page [b]`` (a window): the sweep of a slot starts at that page
    and the pages before it are dropped from the plan like those past the
    length, so a window layer's steps follow the window, not the history."""
    b, max_blocks = block_tables.shape
    n_groups = pl.cdiv(max_blocks, pages)
    steps = jnp.arange(b * n_groups)
    n_live = -(-lengths // block_size)                  # pages of a slot
    if first_page is None:
        groups = jnp.maximum(-(-n_live // pages), 1)    # steps of a slot
    else:
        first_page = jnp.minimum(first_page, n_live)
        groups = jnp.maximum(-(-(n_live - first_page) // pages), 1)
    ends = jnp.cumsum(groups)
    slot = jnp.minimum(
        jnp.sum(steps[:, None] >= ends[None, :], axis=1), b - 1)
    group = steps - (ends - groups)[slot]
    cols = group[:, None] * pages + jnp.arange(pages)[None, :]
    if first_page is not None:
        cols = cols + first_page[slot][:, None]
    live = (cols < n_live[slot][:, None]) & (steps < ends[-1])[:, None]
    own = block_tables[slot[:, None], jnp.minimum(cols, max_blocks - 1)]
    at = steps[:, None]
    after = jax.lax.cummin(jnp.where(live, at, steps.size), axis=0,
                           reverse=True)
    before = jax.lax.cummax(jnp.where(live, at, -1), axis=0)
    src = jnp.where(after < steps.size, after, before)
    held = jnp.take_along_axis(own, jnp.maximum(src, 0), axis=0)
    plan = jnp.where(src >= 0, held, 0).reshape(-1)
    return tuple(x.astype(jnp.int32) for x in (ends[-1], plan, slot, group))


def _decode_kernel(plan_ref, slot_ref, group_ref, len_ref, q_ref, *rest,
                   scale: float, block_size: int, pages: int, hpg: int,
                   has_scales: bool):
    """One grid step = one group of ``pages`` pages of one slot, all heads.
    ``rest``: the K arena bound ``pages`` times (page ``p`` of the group
    each), the V arena likewise, the scale arenas likewise for an int8
    cache, then the output and the softmax state."""
    del plan_ref                    # read by the index maps only
    k_refs, v_refs, ks_refs, vs_refs = (
        rest[a * pages:(a + 1) * pages] for a in range(4))
    if not has_scales:
        ks_refs = vs_refs = (None,) * pages
    o_ref, m_sc, l_sc, acc_sc = rest[-4:]
    step = pl.program_id(0)
    j = group_ref[step]
    length = len_ref[slot_ref[step]]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def all_heads(k_ref, v_ref, ks_ref, vs_ref, first):
        """``hpg == 1``: the scores are a matrix-vector product per head.
        All heads of the page at once on the VPU, in exact fp32: heads on
        sublanes, head_dim on lanes."""
        q = q_ref[0].astype(jnp.float32) * scale                 # [g, d]
        k = k_ref[0].astype(jnp.float32)                         # [bs, g, d]
        v = v_ref[0].astype(jnp.float32)
        if has_scales:
            k = k * ks_ref[0][:, :, None]
            v = v * vs_ref[0][:, :, None]
        s = jnp.sum(k * q[None], axis=2, keepdims=True)          # [bs, g, 1]
        rows = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(rows < length, s, NEG_INF)
        # a live page holds a live row: m_new is finite, and a row past
        # the length weighs exp(NEG_INF - m_new), an exact 0
        m_prev = m_sc[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))          # [g, 1]
        p = jnp.exp(s - m_new[None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_sc[:, :1] * alpha + jnp.sum(p, axis=0)
        acc_sc[...] = acc_sc[...] * alpha + jnp.sum(p * v, axis=0)
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    def per_kv_head(k_ref, v_ref, ks_ref, vs_ref, first):
        """GQA: an MXU contraction per KV head with its ``hpg`` query rows;
        the KV head is read once from VMEM for its whole group."""
        cols = first + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        live = cols < length
        for h in range(k_ref.shape[2]):
            rows = slice(h * hpg, (h + 1) * hpg)
            q = q_ref[0, rows, :].astype(jnp.float32)            # [hpg, d]
            k = _head_rows(k_ref, ks_ref, h)                     # [bs, d]
            v = _head_rows(v_ref, vs_ref, h)
            s = _dot(q, k, ((1,), (1,))) * scale                 # [hpg, bs]
            s = jnp.where(live, s, NEG_INF)
            p, alpha, m_new, l_new = _online_softmax(
                s, m_sc[rows, :1], l_sc[rows, :1])
            acc_sc[rows, :] = acc_sc[rows, :] * alpha + _dot(
                p, v, ((1,), (0,)))
            m_sc[rows, :] = jnp.broadcast_to(m_new, (hpg, _LANES))
            l_sc[rows, :] = jnp.broadcast_to(l_new, (hpg, _LANES))

    page_step = all_heads if hpg == 1 else per_kv_head
    for p in range(pages):
        first = (j * pages + p) * block_size

        # pages past the slot's length were not copied and are not computed
        @pl.when(first < length)
        def _page(p=p, first=first):
            page_step(k_refs[p], v_refs[p], ks_refs[p], vs_refs[p], first)

    @pl.when((j + 1) * pages * block_size >= length)    # the slot's last
    def _finalize():
        l_fin = l_sc[:, :1]
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)


def _check_arena(q_d, k_arena, n, g, k_scales, v_scales):
    if k_arena.shape[-1] != q_d:
        raise ValueError(
            f"head_dim mismatch: q {q_d}, arena {k_arena.shape[-1]}")
    if n % g:
        raise ValueError(f"n_heads ({n}) not a multiple of kv_heads ({g})")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    if k_scales is not None and k_scales.shape != k_arena.shape[:-1]:
        raise ValueError(
            f"scale arena shape {k_scales.shape} != arena rows "
            f"{k_arena.shape[:-1]}")


def paged_attention_decode(q, k_arena, v_arena, block_tables, lengths, *,
                           limits=None, k_scales=None, v_scales=None,
                           block_size: Optional[int] = None,
                           scale: Optional[float] = None,
                           kv_heads: Optional[int] = None,
                           window: Optional[int] = None, sinks=None):
    """One fused gather+dequant+attention pass over the paged cache.

    A 3-D ``k_arena`` is a cache group's flat arena (``[n_blocks, block,
    kv_heads * d_k]`` beside ``[..., kv_heads * d_v]``, ``kv_heads`` given):
    ``d_k`` may differ from ``d_v``, ``window`` drops the pages wholly
    behind it from the step plan and masks inside the edge pages, ``sinks
    [n_heads]`` joins the softmax as one more logit with no value row.  In a
    device trace the call is ``paged_decode_window`` where it has a window
    and ``paged_decode_full`` where not; the pooled arena's is
    ``paged_decode``.

    See the module docstring for layouts.  ``block_tables`` columns past
    a slot's live pages are never fetched, so they may hold any value
    (the scheduler leaves them 0); a slot with ``lengths == 0`` produces
    a zero output row.  ``k_scales``/``v_scales`` (int8 cache)
    are the per-row fp32 scale arenas.

    **Speculative k+1 verify** (ISSUE 13): with ``q`` of shape
    ``[batch, k+1, n, d]`` and per-position ``limits [batch, k+1]``
    (token t attends cache positions ``< limits[:, t]``; 0 = padding —
    a slot drafting fewer than k tokens, or none), the call is the
    fused verify step: all k+1 positions of every slot attend in ONE
    block sweep over the same table-indexed scalar-prefetch index maps,
    with ``lengths`` bounding the sweep at the slot's cache length
    *including* the just-scattered draft rows.
    """
    if q.ndim == 4:
        if limits is None:
            raise ValueError(
                "4-D q (the k+1 verify step) needs per-position limits")
        return _multi_query_attention(
            q, k_arena, v_arena, block_tables, lengths, limits,
            k_scales=k_scales, v_scales=v_scales, scale=scale)
    if limits is not None:
        raise ValueError("limits only apply to a 4-D (multi-query) q")
    if k_arena.ndim == 3:
        return _decode_flat(q, k_arena, v_arena, block_tables, lengths,
                            kv_heads=kv_heads, window=window, sinks=sinks,
                            scale=scale)
    if window is not None or sinks is not None:
        raise NotImplementedError(
            "a window or sinks need a cache group's flat arena")
    b, n, d = q.shape
    n_blocks, bs, g, dk = k_arena.shape
    if block_size is not None and block_size != bs:
        raise ValueError(
            f"block_size ({block_size}) != arena block dim ({bs})")
    _check_arena(d, k_arena, n, g, k_scales, v_scales)
    hpg = n // g
    max_blocks = block_tables.shape[1]
    has_scales = k_scales is not None

    page_bytes = _vmem_bytes((bs, g, d), k_arena.dtype)
    if has_scales:
        page_bytes += _vmem_bytes((bs, g), k_scales.dtype)
    pages = _pages_per_step(page_bytes, max_blocks)
    lengths = lengths.astype(jnp.int32)
    n_steps, plan, slot, group = _step_plan(block_tables, lengths, bs, pages)

    def q_idx(s, plan_ref, slot_ref, group_ref, len_ref):
        return (slot_ref[s], 0, 0)

    def page_spec(p, block):
        def idx(s, plan_ref, slot_ref, group_ref, len_ref):
            return (plan_ref[s * pages + p],) + (0,) * (len(block) - 1)
        return pl.BlockSpec(block, idx)

    # each arena is bound once per page of a group: page p of the step is
    # the block the plan names, copied (double-buffered) by the pipeline
    arenas = [(k_arena, (1, bs, g, d)), (v_arena, (1, bs, g, d))]
    if has_scales:
        arenas += [(k_scales, (1, bs, g)), (v_scales, (1, bs, g))]
    in_specs = [pl.BlockSpec((1, n, d), q_idx)]
    operands = [q]
    for arena, block in arenas:
        in_specs += [page_spec(p, block) for p in range(pages)]
        operands += [arena] * pages
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_steps,),        # as many steps as the lengths need
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((n, _LANES), jnp.float32),
            pltpu.VMEM((n, _LANES), jnp.float32),
            pltpu.VMEM((n, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, scale=_resolve(scale, d),
                               block_size=bs, pages=pages, hpg=hpg,
                               has_scales=has_scales)
    # the scope names the Mosaic custom call in a device trace
    # (``%paged_decode.<n>``): XLA names an instruction after the innermost
    # scope round it, which is otherwise the layer scan's ``closed_call``
    with named_span("paged_decode"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, n, d), q.dtype),
            # in order: a slot's steps carry its softmax state
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=platform.pallas_interpret(),
            name="paged_decode",
        )(plan, slot, group, lengths, *operands)


def _compiler_params():
    """Batch dim is independent (parallel, megacore-splittable); the
    block sweep carries the online-softmax scratch (arbitrary)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _unflat(arena, kv_heads):
    """A cache group's flat arena as ``[n_blocks, block, kv_heads, d]``."""
    if arena.ndim == 4:
        return arena
    if not kv_heads:
        raise ValueError("a flat arena needs kv_heads")
    return arena.reshape(arena.shape[:2] + (kv_heads, -1))


def _window_mask(mask, cols, horizon, window):
    """``mask`` and ``cols >= horizon - window``: a query whose horizon is
    ``horizon`` (it sits at ``horizon - 1``) reads keys ``j > horizon - 1 -
    window``."""
    if window is None:
        return mask
    return mask & (cols >= horizon - window)


def _sink_softmax(s, sinks, head_axis):
    """Softmax weights of masked scores ``s`` over its last axis with an
    optional per-head sink logit in the denominator (``sinks [n]`` along
    ``head_axis``).  Rows with no live key weigh nothing."""
    m = jnp.max(s, axis=-1, keepdims=True)
    if sinks is not None:
        shape = [1] * s.ndim
        shape[head_axis] = -1
        sink = sinks.astype(jnp.float32).reshape(shape)
        dead = m <= NEG_INF * 0.5
        m = jnp.maximum(m, sink)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sink - m)
        return jnp.where(dead, 0.0, p / l)
    m_safe = jnp.where(m <= NEG_INF * 0.5, 0.0, m)
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return p / jnp.where(l == 0.0, 1.0, l)


def _gathered_kv(q, k_arena, v_arena, block_tables, k_scales, v_scales):
    """The unfused twins' shared gather: materialise per-slot K/V (and
    apply int8 row scales) in HBM — the cost the fused kernels avoid."""
    b, n = q.shape[:2]
    _, bs, g, _ = k_arena.shape
    hpg = n // g
    k = jnp.take(k_arena, block_tables, axis=0).astype(jnp.float32)
    v = jnp.take(v_arena, block_tables, axis=0).astype(jnp.float32)
    if k_scales is not None:
        ks = jnp.take(k_scales, block_tables, axis=0)
        vs = jnp.take(v_scales, block_tables, axis=0)
        k = k * ks[..., None]
        v = v * vs[..., None]
    t = block_tables.shape[1] * bs
    k = k.reshape(b, t, g, k.shape[-1])
    v = v.reshape(b, t, g, v.shape[-1])
    if hpg > 1:
        k = jnp.repeat(k, hpg, axis=2)
        v = jnp.repeat(v, hpg, axis=2)
    return k, v, t


def paged_attention_decode_unfused(q, k_arena, v_arena, block_tables,
                                   lengths, *, limits=None, k_scales=None,
                                   v_scales=None,
                                   scale: Optional[float] = None,
                                   kv_heads: Optional[int] = None,
                                   window: Optional[int] = None,
                                   sinks=None):
    """The plain-XLA lowering of the same computation — the parity
    reference.

    Materialises the gathered ``[batch, max_blocks*block, heads, d]``
    K/V copies in HBM and lets XLA lower the softmax chain — the
    unfused decode profile the Pallas kernel exists to beat.  A 4-D
    ``q`` + ``limits`` is the unfused k+1 verify (the fused twin's
    contract, lowered through the prefill-shaped gather).
    """
    if q.ndim == 4:
        if limits is None:
            raise ValueError(
                "4-D q (the k+1 verify step) needs per-position limits")
        return paged_prefill_attention_unfused(
            q, k_arena, v_arena, block_tables, lengths, limits,
            k_scales=k_scales, v_scales=v_scales, scale=scale)
    if limits is not None:
        raise ValueError("limits only apply to a 4-D (multi-query) q")
    b, n, d = q.shape
    k_arena, v_arena = _unflat(k_arena, kv_heads), _unflat(v_arena, kv_heads)
    _check_arena(d, k_arena, n, k_arena.shape[2], k_scales, v_scales)
    k, v, t = _gathered_kv(q, k_arena, v_arena, block_tables,
                           k_scales, v_scales)
    s = jnp.einsum("bnd,btnd->bnt", q.astype(jnp.float32), k)
    s = s * _resolve(scale, d)
    cols = jnp.arange(t)[None, None, :]
    mask = _window_mask(cols < lengths[:, None, None], cols,
                        lengths[:, None, None], window)
    s = jnp.where(mask, s, NEG_INF)
    if window is None and sinks is None:
        m = jnp.max(s, axis=-1, keepdims=True)
        m_safe = jnp.where(m <= NEG_INF * 0.5, 0.0, m)
        p = jnp.exp(s - m_safe)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("bnt,btnd->bnd", p, v) / jnp.where(
            l == 0.0, 1.0, l)
        return out.astype(q.dtype)
    p = _sink_softmax(s, sinks, head_axis=1)
    return jnp.einsum("bnt,btnd->bnd", p, v).astype(q.dtype)


# --------------------------------------------------- chunked prefill


def _prefill_kernel(tab_ref, len_ref, q_ref, lim_ref, k_ref, v_ref, *rest,
                    scale: float, block_size: int, hpg: int,
                    has_scales: bool):
    if has_scales:
        ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_sc, l_sc, acc_sc = rest
    i = pl.program_id(0)
    j = pl.program_id(1)
    num_blocks = pl.num_programs(1)
    length = len_ref[i]
    kv_heads = k_ref.shape[2]
    n_heads = kv_heads * hpg

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(j * block_size < length)
    def _body():
        cols = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        # per-token causal limit: token t sees cache positions < lim[t]
        # (its own row, scattered before the call, is position lim[t]-1)
        live = cols < lim_ref[0]                         # [T, bs]
        for h in range(kv_heads):
            k = _head_rows(k_ref, ks_ref, h)             # [bs, d]
            v = _head_rows(v_ref, vs_ref, h)
            for n in range(h * hpg, (h + 1) * hpg):      # GQA group
                q = q_ref[0, n].astype(jnp.float32)          # [T, d]
                s = _dot(q, k, ((1,), (1,))) * scale         # [T, bs]
                s = jnp.where(live, s, NEG_INF)
                p, alpha, m_new, l_new = _online_softmax(
                    s, m_sc[:, n:n + 1], l_sc[:, n:n + 1])
                acc_sc[n] = acc_sc[n] * alpha + _dot(p, v, ((1,), (0,)))
                m_sc[:, n:n + 1] = m_new
                l_sc[:, n:n + 1] = l_new

    @pl.when(j == num_blocks - 1)
    def _finalize():
        for n in range(n_heads):
            l_fin = l_sc[:, n:n + 1]
            l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
            o_ref[0, n] = (acc_sc[n] / l_safe).astype(o_ref.dtype)


def paged_prefill_attention(q, k_arena, v_arena, block_tables, lengths,
                            limits, *, k_scales=None, v_scales=None,
                            scale: Optional[float] = None,
                            kv_heads: Optional[int] = None,
                            window: Optional[int] = None, sinks=None):
    """Fused chunked-prefill attention: each slot's ``[chunk]`` query
    tokens attend over the slot's paged context in one block sweep.

    ``q [batch, chunk, n, d]``; ``lengths [batch]`` — the slot's total
    live cache length INCLUDING the chunk's own just-scattered rows
    (the block-sweep bound); ``limits [batch, chunk]`` — per-token
    causal horizon (token attends positions ``< limit``; 0 = padding
    row, which produces zeros).  History blocks and the chunk's own
    destination blocks are all just table entries — prefix-cache hits,
    earlier chunks, and in-chunk causality need no separate paths.

    A 3-D ``k_arena`` is a cache group's flat arena (see
    :func:`paged_attention_decode`): ``window`` then starts the block
    sweep at the first block the chunk's first token can read, ``sinks`` as
    there; the call's name in a trace is ``paged_prefill_window`` or
    ``paged_prefill_full``.
    """
    if k_arena.ndim == 3:
        return _prefill_flat(q, k_arena, v_arena, block_tables, lengths,
                             limits, kv_heads=kv_heads, window=window,
                             sinks=sinks, scale=scale)
    if window is not None or sinks is not None:
        raise NotImplementedError(
            "a window or sinks need a cache group's flat arena")
    return _multi_query_attention(
        q, k_arena, v_arena, block_tables, lengths, limits,
        k_scales=k_scales, v_scales=v_scales, scale=scale)


def _multi_query_attention(q, k_arena, v_arena, block_tables, lengths,
                           limits, *, k_scales=None, v_scales=None,
                           scale: Optional[float] = None):
    """The shared fused multi-query block sweep behind the chunked
    prefill AND the speculative k+1 verify (see the module docstring —
    a verify step is a self-proposed chunk)."""
    b, T, n, d = q.shape
    n_blocks, bs, g, dk = k_arena.shape
    _check_arena(d, k_arena, n, g, k_scales, v_scales)
    hpg = n // g
    max_blocks = block_tables.shape[1]
    has_scales = k_scales is not None

    def kv_idx(i, j, tab_ref, len_ref):
        live = jnp.maximum((len_ref[i] - 1) // bs, 0)
        return (tab_ref[i, jnp.minimum(j, live)], 0, 0, 0)

    def sc_idx(i, j, tab_ref, len_ref):
        live = jnp.maximum((len_ref[i] - 1) // bs, 0)
        return (tab_ref[i, jnp.minimum(j, live)], 0, 0)

    def row_idx(i, j, tab_ref, len_ref):
        return (i, 0, 0)

    def q_idx(i, j, tab_ref, len_ref):
        return (i, 0, 0, 0)

    # q and out ride head-major [b, n, T, d]: the kernel loops over
    # heads, and a [T, d] head slice of a [T, n, d] block is a strided
    # sublane access Mosaic refuses to store for packed (bf16) dtypes.
    # The transposes fuse with the ones the callers already do.
    in_specs = [
        pl.BlockSpec((1, n, T, d), q_idx),
        # [b, T, 1]: a (1, T) block over [b, T] breaks the TPU tiling
        # rule (last two block dims full or (8, 128)-aligned)
        pl.BlockSpec((1, T, 1), row_idx),
        pl.BlockSpec((1, bs, g, d), kv_idx),
        pl.BlockSpec((1, bs, g, d), kv_idx),
    ]
    operands = [q.transpose(0, 2, 1, 3),
                limits.astype(jnp.int32)[..., None], k_arena, v_arena]
    if has_scales:
        in_specs += [pl.BlockSpec((1, bs, g), sc_idx),
                     pl.BlockSpec((1, bs, g), sc_idx)]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n, T, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((T, n), jnp.float32),
            pltpu.VMEM((T, n), jnp.float32),
            pltpu.VMEM((n, T, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_prefill_kernel, scale=_resolve(scale, d),
                               block_size=bs, hpg=hpg,
                               has_scales=has_scales)
    with named_span("paged_prefill"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, n, T, d), q.dtype),
            compiler_params=_compiler_params(),
            interpret=platform.pallas_interpret(),
            name="paged_prefill",
        )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
          *operands)
    return out.transpose(0, 2, 1, 3)


def paged_prefill_attention_unfused(q, k_arena, v_arena, block_tables,
                                    lengths, limits, *, k_scales=None,
                                    v_scales=None,
                                    scale: Optional[float] = None,
                                    kv_heads: Optional[int] = None,
                                    window: Optional[int] = None,
                                    sinks=None):
    """Plain-XLA chunked-prefill lowering (A/B baseline + parity
    reference): gather each slot's whole table, mask per token."""
    b, T, n, d = q.shape
    k_arena, v_arena = _unflat(k_arena, kv_heads), _unflat(v_arena, kv_heads)
    _check_arena(d, k_arena, n, k_arena.shape[2], k_scales, v_scales)
    k, v, t = _gathered_kv(q[:, 0], k_arena, v_arena, block_tables,
                           k_scales, v_scales)
    s = jnp.einsum("btnd,bsnd->btns", q.astype(jnp.float32), k)
    s = s * _resolve(scale, d)
    cols = jnp.arange(t)[None, None, None, :]
    mask = _window_mask(cols < limits[:, :, None, None], cols,
                        limits[:, :, None, None], window)
    s = jnp.where(mask, s, NEG_INF)
    if window is not None or sinks is not None:
        p = _sink_softmax(s, sinks, head_axis=2)
        return jnp.einsum("btns,bsnd->btnd", p, v).astype(q.dtype)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(m <= NEG_INF * 0.5, 0.0, m)
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("btns,bsnd->btnd", p, v) / \
        jnp.where(l == 0.0, 1.0, l)
    return out.astype(q.dtype)


# ------------------------------------- cache groups: flat arenas (ISSUE 27)
#
# The arenas of a cache group are ``[n_blocks, block, kv_heads * d]``: the
# rows of all KV heads side by side on the lanes, dense whatever ``d`` is
# (``4 * 192 = 768`` and ``8 * 192 = 1536`` lanes at the widths that
# brought them), ``d_k`` beside another ``d_v``.  The kernels below are the
# step-plan decode sweep (a step's pages joined into one key tile) and the
# multi-query block sweep again, reading a KV head as a lane slice of the
# tile or the page, with two more mechanisms: a sliding
# ``window`` (pages wholly behind it are dropped from the decode plan and
# from the prefill sweep, the edge pages are masked) and per-head ``sinks``
# (one more logit of the softmax denominator, folded in when a row's sweep
# ends).  A bfloat16 arena is multiplied as it lies (bf16 operands, fp32
# accumulation: the products are exact, the probabilities are rounded to
# bf16 for ``p v`` as the flash kernels do); any other dtype goes the fp32
# ``HIGHEST`` way of the kernels above.


def _mxu(a, b, contract, exact: bool):
    """``a . b`` with fp32 accumulation: bf16 operands in one MXU pass, or
    the fp32 ``HIGHEST`` contraction of :func:`_dot`."""
    if exact:
        return _dot(a.astype(jnp.float32), b.astype(jnp.float32), contract)
    return jax.lax.dot_general(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        (contract, ((), ())), preferred_element_type=jnp.float32)


def _fold_sink(m, l, acc, sink):
    """The sink logit as one more term of a finished row's softmax:
    ``m``/``l [rows, 1]``, ``sink`` broadcastable to them.  A row with no
    live key (``m`` at ``NEG_INF``) ends with ``l = 1`` and a zero ``acc``."""
    m_fin = jnp.maximum(m, sink)
    alpha = jnp.exp(m - m_fin)
    return l * alpha + jnp.exp(sink - m_fin), acc * alpha


def _check_flat(q, k_arena, v_arena, kv_heads, sinks):
    n, dk = q.shape[-2:]
    if not kv_heads or n % kv_heads:
        raise ValueError(
            f"n_heads ({n}) not a multiple of kv_heads ({kv_heads})")
    if k_arena.shape[-1] != kv_heads * dk:
        raise ValueError(
            f"K rows of {k_arena.shape[-1]} lanes hold no {kv_heads} heads "
            f"of q's width {dk}")
    if v_arena.shape[-1] % kv_heads or v_arena.shape[:2] != k_arena.shape[:2]:
        raise ValueError(
            f"V arena {v_arena.shape} does not go with K {k_arena.shape}")
    if sinks is not None and sinks.shape != (n,):
        raise ValueError(f"sinks {sinks.shape} are not one per head ({n})")
    return dk, v_arena.shape[-1] // kv_heads


# A grid step of the cache groups' decode kernel joins ``P`` pages of one
# slot into one key tile of ``P * block_size`` rows (ISSUE 32): one score
# product a KV head, one softmax update and one value product a KV head per
# tile, where a page at a time was a chain of ``P * kv_heads`` dependent
# updates of 16 keys each.  A window kernel's tile holds the pages a window
# can touch, so a slot is one step.  A full kernel's tile is at most
# ``_MAX_FLAT_PAGES`` pages, swept on a v5e at the ``mimo-v2-flash`` widths
# (64 slots, histories 128-4200, 4 KV heads of 192 / 128, bfloat16;
# ``examples/bench_paged_decode_groups.py``, ms a call, where a page at a
# time took 9.53): 8 pages 1.18, 16 0.84, 24 0.77, 32 0.73, 40 0.70.  A step
# costs about 0.55 us beside its bytes whatever its width, so the fewer the
# better; past 32 a slot's last tile is mostly masked and the 4% left are
# 0.04 ms of a 20 ms tick (PERF.md §6).
_MAX_FLAT_PAGES = 32


def _window_pages(window: int, block_size: int) -> int:
    """The most pages the ``window`` rows a query reads can touch, wherever
    they lie against the block edges."""
    return pl.cdiv(window, block_size) + 1


def _flat_pages_per_step(page_bytes: int, max_blocks: int, block_size: int,
                         window: Optional[int]) -> int:
    """How many pages a grid step of a cache group's decode kernel joins
    into its key tile (``P``): the pages a window can touch where there is
    one, ``_MAX_FLAT_PAGES`` where not; at most what ``_KV_TILE_BYTES``
    holds twice over for K and for V, and a slot's whole table."""
    want = (_MAX_FLAT_PAGES if window is None
            else _window_pages(window, block_size))
    return max(1, min(_KV_TILE_BYTES // (4 * page_bytes), want, max_blocks))


def _decode_flat_kernel(steps_ref, plan_ref, slot_ref, group_ref, len_ref,
                        first_ref, q_ref, *rest, scale: float,
                        block_size: int, pages: int, g: int, hpg: int,
                        dk: int, dv: int, window: Optional[int],
                        has_sinks: bool, exact: bool, latent: bool = False):
    """One grid step = one key tile of ``pages`` pages of one slot, all
    heads.  ``rest``: the sinks (if any), the K and V arenas (in HBM), the
    output, the double-buffered K and V tiles with their DMA semaphores and
    the softmax state.  A ``latent`` group has no V arena and no V tile:
    the values are the leading ``dv`` lanes of the key tile."""
    if has_sinks:
        sink_ref, rest = rest[0], rest[1:]
    if latent:
        k_hbm, o_ref, k_buf, sems, m_sc, l_sc, acc_sc = rest
        v_hbm, v_buf = None, k_buf
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_sc, l_sc, acc_sc = rest
    step = pl.program_id(0)
    buf = step % 2

    def sweep(s):
        """Step ``s``: its slot's length, its first page, its live pages."""
        slot = slot_ref[s]
        length = len_ref[slot]
        page0 = first_ref[slot] + group_ref[s] * pages
        n_live = jnp.clip(pl.cdiv(length, block_size) - page0, 0, pages)
        return length, page0, n_live

    def for_live_pages(s, into, act):
        """``act`` on the copies of step ``s``'s live pages, each page's
        block of both arenas into its rows of tile ``into``.  A dead page
        has no copy: its rows keep what they held and are masked."""
        n_live = sweep(s)[2]
        for p in range(pages):
            @pl.when(p < n_live)
            def _page(p=p):
                block = plan_ref[s * pages + p]
                rows = pl.ds(p * block_size, block_size)
                act(pltpu.make_async_copy(
                    k_hbm.at[block], k_buf.at[into, rows], sems.at[0, into]))
                if not latent:
                    act(pltpu.make_async_copy(
                        v_hbm.at[block], v_buf.at[into, rows],
                        sems.at[1, into]))

    @pl.when(step == 0)
    def _first():
        # rows no copy has reached weigh 0 in ``p v``: they must be finite
        v_buf[...] = jnp.zeros_like(v_buf)
        for_live_pages(0, 0, lambda copy: copy.start())

    # the next step's pages travel under this step's products
    @pl.when(step + 1 < steps_ref[0])
    def _next():
        for_live_pages(step + 1, 1 - buf, lambda copy: copy.start())

    for_live_pages(step, buf, lambda copy: copy.wait())

    @pl.when(group_ref[step] == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    length, page0, _ = sweep(step)
    cols = page0 * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, pages * block_size), 1)
    live = cols < length
    if window is not None:
        live = live & (cols >= length - window)
    k = k_buf[buf]                                      # [tile, g * dk]
    v = v_buf[buf]                                      # [tile, g * dv]
    # the heads' score products are independent of one another; their
    # rows then share one mask and one update of the softmax state
    s = jnp.concatenate([
        _mxu(q_ref[0, h * hpg:(h + 1) * hpg, :], k[:, h * dk:(h + 1) * dk],
             ((1,), (1,)), exact)
        for h in range(g)], axis=0)                     # [n, tile]
    s = jnp.where(live, s * scale, NEG_INF)
    p, alpha, m_new, l_new = _online_softmax(s, m_sc[:, :1], l_sc[:, :1])
    for h in range(g):
        rows = slice(h * hpg, (h + 1) * hpg)
        acc_sc[rows, :] = acc_sc[rows, :] * alpha[rows] + _mxu(
            p[rows], v[:, h * dv:(h + 1) * dv], ((1,), (0,)), exact)
    m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
    l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when((page0 + pages) * block_size >= length)    # the slot's last
    def _finalize():
        l_fin, acc = l_sc[:, :1], acc_sc[...]
        if has_sinks:
            l_fin, acc = _fold_sink(m_sc[:, :1], l_fin, acc, sink_ref[...])
        l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
        o_ref[0] = (acc / l_safe).astype(o_ref.dtype)


def _decode_flat(q, k_arena, v_arena, block_tables, lengths, *, kv_heads,
                 window, sinks, scale, latent_v: Optional[int] = None):
    """``latent_v``: ``k_arena`` is a latent group's one arena (``v_arena``
    is ``None``), whose leading ``latent_v`` lanes are the values."""
    latent = latent_v is not None
    kind = "latent" if latent else "full" if window is None else "window"
    name = f"paged_decode_{kind}"
    b, n, _ = q.shape
    bs = k_arena.shape[1]
    max_blocks = block_tables.shape[1]
    if latent:
        dk, dv = _check_latent(q, k_arena, latent_v)
        page_bytes = _vmem_bytes((bs, dk), k_arena.dtype)
        pages = max(1, min(_KV_TILE_BYTES // (2 * page_bytes),
                           _MAX_LATENT_PAGES, max_blocks))
    else:
        dk, dv = _check_flat(q, k_arena, v_arena, kv_heads, sinks)
        page_bytes = max(_vmem_bytes((bs, kv_heads * dk), k_arena.dtype),
                         _vmem_bytes((bs, kv_heads * dv), v_arena.dtype))
        pages = _flat_pages_per_step(page_bytes, max_blocks, bs, window)
    g, hpg = kv_heads, n // kv_heads
    lengths = lengths.astype(jnp.int32)
    if window is None:
        first_page = jnp.zeros((b,), jnp.int32)
        span = max_blocks
    else:
        first_page = jnp.maximum(lengths - window, 0) // bs
        span = min(max_blocks, _window_pages(window, bs))
    # what the tiling is, for whoever reads a trace (host only, set when
    # the call is traced); the tiles' fill is ``decode_plan``'s
    # ``kv_tokens_*`` over steps x keys a step
    reg = default_registry()
    reg.gauge(f"paged_decode/keys_per_step/{kind}").set(pages * bs)
    reg.gauge(f"paged_decode/copies_per_step/{kind}").set(
        pages * (1 if latent else 2))
    reg.gauge(f"paged_decode/steps_per_slot_max/{kind}").set(
        pl.cdiv(span, pages))
    n_steps, plan, slot, group = _step_plan(
        block_tables, lengths, bs, pages, first_page)
    # every page copy's source indexes the arena whatever the table holds
    # (a block handed back behind the window reads -1), so the kernel
    # issues its copies without Mosaic's run-time bounds checks
    plan = jnp.clip(plan, 0, k_arena.shape[0] - 1)

    def row_idx(s, steps_ref, plan_ref, slot_ref, *refs):
        return (slot_ref[s], 0, 0)

    in_specs = [pl.BlockSpec((1, n, dk), row_idx)]
    operands = [q]
    if sinks is not None:
        in_specs.append(pl.BlockSpec(
            (n, 1), lambda s, *refs: (0, 0)))
        operands.append(sinks.astype(jnp.float32)[:, None])
    # the arenas stay in HBM: the kernel copies the plan's live pages
    # (their minor dimensions, ``kv_heads * d``, are whole lane tiles)
    arenas = [k_arena] if latent else [k_arena, v_arena]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * len(arenas)
    operands += arenas
    # the double-buffered key tile, and the value tile beside it
    tiles = [pltpu.VMEM((2, pages * bs, g * dk), k_arena.dtype)]
    if not latent:
        tiles.append(pltpu.VMEM((2, pages * bs, g * dv), v_arena.dtype))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_steps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n, dv), row_idx),
        scratch_shapes=tiles + [
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((n, _LANES), jnp.float32),
            pltpu.VMEM((n, _LANES), jnp.float32),
            pltpu.VMEM((n, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_flat_kernel, scale=_resolve(scale, dk), block_size=bs,
        pages=pages, g=g, hpg=hpg, dk=dk, dv=dv, window=window,
        has_sinks=sinks is not None,
        exact=k_arena.dtype != jnp.bfloat16, **(
            {"latent": True} if latent else {}))
    with named_span(name):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, n, dv), q.dtype),
            # in order: a slot's steps carry its softmax state, and a step
            # starts the copies of the next; every copy's indices are in
            # range by construction (the plan above, ``buf`` and static
            # rows), and the checks were a third of a step's bundles
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                disable_bounds_checks=True),
            interpret=platform.pallas_interpret(),
            name=name,
        )(n_steps.reshape(1), plan, slot, group, lengths, first_page,
          *operands)


def _prefill_flat_kernel(tab_ref, len_ref, first_ref, q_ref, lim_ref, *rest,
                         scale: float, block_size: int, g: int, hpg: int,
                         dk: int, dv: int, window: Optional[int],
                         has_sinks: bool, exact: bool):
    del tab_ref
    if has_sinks:
        sink_ref, rest = rest[0], rest[1:]
    k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc = rest
    i = pl.program_id(0)
    j = pl.program_id(1)
    length = len_ref[i]
    page = first_ref[i] + j

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    @pl.when(page * block_size < length)
    def _body():
        cols = page * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        lim = lim_ref[0]                                     # [T, 1]
        live = cols < lim                                    # [T, bs]
        if window is not None:
            live = live & (cols >= lim - window)
        for h in range(g):
            k = k_ref[0, :, h * dk:(h + 1) * dk]             # [bs, dk]
            v = v_ref[0, :, h * dv:(h + 1) * dv]
            for n in range(h * hpg, (h + 1) * hpg):
                s = _mxu(q_ref[0, n], k, ((1,), (1,)), exact) * scale
                s = jnp.where(live, s, NEG_INF)              # [T, bs]
                p, alpha, m_new, l_new = _online_softmax(
                    s, m_sc[:, n:n + 1], l_sc[:, n:n + 1])
                acc_sc[n] = acc_sc[n] * alpha + _mxu(
                    p, v, ((1,), (0,)), exact)
                m_sc[:, n:n + 1] = m_new
                l_sc[:, n:n + 1] = l_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        for n in range(g * hpg):
            l_fin, acc = l_sc[:, n:n + 1], acc_sc[n]
            if has_sinks:
                l_fin, acc = _fold_sink(m_sc[:, n:n + 1], l_fin, acc,
                                        sink_ref[:, n:n + 1])
            l_safe = jnp.where(l_fin == 0.0, 1.0, l_fin)
            o_ref[0, n] = (acc / l_safe).astype(o_ref.dtype)


def _prefill_flat(q, k_arena, v_arena, block_tables, lengths, limits, *,
                  kv_heads, window, sinks, scale):
    name = ("paged_prefill_full" if window is None
            else "paged_prefill_window")
    b, T, n, _ = q.shape
    dk, dv = _check_flat(q, k_arena, v_arena, kv_heads, sinks)
    bs = k_arena.shape[1]
    g, hpg = kv_heads, n // kv_heads
    max_blocks = block_tables.shape[1]
    lengths = lengths.astype(jnp.int32)
    limits = limits.astype(jnp.int32)
    if window is None:
        first_page = jnp.zeros((b,), jnp.int32)
        sweep = max_blocks
    else:
        # the chunk's first token (horizon ``limits[:, 0]``) reads nothing
        # before ``limits[:, 0] - window``; the sweep spans the window and
        # the chunk, wherever they lie against the block edges
        first_page = jnp.maximum(limits[:, 0] - window, 0) // bs
        sweep = min(max_blocks, (window + T - 2) // bs + 2)

    def kv_idx(i, j, tab_ref, len_ref, first_ref):
        live = jnp.maximum((len_ref[i] - 1) // bs, 0)
        entry = tab_ref[i, jnp.minimum(first_ref[i] + j, live)]
        return (jnp.maximum(entry, 0), 0, 0)

    def row_idx(i, j, *refs):
        return (i, 0, 0)

    def q_idx(i, j, *refs):
        return (i, 0, 0, 0)

    in_specs = [pl.BlockSpec((1, n, T, dk), q_idx),
                pl.BlockSpec((1, T, 1), row_idx)]
    operands = [q.transpose(0, 2, 1, 3), limits[..., None]]
    if sinks is not None:
        in_specs.append(pl.BlockSpec((1, n), lambda i, j, *refs: (0, 0)))
        operands.append(sinks.astype(jnp.float32)[None, :])
    in_specs += [pl.BlockSpec((1, bs, g * dk), kv_idx),
                 pl.BlockSpec((1, bs, g * dv), kv_idx)]
    operands += [k_arena, v_arena]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, sweep),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, n, T, dv), q_idx),
        scratch_shapes=[
            pltpu.VMEM((T, n), jnp.float32),
            pltpu.VMEM((T, n), jnp.float32),
            pltpu.VMEM((n, T, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_flat_kernel, scale=_resolve(scale, dk), block_size=bs,
        g=g, hpg=hpg, dk=dk, dv=dv, window=window,
        has_sinks=sinks is not None,
        exact=k_arena.dtype != jnp.bfloat16)
    # q and out blocks of all heads (double-buffered) and the fp32
    # accumulator: more than the default scoped VMEM at 64 heads x 128
    vmem = (2 * _vmem_bytes((n, T, dk), q.dtype)
            + 2 * _vmem_bytes((n, T, dv), q.dtype)
            + _vmem_bytes((n, T, dv), jnp.float32) + (8 << 20))
    with named_span(name):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, n, T, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=max(vmem, 32 << 20)),
            interpret=platform.pallas_interpret(),
            name=name,
        )(block_tables.astype(jnp.int32), lengths, first_page, *operands)
    return out.transpose(0, 2, 1, 3)


# ------------------------------------- a latent cache group (ISSUE 33)
#
# Multi-head latent attention keeps one row a token and layer, ``[normed
# latent (dv) | rotated shared key]``, ``d`` lanes wide (512 + 64 at the
# widths that brought it).  In the absorbed form every head's query is a
# vector of those ``d`` lanes, its scores the products with the cached rows
# as they lie, and its values the rows' leading ``dv`` lanes: one key head
# that all ``n`` query heads share, so a key tile is multiplied by ``[n,
# d]`` queries at decode (the cache groups' decode kernel above, with one
# arena) and by a block of ``tokens x n`` query rows at prefill (the kernel
# below).  Per cached row and query token: ``2 n (d + dv)`` FLOP over ``d``
# elements read, 242 FLOP/B at 128 heads in bfloat16: both the MXU and the
# HBM stream are close to busy, so the tile is wider than the other groups'
# (fewer steps, each ~0.5 us of fixed cost beside 1.4 us of bytes).
_MAX_LATENT_PAGES = 64
# query rows (tokens x heads) a prefill grid step multiplies a key tile by,
# and the pages of that tile, fetched in ``_LATENT_PARTS`` parts so that a
# part's products run under the next part's copy
_LATENT_PREFILL_ROWS = 2048
_LATENT_PREFILL_PAGES = 64
_LATENT_PARTS = 2


def _check_latent(q, arena, v_dim):
    d = q.shape[-1]
    if arena.ndim != 3 or arena.shape[-1] != d:
        raise ValueError(
            f"a latent arena {arena.shape} holds no rows of q's width {d}")
    if not 0 < v_dim <= d:
        raise ValueError(f"values of {v_dim} lanes do not lie in rows of {d}")
    return d, v_dim


def paged_decode_latent(q, arena, block_tables, lengths, *, v_dim: int,
                        scale: float):
    """Decode over a latent group's arena ``[n_blocks, block, d]``: ``q
    [batch, n_heads, d]`` (absorbed queries: every head reads the same
    rows), values the rows' leading ``v_dim`` lanes, ``scale`` the softmax
    scale (it is the model's, not ``d ** -0.5``).  Returns ``[batch,
    n_heads, v_dim]``; in a device trace the call is
    ``paged_decode_latent``."""
    return _decode_flat(q, arena, None, block_tables, lengths, kv_heads=1,
                        window=None, sinks=None, scale=scale,
                        latent_v=v_dim)


def _masked_softmax_rows(s, mask, rows, v_dim, dtype):
    """softmax of masked scores ``s [..., keys]`` times ``rows[..., :v_dim]``
    (the unfused twins'); a row with no live key gives zeros."""
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(m <= NEG_INF * 0.5, 0.0, m))
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("b...s,bsd->b...d", p, rows[..., :v_dim])
    return (out / jnp.where(l == 0.0, 1.0, l)).astype(dtype)


def _gathered_rows(arena, block_tables):
    b, max_blocks = block_tables.shape
    rows = arena[jnp.maximum(block_tables, 0)]       # [b, blocks, bs, d]
    return rows.reshape(b, max_blocks * arena.shape[1], -1).astype(
        jnp.float32)


def paged_decode_latent_unfused(q, arena, block_tables, lengths, *,
                                v_dim: int, scale: float):
    """Plain-XLA twin of :func:`paged_decode_latent`: gather each slot's
    whole table, mask by length."""
    _check_latent(q, arena, v_dim)
    rows = _gathered_rows(arena, block_tables)
    s = jnp.einsum("bnd,bsd->bns", q.astype(jnp.float32), rows) * scale
    cols = jnp.arange(rows.shape[1])[None, None, :]
    return _masked_softmax_rows(s, cols < lengths[:, None, None], rows,
                                v_dim, q.dtype)


def paged_prefill_latent_unfused(q, arena, block_tables, lengths, limits, *,
                                 v_dim: int, scale: float, selected=None):
    """Plain-XLA twin of :func:`paged_prefill_latent`."""
    del lengths
    _check_latent(q, arena, v_dim)
    rows = _gathered_rows(arena, block_tables)
    s = jnp.einsum("btnd,bsd->btns", q.astype(jnp.float32), rows) * scale
    cols = jnp.arange(rows.shape[1])[None, None, None, :]
    live = cols < limits[:, :, None, None]
    if selected is not None:
        live = live & (selected[:, :, None, :rows.shape[1]] > 0)
    return _masked_softmax_rows(s, live, rows, v_dim, q.dtype)


def _prefill_latent_kernel(tab_ref, reach_ref, q_ref, lim_ref, *rest,
                           scale: float, block_size: int, pages: int,
                           dv: int, exact: bool, heads: int = 0):
    """One grid step = one key tile of ``pages`` pages of slot ``i`` against
    query block ``qb`` (``rows`` of tokens x heads, each with its causal
    horizon in ``lim_ref``).  ``reach_ref [b, query blocks]`` is the
    farthest horizon of a block's rows: tiles past it have no step body,
    pages past it no copy.  With ``heads`` (a selection), ``rest`` begins
    with ``sel_ref``, the block's tokens' selections of the tile's keys
    ``[tokens, keys]``, and the rows are head-major (``heads`` x tokens):
    a row attends a key where its token selected it."""
    if heads:
        sel_ref, rest = rest[0], rest[1:]
    rows_hbm, o_ref, buf, sems, m_sc, l_sc, acc_sc = rest
    i, qb, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    reach = reach_ref[i, qb]
    part = pages // _LATENT_PARTS
    n_live = jnp.clip(pl.cdiv(reach, block_size) - j * pages, 0, pages)

    @pl.when((i == 0) & (qb == 0) & (j == 0))
    def _first():
        # rows no copy has reached weigh 0 in ``p v``: they must be finite
        buf[...] = jnp.zeros_like(buf)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def for_live_pages(half, act):
        for p in range(half * part, (half + 1) * part):
            @pl.when(p < n_live)
            def _page(p=p):
                act(pltpu.make_async_copy(
                    rows_hbm.at[tab_ref[i, j * pages + p]],
                    buf.at[pl.ds(p * block_size, block_size)],
                    sems.at[half]))

    @pl.when(n_live > 0)
    def _body():
        for half in range(_LATENT_PARTS):
            for_live_pages(half, lambda copy: copy.start())
        lim = lim_ref[0, 0]                                   # [rows, 1]
        q = q_ref[0, 0]
        for half in range(_LATENT_PARTS):
            @pl.when(half * part < n_live)
            def _part(half=half):
                for_live_pages(half, lambda copy: copy.wait())
                keys = part * block_size
                cols = (j * pages + half * part) * block_size \
                    + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
                rows = buf[pl.ds(half * keys, keys)]          # [keys, d]
                s = _mxu(q, rows, ((1,), (1,)), exact)
                live = cols < lim
                if heads:
                    # each token's selection, once for each head's rows
                    sel = sel_ref[0, 0, :, pl.ds(half * keys, keys)].astype(
                        jnp.float32)
                    live = live & (jnp.broadcast_to(
                        sel[None], (heads,) + sel.shape).reshape(
                            heads * sel.shape[0], keys) > 0.5)
                s = jnp.where(live, s * scale, NEG_INF)
                p, alpha, m_new, l_new = _online_softmax(
                    s, m_sc[:, :1], l_sc[:, :1])
                acc_sc[...] = acc_sc[...] * alpha + _mxu(
                    p, rows[:, :dv], ((1,), (0,)), exact)
                m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
                l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l_fin = l_sc[:, :1]
        o_ref[0, 0] = (acc_sc[...] / jnp.where(l_fin == 0.0, 1.0, l_fin)
                       ).astype(o_ref.dtype)


def paged_prefill_latent(q, arena, block_tables, lengths, limits, *,
                         v_dim: int, scale: float, selected=None):
    """Chunked prefill over a latent group's arena: ``q [batch, chunk,
    n_heads, d]`` absorbed queries, ``limits [batch, chunk]`` each token's
    causal horizon (0 = padding), the chunk's own rows already in the
    arena.  Returns ``[batch, chunk, n_heads, v_dim]``; in a device trace
    the call is ``paged_prefill_latent``.  ``selected [batch, chunk,
    max_blocks * block]`` (int8, a sparse kind's selection, ISSUE 37): a
    token attends only the keys it selected; the kernel then takes its
    rows head-major so that a token's selection is one block for all its
    heads.

    The absorbed form: a chunk's ``chunk * n_heads`` query rows share every
    cached row, so the kernel is one matrix product a key tile and query
    block, ``2 n (d + v_dim)`` FLOP a (token, cached row).  Expanding the
    cached rows to per-head keys and values first would cost ``2 rank n
    (nope + v)`` FLOP a cached row *per call* and ``2 n (k_dim + v)`` a
    pair after it: cheaper only past about 340 query tokens a call and
    slot (2,176 against 640 + 33.5 M / tokens at DeepSeek-V2's widths), and
    a chunk has 128."""
    del lengths                     # the horizons say all the sweep needs
    b, T, n, _ = q.shape
    d, dv = _check_latent(q, arena, v_dim)
    bs = arena.shape[1]
    max_blocks = block_tables.shape[1]
    # tokens a query block: ``_LATENT_PREFILL_ROWS`` rows of tokens x heads
    tq = max(1, min(T, _LATENT_PREFILL_ROWS // n))
    while T % tq:
        tq -= 1
    blocks, rows = T // tq, tq * n
    pages = max(_LATENT_PARTS, min(_LATENT_PREFILL_PAGES, max_blocks)
                // _LATENT_PARTS * _LATENT_PARTS)
    limits = limits.astype(jnp.int32)
    reach = jnp.max(limits.reshape(b, blocks, tq), axis=-1)
    if selected is None:
        lim = jnp.repeat(limits.reshape(b, blocks, tq), n, axis=-1)[..., None]
    else:
        lim = jnp.tile(limits.reshape(b, blocks, tq), (1, 1, n))[..., None]
        q = q.reshape(b, blocks, tq, n, d).swapaxes(2, 3)
    tiles = pl.cdiv(max_blocks, pages)
    # table columns a tile's last pages may name past the table's width
    table = jnp.pad(block_tables.astype(jnp.int32),
                    ((0, 0), (0, tiles * pages - max_blocks)))
    table = jnp.maximum(table, 0)

    def block_idx(i, qb, j, *refs):
        return (i, qb, 0, 0)

    in_specs = [pl.BlockSpec((1, 1, rows, d), block_idx),
                pl.BlockSpec((1, 1, rows, 1), block_idx)]
    operands = [q.reshape(b, blocks, rows, d), lim]
    if selected is not None:
        width = tiles * pages * bs
        in_specs.append(pl.BlockSpec((1, 1, tq, pages * bs),
                                     lambda i, qb, j, *refs: (i, qb, 0, j)))
        operands.append(jnp.pad(
            selected.astype(jnp.int8).reshape(b, blocks, tq, -1),
            ((0, 0), (0, 0), (0, 0), (0, width - selected.shape[-1]))))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, blocks, tiles),
        in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, rows, dv), block_idx),
        scratch_shapes=[
            pltpu.VMEM((pages * bs, d), arena.dtype),
            pltpu.SemaphoreType.DMA((_LATENT_PARTS,)),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _prefill_latent_kernel, scale=scale, block_size=bs, pages=pages,
        dv=dv, exact=arena.dtype != jnp.bfloat16,
        **({} if selected is None else {"heads": n}))
    keys = pages // _LATENT_PARTS * bs
    vmem = (2 * _vmem_bytes((rows, d), q.dtype)
            + 2 * _vmem_bytes((rows, dv), q.dtype)
            + 2 * _vmem_bytes((rows, 1), jnp.int32)
            + 3 * _vmem_bytes((rows, _LANES), jnp.float32)
            + 4 * _vmem_bytes((rows, max(keys, dv)), jnp.float32)
            + (8 << 20))
    name = "paged_prefill_latent"
    with named_span(name):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, blocks, rows, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3,
                vmem_limit_bytes=max(vmem, 32 << 20)),
            interpret=platform.pallas_interpret(),
            name=name,
        )(table, reach, *operands, arena)
    if selected is not None:
        return out.reshape(b, blocks, n, tq, dv).swapaxes(2, 3).reshape(
            b, T, n, dv)
    return out.reshape(b, T, n, dv)

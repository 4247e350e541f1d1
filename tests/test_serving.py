"""apex_tpu.serving — paged KV cache, fused decode kernels, engine.

Fast tier: kernel parity (fused Pallas vs unfused XLA vs a dense
reference — GQA, bf16 dequant, int8 per-row-scale dequant, and the
chunked-prefill kernel pair included), the fused residual/norm
epilogue, block-allocator refcount/copy-on-write invariants, the
prefix cache, decode-vs-prefill logits parity at tp=1, zero-recompile
churn, occupancy admission (eviction + preemption with
recompute-on-readmit at 2x pool oversubscription), chunked prefill,
the sampling policies, the int8 cache, and programmatic preemption
drain (the real-SIGTERM drain lives in scripts/serving_smoke.sh).
Slow tier: the tp=2 parity leg and the train-mesh -> serve-mesh
restore.
"""

import inspect
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import parallel
from apex_tpu.serving import (
    BlockAllocator,
    OutOfBlocksError,
    PrefixCache,
    SamplingParams,
    ServingConfig,
    ServingEngine,
)
from apex_tpu.serving.fused_ops import (
    fused_residual_norm,
    residual_norm_unfused,
)
from apex_tpu.serving import paged_attention as paged_attention_module
from apex_tpu.serving.paged_attention import (
    paged_attention_decode,
    paged_attention_decode_unfused,
    paged_prefill_attention,
    paged_prefill_attention_unfused,
)
from apex_tpu.transformer.testing import TransformerConfig
from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

VOCAB, MAX_SEQ = 64, 32


def _int8_quantize(arr):
    """Host-side mirror of the in-graph per-row symmetric quant."""
    amax = np.abs(arr).max(-1)
    scales = np.maximum(amax / 127.0, 1e-8).astype(np.float32)
    q = np.clip(np.round(arr / scales[..., None]), -127, 127)
    return q.astype(np.int8), scales


# ---------------------------------------------------------------- kernels


def _dense_paged_reference(q, ka, va, tables, lengths, bs):
    """O(everything) host reference: walk each slot's block table."""
    b, n, d = q.shape
    g = ka.shape[2]
    out = np.zeros((b, n, d), np.float32)
    for i in range(b):
        L = int(lengths[i])
        if L == 0:
            continue
        rows_k, rows_v = [], []
        for t in range(L):
            blk = int(tables[i, t // bs])
            rows_k.append(np.asarray(ka[blk, t % bs], np.float32))
            rows_v.append(np.asarray(va[blk, t % bs], np.float32))
        k = np.repeat(np.stack(rows_k), n // g, axis=1)
        v = np.repeat(np.stack(rows_v), n // g, axis=1)
        s = np.einsum("nd,tnd->nt", np.asarray(q[i], np.float32), k)
        s /= np.sqrt(d)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("nt,tnd->nd", p, v)
    return out


class TestPagedAttentionKernel:
    def _case(self, *, g, cache_dtype):
        rng = np.random.RandomState(0)
        b, n, d, bs, n_blocks, mb = 4, 8, 64, 8, 16, 3
        q = jnp.asarray(rng.randn(b, n, d), jnp.float32)
        ka = jnp.asarray(rng.randn(n_blocks, bs, g, d), cache_dtype)
        va = jnp.asarray(rng.randn(n_blocks, bs, g, d), cache_dtype)
        tables = jnp.asarray(
            rng.permutation(n_blocks)[:b * mb].reshape(b, mb), jnp.int32)
        lengths = jnp.asarray([17, 0, 8, 24], jnp.int32)
        return q, ka, va, tables, lengths, bs

    @pytest.mark.parametrize("g", [8, 4])   # MHA and GQA (2 heads/group)
    def test_fused_matches_dense_reference(self, g):
        q, ka, va, tables, lengths, bs = self._case(
            g=g, cache_dtype=jnp.float32)
        out = paged_attention_decode(q, ka, va, tables, lengths)
        ref = _dense_paged_reference(q, ka, va, tables, lengths, bs)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)
        # inactive slot (length 0) produces exactly zeros
        assert np.abs(np.asarray(out[1])).max() == 0.0

    def test_unfused_matches_fused_incl_bf16_dequant(self):
        for dtype in (jnp.float32, jnp.bfloat16):
            q, ka, va, tables, lengths, _ = self._case(
                g=4, cache_dtype=dtype)
            fused = paged_attention_decode(q, ka, va, tables, lengths)
            unfused = paged_attention_decode_unfused(
                q, ka, va, tables, lengths)
            np.testing.assert_allclose(
                np.asarray(fused, np.float32),
                np.asarray(unfused, np.float32), atol=2e-5)

    def test_stale_table_entries_are_harmless(self):
        """Columns past the live blocks may hold garbage ids — the
        clamped index map must never read them."""
        q, ka, va, tables, lengths, bs = self._case(
            g=8, cache_dtype=jnp.float32)
        poisoned = np.asarray(tables).copy()
        for i, L in enumerate(np.asarray(lengths)):
            live = max((int(L) + bs - 1) // bs, 1)
            poisoned[i, live:] = 10_000   # far out of range
        out = paged_attention_decode(
            q, ka, va, jnp.asarray(poisoned), lengths)
        ref = _dense_paged_reference(q, ka, va, tables, lengths, bs)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)

    def test_int8_per_row_scale_dequant(self):
        """ISSUE 12: int8 arenas with per-row fp32 scales — the fused
        in-kernel dequant must match the unfused twin exactly and the
        fp32 cache closely (the quantization error bound, not kernel
        error)."""
        q, ka, va, tables, lengths, bs = self._case(
            g=4, cache_dtype=jnp.float32)
        ka_np, va_np = np.asarray(ka), np.asarray(va)
        qk, sk = _int8_quantize(ka_np)
        qv, sv = _int8_quantize(va_np)
        fused = paged_attention_decode(
            q, jnp.asarray(qk), jnp.asarray(qv), tables, lengths,
            k_scales=jnp.asarray(sk), v_scales=jnp.asarray(sv))
        unfused = paged_attention_decode_unfused(
            q, jnp.asarray(qk), jnp.asarray(qv), tables, lengths,
            k_scales=jnp.asarray(sk), v_scales=jnp.asarray(sv))
        np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                                   atol=2e-5)
        ref = _dense_paged_reference(q, ka, va, tables, lengths, bs)
        np.testing.assert_allclose(np.asarray(fused), ref, atol=0.05)
        # scale arenas must pair up
        with pytest.raises(ValueError, match="both k_scales"):
            paged_attention_decode(q, jnp.asarray(qk), jnp.asarray(qv),
                                   tables, lengths,
                                   k_scales=jnp.asarray(sk))

    # ---- ISSUE 26: a grid step is a group of P pages, all heads at once

    def _group_case(self, *, n, g, d, bs, mb, cache_dtype, seed=7):
        """Eight slots whose histories sit on every boundary of the new
        sweep: 1 token, one page, one short of / exactly / one past a full
        group of ``P`` pages, the whole table; a length-0 slot between live
        slots and as the last slot.  Tables are a permutation of the arena
        (non-contiguous), and every column past a slot's live pages is
        poisoned with an id far outside it."""
        rng = np.random.RandomState(seed)
        arena = np.zeros((1, bs, g, d), cache_dtype)
        page_bytes = paged_attention_module._vmem_bytes(
            arena.shape[1:], arena.dtype)
        if cache_dtype == np.int8:
            page_bytes += paged_attention_module._vmem_bytes(
                (bs, g), np.float32)
        P = paged_attention_module._pages_per_step(page_bytes, mb)
        assert 1 < P < mb and mb % P, (P, mb)      # a ragged last group
        lengths = np.array([1, bs, 0, P * bs - 1, P * bs, P * bs + 1,
                            mb * bs, 0], np.int32)
        b = len(lengths)
        live = -(-lengths // bs)
        n_blocks = int(live.sum()) + 3
        perm = rng.permutation(n_blocks)
        tables = np.full((b, mb), 10_000, np.int32)
        at = 0
        for i in range(b):
            tables[i, :live[i]] = perm[at:at + live[i]]
            at += live[i]
        q = rng.randn(b, n, d).astype(np.float32)
        ka = rng.randn(n_blocks, bs, g, d).astype(np.float32)
        va = rng.randn(n_blocks, bs, g, d).astype(np.float32)
        return q, ka, va, tables, lengths, bs, P

    def _check_group_case(self, q, ka, va, tables, lengths, bs,
                          cache_dtype):
        kwargs = {}
        if cache_dtype == np.int8:
            ka, sk = _int8_quantize(ka)
            va, sv = _int8_quantize(va)
            kwargs = dict(k_scales=jnp.asarray(sk), v_scales=jnp.asarray(sv))
            dense_k = ka.astype(np.float32) * sk[..., None]
            dense_v = va.astype(np.float32) * sv[..., None]
        else:
            ka = jnp.asarray(ka, cache_dtype)
            va = jnp.asarray(va, cache_dtype)
            dense_k, dense_v = (np.asarray(x, np.float32) for x in (ka, va))
        args = (jnp.asarray(q), jnp.asarray(ka), jnp.asarray(va))
        fused = paged_attention_decode(
            *args, jnp.asarray(tables), jnp.asarray(lengths), **kwargs)
        # the twin gathers whole tables: give it the poisoned columns
        # clamped into the arena (it masks what it gathered there)
        clean = np.where(tables == 10_000, 0, tables)
        unfused = paged_attention_decode_unfused(
            *args, jnp.asarray(clean), jnp.asarray(lengths), **kwargs)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                                   atol=2e-5)
        ref = _dense_paged_reference(q, dense_k, dense_v, clean, lengths, bs)
        np.testing.assert_allclose(np.asarray(fused), ref, atol=2e-5)
        for i in np.flatnonzero(lengths == 0):
            assert np.abs(np.asarray(fused[i])).max() == 0.0

    @pytest.mark.parametrize("cache_dtype", [np.float32, jnp.bfloat16,
                                             np.int8], ids=lambda t:
                             jnp.dtype(t).name)
    @pytest.mark.parametrize("hpg", [1, 2, 4])
    def test_page_group_boundaries(self, hpg, cache_dtype):
        q, ka, va, tables, lengths, bs, _ = self._group_case(
            n=8, g=8 // hpg, d=32, bs=4, mb=20, cache_dtype=cache_dtype)
        self._check_group_case(q, ka, va, tables, lengths, bs, cache_dtype)

    def test_page_group_boundaries_at_the_cell_page_shape(self):
        """gpt2-medium's page (16 tokens, 16 heads of 64), a small arena."""
        q, ka, va, tables, lengths, bs, P = self._group_case(
            n=16, g=16, d=64, bs=16, mb=20, cache_dtype=np.float32)
        assert P == 8
        self._check_group_case(q, ka, va, tables, lengths, bs, np.float32)

    @pytest.mark.parametrize("page_bytes,max_blocks,want", [
        (128 * 1024, 64, 8),       # the cell: fp32, 16 x 16 x 64 -> 128 lanes
        (64 * 1024, 64, 8),        # bf16: the operand cap, not the budget
        (128 * 1024, 5, 5),        # a short table
        (512 * 1024, 64, 2),       # a wide page: the VMEM budget
        (64 * 1024 * 1024, 64, 1),  # a page over the budget: still one
        (1024, 1, 1),
    ])
    def test_pages_per_step_is_what_the_shapes_give(self, page_bytes,
                                                    max_blocks, want):
        P = paged_attention_module._pages_per_step(page_bytes, max_blocks)
        assert P == want and 1 <= P <= max_blocks
        # pure: no state, no environment
        assert P == paged_attention_module._pages_per_step(
            page_bytes, max_blocks)

    def test_decode_takes_no_new_argument(self):
        # PR 26's ten, then what a cache group's flat arena brought (ISSUE
        # 27), all keyword-only with defaults that leave the pooled arena's
        # call as it was
        params = inspect.signature(paged_attention_decode).parameters
        assert list(params) \
            == ["q", "k_arena", "v_arena", "block_tables", "lengths",
                "limits", "k_scales", "v_scales", "block_size", "scale",
                "kv_heads", "window", "sinks"]
        assert [params[k].default for k in
                ("kv_heads", "window", "sinks")] == [None, None, None]

    def test_vmem_page_bytes_count_the_tile_padding(self):
        vmem = paged_attention_module._vmem_bytes
        assert vmem((16, 16, 64), np.float32) == 16 * 16 * 128 * 4
        assert vmem((16, 12, 64), np.float32) == 16 * 16 * 128 * 4
        assert vmem((16, 16, 64), jnp.bfloat16) == 16 * 16 * 128 * 2
        assert vmem((16, 16, 64), np.int8) == 16 * 32 * 128
        assert vmem((16, 16), np.float32) == 16 * 128 * 4

    def test_step_plan_copies_live_pages_only(self):
        """The sweep the kernel is given: a step per group of live pages
        (one for an empty slot), and per step the block each page operand
        holds: a live page's table entry, else the block the operand needs
        next (else held last), so that a change of block index (= one copy
        by the pipeline) happens for live pages only and no stale column
        is ever named."""
        _, _, _, tables, lengths, bs, P = self._group_case(
            n=8, g=8, d=32, bs=4, mb=20, cache_dtype=np.float32)
        n_steps, plan, slot, group = (np.asarray(x) for x in (
            paged_attention_module._step_plan(
                jnp.asarray(tables), jnp.asarray(lengths), bs, P)))
        live = -(-lengths // bs)
        want = [(i, j) for i, n in enumerate(live)
                for j in range(max(-(-n // P), 1))]
        assert n_steps == len(want) < len(slot)
        assert list(zip(slot[:n_steps], group[:n_steps])) == want
        plan = plan.reshape(-1, P)
        assert plan.max() < 10_000                  # no poisoned column
        for s, (i, j) in enumerate(want):
            for p in range(P):
                if j * P + p < live[i]:
                    assert plan[s, p] == tables[i, j * P + p]
        # an index changes between two steps only to bring a live page,
        # and the step past the last (which the pipeline looks at) is valid
        swept = plan[:n_steps + 1]
        assert (np.diff(swept, axis=0) != 0).sum() <= live.sum()
        assert ((0 <= swept) & (swept < 10_000)).all()
        # every live page is in place one step early where its operand
        # was idle the step before: the copy runs under that step
        for s, (i, j) in enumerate(want):
            for p in range(P):
                if s and j * P + p < live[i]:
                    pi, pj = want[s - 1]
                    if pj * P + p >= live[pi]:
                        assert plan[s - 1, p] == plan[s, p]


class TestPagedPrefillKernel:
    """The chunked-prefill sweep: per-token causal limits over history
    + the chunk's own just-scattered rows (ISSUE 12)."""

    def _case(self, g=4, dtype=jnp.float32):
        rng = np.random.RandomState(4)
        b, T, n, d, bs, n_blocks, mb = 3, 5, 8, 16, 4, 12, 4
        q = jnp.asarray(rng.randn(b, T, n, d), jnp.float32)
        ka = jnp.asarray(rng.randn(n_blocks, bs, g, d), dtype)
        va = jnp.asarray(rng.randn(n_blocks, bs, g, d), dtype)
        tables = jnp.asarray(
            rng.permutation(n_blocks)[:b * mb].reshape(b, mb), jnp.int32)
        hist = np.asarray([3, 0, 7], np.int32)     # cached history
        chunk = np.asarray([5, 0, 4], np.int32)    # this tick's tokens
        limits = np.zeros((b, T), np.int32)
        for i in range(b):
            for t in range(int(chunk[i])):
                limits[i, t] = int(hist[i]) + t + 1
        lengths = jnp.asarray(hist + chunk, jnp.int32)
        return q, ka, va, tables, lengths, jnp.asarray(limits), bs

    def _reference(self, q, ka, va, tables, limits, bs):
        b, T, n, d = q.shape
        g = ka.shape[2]
        out = np.zeros((b, T, n, d), np.float32)
        for i in range(b):
            for t in range(T):
                L = int(limits[i, t])
                if L == 0:
                    continue
                rk = [np.asarray(ka[int(tables[i, p // bs]), p % bs],
                                 np.float32) for p in range(L)]
                rv = [np.asarray(va[int(tables[i, p // bs]), p % bs],
                                 np.float32) for p in range(L)]
                k = np.repeat(np.stack(rk), n // g, axis=1)
                v = np.repeat(np.stack(rv), n // g, axis=1)
                s = np.einsum("nd,pnd->np",
                              np.asarray(q[i, t], np.float32), k)
                s /= np.sqrt(d)
                p_ = np.exp(s - s.max(-1, keepdims=True))
                p_ /= p_.sum(-1, keepdims=True)
                out[i, t] = np.einsum("np,pnd->nd", p_, v)
        return out

    def test_fused_matches_unfused_and_reference(self):
        q, ka, va, tables, lengths, limits, bs = self._case()
        fused = paged_prefill_attention(q, ka, va, tables, lengths,
                                        limits)
        unfused = paged_prefill_attention_unfused(
            q, ka, va, tables, lengths, limits)
        np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                                   atol=2e-5)
        ref = self._reference(q, ka, va, tables, limits, bs)
        np.testing.assert_allclose(np.asarray(fused), ref, atol=2e-5)
        # the all-padding slot (limit 0 everywhere) emits exact zeros
        assert np.abs(np.asarray(fused[1])).max() == 0.0

    def test_int8_scales(self):
        q, ka, va, tables, lengths, limits, bs = self._case()
        qk, sk = _int8_quantize(np.asarray(ka))
        qv, sv = _int8_quantize(np.asarray(va))
        fused = paged_prefill_attention(
            q, jnp.asarray(qk), jnp.asarray(qv), tables, lengths, limits,
            k_scales=jnp.asarray(sk), v_scales=jnp.asarray(sv))
        unfused = paged_prefill_attention_unfused(
            q, jnp.asarray(qk), jnp.asarray(qv), tables, lengths, limits,
            k_scales=jnp.asarray(sk), v_scales=jnp.asarray(sv))
        np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                                   atol=2e-5)
        ref = self._reference(q, ka, va, tables, limits, bs)
        np.testing.assert_allclose(np.asarray(fused), ref, atol=0.05)


class TestFusedEpilogue:
    def test_matches_unfused(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(3, 2, 128), jnp.float32)
        res = jnp.asarray(rng.randn(3, 2, 128), jnp.float32)
        w = jnp.asarray(rng.randn(128), jnp.float32)
        bl = jnp.asarray(rng.randn(128), jnp.float32)
        bias = jnp.asarray(rng.randn(128), jnp.float32)
        for b in (bias, None):
            y1, r1 = fused_residual_norm(x, res, w, bl, bias=b)
            y2, r2 = residual_norm_unfused(x, res, w, bl, bias=b)
            np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                                       atol=1e-6)

    def test_bf16_wire_dequant(self):
        """bf16 projection output (the 'dequant' input) normalizes in
        fp32 — the fused result must match the unfused fp32-math twin
        at bf16 resolution."""
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(4, 128), jnp.bfloat16)
        res = jnp.asarray(rng.randn(4, 128), jnp.float32)
        w = jnp.ones((128,), jnp.float32)
        bl = jnp.zeros((128,), jnp.float32)
        y1, r1 = fused_residual_norm(x, res, w, bl)
        y2, r2 = residual_norm_unfused(x, res, w, bl)
        np.testing.assert_allclose(np.asarray(y1, np.float32),
                                   np.asarray(y2, np.float32), atol=1e-2)
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r2))


# -------------------------------------------------------------- allocator


class TestBlockAllocator:
    def test_alloc_free_roundtrip_and_invariants(self):
        al = BlockAllocator(10)
        a = al.alloc(4, owner="a")
        b = al.alloc(6, owner="b")
        assert sorted(a + b) == list(range(10)) and al.n_free == 0
        al.check()
        al.free(a, owner="a")
        assert al.n_free == 4
        al.check()
        c = al.alloc(3, owner="c")
        assert set(c) <= set(a)        # LIFO reuse of the freed blocks
        al.check()

    def test_exhaustion_is_atomic(self):
        al = BlockAllocator(4)
        al.alloc(3, owner="x")
        with pytest.raises(OutOfBlocksError):
            al.alloc(2, owner="y")
        assert al.n_free == 1          # failed alloc took nothing
        al.check()

    def test_double_free_and_foreign_free_raise(self):
        al = BlockAllocator(4)
        blocks = al.alloc(2, owner="a")
        al.free(blocks, owner="a")
        with pytest.raises(ValueError, match="double free"):
            al.free(blocks, owner="a")
        more = al.alloc(1, owner="b")
        with pytest.raises(ValueError, match="owned by"):
            al.free(more, owner="intruder")
        al.check()

    def test_fragmentation_free_by_construction(self):
        """Interleaved alloc/free churn: any n <= n_free request always
        succeeds (fixed-size blocks cannot strand capacity) and the
        free/owned partition stays exact."""
        rng = np.random.RandomState(3)
        al = BlockAllocator(32)
        held = {}
        for step in range(200):
            if held and (al.n_free == 0 or rng.rand() < 0.45):
                key = rng.choice(list(held))
                al.free(held.pop(key), owner=key)
            else:
                n = int(rng.randint(1, 6))
                if n <= al.n_free:     # the ONLY admission question
                    key = f"r{step}"
                    held[key] = al.alloc(n, owner=key)
            al.check()
        assert al.n_free + al.n_owned == 32

    # ------------------------- ISSUE 12: refcount / copy-on-write

    def test_shared_free_decrements_not_releases(self):
        """The copy-on-write invariant: freeing a shared block removes
        one holder — the block returns to the pool only from its LAST
        holder."""
        al = BlockAllocator(4)
        (b,) = al.alloc(1, owner="writer")
        al.share(b, "reader")
        assert al.refcount(b) == 2
        al.free([b], owner="writer")      # decrement, NOT release
        assert al.n_free == 3 and al.refcount(b) == 1
        al.check()
        # the writer's hold is gone: a second writer-free is foreign
        with pytest.raises(ValueError, match="owned by"):
            al.free([b], owner="writer")
        al.free([b], owner="reader")      # last holder -> pool
        assert al.n_free == 4 and al.refcount(b) == 0
        with pytest.raises(ValueError, match="double free"):
            al.free([b], owner="reader")
        al.check()

    def test_share_guards(self):
        al = BlockAllocator(2)
        (b,) = al.alloc(1, owner="a")
        with pytest.raises(ValueError, match="free block"):
            al.share(1, "a")              # block 1 was never allocated
        with pytest.raises(ValueError, match="already holds"):
            al.share(b, "a")              # double hold by one owner
        al.check()

    def test_churn_with_sharing_strands_no_capacity(self):
        """200 interleaved alloc/share/free steps: the refcounts must
        drain exactly — at every step free + held partitions the pool,
        and full release returns everything."""
        rng = np.random.RandomState(9)
        al = BlockAllocator(24)
        held = {}                # owner -> list of blocks (ref held)
        for step in range(200):
            r = rng.rand()
            if held and (al.n_free == 0 or r < 0.35):
                key = rng.choice(list(held))
                al.free(held.pop(key), owner=key)
            elif held and r < 0.55:
                # a new owner shares a random existing holder's blocks
                # (the prefix-cache hit shape)
                src = rng.choice(list(held))
                key = f"s{step}"
                for b in held[src]:
                    al.share(b, key)
                held[key] = list(held[src])
            else:
                n = int(rng.randint(1, 5))
                if n <= al.n_free:
                    key = f"r{step}"
                    held[key] = al.alloc(n, owner=key)
            al.check()
        for key in list(held):
            al.free(held.pop(key), owner=key)
        al.check()
        assert al.n_free == 24 and al.n_owned == 0


class TestPrefixCache:
    """The token-hash index over shared blocks (ISSUE 12)."""

    def test_lookup_shares_longest_chain_and_caps(self):
        al = BlockAllocator(8)
        pc = PrefixCache(al, block_size=4)
        toks = list(range(10, 22))           # 12 tokens = 3 full blocks
        blocks = al.alloc(3, owner="w")
        pc.insert(toks, blocks, upto_tokens=12)
        assert len(pc) == 3
        # identical prompt: capped so >= 1 token is left to recompute
        hit = pc.lookup(toks, "r", max_blocks=(len(toks) - 1) // 4)
        assert hit == blocks[:2] and pc.hits == 2
        assert all(al.refcount(b) == 3 for b in hit)  # w + cache + r
        # divergent second block: only the first block chains
        other = toks[:4] + [99] * 8
        hit2 = pc.lookup(other, "r2", max_blocks=2)
        assert hit2 == blocks[:1]
        al.free(hit, "r")
        al.free(hit2, "r2")
        pc.check()

    def test_insert_only_covers_written_tokens(self):
        """Blocks whose K/V has not landed must not be indexed — a
        same-tick hit would read garbage."""
        al = BlockAllocator(8)
        pc = PrefixCache(al, block_size=4)
        toks = list(range(8))
        blocks = al.alloc(2, owner="w")
        pc.insert(toks, blocks, upto_tokens=5)   # only block 0 complete
        assert len(pc) == 1
        pc.insert(toks, blocks, upto_tokens=8)   # chunk 2 lands
        assert len(pc) == 2

    def test_blocked_admit_rolls_back_hit_accounting(self):
        """A FIFO head that hits the cache but cannot admit (pool full)
        hands its shared refs back AND un-counts the hits — a head
        stuck for N ticks must not inflate serving/prefix_cache_hits N
        times with blocks that were never served."""
        from apex_tpu.serving.kv_cache import KVCacheConfig
        from apex_tpu.serving.scheduler import Scheduler

        cache = KVCacheConfig(n_layers=1, n_blocks=4, block_size=4,
                              kv_heads=1, head_dim=8, max_seq=32)
        sched = Scheduler(cache, max_batch=3, chunk_tokens=8)
        a = sched.submit(list(range(8)), 4)          # 2 full blocks
        hog = sched.submit(list(range(20, 27)), 4)   # 2 more blocks
        assert sched.admit() == [a, hog]
        sched.note_prefilled(a, 8)     # a's 2 prompt blocks now cached
        assert len(sched.prefix_cache) == 2
        c = sched.submit(list(range(8)), 4)          # would hit a's chain
        for _ in range(5):             # pool is full: head blocks
            assert sched.admit() == []
        assert sched.prefix_cache.hits == 0, \
            "phantom hits counted for blocks that were handed back"
        sched.allocator.check()
        # capacity appears -> the head admits and the hit finally counts
        sched.note_prefilled(hog, 7)
        sched.finish(a)
        sched.finish(hog)
        assert sched.admit() == [c]
        assert c.hit_blocks == 1 and sched.prefix_cache.hits == 1

    def test_evict_is_lru_and_skips_shared(self):
        al = BlockAllocator(8)
        pc = PrefixCache(al, block_size=4)
        # 5-token sequences: one full shareable block each, one token
        # always left to recompute (the enforced CoW cap)
        a_toks, b_toks = [1] * 4 + [9], [2] * 4 + [9]
        (a,) = al.alloc(1, owner="wa")
        (b,) = al.alloc(1, owner="wb")
        pc.insert(a_toks, [a], 4)
        pc.insert(b_toks, [b], 4)
        al.free([a], "wa")
        al.free([b], "wb")          # both now cache-only (evictable)
        assert pc.lookup(a_toks, "reader") == [a]   # a: shared + MRU
        assert pc.evictable() == 1
        assert pc.evict_one() == b  # LRU *sole-holder* entry
        assert pc.evict_one() is None   # a is shared: not evictable
        assert pc.evict_many(4) == 0    # the sweep skips it too
        al.free([a], "reader")
        assert pc.evict_many(4) == 1    # now sole-holder: one sweep
        assert al.n_free == 8 and pc.evictions == 2
        pc.check()

    def test_lookup_enforces_the_recompute_cap(self):
        """A block-aligned prompt must never be fully served from
        cache — lookup itself caps at (len-1)//block_size even when the
        caller passes no max_blocks (writes stay off shared blocks by
        construction)."""
        al = BlockAllocator(8)
        pc = PrefixCache(al, block_size=4)
        toks = list(range(8))                 # exactly 2 full blocks
        blocks = al.alloc(2, owner="w")
        pc.insert(toks, blocks, 8)
        assert pc.lookup(toks, "r") == blocks[:1]   # never both
        al.free(blocks[:1], "r")


# ----------------------------------------------------------------- engine


def _tiny_cfg(**kw):
    base = dict(
        hidden_size=32, num_layers=2, num_attention_heads=4,
        padded_vocab_size=VOCAB, max_position_embeddings=MAX_SEQ,
        hidden_dropout=0.0, attention_dropout=0.0, tensor_axis="tp",
        use_flash_attention=True)
    base.update(kw)
    return TransformerConfig(**base)


# (mesh, cfg, params) per model config, shared across this module's
# engine tests: the param init is an ~8s XLA compile and every engine
# test would otherwise pay it again.  The cached Mesh object stays
# valid after the autouse registry teardown (only the registration is
# global state), and params are read-only inputs to every engine.
_MODEL_CACHE = {}


def _model(tp, **cfg_kw):
    key = (tp, tuple(sorted(cfg_kw.items())))
    if key not in _MODEL_CACHE:
        mesh = parallel.initialize_model_parallel(
            tensor_model_parallel_size=tp,
            devices=jax.devices()[:max(tp, 1)])
        cfg = _tiny_cfg(**cfg_kw)
        init_fn, _, _ = build_gpt_3d(cfg, num_chunks=cfg.num_layers,
                                     num_microbatches=1, mesh=mesh)
        params, _ = init_fn(jax.random.PRNGKey(0),
                            jnp.zeros((2, 4), jnp.int32))
        _MODEL_CACHE[key] = (mesh, cfg, params)
    return _MODEL_CACHE[key]


def _build_engine(tp, serving=None, **cfg_kw):
    mesh, cfg, params = _model(tp, **cfg_kw)
    serving = serving or ServingConfig(max_batch=3, block_size=4,
                                       max_seq=MAX_SEQ, prefill_len=MAX_SEQ)
    from apex_tpu.observability.metrics import MetricRegistry

    eng = ServingEngine(cfg, serving, params, mesh=mesh,
                        registry=MetricRegistry())
    return mesh, cfg, eng


def _sampling_zeros(B):
    """Greedy policy arrays (temperature 0) for direct program calls."""
    return (np.zeros((B,), np.float32), np.zeros((B,), np.int32),
            np.ones((B,), np.float32), np.zeros((B,), np.uint32),
            np.zeros((B,), np.int32))


def _teacher_forced_parity(eng, seq, prefix_len):
    """Prefill ``seq[:prefix_len]``, then decode the rest teacher-forced;
    every step's logits must match a fresh full prefill of the prefix."""
    from apex_tpu.serving.kv_cache import init_kv_arena

    cache = eng.cache
    bs = cache.block_size
    B, T = eng.serving.max_batch, eng.prefill_len
    mb = cache.max_blocks_per_request
    blocks = list(range(mb))
    tables = np.zeros((B, mb), np.int32)
    tables[0, :mb] = blocks

    def prefill_logits(upto, arenas):
        tokens = np.zeros((B, T), np.int32)
        tokens[0, :upto] = seq[:upto]
        pos = np.zeros((B, T), np.int32)
        pos[0, :upto] = np.arange(upto)
        limits = np.zeros((B, T), np.int32)
        limits[0, :upto] = np.arange(1, upto + 1)
        lengths = np.zeros((B,), np.int32)
        lengths[0] = upto
        db = np.full((B, T), cache.n_blocks, np.int32)
        do = np.zeros((B, T), np.int32)
        db[0, :upto] = [blocks[t // bs] for t in range(upto)]
        do[0, :upto] = [t % bs for t in range(upto)]
        sample_index = np.full((B,), T, np.int32)
        return eng._prefill(arenas, eng.params, tokens, pos,
                            jnp.asarray(tables), lengths, limits, db, do,
                            sample_index, *_sampling_zeros(B))

    arenas, _, _ = prefill_logits(prefix_len, eng.arenas)
    max_err = 0.0
    for t in range(prefix_len, len(seq)):
        toks = np.zeros((B, eng.spec_width), np.int32)
        toks[0, 0] = seq[t]
        pos = np.zeros((B,), np.int32)
        pos[0] = t
        act = np.zeros((B,), bool)
        act[0] = True
        arenas, _, _, logits = eng._decode(
            arenas, eng.params, toks, pos, jnp.asarray(tables), act,
            np.zeros((B,), np.int32), *_sampling_zeros(B))
        arenas2 = init_kv_arena(cache, eng.mesh, eng.tp_axis)
        _, _, full = prefill_logits(t + 1, arenas2)
        err = float(jnp.max(jnp.abs(logits[0, 0] - full[0, t])))
        max_err = max(max_err, err)
    return max_err


def test_decode_vs_prefill_logits_parity_tp1():
    _, _, eng = _build_engine(tp=1)
    seq = np.asarray([5, 9, 33, 12, 44, 2, 17, 60], np.int32)
    err = _teacher_forced_parity(eng, seq, prefix_len=3)
    assert err < 2e-4, err


@pytest.mark.slow
def test_decode_vs_prefill_logits_parity_tp2():
    _, _, eng = _build_engine(tp=2, num_query_groups=2,
                              position_embedding_type="rope")
    seq = np.asarray([5, 9, 33, 12, 44, 2, 17, 60, 21], np.int32)
    err = _teacher_forced_parity(eng, seq, prefix_len=4)
    assert err < 2e-4, err


def test_join_leave_churn_zero_recompiles():
    """Requests joining and leaving mid-flight never change a shape:
    the decode executable compiles exactly once, the fused and unfused
    paths emit identical tokens, and the pool drains clean."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, VOCAB - 1,
                           size=rng.randint(2, 10)).tolist()
               for _ in range(6)]

    def run(fused):
        _, _, eng = _build_engine(
            tp=1, serving=ServingConfig(
                max_batch=2, block_size=4, max_seq=MAX_SEQ,
                prefill_len=MAX_SEQ, fused_attention=fused,
                fuse_epilogue=fused))
        reqs = [eng.submit(prompts[0], 5), eng.submit(prompts[1], 3)]
        pending = iter(prompts[2:])
        for step in range(60):
            if step % 2 == 1:
                p = next(pending, None)
                if p is not None:
                    reqs.append(eng.submit(p, 2 + step % 4))
            eng.step()
            if eng.scheduler.idle and len(reqs) == len(prompts):
                break
        eng.run_until_drained()
        assert eng.decode_compile_count() == 1
        assert eng.prefill_compile_count() == 1
        eng.scheduler.allocator.check()
        # a drained pool is free blocks + prefix-cached blocks (finished
        # requests' full blocks stay behind as evictable capacity)
        al = eng.scheduler.allocator
        pc = eng.scheduler.prefix_cache
        assert al.n_free + pc.n_blocks == al.n_blocks
        assert all(al.refcount(b) == 1
                   for b in pc._entries.values())   # cache-only holds
        pc.check()
        return [r.output_tokens for r in reqs]

    assert run(True) == run(False)


def test_preemption_drain_delivers_in_flight():
    from apex_tpu.resilience import PreemptionGuard
    from apex_tpu.serving.scheduler import RequestState

    guard = PreemptionGuard(signals=())   # programmatic trigger only
    _, _, eng = _build_engine(
        tp=1, serving=ServingConfig(max_batch=2, block_size=4,
                                    max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    eng.guard = guard
    running = [eng.submit([3, 5, 7], 4), eng.submit([11, 13], 4)]
    eng.step()                             # both admitted + first tokens
    queued = [eng.submit([17, 19], 4)]
    guard.trigger()                        # preemption notice
    eng.run_until_drained(max_steps=100)
    assert eng.draining
    for req in running:
        assert req.state is RequestState.FINISHED
        assert len(req.output_tokens) == 4
    assert queued[0].state is RequestState.CANCELLED
    # a post-drain submit is REJECTED at the door (typed, distinct from
    # the drain cancellation of the already-queued request) and counted
    # in its own catalog entry — the signal a fleet router re-routes on
    late = eng.submit([2, 4], 2)
    assert late.state is RequestState.REJECTED
    assert late.done
    # metrics recorded through the registry (catalog: docs/serving.md)
    snap = eng.registry.snapshot()
    assert snap["serving/requests_cancelled"] == 1.0
    assert snap["serving/requests_rejected"] == 1.0
    assert snap["serving/requests_finished"] == 2.0
    assert snap["serving/tpot_ms"]["count"] > 0


def test_cache_dtype_bf16_serves():
    """bf16 KV arena (half the cache HBM; in-kernel dequant) still
    decodes the same greedy tokens as the fp32 cache on this tiny
    model."""
    def run(dtype):
        _, _, eng = _build_engine(
            tp=1, serving=ServingConfig(
                max_batch=2, block_size=4, max_seq=MAX_SEQ,
                prefill_len=MAX_SEQ, cache_dtype=dtype))
        r = eng.submit([5, 6, 7, 8, 9], 4)
        eng.run_until_drained()
        return r.output_tokens

    assert run(jnp.bfloat16) == run(jnp.float32)


# ------------------------------------------------- ISSUE 12: occupancy


def _wave(seed=5, n=6):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, VOCAB - 1, size=rng.randint(4, 14)).tolist(),
             int(rng.randint(6, 14))) for _ in range(n)]


def _run_wave(wave, *, n_blocks=None, admission="occupancy",
              prefill_len=8, sampling=None, cache_dtype=None,
              speculative=None, proposer=None):
    _, _, eng = _build_engine(
        tp=1, serving=ServingConfig(
            max_batch=4, block_size=4, max_seq=MAX_SEQ,
            prefill_len=prefill_len, n_blocks=n_blocks,
            admission=admission, cache_dtype=cache_dtype,
            speculative=speculative))
    if proposer is not None:
        eng.proposer = proposer
    reqs = [eng.submit(p, n, sampling=sampling) for p, n in wave]
    eng.run_until_drained(max_steps=2000)
    eng.scheduler.allocator.check()
    assert eng.decode_compile_count() == 1
    assert eng.prefill_compile_count() == 1
    return eng, [r.output_tokens for r in reqs]


def test_occupancy_2x_oversubscription_finishes_all():
    """The ISSUE 12 acceptance bar: with the pool at a fraction of the
    worst-case demand, occupancy admission (grow + evict + preempt with
    recompute-on-readmit) still FINISHES every admitted request, with
    streams token-identical to an ample-pool run, zero recompiles, and
    the preemption machinery demonstrably exercised."""
    wave = _wave()
    _, ref = _run_wave(wave)                      # ample pool
    worst = sum(-(-min(len(p) + n, MAX_SEQ) // 4) for p, n in wave)
    eng, over = _run_wave(wave, n_blocks=max(8, worst // 4))
    assert over == ref
    assert all(r.state.value == "finished"
               for r in eng.scheduler.running() or []) or \
        eng.scheduler.idle
    assert eng.scheduler.preemptions > 0, \
        "the undersized pool never preempted — the test is not testing"
    assert eng.scheduler.prefix_cache.evictions > 0
    snap = eng.registry.snapshot()
    assert snap["serving/preemptions"] == eng.scheduler.preemptions
    assert snap["serving/evictions"] == eng.scheduler.prefix_cache.evictions


def test_reserve_admission_is_the_pr8_baseline():
    """admission='reserve' keeps worst-case reservation: same outputs,
    no prefix cache, zero preemptions (requests just queue longer)."""
    wave = _wave()
    _, ref = _run_wave(wave)
    worst = sum(-(-min(len(p) + n, MAX_SEQ) // 4) for p, n in wave)
    eng, res = _run_wave(wave, n_blocks=max(8, worst // 4),
                         admission="reserve")
    assert res == ref
    assert eng.scheduler.preemptions == 0
    assert eng.scheduler.prefix_cache is None
    assert eng.scheduler.allocator.n_free == \
        eng.scheduler.allocator.n_blocks      # reserve frees fully


def test_prefix_cache_hit_shares_blocks_and_matches_cold():
    """A repeated prompt prefix hits the cache: blocks shared (counted
    in serving/prefix_cache_hits), outputs identical to the cold run."""
    _, _, eng = _build_engine(
        tp=1, serving=ServingConfig(max_batch=2, block_size=4,
                                    max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    template = [7, 11, 13, 17, 19, 23, 29, 31]     # two full blocks
    cold = eng.submit(template + [3], 4)
    eng.run_until_drained()
    assert cold.hit_blocks == 0
    warm = eng.submit(template + [5], 4)
    eng.run_until_drained()
    assert warm.hit_blocks == 2                    # both template blocks
    # identical full prompt: the whole prefix short of the cap is shared
    again = eng.submit(template + [3], 4)
    eng.run_until_drained()
    assert again.hit_blocks == 2
    assert again.output_tokens == cold.output_tokens
    snap = eng.registry.snapshot()
    assert snap["serving/prefix_cache_hits"] >= 4
    assert eng.introspect()["prefix_cached_blocks"] > 0
    eng.scheduler.prefix_cache.check()


def test_chunked_prefill_matches_one_shot():
    """A prompt longer than the chunk width slices across ticks and
    produces exactly the one-shot engine's stream (and compiles the
    prefill exactly once)."""
    wave = [(list(range(1, 25)), 5), ([30, 31], 3)]   # 24 > chunk of 4
    _, one_shot = _run_wave(wave, prefill_len=MAX_SEQ)
    eng, chunked = _run_wave(wave, prefill_len=4)
    assert chunked == one_shot


def test_sampling_policies_reproducible_and_data_only():
    """Seeded sampling redraws the same stream; top_k=1 degenerates to
    greedy; mixing policies in one batch is data, never shape (zero
    decode recompiles across the whole mix)."""
    wave = [([9, 8, 7], 6), ([4, 5], 6)]
    sp = SamplingParams(temperature=1.5, top_p=0.9, seed=42)
    _, a = _run_wave(wave, sampling=sp, prefill_len=MAX_SEQ)
    _, b = _run_wave(wave, sampling=sp, prefill_len=MAX_SEQ)
    assert a == b                                   # same seeds, same stream
    _, greedy = _run_wave(wave, prefill_len=MAX_SEQ)
    _, k1 = _run_wave(wave, prefill_len=MAX_SEQ,
                      sampling=SamplingParams(temperature=2.0, top_k=1,
                                              seed=7))
    assert k1 == greedy                             # only the argmax survives
    # mixed policies in ONE engine: churn through greedy + sampled slots
    _, _, eng = _build_engine(
        tp=1, serving=ServingConfig(max_batch=4, block_size=4,
                                    max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    r1 = eng.submit([9, 8, 7], 6)
    r2 = eng.submit([9, 8, 7], 6, sampling=sp)
    r3 = eng.submit([9, 8, 7], 6,
                    sampling=SamplingParams(temperature=0.7, top_k=4,
                                            seed=3))
    eng.run_until_drained()
    assert eng.decode_compile_count() == 1
    assert r1.output_tokens == greedy[0][:6] or len(r1.output_tokens) == 6
    assert all(0 <= t < VOCAB for r in (r1, r2, r3)
               for t in r.output_tokens)
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0)


def test_sampled_stream_survives_preemption():
    """The seeded-counter construction: a preempted sampled request
    replayed through the chunked prefill redraws the SAME stream —
    recompute-on-readmit does not fork a stochastic stream."""
    wave = _wave(seed=12, n=5)
    sp = SamplingParams(temperature=1.0, top_p=0.95, seed=99)
    _, ample = _run_wave(wave, sampling=sp)
    worst = sum(-(-min(len(p) + n, MAX_SEQ) // 4) for p, n in wave)
    eng, tight = _run_wave(wave, sampling=sp, n_blocks=max(8, worst // 4))
    assert eng.scheduler.preemptions > 0
    assert tight == ample


def test_int8_cache_greedy_identity():
    """int8 KV (per-row scales, in-kernel dequant) emits the same
    greedy tokens as the fp32 cache on this model — including under
    occupancy pressure."""
    wave = _wave(seed=3, n=5)
    _, fp32 = _run_wave(wave)
    eng, i8 = _run_wave(wave, cache_dtype=jnp.int8)
    assert i8 == fp32
    assert eng.cache.quantized and len(eng.arenas) == 4
    worst = sum(-(-min(len(p) + n, MAX_SEQ) // 4) for p, n in wave)
    eng2, i8_tight = _run_wave(wave, cache_dtype=jnp.int8,
                               n_blocks=max(8, worst // 4))
    assert i8_tight == fp32
    assert eng2.scheduler.preemptions + \
        eng2.scheduler.prefix_cache.evictions > 0


def test_serving_config_validates_admission():
    with pytest.raises(ValueError, match="admission"):
        ServingConfig(admission="optimistic")


@pytest.mark.slow
def test_restore_train_mesh_to_serving_mesh():
    """Train-side [vpp=1, pp=2] layer stack restores bit-exactly onto
    the serving mesh's [L, 1] stack through the PR 6 spec layer, and
    the engine serves from the restored params."""
    import shutil
    import tempfile

    from apex_tpu.parallel import mesh as mesh_lib
    from apex_tpu.resilience import CheckpointManager, reshard
    from apex_tpu.serving.loader import restore_gpt_for_serving
    from apex_tpu.transformer.testing.gpt_parallel_train import (
        gpt3d_logical_folds,
    )

    cfg = _tiny_cfg()
    workdir = tempfile.mkdtemp(prefix="apex_serving_restore_")
    try:
        mesh = parallel.initialize_model_parallel(
            tensor_model_parallel_size=2, pipeline_model_parallel_size=2,
            devices=jax.devices()[:4])
        init_fn, _, _ = build_gpt_3d(cfg, num_chunks=1,
                                     num_microbatches=1, mesh=mesh)
        params, _ = init_fn(jax.random.PRNGKey(0),
                            jnp.zeros((2, 4), jnp.int32))
        tree = {"params": params, "step_count": np.asarray(7)}
        spec = reshard.build_spec(tree, mesh=mesh,
                                  folds=gpt3d_logical_folds(tree))
        CheckpointManager(workdir, sharded=True, spec=spec).save(tree, 7)
        train_host = jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), params)
        mesh_lib.destroy_model_parallel()

        mesh = parallel.initialize_model_parallel(
            tensor_model_parallel_size=2, devices=jax.devices()[:2])
        sparams, _ = restore_gpt_for_serving(workdir, cfg, mesh=mesh)
        serve_host = jax.tree_util.tree_map(
            lambda x: np.asarray(jax.device_get(x)), sparams)
        L = cfg.num_layers
        for a, b in zip(jax.tree_util.tree_leaves(train_host.layers),
                        jax.tree_util.tree_leaves(serve_host.layers)):
            assert np.array_equal(a.reshape((L,) + a.shape[2:]),
                                  b.reshape((L,) + b.shape[2:]))
        for a, b in zip(
                jax.tree_util.tree_leaves(train_host.embedding),
                jax.tree_util.tree_leaves(serve_host.embedding)):
            assert np.array_equal(a, b)

        eng = ServingEngine(
            cfg, ServingConfig(max_batch=2, block_size=4, max_seq=MAX_SEQ,
                               prefill_len=MAX_SEQ),
            sparams, mesh=mesh)
        r = eng.submit([5, 6, 7, 8], 3)
        eng.run_until_drained()
        assert len(r.output_tokens) == 3
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def test_scheduler_rejects_unserviceable_request():
    """A request whose worst-case block need exceeds the WHOLE pool can
    never be admitted — accepting it would park it at the head of the
    FIFO queue forever, starving everything behind it.  Rejected at
    submit, with serviceable requests unaffected."""
    from apex_tpu.serving.kv_cache import KVCacheConfig
    from apex_tpu.serving.scheduler import Scheduler

    cache = KVCacheConfig(n_layers=1, n_blocks=4, block_size=4,
                          kv_heads=1, head_dim=8, max_seq=64)
    sched = Scheduler(cache, max_batch=2)
    with pytest.raises(ValueError, match="worst-case"):
        sched.submit(list(range(1, 21)), 20)   # 10 blocks > 4 in pool
    ok = sched.submit([1, 2, 3], 4)            # 2 blocks: queues fine
    assert sched.admit() == [ok]


def test_engine_rejects_oversized_prompt_and_position_table():
    _, cfg, eng = _build_engine(tp=1)
    # chunked prefill removed the prefill_len bound (a long prompt just
    # slices across ticks); the context cap is the one real limit
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(list(range(MAX_SEQ + 4)), 2)
    with pytest.raises(ValueError, match="max_seq"):
        ServingEngine(cfg, ServingConfig(max_batch=2, block_size=4,
                                         max_seq=MAX_SEQ * 8),
                      eng_params_of(eng), mesh=eng.mesh)


def eng_params_of(eng):
    """Re-wrap engine params into the [vpp=L, pp=1] canonical input."""
    params = eng.params
    return params._replace(layers=jax.tree_util.tree_map(
        lambda l: l.reshape((l.shape[0], 1) + l.shape[1:]), params.layers))


# ------------------------------------------------- ISSUE 10: observability


def test_heartbeat_hung_decode_triggers_drain():
    """ISSUE 10 satellite: the heartbeat armed on the decode loop.  A
    device step that wedges (parked behind an event, the
    faults.hung_writes shape applied to the decode dispatch) stops the
    beats; the monitor's on_hang fires the PreemptionGuard, and the
    engine's next alive step() DRAINS — in-flight requests deliver,
    the queue cancels — instead of the scheduler wedging forever."""
    import threading

    from apex_tpu.observability.metrics import HeartbeatMonitor
    from apex_tpu.resilience import PreemptionGuard
    from apex_tpu.serving.scheduler import RequestState

    guard = PreemptionGuard(signals=())
    hb = HeartbeatMonitor(timeout_s=0.05, on_hang=guard)
    _, _, eng = _build_engine(
        tp=1, serving=ServingConfig(max_batch=2, block_size=4,
                                    max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    eng.guard = guard
    eng.heartbeat = hb

    running = [eng.submit([3, 5, 7], 6), eng.submit([11, 13], 6)]
    eng.step()                        # healthy tick: beat recorded
    queued = [eng.submit([17, 19], 4)]
    assert hb.last_step == 1 and not hb.check_now()

    # park the NEXT decode mid-flight on another thread (the hung
    # device step); the main thread plays the monitor's poll loop
    gate = threading.Event()
    real_decode = eng._decode

    def parked_decode(*args):
        gate.wait()
        return real_decode(*args)

    eng._decode = parked_decode
    t = threading.Thread(target=eng.step, daemon=True)
    t.start()
    deadline = time.monotonic() + 10.0
    while not hb.check_now():         # deterministic poll, no bg thread
        assert time.monotonic() < deadline, "hang never detected"
        time.sleep(0.01)
    assert guard.triggered, "on_hang must fire the guard"
    # the wedge clears (preempted hosts come back long enough to drain)
    gate.set()
    t.join(timeout=30)
    assert not t.is_alive()
    eng._decode = real_decode
    eng.run_until_drained(max_steps=100)
    assert eng.draining
    for req in running:
        assert req.state is RequestState.FINISHED
        assert len(req.output_tokens) == req.max_new_tokens
    assert queued[0].state is RequestState.CANCELLED
    assert hb.hang_count == 1
    assert int(eng.registry.counter(
        "serving/preemption_drains").value) == 1


def test_engine_timeline_lifecycle_and_goodput():
    """With a flight recorder armed, every request leaves a complete
    submit -> admit -> prefill -> decode ticks -> finish trail keyed by
    rid, and serving_goodput_report closes the books over it."""
    from apex_tpu.observability import timeline
    from apex_tpu.observability.goodput import serving_goodput_report
    from apex_tpu.observability.timeline import FlightRecorder

    rec = timeline.arm(FlightRecorder())
    try:
        _, _, eng = _build_engine(
            tp=1, serving=ServingConfig(max_batch=2, block_size=4,
                                        max_seq=MAX_SEQ,
                                        prefill_len=MAX_SEQ))
        eng.timeline_tick_every = 2
        reqs = [eng.submit([3, 5, 7], 5), eng.submit([11, 13], 3)]
        eng.run_until_drained()
        events = rec.events()
        for req in reqs:
            mine = [e for e in events if e.get("rid") == req.rid]
            kinds = [e["kind"] for e in mine]
            assert kinds[0] == "request_submit"
            assert "request_admit" in kinds
            assert kinds[-1] == "request_finish"
            assert any(k == "decode_tick" for k in kinds)
            ticks = [e["tokens"] for e in mine
                     if e["kind"] == "decode_tick"]
            assert all(n % 2 == 0 for n in ticks)  # sampled every 2
        prefills = [e for e in events if e["kind"] == "prefill"]
        assert prefills and "dur_s" in prefills[0]
        assert sorted(r for e in prefills for r in e["rids"]) == \
            sorted(r.rid for r in reqs)
        rep = serving_goodput_report(events)
        assert rep["totals"]["finished"] == 2
        assert rep["totals"]["cancelled"] == 0
        assert rep["goodput_fraction"] is not None
        assert 0.0 < rep["goodput_fraction"] <= 1.0
    finally:
        timeline.disarm()


def test_engine_introspect_and_mfu_reason():
    """introspect() (the /statusz payload) reports live slots/blocks/
    queue plus MFU-or-reason; on the CPU test mesh the reason must name
    the unknown platform peak, never fabricate a number (and the
    serving/mfu gauge stays unset)."""
    _, _, eng = _build_engine(
        tp=1, serving=ServingConfig(max_batch=2, block_size=4,
                                    max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    snap = eng.introspect()
    assert snap["steps"] == 0 and snap["mfu_reason"] is not None
    eng.submit([3, 5, 7], 3)
    eng.step()
    snap = eng.introspect()
    assert snap["active_slots"] == 1
    assert snap["queue_depth"] == 0
    assert snap["decode_compiles"] == 1
    assert snap["free_blocks"] < snap["total_blocks"]
    assert snap["last_decode_ms"] is not None
    # CPU: flops may exist (XLA:CPU reports them) but the peak is
    # undefined -> mfu None with the platform named
    assert snap["mfu"] is None
    assert "cpu" in snap["mfu_reason"]
    assert eng.registry.gauge("serving/mfu").value is None
    eng.run_until_drained()
    assert eng.introspect()["active_slots"] == 0
    assert eng.decode_compile_count() == 1, \
        "the MFU lowering probe must not add a decode compile"


def test_engine_statusz_through_debug_server():
    """The debug server serves the live engine: /statusz carries the
    introspection dict while requests are in flight."""
    import json as _json
    import urllib.request

    from apex_tpu.observability import DebugServer
    from apex_tpu.observability.metrics import MetricRegistry

    _, _, eng = _build_engine(
        tp=1, serving=ServingConfig(max_batch=2, block_size=4,
                                    max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    eng.submit([3, 5, 7], 4)
    eng.step()
    with DebugServer(registry=eng.registry, engine=eng) as srv:
        body = _json.loads(urllib.request.urlopen(
            srv.url("/statusz"), timeout=10).read())
        metrics = urllib.request.urlopen(
            srv.url("/metrics"), timeout=10).read().decode()
    assert body["serving"]["active_slots"] == 1
    assert body["serving"]["draining"] is False
    assert "apex_serving_tokens_generated" in metrics
    assert "apex_serving_active_slots" in metrics
    eng.run_until_drained()


# ------------- ISSUE 16: KV export/import (the disaggregation handoff)


def _migrated_stream(sampling=None, spec=False, after=3, n_new=10):
    """Prefill+decode ``after`` tokens on one engine, export/import the
    paged KV into a second engine, finish there; returns the stitched
    stream plus both engines for invariant checks."""
    import dataclasses

    kw = dict(max_batch=3, block_size=4, max_seq=MAX_SEQ,
              prefill_len=MAX_SEQ)
    if spec:
        from apex_tpu.serving.speculative import SpeculativeConfig
        kw["speculative"] = SpeculativeConfig(k=3)
    _, _, src = _build_engine(1, serving=ServingConfig(
        max_batch=3, block_size=4, max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    _, _, dst = _build_engine(1, serving=ServingConfig(**kw))
    prompt = np.arange(1, 9, dtype=np.int32)
    req = src.submit(prompt, max_new_tokens=n_new, sampling=sampling)
    while len(req.output_tokens) < after and not req.done:
        src.step()
    assert not req.done
    pre = list(req.output_tokens)
    meta, payloads = src.export_request(req)
    # the export invariants the router's phase cross-check rests on
    assert meta["n_out"] == len(pre)
    assert meta["cache_len"] == len(prompt) + len(pre) - 1
    assert meta["n_blocks"] == len(payloads) >= 1
    wire = np.concatenate([prompt, np.asarray(pre, np.int32)])
    s2 = sampling
    if s2 is not None:
        s2 = dataclasses.replace(
            s2, step_offset=s2.step_offset + len(pre))
    req2 = dst.import_request(wire, n_new - len(pre), sampling=s2,
                              cache_len=int(meta["cache_len"]),
                              payloads=payloads)
    src.release_export(req.rid, ok=True)
    for _ in range(120):
        dst.step()
        if req2.done:
            break
    assert req2.done
    return pre + list(req2.output_tokens), src, dst


def _single_stream(sampling=None, spec=False, n_new=10):
    kw = dict(max_batch=3, block_size=4, max_seq=MAX_SEQ,
              prefill_len=MAX_SEQ)
    if spec:
        from apex_tpu.serving.speculative import SpeculativeConfig
        kw["speculative"] = SpeculativeConfig(k=3)
    _, _, eng = _build_engine(1, serving=ServingConfig(**kw))
    req = eng.submit(np.arange(1, 9, dtype=np.int32),
                     max_new_tokens=n_new, sampling=sampling)
    for _ in range(120):
        eng.step()
        if req.done:
            break
    assert req.done
    return list(req.output_tokens)


def test_export_import_greedy_bitwise_identity():
    """The tentpole contract at the engine layer: a stream exported
    after 3 tokens and imported into a fresh engine is bitwise the
    single-engine stream — the imported KV plus a one-token re-prefill
    reproduce the exact decode state."""
    single = _single_stream()
    migrated, src, dst = _migrated_stream()
    assert migrated == single
    # refcount story: the pin released into the prefix cache, every
    # block in both pools is free XOR held
    assert len(src.exports) == 0
    src.scheduler.allocator.check()
    dst.scheduler.allocator.check()


def test_export_import_seeded_bitwise_identity():
    """Seeded sampling across the handoff: the rebased ``step_offset``
    keys the destination's draws at the absolute stream position, so
    sampled streams are bitwise identical too."""
    sp = SamplingParams(temperature=0.8, top_k=8, seed=7)
    single = _single_stream(sampling=sp)
    migrated, src, dst = _migrated_stream(sampling=sp)
    assert migrated == single


def test_export_import_speculative_decode_identity():
    """The decode side of a disaggregated fleet runs k-speculative: an
    imported request verified k+1 at a time still matches the plain
    single-engine stream bitwise (speculation is exact)."""
    single = _single_stream()                      # plain greedy engine
    migrated, src, dst = _migrated_stream(spec=True)
    assert migrated == single


def test_export_refused_while_prefilling_or_unstarted():
    """Export demands a quiescent decode-state request: no slot, a
    pending prefill, or zero emitted tokens must refuse (ValueError)
    rather than ship a cache that disagrees with the stream."""
    _, _, eng = _build_engine(1, serving=ServingConfig(
        max_batch=2, block_size=4, max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    req = eng.submit([3, 5, 7], 4)
    with pytest.raises(ValueError):
        eng.export_request(req)        # nothing prefilled yet
    eng.run_until_drained()
    with pytest.raises(ValueError):
        eng.export_request(req)        # finished: no slot anymore


def test_import_shape_mismatch_refused_before_scatter():
    """A payload whose shape disagrees with the arenas must refuse
    BEFORE any device put — a torn/mismatched transfer can never
    corrupt the destination cache."""
    single = _single_stream(n_new=6)   # warm reference engine unused
    _, _, src = _build_engine(1, serving=ServingConfig(
        max_batch=3, block_size=4, max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    _, _, dst = _build_engine(1, serving=ServingConfig(
        max_batch=3, block_size=4, max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    prompt = np.arange(1, 9, dtype=np.int32)
    req = src.submit(prompt, max_new_tokens=6)
    while len(req.output_tokens) < 2:
        src.step()
    meta, payloads = src.export_request(req)
    torn = [tuple(p[:-1]) for p in payloads]       # one slab short
    wire = np.concatenate(
        [prompt, np.asarray(req.output_tokens, np.int32)])
    with pytest.raises(ValueError):
        dst.import_request(wire, 4, cache_len=int(meta["cache_len"]),
                           payloads=torn)
    src.release_export(req.rid, ok=False)
    dst.scheduler.allocator.check()
    src.scheduler.allocator.check()


def test_export_churn_200_steps_leaks_no_blocks():
    """The refcount-hardening satellite: 200 migrate/fail/retry churn
    steps — export, then either abandon (the dies-before-ack shape,
    released not-ok) or land it — and the allocator invariant stays
    free-XOR-held on both pools; stale double-acks are no-ops."""
    _, _, src = _build_engine(1, serving=ServingConfig(
        max_batch=3, block_size=4, max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    _, _, dst = _build_engine(1, serving=ServingConfig(
        max_batch=3, block_size=4, max_seq=MAX_SEQ, prefill_len=MAX_SEQ))
    prompt = np.arange(1, 9, dtype=np.int32)
    for step in range(200):
        req = src.submit(prompt, max_new_tokens=4)
        while len(req.output_tokens) < 2 and not req.done:
            src.step()
        meta, payloads = src.export_request(req)
        if step % 3 == 0:
            # failed handoff: un-pin not-ok (re-prefill would follow)
            src.release_export(req.rid, ok=False)
            src.release_export(req.rid, ok=False)   # stale ack: no-op
        else:
            wire = np.concatenate(
                [prompt, np.asarray(req.output_tokens, np.int32)])
            req2 = dst.import_request(
                wire, 4 - len(req.output_tokens),
                cache_len=int(meta["cache_len"]), payloads=payloads)
            src.release_export(req.rid, ok=True)
            src.release_export(req.rid, ok=True)    # stale ack: no-op
            while not req2.done:
                dst.step()
        if step % 20 == 0:
            src.scheduler.allocator.check()
            dst.scheduler.allocator.check()
    assert len(src.exports) == 0
    src.exports.check()
    src.scheduler.allocator.check()
    dst.scheduler.allocator.check()
    assert src.introspect()["kv_exports_pinned"] == 0

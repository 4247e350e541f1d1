"""Flash attention + context parallelism numerics.

The reference tests fmha/multihead_attn against python reference
implementations (``apex/contrib/test/fmha/test_fmha.py``); same style here:
Pallas kernels (interpret mode on CPU) vs naive jnp attention, forward and
gradients, then the ring/Ulysses composition vs single-device flash.
"""

import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import parallel
from apex_tpu.ops.flash_attention import (
    flash_attention,
    resolve_default_blocks,
)
from apex_tpu.parallel import collectives as cc
from apex_tpu.transformer.context_parallel import (
    ring_attention,
    ulysses_attention,
)

slow = pytest.mark.slow  # the numerics below; the block resolution is tier-1


def naive_attention(q, k, v, causal, scale=None):
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2:]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


@slow
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 2, 32, 8), (2, 1, 48, 16)])
def test_flash_matches_naive(causal, shape):
    b, h, s, d = shape
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, shape) for kk in ks)

    out = flash_attention(q, k, v, causal=causal)
    ref = naive_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    w = jax.random.normal(jax.random.PRNGKey(3), shape)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) * w)

    def loss_ref(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal) * w)

    g = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_flash(causal):
    """cp=4 ring == single-device flash on the full sequence, fwd + grads."""
    CP = 4
    parallel.initialize_model_parallel(context_parallel_size=CP)
    b, h, s_local, d = 1, 2, 16, 8
    S = s_local * CP
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (b, h, S, d)) for kk in ks)
    w = jax.random.normal(jax.random.PRNGKey(4), (b, h, S, d))

    def ring_loss(q, k, v):
        def local(q, k, v, w):
            out = ring_attention(q, k, v, "cp", causal)
            return jnp.sum(out * w).reshape(1)
        losses = cc.shard_over(
            local,
            in_specs=(P(None, None, "cp"), P(None, None, "cp"),
                      P(None, None, "cp"), P(None, None, "cp")),
            out_specs=P("cp"),
        )(q, k, v, w)
        return jnp.sum(losses)

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) * w)

    np.testing.assert_allclose(float(ring_loss(q, k, v)),
                               float(flash_loss(q, k, v)), rtol=1e-5)

    g = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


@slow
def test_ulysses_attention_matches_flash():
    CP = 4
    parallel.initialize_model_parallel(context_parallel_size=CP)
    b, h, s_local, d = 1, 4, 16, 8
    S = s_local * CP
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (b, h, S, d)) for kk in ks)

    out = cc.shard_over(
        lambda q, k, v: ulysses_attention(q, k, v, "cp", True),
        in_specs=(P(None, None, "cp"),) * 3,
        out_specs=P(None, None, "cp"),
    )(q, k, v)
    ref = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # grads flow through the all_to_all pair
    def loss(q):
        o = cc.shard_over(
            lambda q, k, v: ulysses_attention(q, k, v, "cp", True),
            in_specs=(P(None, None, "cp"),) * 3,
            out_specs=P(None, None, "cp"),
        )(q, k, v)
        return jnp.sum(o * o)

    g = jax.grad(loss)(q)
    gr = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, causal=True)
                                    ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=2e-4, atol=2e-4)


@slow
def test_gpt_flash_attention_matches_fused_softmax():
    """CoreAttention flash path == fused-softmax path on the same params."""
    from apex_tpu.transformer.testing import GPTModel, TransformerConfig

    def cfg(flash):
        return TransformerConfig(
            hidden_size=32, num_layers=2, num_attention_heads=4,
            padded_vocab_size=64, max_position_embeddings=16,
            hidden_dropout=0.0, attention_dropout=0.0, tensor_axis=None,
            use_flash_attention=flash,
        )

    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 64)
    m0, m1 = GPTModel(cfg(False)), GPTModel(cfg(True))
    params = m0.init(jax.random.PRNGKey(1), tokens)["params"]
    l0 = m0.apply({"params": params}, tokens, labels=tokens)
    l1 = m1.apply({"params": params}, tokens, labels=tokens)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                               rtol=2e-5, atol=2e-5)


def naive_attention_masked(q, k, v, causal, seg_q=None, seg_k=None):
    return _reference(q, k, v, causal, seg_q=seg_q, seg_k=seg_k)


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_ids_match_naive(causal):
    """Packed-varlen via segment ids (fmha cu_seqlens parity)."""
    b, h, s, d = 2, 2, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    # two packed sequences of length 24 and 40 per row
    seg = jnp.concatenate([jnp.zeros((b, 24), jnp.int32),
                           jnp.ones((b, 40), jnp.int32)], axis=1)

    out = flash_attention(q, k, v, causal=causal,
                          segment_ids_q=seg, segment_ids_kv=seg)
    ref = naive_attention_masked(q, k, v, causal, seg, seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    w = jax.random.normal(jax.random.PRNGKey(5), (b, h, s, d))
    g = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=causal, segment_ids_q=seg,
                        segment_ids_kv=seg) * w), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        naive_attention_masked(q, k, v, causal, seg, seg) * w),
        argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


@slow
@pytest.mark.parametrize("s", [17, 100, 130])
def test_flash_non_power_of_two_lengths(s):
    """Odd lengths pad to the block grid instead of degrading to block=s."""
    b, h, d = 1, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal)
        ref = naive_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, causal=True)))(q)
    gr = jax.grad(lambda q: jnp.sum(naive_attention(q, k, v, True)))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=2e-4, atol=2e-4)


@slow
def test_flash_cross_attention_lengths():
    """sq != sk, both non-multiples of the block."""
    b, h, d = 2, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(0), (b, h, 33, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, h, 57, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, h, 57, d))
    out = flash_attention(q, k, v, causal=False)
    ref = naive_attention(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@slow
def test_flash_fully_masked_rows_zero():
    """A q shard strictly before the kv shard under causal masking must
    produce zero output / NEG_INF lse, not mean(V) (round-1 ADVICE)."""
    from apex_tpu.ops.flash_attention import NEG_INF, flash_attention_with_lse

    b, h, s, d = 1, 1, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    # kv chunk lives entirely *after* the q chunk: every row fully masked
    out, lse = flash_attention_with_lse(q, k, v, True, None, 256, 512,
                                        0, s + 64)
    assert np.allclose(np.asarray(out), 0.0)
    assert np.all(np.asarray(lse) <= NEG_INF * 0.5)

    # gradients through the chunk entry points are zero too
    from apex_tpu.ops.flash_attention import dkv_chunk, dq_chunk
    do = jax.random.normal(jax.random.PRNGKey(4), (b, h, s, d))
    delta = jnp.sum(do * out, axis=-1)
    dq = dq_chunk(q, k, v, do, lse, delta, causal=True, kv_offset=s + 64)
    dk, dv = dkv_chunk(q, k, v, do, lse, delta, causal=True,
                       kv_offset=s + 64)
    assert np.allclose(np.asarray(dq), 0.0)
    assert np.allclose(np.asarray(dk), 0.0)
    assert np.allclose(np.asarray(dv), 0.0)


@slow
def test_flash_dropout_statistics_and_determinism():
    b, h, s, d = 2, 2, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    rate = 0.3

    o1 = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=7)
    o2 = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=7)
    o3 = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=8)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert not np.allclose(np.asarray(o1), np.asarray(o3))

    # E[dropout(attn)] == attn: average over seeds approaches the clean out
    outs = [flash_attention(q, k, v, dropout_rate=rate, dropout_seed=i)
            for i in range(64)]
    mean = np.mean([np.asarray(o) for o in outs], axis=0)
    clean = np.asarray(flash_attention(q, k, v))
    np.testing.assert_allclose(mean, clean, atol=0.15)

    # gradient determinism (bwd regenerates the identical mask)
    g1 = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, dropout_rate=rate, dropout_seed=7)))(q)
    g2 = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, dropout_rate=rate, dropout_seed=7)))(q)
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))


@slow
def test_flash_dropout_grad_matches_masked_reference():
    """Grads under dropout == grads of an explicitly-masked naive attention
    built from the kernel's own keep mask."""
    from apex_tpu.ops.flash_attention import _keep_mask

    b, h, s, d = 1, 2, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    rate, seed = 0.25, 11

    rows = jnp.arange(s, dtype=jnp.int32)[:, None]
    cols = jnp.arange(s, dtype=jnp.int32)[None, :]
    keeps = jnp.stack([
        jnp.stack([_keep_mask(jnp.int32(seed), bh, rows, cols, rate)
                   for bh in range(b * h)]).reshape(h, s, s)
    ])  # b=1

    def ref(q, k, v):
        sc = 1.0 / np.sqrt(d)
        sm = jax.nn.softmax(
            jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sc,
            axis=-1)
        sm = jnp.where(keeps, sm / (1 - rate), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", sm.astype(q.dtype), v)

    out = flash_attention(q, k, v, dropout_rate=rate, dropout_seed=seed)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    w = jax.random.normal(jax.random.PRNGKey(9), (b, h, s, d))
    g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, dropout_rate=rate, dropout_seed=seed) * w),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(ref(q, k, v) * w),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def _reference(q, k, v, causal, q_offset=0, kv_offset=0, seg_q=None,
               seg_k=None, keep=None, rate=0.0):
    """Naive attention in float32 with the masks at global positions (a q
    shard at ``q_offset`` against a k shard at ``kv_offset``); fully masked
    rows give zero; ``keep`` is a dropout keep mask applied after softmax."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    sq, sk = s.shape[-2:]
    mask = jnp.ones((q.shape[0], 1, sq, sk), bool)
    if causal:
        mask &= ((jnp.arange(sq)[:, None] + q_offset)
                 >= (jnp.arange(sk)[None, :] + kv_offset))
    if seg_q is not None:
        mask &= seg_q[:, None, :, None] == seg_k[:, None, None, :]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    if keep is not None:
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _segments(b, s, edges):
    """``[b, s]`` ids that step up at each of ``edges``."""
    ids = sum((jnp.arange(s) >= e).astype(jnp.int32) for e in edges)
    return jnp.broadcast_to(ids, (b, s))


# sq, sk, d, dtype, causal, block_q, block_k, q_offset, kv_offset, segment
# edges (q, k) or None.  A compute sub-tile is 128 x 128 and a fetched tile
# block_q / block_k rows (the head, where the blocks allow it), so these
# cross both kinds of edge.
_TILE_CASES = {
    "s130_pads_to_two_sub_tiles": (
        130, 130, 64, "float32", True, None, None, 0, 0, None),
    "s384_three_fetched_tiles_bf16": (
        384, 384, 64, "bfloat16", True, 128, 128, 0, 0, None),
    "s640_pads_to_three_tiles_of_two": (
        640, 640, 64, "float32", True, 256, 256, 0, 0, None),
    "s384_whole_head_one_step": (
        384, 384, 64, "float32", True, 512, 512, 0, 0, None),
    "d128_bf16_not_causal": (
        256, 384, 128, "bfloat16", False, 128, 256, 0, 0, None),
    "d128_float32_causal": (
        256, 256, 128, "float32", True, 128, 128, 0, 0, None),
    "ring_chunk_behind_the_diagonal": (
        256, 256, 64, "float32", True, 128, 128, 256, 128, None),
    "ring_chunk_half_ahead_of_the_diagonal": (
        256, 256, 64, "float32", True, 128, 128, 128, 256, None),
    "offset_off_the_sub_tile_grid": (
        256, 256, 64, "float32", True, 128, 128, 5, 0, None),
    "segments_change_inside_a_sub_tile_causal": (
        256, 256, 64, "float32", True, 128, 128, 0, 0,
        ((70, 200), (70, 200))),
    "segments_change_inside_a_sub_tile_padding": (
        200, 330, 64, "float32", False, 128, 128, 0, 0,
        ((150,), (90, 300))),
    "cross_lengths": (
        200, 330, 64, "float32", False, 128, 128, 0, 0, None),
    "cross_lengths_short_keys": (
        140, 40, 64, "bfloat16", False, None, None, 0, 0, None),
}


@pytest.mark.parametrize("case", list(_TILE_CASES), ids=list(_TILE_CASES))
def test_flash_matches_reference_across_tile_edges(case):
    """Forward, dq, dk and dv against naive attention on shapes that cross
    sub-tile and fetched-tile edges."""
    from apex_tpu.ops.flash_attention import flash_attention_with_lse

    (sq, sk, d, dtype, causal, block_q, block_k, q_offset, kv_offset,
     edges) = _TILE_CASES[case]
    b, h = 1, 2
    ks = jax.random.split(jax.random.PRNGKey(sq + sk + d), 4)
    q = jax.random.normal(ks[0], (b, h, sq, d), dtype)
    k, v = (jax.random.normal(kk, (b, h, sk, d), dtype) for kk in ks[1:3])
    w = jax.random.normal(ks[3], (b, h, sq, d))
    seg_q = seg_k = None
    if edges is not None:
        seg_q, seg_k = _segments(b, sq, edges[0]), _segments(b, sk, edges[1])

    def flash(q, k, v):
        return flash_attention_with_lse(
            q, k, v, causal, None, block_q, block_k, q_offset, kv_offset,
            segment_ids_q=seg_q, segment_ids_kv=seg_k)[0]

    def reference(q, k, v):
        return _reference(q, k, v, causal, q_offset, kv_offset, seg_q, seg_k)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    # bf16: p, ds and the outputs round to 8 bits of mantissa
    tol, gtol = (2e-5, 2e-4) if dtype == "float32" else (2e-2, 6e-2)
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(reference(q, k, v)), rtol=tol, atol=tol)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32),
            rtol=gtol, atol=gtol, err_msg=name)


def _window_reference(q, k, v, window, q_offset=0, kv_offset=0, seg_q=None,
                      seg_k=None):
    """Naive attention where position ``i`` sees keys ``i - window < j <=
    i``; K and V with fewer heads are repeated for their query heads."""
    rep = q.shape[1] // k.shape[1]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = (jnp.repeat(x, rep, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[2])[:, None] + q_offset
    j = jnp.arange(k.shape[2])[None, :] + kv_offset
    mask = jnp.broadcast_to(j <= i, (q.shape[0], 1) + s.shape[-2:])
    if window is not None:
        mask &= j > i - window
    if seg_q is not None:
        mask &= seg_q[:, None, :, None] == seg_k[:, None, None, :]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.where(jnp.isnan(p), 0.0, p), v)


# s, d, dtype, heads, kv heads, window, block, q_offset, kv_offset, segment
# edges.  A computed tile is ``block`` x ``block`` where the block is 128
# (one tile a grid step) and 256 x 256 at d 128 in float32 under a block of
# 512 (four tiles a grid step, two grid steps a side), so the windows below
# end inside a tile, on a tile's edge and several tiles back, on both grids.
_WINDOW_CASES = {
    "window_1_sees_itself": (256, 64, "float32", 2, 2, 1, 128, 0, 0, None),
    "window_inside_a_tile": (384, 64, "float32", 2, 2, 50, 128, 0, 0, None),
    "window_is_a_tile": (384, 64, "float32", 2, 1, 128, 128, 0, 0, None),
    "window_crosses_tiles": (512, 64, "float32", 4, 2, 200, 128, 0, 0, None),
    "window_two_tiles_bf16": (512, 64, "bfloat16", 2, 2, 256, 128, 0, 0,
                              None),
    "window_in_a_whole_head": (512, 64, "float32", 2, 1, 130, None, 0, 0,
                               None),
    "window_aligned_tiles_in_a_step": (1024, 128, "float32", 2, 1, 256, 512,
                                       0, 0, None),
    "window_unaligned_tiles_in_a_step": (1024, 128, "float32", 1, 1, 300,
                                         512, 0, 0, None),
    "window_with_segments": (384, 64, "float32", 2, 1, 100, 128, 0, 0,
                             (70, 200)),
    "window_ring_chunk_behind": (256, 64, "float32", 2, 2, 200, 128, 256,
                                 128, None),
    "window_length_pads": (330, 64, "float32", 2, 1, 96, 128, 0, 0, None),
    "grouped_heads_no_window": (256, 64, "float32", 8, 2, None, 128, 0, 0,
                                None),
}


@pytest.mark.parametrize("case", list(_WINDOW_CASES), ids=list(_WINDOW_CASES))
def test_flash_window_and_grouped_heads_match_reference(case):
    """Forward, dq, dk and dv with a sliding window and with fewer K/V
    heads than query heads, against naive attention."""
    from apex_tpu.ops.flash_attention import flash_attention_with_lse

    (s, d, dtype, h, hk, window, block, q_offset, kv_offset,
     edges) = _WINDOW_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(s + d + h), 4)
    q = jax.random.normal(ks[0], (1, h, s, d), dtype)
    k, v = (jax.random.normal(kk, (1, hk, s, d), dtype) for kk in ks[1:3])
    w = jax.random.normal(ks[3], (1, h, s, d))
    seg = None if edges is None else _segments(1, s, edges)

    def flash(q, k, v):
        return flash_attention_with_lse(
            q, k, v, True, None, block, block, q_offset, kv_offset,
            segment_ids_q=seg, segment_ids_kv=seg, window=window)[0]

    def reference(q, k, v):
        return _window_reference(q, k, v, window, q_offset, kv_offset, seg,
                                 seg)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    tol, gtol = (2e-5, 2e-4) if dtype == "float32" else (2e-2, 6e-2)
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(reference(q, k, v)), rtol=tol, atol=tol)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b_.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32),
            rtol=gtol, atol=gtol, err_msg=name)


@pytest.mark.parametrize("block", [128, None], ids=["tiles", "whole_head"])
def test_flash_window_past_the_sequence_is_no_window(block):
    """A window that holds the whole sequence masks nothing: the output and
    the three gradients equal ``window=None``'s bit for bit."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k, v = (jax.random.normal(kk, (1, 1, 256, 64)) for kk in ks[1:3])
    w = jax.random.normal(ks[3], q.shape)

    def run(window):
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, block_q=block,
                                  block_k=block, window=window)
            return jnp.sum(out * w), out
        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
        return (out,) + grads

    for a, b_ in zip(run(None), run(256)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    for a, b_ in zip(run(None), run(5000)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_flash_window_refuses_what_it_cannot_mean():
    x = jnp.zeros((1, 2, 128, 64))
    with pytest.raises(ValueError, match="window"):
        flash_attention(x, x, x, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        flash_attention(x, x, x, causal=True, window=0)
    with pytest.raises(ValueError, match="K/V heads"):
        flash_attention(jnp.zeros((1, 3, 128, 64)), x, x, causal=True)


def test_flash_window_gauges_at_the_training_shape():
    """At ``(1, 32, 8192, 128)`` in bfloat16 (fetched tiles of 2048,
    computed tiles of 512) a window of 2048 visits under half the sub-tiles
    a full causal layer visits, in all three kernels, and few of them take
    a compare.  Traced only, nothing runs."""
    from apex_tpu.observability.metrics import default_registry
    from apex_tpu.ops.flash_attention import dkv_chunk, dq_chunk

    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((1, 32, 8192), jnp.float32)
    reg = default_registry()

    def counts(window):
        out = {}
        calls = {
            "fwd": lambda: jax.eval_shape(
                lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                window=window), q, kv, kv),
            "dq": lambda: jax.eval_shape(
                lambda *a: dq_chunk(*a, causal=True, window=window),
                q, kv, kv, q, row, row),
            "dkv": lambda: jax.eval_shape(
                lambda *a: dkv_chunk(*a, causal=True, window=window),
                q, kv, kv, q, row, row)}
        for name, call in calls.items():
            call()
            out[name] = tuple(reg.gauge(g).value for g in (
                "flash/sub_tiles", "flash/sub_tiles_masked",
                "flash/live_score_share", "flash/window"))
        return out

    full, windowed = counts(None), counts(2048)
    # 16 q blocks of 512: block i meets i whole tiles and one on the diagonal
    assert full["fwd"][:2] == (2176, 256) and full["dq"][:2] == (2112, 128)
    # from block 4 on: one edge tile (12 sub-tiles, 8 compared), three
    # whole ones, the diagonal one
    assert windowed["fwd"][:2] == (1072, 352)
    assert windowed["dq"][:2] == windowed["dkv"][:2] == (1008, 224)
    for name in full:
        assert windowed[name][0] < 0.5 * full[name][0], name
        assert full[name][3] == 0 and windowed[name][3] == 2048
    keys = sum(min(i + 1, 2048) for i in range(8192))
    assert windowed["fwd"][2] == pytest.approx(keys / (1072 * 128 * 128))


def test_flash_dropout_mask_and_masked_rows_are_the_parents():
    """The keep mask is the one the kernels had before they computed in
    sub-tiles, bit for bit (a digest of it taken then), the kernels apply it
    at global coordinates across sub-tile and fetched-tile edges, and a q
    shard wholly ahead of its kv shard still gives zero output, ``lse`` of
    ``NEG_INF`` and zero gradients."""
    import hashlib

    from apex_tpu.ops.flash_attention import (
        NEG_INF,
        _keep_mask,
        dkv_chunk,
        dq_chunk,
        flash_attention_with_lse,
    )

    rows = jnp.arange(300, dtype=jnp.int32)[:, None] + 7
    cols = jnp.arange(260, dtype=jnp.int32)[None, :] + 3
    mask = np.asarray(_keep_mask(jnp.int32(11), 3, rows, cols, 0.25))
    assert int(mask.sum()) == 58601
    assert hashlib.sha256(
        np.packbits(mask).tobytes()).hexdigest()[:16] == "9cf3625d859595d1"
    # laid out as the kernels do it: q positions a row, k positions a column
    np.testing.assert_array_equal(
        np.asarray(_keep_mask(jnp.int32(11), 3, rows.T, cols.T, 0.25)).T,
        mask)

    b, h, s, d = 1, 2, 256, 64
    rate, seed, q_offset, kv_offset = 0.25, 11, 128, 0
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    q, k, v, w = (jax.random.normal(kk, (b, h, s, d)) for kk in ks)
    keep = jnp.stack([_keep_mask(
        jnp.int32(seed), bh,
        jnp.arange(s, dtype=jnp.int32)[:, None] + q_offset,
        jnp.arange(s, dtype=jnp.int32)[None, :] + kv_offset, rate)
        for bh in range(b * h)]).reshape(b, h, s, s)

    def flash(q, k, v):
        return flash_attention_with_lse(
            q, k, v, True, None, 128, 128, q_offset, kv_offset,
            dropout_rate=rate, dropout_seed=seed)[0]

    def reference(q, k, v):
        return _reference(q, k, v, True, q_offset, kv_offset, keep=keep,
                          rate=rate)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(reference(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *x: jnp.sum(flash(*x) * w), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *x: jnp.sum(reference(*x) * w),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)

    # the kv shard lies wholly after the q shard: every row fully masked
    out, lse = flash_attention_with_lse(q, k, v, True, None, 128, 128,
                                        0, s + 64)
    assert np.all(np.asarray(out) == 0.0)
    assert np.all(np.asarray(lse) <= NEG_INF * 0.5)
    delta = jnp.sum(w * out, axis=-1)
    kw = dict(causal=True, block_q=128, block_k=128, kv_offset=s + 64)
    assert np.all(np.asarray(dq_chunk(q, k, v, w, lse, delta, **kw)) == 0.0)
    for grad in dkv_chunk(q, k, v, w, lse, delta, **kw):
        assert np.all(np.asarray(grad) == 0.0)


@pytest.mark.parametrize("causal,want", [
    # forward: two strips of 512 q lanes, 512 and 1024 k rows
    (True, {"fwd": (48, 32), "dq": (40, 16), "dkv": (40, 16)}),
    (False, {"fwd": (64, 0), "dq": (64, 0), "dkv": (64, 0)}),
], ids=["causal", "not_causal"])
def test_flash_tiling_gauges(causal, want):
    """At ``(1, 2, 1024, 64)`` a head is 8 x 8 sub-tiles of 128 x 128 and
    one fetched tile; causal, the strips of the one computed tile stop at
    their diagonal blocks (36 sub-tiles lie on or under the diagonal, 8 of
    them on it: strips one sub-tile wide would compute just those, and
    measured slower, PERF.md §6).  Traced only, nothing runs."""
    from apex_tpu.observability.metrics import default_registry
    from apex_tpu.ops.flash_attention import dkv_chunk, dq_chunk

    x = jax.ShapeDtypeStruct((1, 2, 1024, 64), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((1, 2, 1024), jnp.float32)
    names = ("flash/sub_tiles", "flash/sub_tiles_masked",
             "flash/live_score_share", "flash/fetch_tile_rows")
    reg = default_registry()
    calls = {
        "fwd": lambda: jax.eval_shape(
            lambda q, k, v: flash_attention(q, k, v, causal=causal),
            x, x, x),
        "dq": lambda: jax.eval_shape(
            lambda *a: dq_chunk(*a, causal=causal), x, x, x, x, row, row),
        "dkv": lambda: jax.eval_shape(
            lambda *a: dkv_chunk(*a, causal=causal), x, x, x, x, row, row),
    }
    scores = 1024 * 1025 // 2 if causal else 1024 * 1024
    for name, call in calls.items():
        for gauge in names:
            reg.gauge(gauge).set(-1.0)
        call()
        visited, masked, share, fetched = (
            reg.gauge(gauge).value for gauge in names)
        assert (visited, masked) == want[name], name
        assert share == pytest.approx(scores / (visited * 128 * 128)), name
        assert fetched == 1024, name

    # one sub-tile wide strips, as a block of 128 forces them: the 36 and 8
    jax.eval_shape(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128), x, x, x)
    got = tuple(reg.gauge(gauge).value for gauge in names)
    assert got[:2] == ((36, 8) if causal else (64, 0))
    assert round(got[2], 2) == (0.89 if causal else 1.0)
    assert got[3] == 128


_Q, _K = "APEX_TPU_FLASH_BLOCK_Q", "APEX_TPU_FLASH_BLOCK_K"

# What a process on a v5e would see, with a tuned record planted where the
# deleted loader looked (<checkout>/bench_results/flash_blocks_tuned.json).
_PLANTED = """
import jax
class Dev:
    platform = "tpu"
    device_kind = "TPU v5 lite"
asked = []
jax.devices = lambda *a, **k: asked.append(1) or [Dev()]
from apex_tpu.ops.flash_attention import resolve_default_blocks
print(list(resolve_default_blocks()), len(asked))
"""


@pytest.mark.parametrize("args,env,want,warns,planted", [
    ((None, None), {}, (2048, 2048), False, False),
    ((128, 64), {_Q: "32", _K: "32"}, (128, 64), False, False),
    ((None, None), {_Q: "128", _K: "1024"}, (128, 1024), False, False),
    ((None, 128), {_Q: "64"}, (64, 128), False, False),
    ((None, None), {_Q: "wide"}, (2048, 2048), True, False),
    ((None, None), {_Q: "128", _K: "0"}, (128, 2048), True, False),
    ((None, None), {_K: "-8"}, (2048, 2048), True, False),
    ((None, None), {}, (2048, 2048), False, True),
], ids=["nothing_set", "arguments_win", "env_wins_over_default",
        "per_dimension", "malformed_env_warns", "zero_env_warns",
        "negative_env_warns", "planted_tuned_file_ignored"])
def test_resolve_default_blocks(args, env, want, warns, planted,
                                monkeypatch, tmp_path):
    """Argument, else the environment knob, else 2048/2048 — and nothing
    else: no file in the checkout and no look at the device decides the
    train cell's tiles."""
    for name in (_Q, _K):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    if planted:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        os.symlink(os.path.join(repo, "apex_tpu"), tmp_path / "apex_tpu")
        (tmp_path / "bench_results").mkdir()
        (tmp_path / "bench_results" / "flash_blocks_tuned.json").write_text(
            json.dumps({"block_q": 128, "block_k": 128,
                        "device_kind": "TPU v5 lite"}))
        proc = subprocess.run(
            [sys.executable, "-c", _PLANTED], cwd=tmp_path,
            env={**os.environ, "PYTHONPATH": str(tmp_path),
                 "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.split("\n")[-2] == f"{list(want)} 0"
        return
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert resolve_default_blocks(*args) == want
    named = [w for w in caught
             if any(name in str(w.message) for name in (_Q, _K))]
    assert bool(named) == warns

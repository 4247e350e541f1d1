"""Tree flatten/unflatten + RNG policy tests (apex_C / multi_tensor_l2norm /
random.py analogs)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import parallel
from apex_tpu.parallel import collectives as cc
from apex_tpu.utils import (
    chunked_per_leaf_sumsq,
    flatten_to_buffer,
    flatten_to_chunked,
    unflatten_from_buffer,
    unflatten_from_chunked,
    per_leaf_l2_norms,
    tree_l2_norm,
    tree_size,
    model_parallel_rngs,
)


class TestFlatten:
    def test_roundtrip(self):
        tree = {
            "a": jnp.arange(6.0).reshape(2, 3),
            "b": jnp.ones((4,), jnp.bfloat16),
            "c": jnp.float32(7.0),
        }
        buf, meta = flatten_to_buffer(tree, dtype=jnp.float32)
        assert buf.ndim == 1 and buf.dtype == jnp.float32
        out = unflatten_from_buffer(buf, meta)
        assert out["b"].dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out["a"]), np.arange(6.0).reshape(2, 3))
        np.testing.assert_allclose(float(out["c"]), 7.0)

    def test_padding(self):
        buf, meta = flatten_to_buffer({"a": jnp.ones(5)}, pad_to=8)
        assert buf.shape == (8,)
        assert meta.total == 5

    def test_jit_roundtrip(self):
        tree = {"a": jnp.arange(4.0), "b": jnp.ones((2, 2))}

        _, meta = flatten_to_buffer(tree)

        @jax.jit
        def f(t):
            buf, _ = flatten_to_buffer(t)
            return unflatten_from_buffer(buf, meta)

        out = f(tree)
        np.testing.assert_allclose(np.asarray(out["a"]), np.arange(4.0))


class TestChunkedFlatten:
    """flatten_to_chunked / unflatten_from_chunked / chunked_per_leaf_sumsq
    — the (rows, chunk) multi_tensor workspace behind FusedLAMB(flat=True)."""

    def test_roundtrip_mixed_shapes(self):
        tree = {
            "w": jnp.arange(300, dtype=jnp.float32).reshape(30, 10),
            "b": jnp.arange(7, dtype=jnp.float32),
            "scalar": jnp.float32(3.5),
            "half": jnp.ones((130,), jnp.bfloat16),
        }
        buf, meta = flatten_to_chunked(tree, chunk=64)
        assert buf.shape[1] == 64
        # leaf boundaries are row-aligned: each leaf starts a fresh row
        assert meta.leaf_ids.shape == (buf.shape[0],)
        out = jax.tree_util.tree_map(lambda x: x, unflatten_from_chunked(buf, meta))
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(tree)):
            assert a.dtype == jnp.asarray(b).dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    def test_zero_size_leaves(self):
        # zero-size leaves occupy no rows and must round-trip (the
        # r5 review's reproduced crash: all-empty trees)
        for tree in ({"e": jnp.zeros((0, 4))},
                     {"e": jnp.zeros((0, 4)), "w": jnp.ones((5,))}):
            buf, meta = flatten_to_chunked(tree, chunk=8)
            out = unflatten_from_chunked(buf, meta)
            for a, b in zip(jax.tree_util.tree_leaves(out),
                            jax.tree_util.tree_leaves(tree)):
                assert a.shape == b.shape and a.dtype == b.dtype

    def test_per_leaf_sumsq_exact(self):
        tree = {"a": jnp.full((100,), 2.0), "b": jnp.full((3, 3), -1.0),
                "z": jnp.zeros((0,))}
        buf, meta = flatten_to_chunked(tree, chunk=32)
        got = np.asarray(chunked_per_leaf_sumsq(buf, meta))
        np.testing.assert_allclose(sorted(got), sorted([0.0, 9.0, 400.0]))

    def test_jit_roundtrip(self):
        tree = {"a": jnp.ones((50,)), "b": jnp.ones((4, 4))}
        _, meta = flatten_to_chunked(tree)

        @jax.jit
        def f(t):
            buf, _ = flatten_to_chunked(t)
            return unflatten_from_chunked(buf * 2.0, meta)

        out = f(tree)
        np.testing.assert_array_equal(np.asarray(out["b"]), 2.0 * np.ones((4, 4)))


class TestNorms:
    def test_global_norm(self):
        tree = {"a": jnp.full((3,), 2.0), "b": jnp.full((4,), 2.0)}
        np.testing.assert_allclose(float(tree_l2_norm(tree)), np.sqrt(7 * 4.0))

    def test_per_leaf(self):
        norms = per_leaf_l2_norms({"a": jnp.full((4,), 3.0)})
        np.testing.assert_allclose(float(norms[0]), 6.0)

    def test_size(self):
        assert tree_size({"a": jnp.ones((2, 3)), "b": jnp.float32(1)}) == 7

    def test_size_empty_leaf(self):
        assert tree_size({"a": jnp.zeros((0,)), "b": jnp.ones(3)}) == 3

    def test_mixed_dtype_without_explicit_dtype_raises(self):
        with pytest.raises(ValueError):
            flatten_to_buffer({"a": jnp.ones(2), "b": jnp.ones(2, jnp.bfloat16)})


class TestModelParallelRng:
    def test_mp_keys_differ_across_ranks(self):
        parallel.initialize_model_parallel(tensor_model_parallel_size=8)

        def fn(_):
            key = jax.random.PRNGKey(0)
            rep, mp = model_parallel_rngs(key)
            return (
                jax.random.uniform(rep, (1, 2)),
                jax.random.uniform(mp, (1, 2)),
            )

        f = cc.shard_over(
            fn, in_specs=P("tp"), out_specs=(P("tp", None), P("tp", None))
        )
        rep, mp = f(jnp.zeros(8))
        rep, mp = np.asarray(rep), np.asarray(mp)
        # replicated stream identical on all ranks
        for r in range(1, 8):
            np.testing.assert_allclose(rep[r], rep[0])
        # model-parallel stream unique per rank
        assert len({tuple(row) for row in mp}) == 8


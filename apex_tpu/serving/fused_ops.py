"""Fused dequant/residual/norm epilogue for the decode hot path.

Between the attention (or MLP) row-parallel projection and the next
sublayer sit four ops: the skip-bias add, the residual add, an upcast
of the projection output from the wire/compute dtype, and a LayerNorm.
In the unfused XLA lowering each is its own elementwise/reduction HLO
over an HBM round trip — at decode shapes (``[max_batch, hidden]``,
one token per slot) that chain is pure memory latency, the exact
profile the operation-fusion paper (PAPERS.md arxiv 2502.17728) finds
dominating the decode step.

:func:`fused_residual_norm` does all four in ONE Pallas kernel: the row
is read once into VMEM, dequantized (upcast to fp32), bias- and
residual-added, normalized against the fp32 statistics, and both
outputs (the normed row for the next GEMM and the new residual for the
next skip connection) written back — two reads, two writes, zero
intermediates in HBM.  Forward-only by design: this is the serving hot
path, nothing differentiates it (the training twin is
:mod:`apex_tpu.ops.pallas_norm`, which carries the custom VJP).

The unfused twin :func:`residual_norm_unfused` is the A/B baseline and
the parity reference (``tests/test_serving.py``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.utils import platform

__all__ = ["fused_residual_norm", "residual_norm_unfused"]


def _kernel(x_ref, res_ref, b_ref, w_ref, beta_ref, y_ref, new_res_ref, *,
            eps: float, has_bias: bool):
    # dequant: wire dtype (bf16 projection output) -> fp32, in VMEM
    x = x_ref[...].astype(jnp.float32)
    if has_bias:
        x = x + b_ref[...].astype(jnp.float32)
    r = x + res_ref[...].astype(jnp.float32)
    mean = jnp.mean(r, axis=-1, keepdims=True)
    rc = r - mean
    var = jnp.mean(rc * rc, axis=-1, keepdims=True)
    y = rc * jax.lax.rsqrt(var + eps)
    y = y * w_ref[...].astype(jnp.float32) + beta_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    new_res_ref[...] = r.astype(new_res_ref.dtype)


def fused_residual_norm(x, residual, weight, bias_ln, *, bias=None,
                        eps: float = 1e-5, block_rows: int = 256
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``normed, new_residual = LN(x [+ bias] + residual), x [+ bias] + residual``.

    ``x``/``residual``: ``[..., hidden]`` (leading dims flattened to
    rows); ``weight``/``bias_ln``: the LayerNorm affine params
    (``scale``/``bias`` of :class:`~apex_tpu.normalization.FusedLayerNorm`);
    ``bias``: optional skip-bias of the preceding row-parallel linear
    (``skip_bias_add`` convention).  Outputs keep ``x``'s dtype for
    ``normed`` and ``residual``'s dtype for the carried residual.
    """
    orig_shape = x.shape
    hidden = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, hidden)
    res2 = residual.reshape(rows, hidden)
    has_bias = bias is not None
    b = (jnp.zeros((hidden,), x.dtype) if bias is None
         else bias.reshape(hidden))
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    row_spec = pl.BlockSpec((block_rows, hidden), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((hidden,), lambda i: (0,))
    y, new_res = pl.pallas_call(
        functools.partial(_kernel, eps=eps, has_bias=has_bias),
        grid=grid,
        in_specs=[row_spec, row_spec, vec_spec, vec_spec, vec_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, hidden), x.dtype),
            jax.ShapeDtypeStruct((rows, hidden), residual.dtype),
        ],
        interpret=platform.pallas_interpret(),
    )(x2, res2, b, weight, bias_ln)
    return y.reshape(orig_shape), new_res.reshape(orig_shape)


def residual_norm_unfused(x, residual, weight, bias_ln, *, bias=None,
                          eps: float = 1e-5):
    """The separate-ops lowering (A/B baseline, parity reference)."""
    r = x if bias is None else x + bias
    r = (r + residual).astype(jnp.float32)
    mean = jnp.mean(r, axis=-1, keepdims=True)
    rc = r - mean
    var = jnp.mean(rc * rc, axis=-1, keepdims=True)
    y = rc * jax.lax.rsqrt(var + eps)
    y = y * weight.astype(jnp.float32) + bias_ln.astype(jnp.float32)
    return y.astype(x.dtype), r.astype(residual.dtype)

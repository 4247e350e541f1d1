"""Pallas row-norm kernels — TPU-native FusedLayerNorm fast path.

The XLA-fused :mod:`apex_tpu.normalization` path is usually optimal (row
reductions fuse with neighbours), but for odd widths or when the norm is the
only op between two big GEMMs a hand-tiled kernel keeps rows resident in
VMEM across the two reduction passes — the same motivation as the
persistent "FastLayerNorm" in ``apex/contrib/csrc/layer_norm``
(``ln_fwd_cuda_kernel.cu``) which exists because the generic
``csrc/layer_norm_cuda_kernel.cu`` was not fast enough at large hidden
sizes.

The Pallas kernel computes the forward; the backward is wired via
``custom_vjp`` to the analytic gradients of
:mod:`apex_tpu.normalization.fused_layer_norm` (recomputing statistics —
the memory-efficient trade), because the backward is bandwidth-bound either
way and XLA fuses it well.

Usage: ``pallas_layer_norm(x, w, b)`` with ``x: [rows, hidden]``; rows are
tiled in blocks of ``block_rows``; hidden must be a multiple of 128 (lane
width) — callers should fall back to the jnp path otherwise (the
``is_available`` predicate mirrors ``is_kernel_available``,
``apex/transformer/functional/fused_softmax.py:222``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

__all__ = ["pallas_layer_norm", "pallas_rms_norm", "is_available"]


def is_available(hidden: int) -> bool:
    """Shape gate for the Pallas path (lane-width aligned)."""
    return hidden % 128 == 0


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y = y * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    y = y * w_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def pallas_layer_norm(
    x,
    weight,
    bias,
    eps: float = 1e-5,
    block_rows: int = 256,
    interpret: bool = False,
):
    """LayerNorm as a Pallas forward kernel + analytic custom backward;
    x: [..., hidden] (leading dims flattened to rows)."""
    return _pallas_ln_fwd_call(x, weight, bias, eps, block_rows, interpret)


def _pallas_ln_bwd(eps, block_rows, interpret, res, dy):
    from apex_tpu.normalization import fused_layer_norm_affine

    x, weight, bias = res
    shape = (x.shape[-1],)
    return jax.vjp(
        lambda x_, w_, b_: fused_layer_norm_affine(x_, w_, b_, shape, eps),
        x, weight, bias,
    )[1](dy)


pallas_layer_norm.defvjp(
    lambda x, w, b, eps, block_rows, interpret: (
        _pallas_ln_fwd_call(x, w, b, eps, block_rows, interpret),
        (x, w, b),
    ),
    _pallas_ln_bwd,
)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def _pallas_ln_fwd_call(x, weight, bias, eps, block_rows, interpret):
    orig_shape = x.shape
    hidden = orig_shape[-1]
    rows = math.prod(orig_shape[:-1]) if len(orig_shape) > 1 else 1
    x2 = x.reshape(rows, hidden)
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
            pl.BlockSpec((hidden,), lambda i: (0,)),
            pl.BlockSpec((hidden,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), x.dtype),
        interpret=interpret,
    )(x2, weight, bias)
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def pallas_rms_norm(
    x,
    weight,
    eps: float = 1e-5,
    block_rows: int = 256,
    interpret: bool = False,
):
    """RMSNorm as a Pallas forward kernel + analytic custom backward."""
    return _pallas_rms_fwd_call(x, weight, eps, block_rows, interpret)


def _pallas_rms_bwd(eps, block_rows, interpret, res, dy):
    from apex_tpu.normalization import fused_rms_norm_affine

    x, weight = res
    shape = (x.shape[-1],)
    return jax.vjp(
        lambda x_, w_: fused_rms_norm_affine(x_, w_, shape, eps), x, weight
    )[1](dy)


pallas_rms_norm.defvjp(
    lambda x, w, eps, block_rows, interpret: (
        _pallas_rms_fwd_call(x, w, eps, block_rows, interpret),
        (x, w),
    ),
    _pallas_rms_bwd,
)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def _pallas_rms_fwd_call(x, weight, eps, block_rows, interpret):
    orig_shape = x.shape
    hidden = orig_shape[-1]
    rows = math.prod(orig_shape[:-1]) if len(orig_shape) > 1 else 1
    x2 = x.reshape(rows, hidden)
    block_rows = min(block_rows, rows)
    grid = (pl.cdiv(rows, block_rows),)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
            pl.BlockSpec((hidden,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, hidden), x.dtype),
        interpret=interpret,
    )(x2, weight)
    return out.reshape(orig_shape)

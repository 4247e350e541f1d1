"""Collective communication layer — the NCCL/UCC analog.

The reference routes every collective through ``torch.distributed`` with NCCL
(process-group plumbing in ``apex/transformer/parallel_state.py:83-153``, raw
p2p in ``apex/contrib/csrc/nccl_p2p/``).  On TPU the transport is the ICI mesh
(DCN across slices) and the API is ``jax.lax`` collectives bound to named mesh
axes; XLA schedules and overlaps them.  This module is the single place that
names those primitives so higher layers (tensor_parallel.mappings, pipeline
p2p, SyncBN, DDP) never spell ``jax.lax.psum`` themselves.

All functions here must run inside a ``shard_map``/``pmap`` context where
``axis_name`` is bound.  ``shard_over`` is the helper that enters that context
from the outside using the registered global mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from apex_tpu.parallel import mesh as mesh_lib

__all__ = [
    "all_reduce",
    "all_gather",
    "reduce_scatter",
    "hierarchical_reduce_scatter",
    "hierarchical_all_gather",
    "ppermute",
    "ring_chunks",
    "all_to_all",
    "broadcast",
    "axis_index",
    "axis_size",
    "bound_axis_size",
    "send_recv_next",
    "send_recv_prev",
    "shard_over",
]

AxisName = Union[str, Sequence[str]]


def axis_index(axis: AxisName):
    """Rank along a mesh axis (inside shard_map). Replaces
    ``torch.distributed.get_rank(group)``."""
    return lax.axis_index(axis)


def bound_axis_size(axis: Optional[AxisName]) -> int:
    """Size of ``axis`` if it is bound by an enclosing ``shard_map``/``pmap``,
    else 1.  Lets axis-parameterized modules degrade to their single-rank
    form when traced outside any mapped context (``axis=None`` or unbound)."""
    if axis is None:
        return 1
    try:
        return lax.axis_size(axis)
    except NameError:
        return 1


def axis_size(axis: AxisName) -> int:
    """World size along a mesh axis (inside shard_map)."""
    return lax.axis_size(axis)


def all_reduce(x, axis: AxisName, op: str = "sum"):
    """All-reduce over a mesh axis.

    Replaces ``torch.distributed.all_reduce`` on the TP/DP groups (e.g.
    ``apex/transformer/tensor_parallel/mappings.py:31`` ``_reduce``).
    """
    if op == "sum":
        return lax.psum(x, axis)
    if op == "mean":
        return lax.pmean(x, axis)
    if op == "max":
        return lax.pmax(x, axis)
    if op == "min":
        return lax.pmin(x, axis)
    raise ValueError(f"unsupported all_reduce op: {op!r}")


def all_gather(x, axis: AxisName, *, concat_axis: int = 0, tiled: bool = True):
    """All-gather shards along ``concat_axis``.

    Replaces ``torch.distributed.all_gather`` / ``_all_gather_base`` (e.g.
    sequence-parallel gather ``apex/transformer/tensor_parallel/mappings.py:103``).
    ``tiled=True`` concatenates (the Megatron convention); ``tiled=False``
    stacks a new leading axis (the raw all_gather convention).
    """
    return lax.all_gather(x, axis, axis=concat_axis, tiled=tiled)


def reduce_scatter(x, axis: AxisName, *, scatter_axis: int = 0):
    """Reduce-scatter: sum over the axis group, keep this rank's shard.

    Replaces ``torch.distributed.reduce_scatter_tensor`` (sequence-parallel
    reduce-scatter ``apex/transformer/tensor_parallel/mappings.py:122``).
    """
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


def hierarchical_reduce_scatter(
    x,
    inner_axis: AxisName,
    outer_axis: Optional[str] = None,
    *,
    scatter_axis: int = 0,
    outer_reduce_dtype=None,
):
    """Two-tier reduce-scatter for the ICI/DCN fabric.

    Instead of treating ``(dcn, dp)`` as one flat reduction group (which
    interleaves 1/(dcn*dp)-sized exchanges over the slow cross-slice
    network), reduce-scatter over the intra-slice ``inner_axis`` (ICI)
    first, then all-reduce the 1/dp-sized shard across ``outer_axis``
    (DCN) — the hierarchical schedule of "Automatic Cross-Replica Sharding
    of Weight Update in Data-Parallel Training" (Xu et al.; the analog of
    the reference's IB-block vs socket NCCL group split,
    ``apex/transformer/parallel_state.py:83-153``).  The result is the
    fully-summed shard, *replicated* over ``outer_axis``.

    ``outer_reduce_dtype`` optionally casts the shard for the DCN hop
    (e.g. ``jnp.bfloat16`` halves cross-slice bytes) and casts back.
    The outer hop is skipped when ``outer_axis`` is ``None``, unbound, or
    size 1, so call sites are correct at any scale.
    """
    shard = lax.psum_scatter(
        x, inner_axis, scatter_dimension=scatter_axis, tiled=True
    )
    if outer_axis is not None and bound_axis_size(outer_axis) > 1:
        if outer_reduce_dtype is not None:
            orig = shard.dtype
            shard = lax.psum(
                jnp.asarray(shard, outer_reduce_dtype), outer_axis
            )
            shard = jnp.asarray(shard, orig)
        else:
            shard = lax.psum(shard, outer_axis)
    return shard


def hierarchical_all_gather(x, inner_axis: AxisName, *, concat_axis: int = 0,
                            tiled: bool = True):
    """Gather back shards produced by :func:`hierarchical_reduce_scatter`.

    Because the outer (DCN) tier all-*reduces* — every slice ends up with
    identical shards — the gather only ever runs over the intra-slice
    ``inner_axis``: zero DCN bytes on the parameter path.  Provided as a
    named pair so call sites state the intent (and stay correct if the
    outer tier ever becomes a scatter)."""
    return lax.all_gather(x, inner_axis, axis=concat_axis, tiled=tiled)


def ppermute(x, axis: AxisName, perm):
    """Point-to-point permutation — the p2p send/recv analog
    (``apex/transformer/pipeline_parallel/p2p_communication.py:48-166``).

    ``perm`` must be a valid partial permutation (each rank at most once
    as source and once as target) — jax does NOT validate this at trace
    time, and a mismatched ring deadlocks real ICI; analyzer rules
    APX104/APX202 (:mod:`apex_tpu.analysis`) check it statically."""
    return lax.ppermute(x, axis, perm)


def ring_chunks(x, axis: Union[AxisName, int], dim: int = 0):
    """View ``x`` with dimension ``dim`` split into the axis's per-rank
    chunks, chunk index leading: ``[..., n*c, ...] -> [n, ..., c, ...]``.

    Chunk ``i`` is rank ``i``'s shard of ``dim`` (the tiled all-gather /
    reduce-scatter layout), which is exactly the order ring-decomposed
    collectives walk one ``ppermute`` hop at a time — the collective-matmul
    rings (:mod:`apex_tpu.transformer.tensor_parallel.overlap`) index these
    chunks with ``lax.dynamic_index_in_dim`` at a traced rank offset.
    ``axis`` may be a bound mesh axis name or an explicit chunk count.
    """
    n = axis if isinstance(axis, int) else lax.axis_size(axis)
    dim = dim % x.ndim
    if x.shape[dim] % n:
        raise ValueError(
            f"dimension {dim} of size {x.shape[dim]} not divisible into "
            f"{n} ring chunks"
        )
    c = x.shape[dim] // n
    split = x.reshape(x.shape[:dim] + (n, c) + x.shape[dim + 1:])
    return jnp.moveaxis(split, dim, 0)


def send_recv_next(x, axis: AxisName):
    """Send to rank+1, receive from rank-1 along ``axis`` (ring, wrapping).

    The pipeline forward-direction transfer: stage i's activations arrive at
    stage i+1 (``p2p_communication.send_forward`` ``:445``).  The wrap-around
    edge (last→first) carries data the consumer must mask/ignore, matching the
    reference where first stage never reads a recv'd activation.
    """
    n = lax.axis_size(axis)
    return lax.ppermute(x, axis, [(i, (i + 1) % n) for i in range(n)])


def send_recv_prev(x, axis: AxisName):
    """Send to rank-1, receive from rank+1 (pipeline backward direction,
    ``p2p_communication.send_backward`` ``:469``)."""
    n = lax.axis_size(axis)
    return lax.ppermute(x, axis, [(i, (i - 1) % n) for i in range(n)])


def all_to_all(x, axis: AxisName, *, split_axis: int, concat_axis: int):
    """All-to-all — used by DeepSpeed-Ulysses-style sequence parallelism and
    expert parallelism (absent in the reference; first-class here)."""
    return lax.all_to_all(
        x, axis, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )


def broadcast(x, axis: AxisName, root: int = 0):
    """Broadcast ``root``'s shard to every rank on the axis.

    Replaces ``torch.distributed.broadcast`` (e.g. tensor-parallel input-data
    broadcast ``apex/transformer/tensor_parallel/data.py:80``).  Implemented as
    a masked psum: ranks != root contribute zeros.
    """
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis)


def shard_over(
    fn: Callable,
    *,
    mesh: Optional[Mesh] = None,
    in_specs: Any,
    out_specs: Any,
    check_vma: bool = False,
):
    """Wrap ``fn`` in a ``shard_map`` over the registered global mesh.

    The bridge from the outer (global-array) world into the per-shard world
    where the collectives above are legal.  Pipeline schedules and the
    distributed tests use this; most library code instead relies on sharding
    annotations and lets XLA infer collectives.

    Old-jax contract: if the wrapped function will be differentiated
    (``jax.grad`` *across* this boundary), no rank-0 inexact value may
    cross it — 0.4.x shard_map cannot name-check scalar residuals in the
    transposed program (``_SpecError``).  Keep such scalars ``(1,)``-shaped
    inside and squeeze outside; analyzer rule APX101
    (:mod:`apex_tpu.analysis`, ``lint_traced(fn, ...,
    differentiated=True)``) enforces this mechanically.
    """
    if mesh is None:
        mesh = mesh_lib.get_mesh()
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma,
    )


"""Shared machinery for the fused optimizer family.

The reference's optimizers are one CUDA ``multi_tensor_apply`` launch per
(dtype-group, op) — chunked kernels over tensor lists
(``csrc/multi_tensor_apply.cuh:16-33``, dispatcher
``apex/multi_tensor_apply/multi_tensor_apply.py:3-30``) — because thousands
of separate small CUDA kernels would be launch-bound.  Under XLA all leaf
updates compile into one executable, so the *mechanism* dissolves; what we
keep is the *semantics*:

- update math in fp32 regardless of storage dtype (every functor casts to
  ``MATH_T=float``, e.g. ``csrc/multi_tensor_adam.cu:64-87``);
- optional fp32 master params carried in optimizer state
  (``FusedAdam(master_weights=True)``, ``apex/optimizers/fused_adam.py:71``);
- gradient unscaling folded into the update (``scale`` argument of
  ``FusedSGD.step`` / ``multi_tensor_adam``'s ``div_scale``);
- overflow skip as predication rather than a host branch (the ``noop_flag``
  short-circuit in every kernel).

Every optimizer here follows the same protocol::

    opt   = FusedFoo(lr=..., ...)
    state = opt.init(params)
    params, state = opt.step(grads, state, params,
                             lr=None,          # per-step override (schedules)
                             grad_scale=None,  # divide grads by this (loss scale)
                             skip_update=None) # bool scalar: keep old state/params

``step`` is pure — jit it (donating ``state``/``params``) at the call site,
or use :func:`apex_tpu.optimizers.fused_step` which does so with donation.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "f32",
    "adam_apply",
    "tree_f32",
    "tree_zeros_f32",
    "advance_step",
    "cast_like",
    "apply_skip",
    "resolve_master",
    "finalize_params",
    "tree_map_multi",
    "OptState",
]

Pytree = Any


def f32(x):
    return jnp.asarray(x, jnp.float32)


def adam_apply(p, g, m, v, *, lr, b1, b2, eps, wd, bc1, bc2, adam_w_mode):
    """One Adam/AdamW update on fp32 values — the elementwise core of
    ``csrc/multi_tensor_adam.cu:64-87`` (``ADAM_MODE_0`` folds ``wd*p``
    into the grad, ``ADAM_MODE_1`` decouples the decay into the update).
    Shape-agnostic: the fused optimizer maps it over leaves or chunked
    buffers, the ZeRO-sharded ones over per-leaf chunks or flat-bucket
    shards — one definition of the math, four call shapes."""
    if not adam_w_mode and wd != 0.0:
        g = g + wd * p
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w_mode and wd != 0.0:
        update = update + wd * p
    return p - lr * update, m, v


def tree_f32(tree):
    """fp32 master copy of ``params``.

    Always copies — even fp32 leaves — so the master state never aliases the
    model params' buffers (aliasing breaks ``donate_argnums`` train steps
    with "attempt to donate the same buffer twice").
    """
    return jax.tree_util.tree_map(
        lambda x: jnp.array(x, dtype=jnp.float32, copy=True), tree
    )


def tree_zeros_f32(params):
    """fp32 zero slots shaped like ``params`` (optimizer state init).
    ``zeros_like`` keeps a sharded parameter's placement, so the state of
    a model spread over a mesh is not born whole on device 0."""
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(x, dtype=jnp.float32), params
    )


def advance_step(step, skip_update):
    """Advance the step counter unless the update is skipped — the reference
    predicates the counter on the overflow flag
    (``apex/optimizers/fused_adam.py:152``: ``group['step'] +=
    (self._dummy_overflow_buf != 1)``), keeping bias corrections aligned with
    the number of *applied* updates."""
    if skip_update is None:
        return step + 1
    return step + jnp.where(jnp.asarray(skip_update), 0, 1)


def cast_like(new, ref):
    """Cast ``new`` leaves to the dtypes of ``ref`` leaves."""
    return jax.tree_util.tree_map(
        lambda n, r: jnp.asarray(n, jnp.asarray(r).dtype), new, ref
    )


def apply_skip(skip_update, new_tree, old_tree):
    """Predicated state/param update: where ``skip_update`` is True keep the
    old values (the kernels' ``noop_flag`` early-out; the amp skip-step
    ``apex/amp/handle.py:128-154``)."""
    if skip_update is None:
        return new_tree
    keep_old = jnp.asarray(skip_update)
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(keep_old, o, n), new_tree, old_tree
    )


def scale_grads(grads, grad_scale):
    """Fold loss-scale division into the update (``div_scale`` arg of
    ``multi_tensor_adam_capturable``; ``scale`` of ``FusedSGD.step``)."""
    if grad_scale is None:
        return tree_f32(grads)
    inv = 1.0 / f32(grad_scale)
    return jax.tree_util.tree_map(lambda g: f32(g) * inv, grads)


def resolve_master(params, state_master, use_master: bool):
    """Pick the fp32 tree the update math runs on."""
    if use_master:
        return state_master
    return tree_f32(params)


def finalize_params(params_f32_new, model_params, use_master: bool):
    """Derive the model-dtype params from the stepped fp32 tree
    (``_master_params_to_model_params``, ``apex/amp/_process_optimizer.py:14``)."""
    return cast_like(params_f32_new, model_params)


def tree_map_multi(fn: Callable, n_out: int, *trees) -> Tuple[Pytree, ...]:
    """Map ``fn`` (returning an ``n_out``-tuple) over leaves of ``trees``,
    returning ``n_out`` trees.  Robust against tuple-valued leaves (unlike
    post-hoc unzipping with ``is_leaf=tuple``)."""
    leaves0, treedef = jax.tree_util.tree_flatten(trees[0])
    rest = [treedef.flatten_up_to(t) for t in trees[1:]]
    results = [fn(*args) for args in zip(leaves0, *rest)]
    return tuple(
        treedef.unflatten([r[i] for r in results]) for i in range(n_out)
    )


def tree_map_flat(fn: Callable, n_out: int, *trees) -> Tuple[Pytree, ...]:
    """Like :func:`tree_map_multi` for a purely **elementwise** ``fn``,
    but applied once over one chunked ``(rows, 256)`` buffer per tree —
    the ``multi_tensor_apply`` list-kernel shape (one wide kernel per op
    instead of one small kernel per tensor; ``csrc/multi_tensor_apply.cuh``).
    Elementwise means no reductions, so the result matches the per-leaf
    map to compiler instruction-fusion (fma) noise, ~1 ulp; outputs take
    the FIRST tree's structure/dtypes (inputs are cast to its fp32
    workspace).  For updates that also need
    per-tensor reductions, see ``FusedLAMB._flat_update``."""
    from apex_tpu.utils.tree import (
        flatten_to_chunked,
        unflatten_from_chunked,
    )

    bufs, meta = [], None
    for t in trees:
        b, m = flatten_to_chunked(t)
        if meta is None:
            meta = m
        bufs.append(b)
    outs = fn(*bufs)
    if n_out == 1:
        outs = (outs,)
    return tuple(unflatten_from_chunked(o, meta) for o in outs)


class OptState(NamedTuple):
    """Generic optimizer state: a step counter, named slot trees, and the
    optional fp32 master params."""

    step: jnp.ndarray
    slots: Any  # dict name -> pytree (same structure as params)
    master: Optional[Any]  # fp32 params pytree or None

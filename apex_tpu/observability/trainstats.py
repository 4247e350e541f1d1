"""In-graph training telemetry — the :class:`TrainStats` pytree.

Production trainers treat per-step metrics as part of the *program*, not a
bolt-on (TorchTitan logs loss/grad-norm/MFU from inside the step,
arxiv 2410.06511 §3; veScale validates its overlap schedules against the
same counters, arxiv 2509.07003).  The contract here is strict, because a
metrics layer that slows the step it measures is worse than none:

- **zero extra host syncs** — every field is a jnp value computed inside
  the jitted step; nothing is fetched until a host-side logger decides to
  (:class:`TrainStatsLogger`, ``every_n`` steps), so steady-state steps
  dispatch fully async;
- **at most the collectives already on the path** — stats that need
  cross-rank agreement ride an all-reduce the trainer already performs
  (the loss reduction), *widened* by a few elements rather than added
  (:func:`pack_local_stats` / :func:`stats_from_reduced`); stats on
  replicated values (params, global grads) are local arithmetic.
  ``tests/test_observability.py`` pins this with an HLO collective-count
  compare (instrumented == bare) via :mod:`apex_tpu.analysis.hlo`;
- **bit-identical training** — the instrumented step's params/optimizer
  state match the uninstrumented step's bit for bit (observation never
  feeds back; auxiliary outputs are ``stop_gradient``-cut so the
  backward program is unchanged).

Threaded through
:func:`apex_tpu.parallel.distributed.zero_data_parallel_train_step`,
``build_gpt_3d``'s ``make_train_step`` (``collect_stats=True``), and the
driver dryrun entry; the metric catalog lives in ``docs/observability.md``.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu.utils.tree import tree_l2_norm

__all__ = [
    "TrainStats",
    "PartialTrainStats",
    "train_stats",
    "partial_train_stats",
    "device_partial_norms",
    "local_grad_stats",
    "pack_local_stats",
    "stats_from_reduced",
    "stats_partition_specs",
    "TrainStatsLogger",
]


class TrainStats(NamedTuple):
    """Per-step telemetry, jit-carried (all jnp values, no host sync).

    ``loss``             — unscaled mean training loss (fp32).
    ``grad_norm``        — global L2 norm of the (unscaled) gradients.
                           On the ZeRO shard_map path this is the norm of
                           the *stacked per-replica local* grads (exactly
                           what rode the wire), not of their mean — see
                           docs/observability.md for the distinction.
    ``param_norm``       — global L2 norm of the parameters (pre-update).
    ``nonfinite_leaves`` — int32 count of gradient leaves containing any
                           NaN/Inf this step (0 on a healthy step; the
                           per-leaf refinement of ``amp.all_finite``).
    ``loss_scale``       — the loss scale the step ran under (1.0 when no
                           scaler is armed).
    ``skipped_steps``    — cumulative skipped updates from
                           ``resilience.SentinelState`` (0 when no
                           sentinel is armed).
    ``moe_aux``          — per-microbatch MoE auxiliary loss ``[m]``
                           (``None`` for dense models / trainers without
                           microbatch structure).
    ``moe_pairs``        — int32 ``[microbatches, expert layers, held
                           experts]``: the ``(token, expert)`` pairs each
                           held expert of each dropless expert layer took
                           (``None`` for a model without such layers).
    ``moe_choices``      — int32 ``[microbatches, expert layers, tokens,
                           top_k]``: the experts each token's router chose
                           (what a comparison with another precision
                           follows; ``None`` likewise; the logger leaves
                           it on the device).
    """

    loss: jnp.ndarray
    grad_norm: jnp.ndarray
    param_norm: jnp.ndarray
    nonfinite_leaves: jnp.ndarray
    loss_scale: jnp.ndarray
    skipped_steps: jnp.ndarray
    moe_aux: Optional[jnp.ndarray] = None
    moe_pairs: Optional[jnp.ndarray] = None
    moe_choices: Optional[jnp.ndarray] = None


def stats_partition_specs(*, moe_aux: bool = False) -> TrainStats:
    """Replicated ``PartitionSpec`` tree matching a :class:`TrainStats`
    output crossing a ``shard_map`` boundary (``None`` for an absent
    ``moe_aux`` keeps the pytree structures aligned)."""
    return TrainStats(
        loss=P(), grad_norm=P(), param_norm=P(), nonfinite_leaves=P(),
        loss_scale=P(), skipped_steps=P(),
        moe_aux=P() if moe_aux else None,
    )


def local_grad_stats(grads):
    """``(sumsq, nonfinite_leaves)`` of a gradient tree — pure local
    arithmetic (fp32 sum of squares; int32 count of floating leaves with
    any non-finite element).  No collective, no host sync."""
    leaves = [
        jnp.asarray(x) for x in jax.tree_util.tree_leaves(grads)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
    ]
    if not leaves:
        return jnp.float32(0), jnp.int32(0)
    sumsq = jnp.sum(jnp.stack(
        [jnp.sum(jnp.square(jnp.asarray(x, jnp.float32))) for x in leaves]))
    bad = jnp.sum(jnp.stack(
        [jnp.any(~jnp.isfinite(x)) for x in leaves]).astype(jnp.int32))
    return sumsq, bad


def _f32(x, default):
    return jnp.float32(default) if x is None else jnp.asarray(x, jnp.float32)


def train_stats(
    loss,
    grads,
    params,
    *,
    grad_scale=None,
    loss_scale=None,
    skipped_steps=None,
    moe_aux=None,
    moe_pairs=None,
    moe_choices=None,
) -> TrainStats:
    """Stats for **unsharded/replicated** global arrays (single-device
    trainers, host-side tests): everything is local arithmetic, so
    instrumentation adds zero collectives by construction.  For a
    trainer whose params are SHARDED global arrays (``build_gpt_3d``),
    plain arithmetic here would make the SPMD partitioner insert one
    all-reduce per leaf — use :func:`device_partial_norms` +
    :func:`partial_train_stats` instead.

    ``grad_scale`` — the scale the loss (hence grads) was multiplied by;
    the reported ``grad_norm`` is divided back so it is always unscaled.
    ``moe_aux`` is recorded via ``stop_gradient`` upstream (observational
    only — the backward program must not change).
    """
    sumsq, bad = local_grad_stats(grads)
    inv = 1.0 if grad_scale is None else 1.0 / _f32(grad_scale, 1.0)
    return TrainStats(
        loss=_f32(loss, 0.0),
        grad_norm=jnp.sqrt(sumsq) * inv,
        param_norm=tree_l2_norm(params),
        nonfinite_leaves=bad,
        loss_scale=_f32(loss_scale, 1.0),
        skipped_steps=(jnp.int32(0) if skipped_steps is None
                       else jnp.asarray(skipped_steps, jnp.int32)),
        moe_aux=moe_aux,
        moe_pairs=moe_pairs,
        moe_choices=moe_choices,
    )


# --- shard_map path: ride the existing loss all-reduce -------------------

# Element layout of the packed stats vector (one widened collective):
_PACK_LOSS, _PACK_SUMSQ, _PACK_BAD, PACK_LEN = 0, 1, 2, 3


def pack_local_stats(loss, grads) -> jnp.ndarray:
    """``[loss, grad_sumsq, nonfinite_leaves]`` as one ``(3,)`` fp32
    vector, to be **sum**-reduced over the data axes *in place of* the
    trainer's existing scalar loss reduction — the collective count stays
    exactly what the bare step had; only its payload widens by two
    elements.  Pass the loss pre-divided by any loss scale so element 0
    reduces to the same value (bitwise) the bare path's ``pmean``
    produced."""
    return jnp.stack([
        jnp.asarray(loss, jnp.float32).reshape(()),
        *local_grad_stats(grads),
    ]).astype(jnp.float32)


def stats_from_reduced(
    reduced: jnp.ndarray,
    world: int,
    params,
    *,
    grad_scale=None,
    loss_scale=None,
    skipped_steps=None,
    moe_aux=None,
):
    """Unpack the sum-reduced stats vector into ``(mean_loss,
    TrainStats)``.  ``world`` is the static replica count of the
    reduction axes, so ``reduced[0] / world`` reproduces ``pmean`` of the
    loss exactly (``lax.pmean`` is ``psum`` followed by the same static
    division).  ``grad_norm`` here is the L2 norm over the *stacked*
    per-replica local grads (``sqrt`` of the summed local sum-of-squares)
    — the honest quantity available without adding a second, full-width
    gradient collective; ``nonfinite_leaves`` sums every replica's count.
    ``param_norm`` stays local arithmetic (params are replicated)."""
    loss = reduced[_PACK_LOSS] / world
    inv = 1.0 if grad_scale is None else 1.0 / _f32(grad_scale, 1.0)
    stats = TrainStats(
        loss=loss,
        grad_norm=jnp.sqrt(reduced[_PACK_SUMSQ]) * inv,
        param_norm=tree_l2_norm(params),
        nonfinite_leaves=jnp.round(reduced[_PACK_BAD]).astype(jnp.int32),
        loss_scale=_f32(loss_scale, 1.0),
        skipped_steps=(jnp.int32(0) if skipped_steps is None
                       else jnp.asarray(skipped_steps, jnp.int32)),
        moe_aux=moe_aux,
    )
    return loss, stats


# --- sharded global-array path: per-device partials, host finalize -------


class PartialTrainStats(NamedTuple):
    """Device-partial form of :class:`TrainStats`, for trainers whose
    params/grads are SHARDED global arrays (``build_gpt_3d``).

    A global norm over a tp/pp-sharded tree cannot be computed in-graph
    without cross-shard reductions: written as plain arithmetic the SPMD
    partitioner inserts one all-reduce per leaf (dozens of collectives
    the bare step never performs).  So the step instead emits
    ``norm_partials`` — a tiny ``[n_devices, 2 + n_leaves]`` matrix of
    per-device partial sums produced by a ``shard_map`` whose outputs
    keep the device axis (:func:`device_partial_norms`, ZERO collectives
    by construction) — and the final reduction over that matrix happens
    on the **host**, at fetch time, where it is free.

    :class:`TrainStatsLogger` finalizes transparently; after a manual
    ``jax.device_get`` call :meth:`finalize` to get scalar
    :class:`TrainStats`.
    """

    loss: jnp.ndarray
    norm_partials: jnp.ndarray  # [D, 2+L] — see device_partial_norms
    grad_scale: jnp.ndarray
    loss_scale: jnp.ndarray
    skipped_steps: jnp.ndarray
    moe_aux: Optional[jnp.ndarray] = None
    moe_pairs: Optional[jnp.ndarray] = None
    moe_choices: Optional[jnp.ndarray] = None

    def finalize(self) -> TrainStats:
        """Host-side reduction of the partials matrix (numpy — call on
        fetched values, not inside jit)."""
        import numpy as np

        parts = np.asarray(self.norm_partials, np.float32)
        g_sumsq = parts[:, 0].sum()
        p_sumsq = parts[:, 1].sum()
        # A leaf is non-finite if ANY device's shard of it was.
        leaf_bad = parts[:, 2:].max(axis=0) > 0.5
        inv = 1.0 / float(np.float32(self.grad_scale))
        return TrainStats(
            loss=np.float32(self.loss),
            grad_norm=np.float32(np.sqrt(g_sumsq) * inv),
            param_norm=np.float32(np.sqrt(p_sumsq)),
            nonfinite_leaves=np.int32(leaf_bad.sum()),
            loss_scale=np.float32(self.loss_scale),
            skipped_steps=np.int32(self.skipped_steps),
            moe_aux=self.moe_aux,
            moe_pairs=self.moe_pairs,
            moe_choices=self.moe_choices,
        )


def device_partial_norms(mesh, param_specs):
    """Build ``fn(grads, params) -> [n_devices, 2 + n_leaves]`` — the
    per-device norm partials feeding :class:`PartialTrainStats`.

    Runs a dedicated ``shard_map`` over the FULL mesh whose output keeps
    the device axis, so the compiled program contains zero collectives
    (pinned by the instrumented-vs-bare HLO compare in
    ``tests/test_observability.py``).  Columns:

    - 0 — this device's gradient sum-of-squares, weighted by
      1/replication (a leaf replicated over mesh axes its spec does not
      mention would otherwise be counted once per replica), so the
      column's SUM over devices is the exact global sum of squares;
    - 1 — the same for the params;
    - ``2+k`` — 1.0 iff any element of this device's shard of gradient
      leaf ``k`` is non-finite (the host ORs the column across devices,
      then counts flagged leaves).
    """
    from apex_tpu.parallel import collectives as cc

    axis_names = tuple(mesh.axis_names)
    n_devices = 1
    for a in axis_names:
        n_devices *= mesh.shape[a]
    spec_leaves = jax.tree_util.tree_leaves(
        param_specs, is_leaf=lambda x: isinstance(x, P))
    weights = []
    for spec in spec_leaves:
        sharded = 1
        for entry in tuple(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, (tuple, list)) else (entry,)
            for a in axes:
                sharded *= mesh.shape[a]
        weights.append(sharded / n_devices)

    def local(grads, params):
        g_leaves = jax.tree_util.tree_leaves(grads)
        p_leaves = jax.tree_util.tree_leaves(params)
        if len(g_leaves) != len(weights) or len(p_leaves) != len(weights):
            raise ValueError(
                f"param_specs leaves ({len(weights)}) do not match "
                f"grads ({len(g_leaves)}) / params ({len(p_leaves)})")

        def wsumsq(leaves):
            return jnp.sum(jnp.stack([
                w * jnp.sum(jnp.square(jnp.asarray(x, jnp.float32)))
                for w, x in zip(weights, leaves)]))

        flags = jnp.stack([
            jnp.any(~jnp.isfinite(jnp.asarray(x, jnp.float32)))
            for x in g_leaves]).astype(jnp.float32)
        vec = jnp.concatenate(
            [jnp.stack([wsumsq(g_leaves), wsumsq(p_leaves)]), flags])
        return vec[None, :]

    return cc.shard_over(
        local, mesh=mesh, in_specs=(param_specs, param_specs),
        out_specs=P(axis_names))


def partial_train_stats(
    loss,
    norm_partials,
    *,
    grad_scale=None,
    loss_scale=None,
    skipped_steps=None,
    moe_aux=None,
    moe_pairs=None,
    moe_choices=None,
) -> PartialTrainStats:
    """Assemble a :class:`PartialTrainStats` (defaults mirror
    :func:`train_stats`; ``grad_scale`` divides the reported grad norm
    back to unscaled at finalize time)."""
    return PartialTrainStats(
        loss=_f32(loss, 0.0),
        norm_partials=norm_partials,
        grad_scale=_f32(grad_scale, 1.0),
        loss_scale=_f32(loss_scale, 1.0),
        skipped_steps=(jnp.int32(0) if skipped_steps is None
                       else jnp.asarray(skipped_steps, jnp.int32)),
        moe_aux=moe_aux,
        moe_pairs=moe_pairs,
        moe_choices=moe_choices,
    )


# --- host side: the log_every_n fetch ------------------------------------


class TrainStatsLogger:
    """The only place device stats meet the host — on a schedule.

    ``maybe_log(step, stats)`` is a no-op (not even a device poll) except
    every ``every_n``-th step, when the :class:`TrainStats` is fetched
    (ONE blocking transfer of a handful of scalars), written into the
    registry's gauges, and flushed to ``writer`` (a
    :class:`apex_tpu.observability.JsonlWriter`) — so the steady-state
    step stays fully async while the logged step pays one small sync.
    Returns the fetched ``dict`` when it logged, else ``None``.
    """

    def __init__(self, registry=None, *, every_n: int = 50, writer=None,
                 prefix: str = "train"):
        if every_n < 1:
            raise ValueError(f"every_n must be >= 1, got {every_n}")
        if registry is None:
            from apex_tpu.observability.metrics import default_registry

            registry = default_registry()
        self.registry = registry
        self.every_n = every_n
        self.writer = writer
        self.prefix = prefix

    def fetch(self, stats) -> dict:
        """Blocking device→host fetch of one stats pytree
        (:class:`TrainStats` or :class:`PartialTrainStats` — partials
        are finalized here), flattened to plain floats/ints
        (``moe_aux`` becomes a list, ``moe_pairs`` nested lists of ints).
        ``moe_choices``, half a million ids a step, stays on the device:
        whoever compares the choices reads ``stats.moe_choices`` itself."""
        import numpy as np

        host = jax.device_get(stats._replace(moe_choices=None))
        if hasattr(host, "finalize"):
            host = host.finalize()
        out = {}
        for name, val in zip(TrainStats._fields, host):
            if val is None:
                continue
            # Everything is on the host already — plain numpy, no
            # round-trip back through a device array.
            arr = np.asarray(val)
            if arr.ndim == 0:
                out[name] = (int(arr) if np.issubdtype(arr.dtype, np.integer)
                             else float(arr))
            elif np.issubdtype(arr.dtype, np.integer):
                out[name] = arr.tolist()
            else:
                out[name] = [float(v) for v in arr.tolist()]
        return out

    def maybe_log(self, step: int, stats: TrainStats,
                  extra: Optional[dict] = None):
        if step % self.every_n:
            return None
        return self.log(step, stats, extra=extra)

    def log(self, step: int, stats: TrainStats,
            extra: Optional[dict] = None) -> dict:
        """Unconditional fetch + record (the ``every_n`` hit path)."""
        values = self.fetch(stats)
        for name, val in values.items():
            if name == "moe_pairs":
                # pairs a step routed to the held experts, and how uneven:
                # the busiest held expert over the mean, per layer and
                # microbatch, averaged
                import numpy as np

                pairs = np.asarray(val, np.float64)
                self.registry.gauge(f"{self.prefix}/moe_pairs").set(
                    float(pairs.sum()))
                mean = np.maximum(pairs.mean(-1), 1e-9)
                self.registry.gauge(
                    f"{self.prefix}/moe_expert_load_peak").set(
                        float(np.mean(pairs.max(-1) / mean)))
                continue
            if isinstance(val, list):  # per-microbatch vector: log the mean
                if val:
                    self.registry.gauge(
                        f"{self.prefix}/{name}_mean").set(
                            sum(val) / len(val))
                continue
            self.registry.gauge(f"{self.prefix}/{name}").set(val)
        self.registry.counter(f"{self.prefix}/logged_steps").inc()
        record = dict(values)
        if extra:
            record.update(extra)
        self.registry.flush(self.writer, step=step, extra=record)
        return values

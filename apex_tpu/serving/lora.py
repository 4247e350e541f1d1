"""Batched multi-LoRA serving: paged adapter arena + gathered delta.

One replica, one base checkpoint, many tenants: each tenant's
fine-tune is a low-rank (LoRA) update ``W + B @ A * alpha/rank`` on the
four projections of every layer (fused QKV, attention dense, MLP fc1,
MLP fc2).  This module applies the paged-KV trick to *weights*:

- **Adapter arena** — the A/B pairs of every resident adapter live in
  stacked device arrays ``[L, n_slots, ...]``, one *slot* per adapter,
  managed host-side by :class:`AdapterArena` on the exact
  :class:`~apex_tpu.serving.kv_cache.BlockAllocator` refcount machinery
  the KV cache uses (one "block" = one adapter slot).  Slot 0 is the
  permanent **zero adapter**: all-zero A/B rows that every
  ``adapter_id=None`` request gathers, making the delta an exact zero
  and the stream bitwise identical to the bare engine.  Registered
  adapters are LRU-evicted like prefix blocks when cold; a pin per
  active request (``share``/``free`` under the request's rid) keeps a
  hot adapter resident for as long as any slot references it.
- **Gathered delta** — the decode/prefill step receives a per-slot
  ``[max_batch]`` adapter-slot vector as DATA (never shape) and
  computes ``delta = (x @ A[slot]) @ B_scaled[slot]`` per batch slot:
  the same scalar-prefetch index-map pattern
  :func:`~apex_tpu.serving.paged_attention.paged_attention_decode` uses
  for block tables, so adapter mix/churn never recompiles.  The base
  GEMM is untouched; the rank-r bypass adds ``O(r/H)`` relative FLOPs.

Tensor parallelism follows the base projections: for column-parallel
layers (qkv, fc1) A is replicated and B is sharded on the output dim —
the delta lands pre-split exactly like the base output.  For
row-parallel layers (dense, fc2) A is sharded on the *input* dim and B
replicated — each rank computes a partial delta from its input shard
and the engine all-reduces it alongside nothing else (one extra psum
per row-parallel projection per layer, only when tp > 1).

``B`` is stored pre-scaled by ``alpha/rank`` at registration, so the
runtime step is two plain matmuls.
"""

from __future__ import annotations

import dataclasses
import logging
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import numpy as np

from apex_tpu.serving.kv_cache import BlockAllocator, OutOfBlocksError

__all__ = [
    "ADAPTER_REGISTRY",
    "AdapterArena",
    "LoRAConfig",
    "adapter_partition_specs",
    "adapter_shapes",
    "init_adapter_arena",
    "init_adapter_weights",
    "lora_delta",
    "pack_adapter_values",
    "restore_adapter_for_serving",
]

logger = logging.getLogger(__name__)

#: Composite owner under which the arena itself holds every resident
#: adapter's slot (the ``CACHE_OWNER`` pattern from kv_cache.py): a
#: slot is evictable exactly when the registry is its only holder.
ADAPTER_REGISTRY = "<adapter-registry>"

#: Arena array order: (A, B) per projection, projections in this order.
PROJECTIONS = ("qkv", "dense", "fc1", "fc2")


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    """Adapter-arena shape knobs (compile-time constants of the engine).

    ``max_adapters`` is the number of *resident* adapter slots — the
    zero adapter (slot 0) is always present on top of it.  ``rank`` is
    the shared low-rank width every registered adapter must match (the
    arena arrays are stacked, so rank is shape).  ``alpha`` is the
    conventional LoRA scale; B is stored pre-multiplied by
    ``alpha/rank``.  ``fused=True`` gathers A/B rows with the Pallas
    scalar-prefetch kernel; ``False`` uses the jnp.take reference twin
    (same values, used by the parity test and as the interpret
    fallback's sanity check).
    """

    rank: int = 8
    max_adapters: int = 8
    alpha: float = 16.0
    fused: bool = True

    def __post_init__(self):
        if self.rank < 1:
            raise ValueError(f"lora rank must be >= 1 (got {self.rank})")
        if self.max_adapters < 1:
            raise ValueError(
                f"max_adapters must be >= 1 (got {self.max_adapters})")

    @property
    def n_slots(self) -> int:
        """Resident slots + the permanent zero adapter at slot 0."""
        return self.max_adapters + 1


# ---------------------------------------------------------------------------
# Shapes, device arrays, partition specs
# ---------------------------------------------------------------------------


def adapter_shapes(config, lora: LoRAConfig
                   ) -> Dict[str, Tuple[Tuple[int, int], Tuple[int, int]]]:
    """Per-projection ``(A, B)`` shapes (without the ``[L, n_slots]``
    stack dims), matching the serving model's fused projections."""
    d = config.head_dim
    n, g = config.num_attention_heads, config.query_groups
    h, f, r = config.hidden_size, config.ffn_size, lora.rank
    return {
        "qkv": ((h, r), (r, (n + 2 * g) * d)),
        "dense": ((n * d, r), (r, h)),
        "fc1": ((h, r), (r, f)),
        "fc2": ((f, r), (r, h)),
    }


def adapter_partition_specs(tp_axis: Optional[str]):
    """shard_map partition specs for the 8 arena arrays, in arena order
    ``(qkv_a, qkv_b, dense_a, dense_b, fc1_a, fc1_b, fc2_a, fc2_b)``.

    Column-parallel projections (qkv, fc1) shard B on the output dim
    (array dim 3); row-parallel ones (dense, fc2) shard A on the input
    dim (array dim 2); everything else is replicated.
    """
    from jax.sharding import PartitionSpec as P

    rep = P(None, None, None, None)
    col_b = P(None, None, None, tp_axis)
    row_a = P(None, None, tp_axis, None)
    return (rep, col_b, row_a, rep, rep, col_b, row_a, rep)


def init_adapter_arena(config, lora: LoRAConfig, mesh=None,
                       tp_axis: str = "tp"):
    """Zero-initialized adapter arrays ``[L, n_slots, *shape]`` in arena
    order, placed on ``mesh`` when given.

    All slots start as the zero adapter, so a fresh arena is inert: a
    request gathering any slot gets an exact-zero delta.  Like the int8
    scale arenas, placement uses replicated specs when the tp axis has
    size 1 — that is what jit emits for the step outputs there, so the
    engine's adapter round trip stays jit-cache-stable.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    shapes = adapter_shapes(config, lora)
    L, s = config.num_layers, lora.n_slots
    dtype = config.param_dtype
    arrays = []
    for proj in PROJECTIONS:
        for shape in shapes[proj]:
            arrays.append(jnp.zeros((L, s) + shape, dtype))
    if mesh is None:
        return tuple(arrays)
    specs = adapter_partition_specs(tp_axis)
    if mesh.shape.get(tp_axis, 1) == 1:
        specs = tuple(P() for _ in specs)
    return tuple(
        jax.device_put(a, NamedSharding(mesh, spec))
        for a, spec in zip(arrays, specs))


# ---------------------------------------------------------------------------
# Host weights: deterministic fixtures, packing, checkpoint restore
# ---------------------------------------------------------------------------


def init_adapter_weights(config, lora: LoRAConfig, *, seed: int = 0
                         ) -> Dict[str, Tuple[np.ndarray, np.ndarray]]:
    """Deterministic random host weights ``{proj: (A [L, in, r],
    B [L, r, out])}`` for one adapter.

    Both A and B are nonzero (unlike training-time LoRA init, which
    zeroes B) and deliberately LOUD (0.25-std entries) so two adapters
    seeded differently produce visibly different token streams even on
    tiny test models — this is the test fixture; production
    registers trained pairs via :func:`restore_adapter_for_serving`.
    """
    rng = np.random.default_rng(int(seed))
    shapes = adapter_shapes(config, lora)
    L = config.num_layers
    out = {}
    for proj in PROJECTIONS:
        (ai, ar), (br, bo) = shapes[proj]
        a = rng.standard_normal((L, ai, ar)).astype(np.float32) * 0.25
        b = rng.standard_normal((L, br, bo)).astype(np.float32) * 0.25
        out[proj] = (a, b)
    return out


def pack_adapter_values(config, lora: LoRAConfig, weights, dtype
                        ) -> Tuple[np.ndarray, ...]:
    """Validate one adapter's host weights and pack them into the 8
    arena-ordered per-slot values ``[L, *shape]``, B pre-scaled by
    ``alpha/rank`` (the arena stores the runtime form)."""
    shapes = adapter_shapes(config, lora)
    L = config.num_layers
    scale = lora.alpha / lora.rank
    vals = []
    for proj in PROJECTIONS:
        try:
            a, b = weights[proj]
        except (KeyError, TypeError, ValueError):
            raise ValueError(
                f"adapter weights missing projection {proj!r} "
                f"(need {{proj: (A, B)}} for {PROJECTIONS})") from None
        a = np.asarray(a)
        b = np.asarray(b)
        want_a, want_b = ((L,) + shapes[proj][0], (L,) + shapes[proj][1])
        if a.shape != want_a or b.shape != want_b:
            raise ValueError(
                f"adapter {proj!r} shapes {a.shape}/{b.shape} do not "
                f"match arena {want_a}/{want_b} (rank={lora.rank})")
        vals.append(np.asarray(a, dtype))
        vals.append(np.asarray(b * scale, dtype))
    return tuple(vals)


def restore_adapter_for_serving(ckpt_dir: str, config, lora: LoRAConfig, *,
                                key: str = "lora", sharded: bool = True,
                                verify: bool = True, with_step: bool = False):
    """Restore the newest intact adapter checkpoint as host weights.

    The spec-layer restore path from ``loader.restore_gpt_for_serving``,
    pointed at an adapter checkpoint: a
    :class:`~apex_tpu.resilience.CheckpointManager` directory whose
    checkpoints carry ``{key: {proj: {"a": ..., "b": ...}}}`` (any
    layer-stack factoring — placement is reshape-only via the
    mesh-independent ``load_logical`` view).  Checksum-verified, corrupt
    newest falls back to the previous committed step.  Returns the
    ``{proj: (A, B)}`` dict :meth:`ServingEngine.register_adapter`
    takes (plus the step with ``with_step=True``).
    """
    from apex_tpu import checkpoint as ckpt
    from apex_tpu.resilience import CheckpointManager, reshard

    shapes = adapter_shapes(config, lora)
    L = config.num_layers
    mgr = CheckpointManager(ckpt_dir, sharded=sharded)
    failures = []
    for step in reversed(mgr.all_steps()):
        try:
            if verify:
                mgr.verify(step)
            logical, _ = reshard.load_logical(mgr.step_path(step))
            weights = {}
            for proj in PROJECTIONS:
                pair = []
                for part, shape in zip(("a", "b"), shapes[proj]):
                    path = f"{key}/{proj}/{part}"
                    if path not in logical:
                        raise ckpt.CheckpointCorruptError(
                            f"adapter checkpoint has no leaf {path!r}")
                    host = logical[path]
                    tgt = (L,) + shape
                    if int(np.prod(host.shape)) != int(np.prod(tgt)):
                        raise ckpt.CheckpointCorruptError(
                            f"{path}: logical shape {list(host.shape)} "
                            f"cannot reshape to adapter shape {list(tgt)}")
                    pair.append(np.ascontiguousarray(host).reshape(tgt))
                weights[proj] = tuple(pair)
            if failures:
                logger.warning(
                    "adapter restore fell back to step %d past %s",
                    step, "; ".join(failures))
            if with_step:
                return weights, step
            return weights
        except (ckpt.CheckpointCorruptError, ValueError, OSError,
                KeyError) as e:
            failures.append(f"step {step}: {e!r}")
            logger.warning(
                "adapter checkpoint step %d unusable (%r); falling back",
                step, e)
    raise FileNotFoundError(
        f"no adapter checkpoint under {ckpt_dir!r} restorable"
        + (f" (tried: {'; '.join(failures)})" if failures else ""))


# ---------------------------------------------------------------------------
# The refcounted slot registry
# ---------------------------------------------------------------------------


class OutOfAdapterSlotsError(OutOfBlocksError):
    """Raised when registration needs a slot and every resident adapter
    is pinned by an active request (nothing is LRU-evictable)."""


class AdapterArena:
    """Host-side slot registry for the device adapter arrays.

    ``BlockAllocator(n_slots)`` does the refcounting: the registry
    itself holds every resident adapter's slot under
    :data:`ADAPTER_REGISTRY` (the ``CACHE_OWNER`` pattern), and every
    active request that names the adapter ``share``s the slot under its
    rid.  A slot is LRU-evictable exactly when its refcount is 1 —
    registry-only, no live pins.  Slot 0 (the zero adapter every
    ``adapter_id=None`` request gathers) is allocated once at
    construction and never enters the LRU.
    """

    def __init__(self, n_slots: int):
        if n_slots < 2:
            raise ValueError(
                f"adapter arena needs >= 2 slots (zero adapter + one "
                f"resident), got {n_slots}")
        self.n_slots = n_slots
        self.allocator = BlockAllocator(n_slots)
        (self.zero_slot,) = self.allocator.alloc(1, ADAPTER_REGISTRY)
        assert self.zero_slot == 0, "zero adapter must land in slot 0"
        # adapter_id -> slot, LRU order (oldest first; register/pin
        # move-to-end, eviction walks from the front)
        self._slots: "OrderedDict[str, int]" = OrderedDict()
        self._pins: Dict[Any, int] = {}      # rid -> pinned slot
        self.loads = 0                       # lifetime registrations
        self.evictions = 0                   # lifetime LRU evictions

    def __len__(self) -> int:
        return len(self._slots)

    def resident(self, adapter_id) -> bool:
        return adapter_id in self._slots

    def slot_of(self, adapter_id) -> Optional[int]:
        return self._slots.get(adapter_id)

    def residents(self):
        """Resident adapter ids, LRU-oldest first (heartbeat payload
        for the fleet's adapter-affinity placement)."""
        return list(self._slots)

    @property
    def active(self) -> int:
        """Live request pins across all adapters."""
        return len(self._pins)

    def register(self, adapter_id) -> Tuple[int, Optional[str]]:
        """Claim a slot for ``adapter_id``; returns ``(slot, evicted)``.

        A resident id re-registers **in place** (same slot, moved to
        LRU front) — that is the hot-swap path: the caller overwrites
        the slot's rows and in-flight requests pinning the old version
        keep their already-gathered semantics tick-to-tick.  A new id
        takes a free slot, LRU-evicting the coldest unpinned adapter if
        the arena is full; if every resident adapter is pinned,
        :class:`OutOfAdapterSlotsError`.
        """
        self.loads += 1
        if adapter_id in self._slots:
            self._slots.move_to_end(adapter_id)
            return self._slots[adapter_id], None
        evicted = None
        if not self.allocator.can_alloc(1):
            evicted = self._evict_one()
            if evicted is None:
                self.loads -= 1
                raise OutOfAdapterSlotsError(
                    f"no adapter slot free: all {len(self._slots)} "
                    f"resident adapters are pinned by active requests")
        (slot,) = self.allocator.alloc(1, ADAPTER_REGISTRY)
        self._slots[adapter_id] = slot
        return slot, evicted

    def _evict_one(self) -> Optional[str]:
        for aid, slot in self._slots.items():
            if self.allocator.refcount(slot) == 1:   # registry-only
                del self._slots[aid]
                self.allocator.free([slot], ADAPTER_REGISTRY)
                self.evictions += 1
                return aid
        return None

    def unregister(self, adapter_id) -> int:
        """Drop the registry's hold on ``adapter_id``.  The slot stays
        allocated (and its rows live) until the last pinning request
        finishes; new requests can no longer name the adapter."""
        slot = self._slots.pop(adapter_id, None)
        if slot is None:
            raise KeyError(f"adapter {adapter_id!r} is not resident")
        self.allocator.free([slot], ADAPTER_REGISTRY)
        return slot

    def pin(self, adapter_id, rid) -> int:
        """Pin ``adapter_id`` for request ``rid``; returns the slot the
        request's batch entry should gather."""
        slot = self._slots.get(adapter_id)
        if slot is None:
            raise KeyError(f"adapter {adapter_id!r} is not resident")
        if rid in self._pins:
            raise ValueError(f"request {rid!r} already pins a slot")
        self.allocator.share(slot, rid)
        self._slots.move_to_end(adapter_id)
        self._pins[rid] = slot
        return slot

    def unpin(self, rid) -> None:
        """Release ``rid``'s pin.  Idempotent no-op for a request that
        never pinned (the ``adapter_id=None`` common case), so every
        terminal path can call it unconditionally."""
        slot = self._pins.pop(rid, None)
        if slot is not None:
            self.allocator.free([slot], rid)

    def pinned_slot(self, rid) -> int:
        """The arena slot ``rid`` gathers (zero slot when unpinned)."""
        return self._pins.get(rid, self.zero_slot)

    def check(self) -> None:
        """Arena invariants (test hook, mirrors ``BlockAllocator.check``):
        allocator free-XOR-held; every resident slot held by the
        registry; every pin a share on a known slot."""
        self.allocator.check()
        seen = set()
        for aid, slot in self._slots.items():
            assert slot not in seen, f"slot {slot} mapped twice"
            seen.add(slot)
            assert self.allocator.refcount(slot) >= 1, \
                f"resident adapter {aid!r} slot {slot} has no holders"
        for rid, slot in self._pins.items():
            assert self.allocator.refcount(slot) >= 1, \
                f"pin {rid!r} on slot {slot} with no holders"
        assert self.allocator.refcount(self.zero_slot) >= 1, \
            "zero adapter slot was freed"


# ---------------------------------------------------------------------------
# The gathered delta: Pallas scalar-prefetch kernel + reference twin
# ---------------------------------------------------------------------------


def _delta_kernel(slots_ref, x_ref, a_ref, b_ref, o_ref):
    """One batch slot's rank-r bypass: ``(x @ A[slot]) @ B[slot]`` in
    fp32 on the MXU.  ``slots_ref`` is the scalar-prefetch vector the
    index maps consumed; the body never reads it."""
    import jax
    import jax.numpy as jnp

    del slots_ref
    x = x_ref[0].astype(jnp.float32)                   # [S, in]
    a = a_ref[0].astype(jnp.float32)                   # [in, r]
    b = b_ref[0].astype(jnp.float32)                   # [r, out]
    # HIGHEST pins the fp32 contraction the twin (and interpret mode on
    # the CPU) computes, instead of the compiler's default precision
    t = jnp.dot(x, a, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
    o_ref[0] = jnp.dot(
        t, b, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def lora_delta_fused(x, a, b, slots):
    """Gathered LoRA delta via scalar-prefetch (the block-table trick
    on weights): grid over batch slots, A/B block index maps read
    ``slots[i]`` — which adapter a slot runs is data the prefetched
    vector carries, never a shape.

    ``x [S, B, in]`` seq-major activations; ``a [n_slots, in, r]``;
    ``b [n_slots, r, out]`` (pre-scaled); ``slots [B]`` int.  Returns
    ``[S, B, out]`` in ``x.dtype``.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from apex_tpu.utils import platform

    S, B, IN = x.shape
    r, out = b.shape[1], b.shape[2]

    def x_idx(i, slots_ref):
        return (i, 0, 0)

    def ab_idx(i, slots_ref):
        return (slots_ref[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        # x and out ride batch-major [B, S, .]: one slot's (1, S, .)
        # block keeps its last two dims full, which the TPU tiling rule
        # needs and an (S, 1, .) block of the seq-major array breaks
        in_specs=[
            pl.BlockSpec((1, S, IN), x_idx),
            pl.BlockSpec((1, IN, r), ab_idx),
            pl.BlockSpec((1, r, out), ab_idx),
        ],
        out_specs=pl.BlockSpec((1, S, out), x_idx),
    )
    delta = pl.pallas_call(
        _delta_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, out), x.dtype),
        # batch slots are independent (parallel, megacore-splittable)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=platform.pallas_interpret(),
    )(slots.astype(jnp.int32), x.transpose(1, 0, 2), a, b)
    return delta.transpose(1, 0, 2)


def lora_delta_unfused(x, a, b, slots):
    """Reference twin of :func:`lora_delta_fused`: materialize the
    per-slot A/B gather with ``jnp.take`` and contract in fp32."""
    import jax.numpy as jnp

    ag = jnp.take(a, slots, axis=0).astype(jnp.float32)    # [B, in, r]
    bg = jnp.take(b, slots, axis=0).astype(jnp.float32)    # [B, r, out]
    t = jnp.einsum("sbi,bir->sbr", x.astype(jnp.float32), ag)
    return jnp.einsum("sbr,bro->sbo", t, bg).astype(x.dtype)


def lora_delta(x, a, b, slots, *, fused: bool = True):
    """``delta[s, i] = (x[s, i] @ A[slots[i]]) @ B_scaled[slots[i]]``."""
    if fused:
        return lora_delta_fused(x, a, b, slots)
    return lora_delta_unfused(x, a, b, slots)

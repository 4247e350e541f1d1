"""The serving engine: continuous batching over the paged-cache decode.

One object owns the whole runtime: the compiled prefill/decode programs
(each built ONCE — request churn, chunked prefill, prefix-cache hits,
eviction, preemption and per-request sampling policies are all data,
never shape, so both steps compile exactly once per process;
:meth:`ServingEngine.decode_compile_count` pins this in tests), the
sharded KV arenas (donated through every step so XLA updates them in
place — APX204, analyzer entry ``serving_decode``), the host scheduler,
the PR 5 metrics, and the PR 3 preemption drain.

Step anatomy (:meth:`ServingEngine.step`)::

    [preemption?] -> admit waiting requests   (slot + first-chunk
                                               blocks; prefix-cache
                                               hits shared, not
                                               recomputed)
                  -> one chunked-prefill call  (each prefilling slot
                                               advances <= prefill_len
                                               tokens — a long prompt
                                               never stalls the tick)
                  -> grow decode blocks        (evict cached LRU, then
                                               preempt newest)
                  -> one batched decode step   (paged attention +
                                               in-graph sampling; with
                                               speculation: the k+1
                                               verify — n-gram drafts
                                               proposed host-side,
                                               verified in-graph, the
                                               accepted prefix emitted
                                               as 1..k+1 tokens)
                  -> append/finish bookkeeping (host; rejection = the
                                               length never advances —
                                               O(1), no KV copies)

A model with cache groups runs one call ahead (ISSUE 34): its tick
dispatches decode call n+1 and only then fetches and delivers call n, so
the plan, the way back and the delivery run under the device's work.  The
sampled token stays on the device between the two calls (the compiled
decode entry selects it by a mask), and the plan works from the host's
counts as they will be once the call in flight is delivered.
:meth:`ServingEngine.settle` brings the host's view up to date; the engine
calls it wherever that view has to be current (docs/serving.md, "One call
ahead").  Every other model settles in the tick that dispatched.

Metric catalog (rank-aware registry, docs/observability.md +
docs/serving.md):

- ``serving/ttft_ms``      histogram (sampled: p50/p99) — submit to
  first token, per request
- ``serving/tpot_ms``      histogram (sampled: p50/p99) — inter-token
  interval on the decode path, per token
- ``serving/tokens_generated`` / ``serving/requests_finished`` /
  ``serving/requests_cancelled`` / ``serving/requests_rejected``
  counters (rejected = refused at submit while draining — a typed
  terminal state, distinct from accepted-then-drained cancellation)
- ``serving/active_slots`` / ``serving/free_blocks`` gauges
- ``serving/kv_occupancy`` gauge — fraction of the block pool holding
  live or cached KV (the occupancy worst-case reservation kept low)
- ``serving/prefix_cache_hits`` counter — blocks served from the
  prefix cache instead of recomputed
- ``serving/preemptions``  counter — requests evicted back to the
  queue for recompute-on-readmit
- ``serving/evictions``    counter — prefix-cache blocks returned to
  the free list under pool pressure
- ``serving/preemption_drains`` counter
- ``serving/spec_proposed`` / ``serving/spec_accepted`` counters —
  drafted tokens entering the k+1 verify and the drafts it accepted
  (ISSUE 13; zero when ``ServingConfig.speculative`` is off)
- ``serving/spec_acceptance`` gauge — lifetime accepted/proposed ratio
  (the drafting hit rate the adaptive back-off steers on)
- ``serving/mfu``          gauge — decode-step MFU when the device peak
  is known (``introspect()["mfu_reason"]`` says why otherwise)
- ``serving/ticks`` / ``serving/prefill_calls`` /
  ``serving/decode_calls`` counters — ``step()`` calls and the device
  calls they made (ISSUE 25)
- ``serving/prefill_tokens`` / ``serving/prefill_capacity_tokens``
  counters — prompt tokens the prefill calls advanced, and the
  ``max_batch x prefill_len`` their fixed shape has room for
- ``serving/decode_slot_steps`` counter — slots that took part in a
  decode call, summed over calls
- ``serving/decode_calls_ahead`` counter — decode calls dispatched while
  the call before was still unfetched (over ``serving/decode_calls``: the
  share of calls that overlapped the host's work), and
  ``serving/decode_rows_discarded`` — rows of such a call thrown away
  because the delivery before it ended their request on its ``eos_id``
  (a model with cache groups only; ISSUE 34)
- ``serving/drawn_calls`` / ``serving/drawn_rows`` counters — decode and
  prefill calls that held at least one slot of temperature > 0 (the
  in-graph draw ran: ``sampling.sample_tokens``' ``cond`` was true), and
  such slots summed over those calls (ISSUE 28)
- ``serving/decode_kv_tokens`` / ``serving/decode_kv_pages`` counters —
  cache rows the decode calls' slots attended, and the KV blocks that
  hold them (``ceil(rows / block_size)`` per slot): what the paged
  decode kernel had to read (ISSUE 26)
- ``serving/queue_wait_ms`` histogram (sampled) — submit to first
  admission, per request
- ``serving/moe_pairs`` / ``serving/moe_experts_hit`` counters — the
  ``(token, expert)`` pairs the decode and prefill calls routed to held
  experts, and the (layer, expert) entries they hit (ISSUE 27; a model
  with expert layers only); ``serving/moe_group_tokens`` — the live
  tokens, summed over expert layers, whose router kept a group of experts
  held here (every live token without a group limit; ISSUE 33)
- ``serving/kv_latent_bytes_per_token`` gauge — what the latent cache
  groups' arenas hold over the tokens they can hold, all layers (ISSUE 33)
- ``serving/window_blocks_freed`` counter — blocks a window cache group
  handed back behind the window
- ``dsa/rows_scanned`` / ``dsa/rows_selected`` counters — the cached
  tokens the indexers of sparse latent layers scored, and those the
  queries then attended, summed over those layers, of decode calls;
  ``dsa/prefill_rows_scanned`` / ``dsa/prefill_rows_selected`` the same of
  prefill calls (ISSUE 37; a model with sparse layers only)

Host spans (ISSUE 25): every :meth:`ServingEngine.step` is one
``serving/tick`` span of :mod:`apex_tpu.observability.spans` with one
child per phase that ran (``admit``, ``prefill_plan``,
``prefill_dispatch``, ``prefill_fetch``, ``prefill_deliver``,
``decode_plan``, ``decode_dispatch``, ``decode_fetch``, ``deliver``) —
in the span ring, in ``span_ms/*`` of the engine's registry, and on the
profiler's host plane while a session runs; fields and coverage are in
docs/observability.md.

Run-timeline (ISSUE 10): with a flight recorder armed
(:mod:`apex_tpu.observability.timeline`) the engine additionally logs
the full request lifecycle keyed by request id — including
``request_preempt`` and the re-``request_admit`` of the recompute —
see the class docstring and docs/observability.md.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import types
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from apex_tpu.observability import spans, timeline
from apex_tpu.observability.metrics import (
    compiled_flops,
    default_registry,
    mfu_or_reason,
)
from apex_tpu.parallel import collectives as cc
from apex_tpu.parallel.mesh import TENSOR_AXIS, get_mesh
from apex_tpu.serving.kv_cache import (
    CacheGroup,
    ExportLedger,
    KVCacheConfig,
    arena_partition_spec,
    init_group_arenas,
    init_kv_arena,
    scale_partition_spec,
)
from apex_tpu.serving.lora import (
    AdapterArena,
    LoRAConfig,
    adapter_partition_specs,
    init_adapter_arena,
    init_adapter_weights,
    pack_adapter_values,
)
from apex_tpu.serving.model import decode_model
from apex_tpu.serving.sampling import SamplingParams
from apex_tpu.serving.scheduler import (
    Request,
    RequestState,
    Scheduler,
    trace_fields,
)
from apex_tpu.serving.speculative import NGramProposer, SpeculativeConfig

__all__ = ["ServingConfig", "ServingEngine"]


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Static shape of the runtime (everything that pins a compile).

    ``prefill_len`` is the per-slot chunk width of the batched chunked
    prefill — the most prompt tokens any one request advances per tick
    (long prompts slice across ticks and never stall the decode).
    ``admission`` selects occupancy admission (on-demand growth +
    eviction + preemption, the production policy) or the PR 8
    worst-case ``"reserve"`` baseline; ``prefix_caching`` toggles
    copy-on-write prompt-prefix sharing (occupancy mode only).
    ``cache_dtype=jnp.int8`` stores the KV arenas quantized with
    per-row fp32 scales dequantized inside the paged kernels.
    ``speculative`` (a :class:`~apex_tpu.serving.speculative.
    SpeculativeConfig`, ISSUE 13) turns the decode step into the
    ``[max_batch, k + 1]`` self-speculative verify — ``k + 1`` pins the
    compiled decode shape (one compile; per-slot draft counts are
    data); ``None`` keeps the plain one-token step.
    ``lora`` (a :class:`~apex_tpu.serving.lora.LoRAConfig`) enables
    batched multi-LoRA serving: per-request adapters gathered from a
    paged adapter arena inside the same compiled step — rank and slot
    count pin the compile; which adapter each slot runs is data.
    ``None`` keeps the engine byte-identical to the bare path.
    A model whose layers are of more than one kind
    (``TransformerConfig.hybrid``) gets one cache group per attention
    kind: ``n_blocks`` sizes the groups without a window (a latent
    kind's group among them: one arena a layer of ``latent_rank +
    rotary_dim`` channels a token, no value arena), and a group
    with one gets what ``max_batch`` slots need for the window and one
    chunk; it takes ``prefix_caching=False`` and none of ``speculative``,
    ``lora`` or an int8 cache yet.
    """

    max_batch: int = 8           # concurrent decode slots
    block_size: int = 16         # tokens per KV block
    max_seq: int = 256           # per-request context cap (prompt+output)
    n_blocks: Optional[int] = None   # arena size; default = worst case
    prefill_len: Optional[int] = None  # chunk width; default max_seq
    cache_dtype: Any = None      # arena storage dtype; default param dtype
    fused_attention: bool = True   # Pallas paged kernels vs unfused XLA
    fuse_epilogue: bool = True     # fused residual/norm epilogue kernel
    admission: str = "occupancy"   # or "reserve" (PR 8 worst-case A/B)
    prefix_caching: bool = True    # share prompt-prefix blocks
    speculative: Optional[SpeculativeConfig] = None  # n-gram drafting
    lora: Optional[LoRAConfig] = None  # multi-LoRA adapter arena

    def __post_init__(self):
        if self.admission not in ("occupancy", "reserve"):
            raise ValueError(
                f"admission must be 'occupancy' or 'reserve', got "
                f"{self.admission!r}")

    def resolve_n_blocks(self, max_blocks_per_request: int) -> int:
        if self.n_blocks is not None:
            return self.n_blocks
        return self.max_batch * max_blocks_per_request


@dataclasses.dataclass
class _DecodeCall:
    """A dispatched decode call whose results are still on the device:
    what a settle fetches and delivers."""

    rows: List[Tuple[Request, int]]     # each request with its slot
    rids: frozenset                     # the requests' ids
    drafts: dict
    out_tokens: Any                     # [max_batch, spec_width]
    accepted: Any
    logits: Any
    routed: Any                         # expert layers only, else None
    reached: Any
    selected: Any                       # sparse layers only, else None
    dispatch: spans.span                # its ``decode_dispatch`` span
    step: int                           # the tick that dispatched it


class ServingEngine:
    """Continuous-batching decode runtime over a GPT checkpoint.

    ``params``: a :class:`~apex_tpu.transformer.testing.
    gpt_parallel_train.GPT3DParams` with the layer stack in the
    canonical ``[vpp, pp, ...]`` form (what ``build_gpt_3d``'s init and
    the :mod:`~apex_tpu.serving.loader` restore both produce — the two
    leading dims are merged row-major into the ``[L, ...]`` serving
    stack).  ``guard``: an optional
    :class:`~apex_tpu.resilience.PreemptionGuard`; once it trips, the
    engine drains — no admissions, running requests decode to
    completion and deliver, waiting ones are cancelled.

    ``heartbeat``: an optional :class:`~apex_tpu.observability.metrics.
    HeartbeatMonitor` — the engine beats it at the end of every
    :meth:`step` (after the decode results it fetched materialize: the
    tick's own call, or the call before's where the engine runs one call
    ahead), so a hung
    device step (dead collective, wedged transfer) stops the beats, the
    monitor's ``on_hang`` fires the guard, and the engine's next alive
    moment **drains** — delivering in-flight responses — instead of the
    scheduler wedging forever (ISSUE 10 satellite; wire ``on_hang`` to
    the same ``guard``).

    ``timeline_tick_every``: when a flight recorder is armed
    (:mod:`apex_tpu.observability.timeline`), every request's lifecycle
    is logged (submit → admit → prefill chunks → decode ticks →
    preempt/re-admit → finish/cancel, keyed by ``rid``); decode ticks
    are sampled every N generated tokens so the hot loop pays one host
    dict per N tokens, not per token.
    """

    def __init__(self, config, serving: ServingConfig, params, *,
                 mesh=None, tp_axis: str = TENSOR_AXIS, registry=None,
                 guard=None, heartbeat=None, timeline_tick_every: int = 8):
        import jax.numpy as jnp

        self.mesh = mesh if mesh is not None else get_mesh()
        self.tp_axis = tp_axis
        self.serving = serving
        if (config.position_embedding_type == "learned"
                and config.max_position_embeddings < serving.max_seq):
            raise ValueError(
                f"max_seq ({serving.max_seq}) exceeds the learned position "
                f"table ({config.max_position_embeddings})")
        # speculative decode (ISSUE 13): the decode step's query width
        # is k+1 — a compile-time constant; per-slot draft counts are
        # data, so acceptance churn never recompiles
        self.spec = serving.speculative
        self.spec_width = 1 + (self.spec.k if self.spec is not None else 0)
        if serving.max_seq < self.spec_width:
            raise ValueError(
                f"max_seq ({serving.max_seq}) below the speculative "
                f"width ({self.spec_width})")
        self.proposer = (NGramProposer(self.spec)
                         if self.spec is not None else None)

        cache_dtype = (serving.cache_dtype if serving.cache_dtype is not None
                       else config.param_dtype)
        probe = KVCacheConfig(
            n_layers=config.num_layers, n_blocks=1,
            block_size=serving.block_size, kv_heads=config.query_groups,
            head_dim=config.head_dim, max_seq=serving.max_seq,
            dtype=cache_dtype)
        self.prefill_len = serving.prefill_len or serving.max_seq
        n_blocks = serving.resolve_n_blocks(probe.max_blocks_per_request)
        self.hybrid = config.hybrid is not None
        if self.hybrid:
            if self.spec is not None or self.mesh.devices.size > 1:
                raise NotImplementedError(
                    "hybrid layers: one chip, no speculation yet")
            groups = self._cache_groups(config.hybrid, serving, n_blocks)
            probe = dataclasses.replace(
                probe, groups=groups, n_layers=len(groups[0].layers),
                kv_heads=groups[0].kv_heads, head_dim=groups[0].k_dim)
            n_blocks = groups[0].n_blocks
        self.cache = dataclasses.replace(probe, n_blocks=n_blocks)
        self.model = decode_model(
            config, self.cache, fused_attention=serving.fused_attention,
            fuse_epilogue=serving.fuse_epilogue, lora=serving.lora)
        # Live-retunable knobs (ISSUE 18): data-only caps an autopilot
        # can actuate at runtime over the command wire.  Neither touches
        # a compiled shape — the prefill call keeps its [B, T] program
        # and the verify keeps [B, k+1]; the caps only shrink how much
        # of each fixed-shape call is *used*, so retuning never
        # recompiles.  None means "engine default" (the knob is unset).
        self.live_prefill_chunk: Optional[int] = None
        self.live_spec_k: Optional[int] = None

        self._jnp = jnp
        if self.hybrid:
            self._init_hybrid(params)
        else:
            self._init_uniform(config, params)
        self._finish_init(serving, registry, guard, heartbeat,
                          timeline_tick_every)

    def _cache_groups(self, hybrid, serving: ServingConfig, n_blocks: int):
        """One cache group per attention kind that has layers, holding the
        kind's rows (a latent kind's one row of latent and shared key):
        those without a window get ``n_blocks``, a window group what
        ``max_batch`` slots need for the window and one chunk, wherever
        they lie against the block edges."""
        groups = []
        for ki, kind in enumerate(hybrid.kinds):
            layers = hybrid.layers_of(ki)
            if not layers:
                continue
            kv_heads, k_dim, v_dim, latent = kind.cache_row
            group = CacheGroup(
                layers=layers, kv_heads=kv_heads, k_dim=k_dim, v_dim=v_dim,
                n_blocks=n_blocks, window=kind.window, latent=latent,
                index_dim=kind.index_row)
            if kind.window is not None:
                per_slot = group.blocks_spanned(
                    self.prefill_len, serving.block_size, n_blocks)
                group = dataclasses.replace(
                    group, n_blocks=serving.max_batch * per_slot)
            groups.append(group)
        return tuple(groups)

    def _init_hybrid(self, params) -> None:
        """Layers of several kinds: per-layer arenas in cache groups, one
        jit per entry point (one chip: no shard_map to bind)."""
        import jax

        self.params = params
        self.param_specs = None
        self.arenas = init_group_arenas(self.cache)
        self.lora = None
        self.adapter_arena = None
        self.adapters = None
        model = self.model

        def decode_step(arenas, params, tokens, carried, from_carried,
                        *rest):
            # a slot carried over from the call before takes the token that
            # call sampled, which has not left the device
            with spans.named_span("embed"):
                tokens = jax.numpy.where(from_carried[:, None], carried,
                                         tokens)
            return model.decode_step(arenas, params, tokens, *rest)

        self._decode = jax.jit(decode_step, donate_argnums=(0,))
        self._prefill = jax.jit(self.model.prefill, donate_argnums=(0,))
        # nothing on this lowering's host (no proposer, no adapters, no KV
        # export) needs a call's tokens before the next plan, so the next
        # call is dispatched before they are fetched.  A call's tokens are
        # handed to the next as they are; the first call takes zeros placed
        # as a call's results will be (committed to a device if an argument
        # is), so that there is one compiled decode program.
        self._runs_ahead = True
        self._no_tokens = jax.numpy.zeros((self.serving.max_batch, 1),
                                          jax.numpy.int32)
        placed = [leaf for leaf in jax.tree_util.tree_leaves(
            (self.arenas, self.params)) if getattr(leaf, "committed", False)]
        if placed:
            self._no_tokens = jax.device_put(self._no_tokens,
                                             placed[0].sharding)

    def _init_uniform(self, config, params) -> None:
        """Every layer alike: the stacked arena and the tensor-parallel
        shard_map bodies."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from apex_tpu.transformer.tensor_parallel import infer_param_specs

        serving, tp_axis = self.serving, self.tp_axis
        # the proposer, the adapters and KV export read a call's tokens on
        # the host before the next plan: fetch in the tick that dispatched
        self._runs_ahead = False
        # [vpp, pp, ...] -> [L, ...] (row-major merge == virtual-stage
        # major == plain layer order; gpt3d_logical_folds rationale)
        L = config.num_layers
        params = params._replace(layers=jax.tree_util.tree_map(
            lambda l: l.reshape((L,) + l.shape[2:]), params.layers))
        self.params = params

        e_specs = infer_param_specs(params.embedding, axis=tp_axis)
        per_layer = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
            params.layers)
        l_specs = jax.tree_util.tree_map(
            lambda s: P(None, *tuple(s)),
            infer_param_specs(per_layer, axis=tp_axis),
            is_leaf=lambda x: isinstance(x, P))
        ln_specs = jax.tree_util.tree_map(lambda _: P(), params.final_ln)
        self.param_specs = type(params)(
            embedding=e_specs, layers=l_specs, final_ln=ln_specs)

        self.arenas: Tuple[Any, ...] = init_kv_arena(
            self.cache, self.mesh, tp_axis)
        a_spec = arena_partition_spec(tp_axis)
        arena_specs: Tuple[Any, ...] = (a_spec, a_spec)
        if self.cache.quantized:
            s_spec = scale_partition_spec(tp_axis)
            arena_specs = (a_spec, a_spec, s_spec, s_spec)

        # multi-LoRA (ISSUE 17): the adapter arrays are a second donated
        # arena set threaded through both steps; each request's arena
        # slot is [max_batch] data gathered in-kernel, so the adapter
        # mix never pins a compile
        self.lora = serving.lora
        self.adapter_arena: Optional[AdapterArena] = None
        self.adapters: Optional[Tuple[Any, ...]] = None
        self._adapter_dtype = config.param_dtype
        if self.lora is not None:
            self.adapter_arena = AdapterArena(self.lora.n_slots)
            self.adapters = init_adapter_arena(
                config, self.lora, self.mesh, tp_axis)

        rep = P()
        if self.lora is None:
            decode_body = cc.shard_over(
                self.model.decode_step, mesh=self.mesh,
                in_specs=(arena_specs, self.param_specs) + (rep,) * 10,
                out_specs=(arena_specs, P(None, None), P(None),
                           P(None, None, None)),
            )
            prefill_body = cc.shard_over(
                self.model.prefill, mesh=self.mesh,
                in_specs=(arena_specs, self.param_specs) + (rep,) * 13,
                out_specs=(arena_specs, P(None), P(None, None, None)),
            )
        else:
            adapter_specs = adapter_partition_specs(tp_axis)
            model = self.model

            def decode_step_lora(arenas, adapters, params, tokens,
                                 positions, block_tables, active, n_draft,
                                 adapter_slots, temperature, top_k, top_p,
                                 seeds, steps):
                return model.decode_step(
                    arenas, params, tokens, positions, block_tables,
                    active, n_draft, temperature, top_k, top_p, seeds,
                    steps, adapters=adapters,
                    adapter_slots=adapter_slots)

            def prefill_lora(arenas, adapters, params, tokens,
                             position_ids, block_tables, lengths, limits,
                             dest_blocks, dest_offsets, sample_index,
                             adapter_slots, temperature, top_k, top_p,
                             seeds, steps):
                return model.prefill(
                    arenas, params, tokens, position_ids, block_tables,
                    lengths, limits, dest_blocks, dest_offsets,
                    sample_index, temperature, top_k, top_p, seeds,
                    steps, adapters=adapters,
                    adapter_slots=adapter_slots)

            decode_body = cc.shard_over(
                decode_step_lora, mesh=self.mesh,
                in_specs=(arena_specs, adapter_specs, self.param_specs)
                + (rep,) * 11,
                out_specs=(arena_specs, adapter_specs, P(None, None),
                           P(None), P(None, None, None)),
            )
            prefill_body = cc.shard_over(
                prefill_lora, mesh=self.mesh,
                in_specs=(arena_specs, adapter_specs, self.param_specs)
                + (rep,) * 14,
                out_specs=(arena_specs, adapter_specs, P(None),
                           P(None, None, None)),
            )
        # the arenas are donated: the KV cache must alias in->out or the
        # biggest HBM tenant of the chip doubles (APX204, entry
        # serving_decode); with LoRA the adapter arrays donate alongside
        donated = (0,) if self.lora is None else (0, 1)
        self._decode = jax.jit(decode_body, donate_argnums=donated)
        self._prefill = jax.jit(prefill_body, donate_argnums=donated)
        # adapter (un)load: one donated in-place row update per
        # registration — the slot index is traced data, so churning
        # adapters through the arena reuses one compiled scatter
        self._adapter_set = jax.jit(
            lambda ad, slot, vals: tuple(
                a.at[:, slot].set(v) for a, v in zip(ad, vals)),
            donate_argnums=(0,))
        # KV-block migration (ISSUE 16): one donated scatter lands a
        # whole imported run in the arenas per migration flush — one
        # device put per flush, never one per block
        self._import_scatter = jax.jit(
            lambda arenas, idx, vals: tuple(
                a.at[:, idx].set(v) for a, v in zip(arenas, vals)),
            donate_argnums=(0,))

    def _finish_init(self, serving, registry, guard, heartbeat,
                     timeline_tick_every) -> None:
        self.scheduler = Scheduler(
            self.cache, serving.max_batch, chunk_tokens=self.prefill_len,
            admission=serving.admission,
            prefix_caching=serving.prefix_caching)
        # pin-until-ack ledger for exported (migrating) block runs: the
        # run stays held until the decode side acks, then frees into the
        # prefix cache as evictable capacity
        self.exports = ExportLedger(self.scheduler.allocator,
                                    self.scheduler.prefix_cache)
        self.registry = registry if registry is not None else \
            default_registry()
        self.guard = guard
        self.heartbeat = heartbeat
        if timeline_tick_every < 1:
            raise ValueError(
                f"timeline_tick_every must be >= 1, got "
                f"{timeline_tick_every}")
        self.timeline_tick_every = timeline_tick_every
        # one block table per cache group; ``_tables`` is group 0's
        self._group_tables = [
            np.zeros((serving.max_batch, self.cache.max_blocks_per_request),
                     np.int32) for _ in self.cache.cache_groups]
        self._tables = self._group_tables[0]
        self._windowed = any(g.window is not None
                             for g in self.cache.cache_groups)
        self._steps = 0
        # the tick's host spans (serving/tick and its phases) land in this
        # engine's registry as span_ms/* and in the process-wide ring
        self._span = functools.partial(spans.span, registry=self.registry)
        counter = self.registry.counter
        self._counters = types.SimpleNamespace(
            ticks=counter("serving/ticks"),
            prefill_calls=counter("serving/prefill_calls"),
            prefill_tokens=counter("serving/prefill_tokens"),
            prefill_capacity=counter("serving/prefill_capacity_tokens"),
            decode_calls=counter("serving/decode_calls"),
            decode_slot_steps=counter("serving/decode_slot_steps"),
            drawn_calls=counter("serving/drawn_calls"),
            drawn_rows=counter("serving/drawn_rows"),
            decode_kv_tokens=counter("serving/decode_kv_tokens"),
            decode_kv_pages=counter("serving/decode_kv_pages"))
        if self.hybrid:
            self._counters.moe_pairs = counter("serving/moe_pairs")
            self._counters.moe_experts_hit = counter(
                "serving/moe_experts_hit")
            self._counters.window_blocks_freed = counter(
                "serving/window_blocks_freed")
            self._counters.moe_group_tokens = counter(
                "serving/moe_group_tokens")
            # (index_topk, layers) of the sparse latent groups
            self._sparse_reads = [
                (self.model.spec.kinds[self.model.spec.layer_kinds[
                    g.layers[0]]].index_topk, len(g.layers))
                for g in self.cache.cache_groups if g.index_dim]
            if self._sparse_reads:
                for name in ("rows_scanned", "rows_selected",
                             "prefill_rows_scanned",
                             "prefill_rows_selected"):
                    setattr(self._counters, "dsa_" + name,
                            counter("dsa/" + name))
            latent = [g for g in self.cache.cache_groups if g.latent]
            if latent:
                # what the latent groups' arenas hold over the tokens they
                # can hold (all layers; a row's lane padding counts: it is
                # held)
                self.registry.gauge("serving/kv_latent_bytes_per_token").set(
                    sum((g.row_lanes + g.index_dim) * len(g.layers)
                        for g in latent)
                    * np.dtype(self.cache.dtype).itemsize)
        if self._runs_ahead:
            self._counters.decode_calls_ahead = counter(
                "serving/decode_calls_ahead")
            self._counters.decode_rows_discarded = counter(
                "serving/decode_rows_discarded")
        self._counted_window_freed = 0
        self._queue_wait = self.registry.histogram(
            "serving/queue_wait_ms", keep_samples=4096)
        self._decode_calls = 0         # device decode/verify invocations
        self._slot_steps = 0           # per-slot verify participations
        #                                (mean accept length denominator)
        # the last delivered decode call's logits and the slots whose row
        # was delivered
        self._last_logits: Optional[Tuple[Any, Tuple[int, ...]]] = None
        self._last_selected = None
        # the decode call dispatched and not yet fetched (between ticks:
        # only where the engine runs one call ahead)
        self._in_flight: Optional[_DecodeCall] = None
        self._delivered_at = 0.0       # end of the last decode_fetch
        self._tick_tokens = 0          # what this tick's deliveries emitted
        self._tick_choices: List[Tuple[Any, Tuple[Tuple[int, ...], ...]]] = []
        self._counted_preempts = 0     # flushed-so-far deltas
        self._counted_hits = 0
        self._counted_evictions = 0
        self.spec_proposed = 0         # drafted tokens (lifetime)
        self.spec_accepted = 0         # drafts accepted by the verify
        # adapter_id -> [proposed, accepted] (ISSUE 18 satellite):
        # per-tenant acceptance so one template-poor adapter is visible
        # on /fleet/statusz instead of hidden inside the fleet mean
        self.spec_by_adapter: Dict[str, List[int]] = {}
        # MFU bookkeeping (ISSUE 10 satellite): FLOPs of the decode
        # program probed once (lazily, pre-donation); the last decode
        # call's wall time is that of its decode_dispatch and
        # decode_fetch spans, or for a call fetched in a later tick the
        # time since the delivery before it; serving/mfu flushed as a
        # gauge when defined, else the reason string is kept for /statusz.
        self._decode_flops: Optional[float] = None
        self._decode_ms: Optional[float] = None
        self._flops_probed = False
        self._prefill_registered = False
        self._probe_fail_reason: Optional[str] = None
        self.mfu: Optional[float] = None
        self.mfu_reason: Optional[str] = "decode step has not run yet"

    # -------------------------------------------------------------- intro

    def decode_compile_count(self) -> int:
        """Compiled-variant count of the decode step (the zero-recompile
        contract: stays 1 across any request churn, preemption,
        eviction, and sampling-policy mix)."""
        return int(self._decode._cache_size())

    def prefill_compile_count(self) -> int:
        """Compiled-variant count of the chunked prefill (the fixed
        ``[max_batch, prefill_len]`` chunk shape: also exactly 1)."""
        return int(self._prefill._cache_size())

    # -------------------------------------------------------------- knobs

    def knobs(self) -> Dict[str, Any]:
        """Current live-knob state plus the engine's compile-time
        bounds — the autopilot reads the bounds off the state heartbeat
        to pick targets, and the ack of ``set_knobs`` echoes this dict
        so the controller's committed view matches the replica's."""
        return {"prefill_chunk": self.live_prefill_chunk,
                "spec_k": self.live_spec_k,
                "prefill_len": int(self.prefill_len),
                "spec_k_max": int(self.spec_width - 1)}

    def set_knobs(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        """Apply live-retunable serving knobs (ISSUE 18).

        Recognized keys (each optional; ``None`` resets to the engine
        default):

        - ``prefill_chunk``: cap on tokens prefilled per slot per tick
          (clamped to ``[1, prefill_len]``).  Shrinking it trades
          prefill throughput for decode-tick latency when ``prefill``
          dominates tail traces.
        - ``spec_k``: cap on drafted tokens per tick (clamped to
          ``[0, spec_width - 1]``; 0 disables drafting).  Lowering it
          cuts wasted verify work when acceptance sags.

        Both are data-only: the compiled [B, T] prefill and
        [B, spec_width] verify shapes never change, so a knob change
        never recompiles.  Unknown keys raise (a typo'd controller must
        fail its ack, not silently no-op).  Returns :meth:`knobs` — the
        applied state, echoed back over the ack wire."""
        unknown = set(payload) - {"prefill_chunk", "spec_k"}
        if unknown:
            raise ValueError(f"unknown knobs: {sorted(unknown)}")
        if "prefill_chunk" in payload:
            v = payload["prefill_chunk"]
            if v is not None:
                v = int(v)
                if v < 1:
                    raise ValueError(
                        f"prefill_chunk must be >= 1, got {v}")
                v = min(v, int(self.prefill_len))
            self.live_prefill_chunk = v
            # mirror into admission's first-chunk sizing so the ask for
            # blocks matches what the device call will actually cover
            self.scheduler.chunk_tokens = (
                v if v is not None else int(self.prefill_len))
        if "spec_k" in payload:
            v = payload["spec_k"]
            if v is not None:
                v = int(v)
                if v < 0:
                    raise ValueError(f"spec_k must be >= 0, got {v}")
                v = min(v, int(self.spec_width - 1))
            self.live_spec_k = v
        return self.knobs()

    @property
    def draining(self) -> bool:
        return self.scheduler.draining

    # -------------------------------------------------------------- submit

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               trace: Optional[dict] = None) -> Request:
        """``trace``: the fleet-minted trace context riding the replica
        wire (``{"trace_id": ..., "attempt": ...}``, ISSUE 15) — every
        timeline event of this request then carries the fleet-wide id,
        so N processes' spills stitch into one span tree.  ``None``
        (standalone engines, untraced fleets) keeps the events exactly
        as before."""
        if len(np.shape(prompt)) != 1:
            raise ValueError(
                f"prompt must be 1-D, got shape {np.shape(prompt)}")
        req = self.scheduler.submit(prompt, max_new_tokens, eos_id,
                                    sampling)
        if trace is not None:
            req.trace_id = trace.get("trace_id")
            req.trace_attempt = int(trace.get("attempt", 0))
        aid = getattr(sampling, "adapter_id", None) \
            if sampling is not None else None
        if (aid is not None and req.state is not RequestState.REJECTED
                and (self.adapter_arena is None
                     or not self.adapter_arena.resident(aid))):
            # unknown adapter: refuse with the same typed terminal
            # state as the drain window — never queued, never a hang;
            # the router re-routes (another replica may hold it)
            self.scheduler.waiting.remove(req)
            req.state = RequestState.REJECTED
        timeline.emit("request_submit", rid=req.rid,
                      prompt_tokens=len(req.prompt),
                      max_new_tokens=max_new_tokens,
                      **trace_fields(req))
        if req.state is RequestState.REJECTED:
            # submitted into the drain window: refused with a typed
            # terminal state (never queued, never a hang) and counted
            # apart from drain cancellations — a router re-routes a
            # REJECTED request, it does not mourn it
            self.registry.counter("serving/requests_rejected").inc()
            timeline.emit("request_reject", rid=req.rid,
                          **trace_fields(req))
        elif aid is not None:
            # pinned for the request's whole life (queue wait included):
            # its adapter can never be LRU-evicted out from under it
            self.adapter_arena.pin(aid, req.rid)
            self.registry.gauge("serving/adapter_active").set(
                self.adapter_arena.active)
        return req

    # --------------------------------------------------------------- drain

    def drain(self) -> List[Request]:
        """Preemption path: cancel the queue, keep decoding the running
        requests until their responses are delivered."""
        self.settle()
        timeline.emit("preemption", wall_ts=time.time())
        cancelled = self.scheduler.drain()
        if cancelled:
            self.registry.counter("serving/requests_cancelled").inc(
                len(cancelled))
        for req in cancelled:
            self._unpin_adapter(req)
            timeline.emit("request_cancel", rid=req.rid,
                          **trace_fields(req))
        self.registry.counter("serving/preemption_drains").inc()
        return cancelled

    # ------------------------------------------------- KV migration (ISSUE 16)

    def export_request(self, req: Request) -> Tuple[dict, List[tuple]]:
        """Extract a RUNNING request's KV-block run for migration to a
        decode replica.

        One batched device gather per arena pulls the run
        (``blocks_for(cache_len)`` blocks) to the host; each block
        becomes one payload tuple — ``(k, v)`` or ``(k, v, k_scale,
        v_scale)`` per-block slabs — sized to ride one wire frame, so
        the transfer streams and resumes at block boundaries.  The run
        is then **pinned** in the export ledger (refcount +1 under the
        export owner) and the request leaves the scheduler silently (no
        finish/cancel event — the stream continues on the decode side);
        its own block refs free normally, so the run survives at
        refcount 1 until :meth:`release_export`.

        Returns ``(meta, payloads)``.  Raises ``ValueError`` when the
        request is not in an exportable state (still prefilling, no
        token emitted yet, already exporting) — the caller degrades to
        letting it keep decoding locally."""
        if self.hybrid:
            raise NotImplementedError(
                "KV migration moves one pooled arena, not cache groups")
        if req.state is not RequestState.RUNNING or req.slot is None:
            raise ValueError(
                f"request {req.rid} is {req.state}, not exportable")
        if req.prefilling or not req.output_tokens:
            raise ValueError(
                f"request {req.rid} has not completed prefill + first "
                "token; nothing to migrate yet")
        seq = req.sequence_tokens()
        if req.cache_len != len(seq) - 1:
            raise ValueError(
                f"request {req.rid} cache_len {req.cache_len} out of "
                f"phase with its {len(seq)}-token stream")
        n_blocks = self.cache.blocks_for(req.cache_len)
        run = list(req.blocks[:n_blocks])
        idx = self._jnp.asarray(np.asarray(run, np.int32))
        # one gather + one device->host transfer per arena (batched tx)
        slabs = [np.asarray(a[:, idx]) for a in self.arenas]
        payloads = [tuple(slab[:, j] for slab in slabs)
                    for j in range(n_blocks)]
        n_bytes = int(sum(s.nbytes for s in slabs))
        self.exports.pin(req.rid, run, seq[:req.cache_len],
                         req.cache_len)
        # the request leaves this engine silently: the slot's table row
        # zeroes and its own refs free (the export pin keeps the run);
        # the destination replica takes its own adapter pin
        self._tables[req.slot][:] = 0
        self.scheduler.finish(req)
        self._unpin_adapter(req)
        self.registry.counter("serving/kv_export_blocks").inc(n_blocks)
        timeline.emit("request_export", rid=req.rid,
                      tokens=len(req.output_tokens), blocks=n_blocks,
                      **trace_fields(req))
        meta = {
            "cache_len": req.cache_len,
            "n_blocks": n_blocks,
            "n_out": len(req.output_tokens),
            "block_size": self.cache.block_size,
            "n_layers": self.cache.n_layers,
            "kv_heads": self.cache.kv_heads,
            "head_dim": self.cache.head_dim,
            "dtype": str(np.dtype(self.cache.dtype)),
            "bytes": n_bytes,
        }
        return meta, payloads

    def release_export(self, rid, *, ok: bool) -> None:
        """Drop the pin on an exported run (the decode side's ack, or
        the router's abort).  Either way the run's full blocks index
        into the local prefix cache — the KV is valid content, and a
        failed migration's re-prefill routed back here then hits it —
        and the pin frees.  Idempotent: a duplicate/stale ack is a
        no-op."""
        self.exports.release(rid, to_cache=True)
        if not ok:
            self.registry.counter("serving/kv_export_aborts").inc()

    def _check_import_payloads(self, payloads: List[tuple]) -> None:
        """Reject a malformed migration payload BEFORE any device put —
        a torn or mismatched transfer must degrade to re-prefill, never
        land partial garbage in the arena."""
        want_shapes = [a.shape[:1] + a.shape[2:] for a in self.arenas]
        want_dtypes = [a.dtype for a in self.arenas]
        for j, p in enumerate(payloads):
            if len(p) != len(self.arenas):
                raise ValueError(
                    f"imported block {j} carries {len(p)} slabs, arena "
                    f"set has {len(self.arenas)}")
            for s, shape, dtype in zip(p, want_shapes, want_dtypes):
                if tuple(np.shape(s)) != tuple(shape) \
                        or np.dtype(getattr(s, "dtype", None)) != dtype:
                    raise ValueError(
                        f"imported block {j} slab shape/dtype "
                        f"{np.shape(s)}/{getattr(s, 'dtype', None)} != "
                        f"arena {tuple(shape)}/{dtype}")

    def import_request(self, prompt: Sequence[int], max_new_tokens: int,
                       eos_id: Optional[int] = None,
                       sampling: Optional[SamplingParams] = None,
                       trace: Optional[dict] = None, *,
                       cache_len: int,
                       payloads: List[tuple]) -> Request:
        """Admit a migrated request with its KV run injected into the
        local arenas (the decode side of a KV-block migration).

        ``prompt`` is the request's full wire sequence so far (original
        prompt + every token already streamed — exactly the failover-
        replay wire), ``cache_len`` the tokens the imported run covers
        (always ``len(prompt) - 1``: the last wire token recomputes
        here, which is what makes the continued stream bitwise the
        replay stream), ``payloads`` the per-block slabs from
        :meth:`export_request`.  The injection is ONE donated scatter
        per migration flush across all arenas.  Raises on missing
        capacity or a malformed payload — the caller reports a typed
        failure and the router degrades to re-prefill."""
        self._check_import_payloads(payloads)
        aid = getattr(sampling, "adapter_id", None) \
            if sampling is not None else None
        if aid is not None and (self.adapter_arena is None
                                or not self.adapter_arena.resident(aid)):
            # checked BEFORE admission claims a slot: the typed failure
            # relays as a failed import and the router degrades
            raise ValueError(
                f"adapter {aid!r} is not resident on this replica")
        req = self.scheduler.admit_imported(
            prompt, max_new_tokens, eos_id, sampling,
            cache_len=cache_len, n_blocks=len(payloads))
        if trace is not None:
            req.trace_id = trace.get("trace_id")
            req.trace_attempt = int(trace.get("attempt", 0))
        timeline.emit("request_submit", rid=req.rid,
                      prompt_tokens=len(req.prompt),
                      max_new_tokens=max_new_tokens, imported=True,
                      **trace_fields(req))
        if req.state is RequestState.REJECTED:
            self.registry.counter("serving/requests_rejected").inc()
            timeline.emit("request_reject", rid=req.rid,
                          **trace_fields(req))
            return req
        if aid is not None:
            self.adapter_arena.pin(aid, req.rid)
            self.registry.gauge("serving/adapter_active").set(
                self.adapter_arena.active)
        idx = self._jnp.asarray(
            np.asarray(req.blocks[:len(payloads)], np.int32))
        vals = tuple(
            np.stack([p[i] for p in payloads], axis=1)
            for i in range(len(self.arenas)))
        self.arenas = self._import_scatter(self.arenas, idx, vals)
        self.scheduler.note_imported(req)
        self.registry.counter("serving/kv_import_blocks").inc(
            len(payloads))
        timeline.emit("request_admit", rid=req.rid, slot=req.slot,
                      blocks=len(req.blocks), hit_blocks=0,
                      imported=True, **trace_fields(req))
        return req

    # ---------------------------------------------------------------- step

    def step(self) -> None:
        """One engine tick: admit, advance prefill chunks, one decode
        step.  The tick and its phases are host spans
        (:class:`~apex_tpu.observability.spans.span`; the span map is in
        docs/observability.md): disjoint, in this order, and a phase
        that did not run records none.  Where the engine runs one call
        ahead, the tick's ``decode_fetch`` and ``deliver`` are the call
        before's, and come first in a tick that has to settle before it
        plans."""
        sched = self.scheduler
        self._tick_choices = []
        self._tick_tokens = 0
        with self._span("serving/tick", step=self._steps,
                        live=len(sched.running()),
                        waiting=len(sched.waiting), prefill_rows=0,
                        prefill_tokens=0, prefill_capacity=0,
                        decode_slots=0) as tick:
            if (self._in_flight is not None and self.guard is not None
                    and self.guard.triggered and not self.draining):
                self.settle()       # the drain below sees a current view
            with self._span("serving/tick/admit") as phase:
                if (self.guard is not None and self.guard.triggered
                        and not self.draining):
                    self.drain()
                admitted = sched.admit()
                now = time.monotonic()
                for req in admitted:
                    if req.t_admit is None:
                        # the first admission: a re-admitted request
                        # waited for blocks, not in the queue
                        self._queue_wait.observe(
                            (now - req.t_submit) * 1e3)
                    req.t_admit = now
                    timeline.emit("request_admit", rid=req.rid,
                                  slot=req.slot, blocks=len(req.blocks),
                                  hit_blocks=req.hit_blocks,
                                  **trace_fields(req))
                phase.note(admitted=len(admitted))
            if self._in_flight is not None and self._growth_may_preempt():
                # a request is not preempted with a row of it in flight
                self.settle()
            self._prefill_tick(tick)
            fetched = self._decode_once(tick)
            with self._span("serving/tick/deliver") as phase:
                if fetched:
                    self._deliver(*fetched)
                phase.note(tokens=self._tick_tokens)
                self._steps += 1
                self._counters.ticks.inc()
                self.registry.gauge("serving/active_slots").set(
                    len(sched.running()))
                self.registry.gauge("serving/free_blocks").set(
                    sched.allocator.n_free)
                self.registry.gauge("serving/kv_occupancy").set(
                    sched.kv_occupancy())
                self._flush_occupancy_counters()
                # the beat lands only after the device work this tick
                # fetched materialized (its own call's, or the call
                # before's where the engine runs ahead) — a wedged decode
                # stops the beats, one tick later there, and the monitor
                # fires the guard, turning a scheduler wedge into an
                # ordinary drain
                if self.heartbeat is not None:
                    self.heartbeat.beat(self._steps)

    def _flush_occupancy_counters(self) -> None:
        sched = self.scheduler
        if sched.preemptions > self._counted_preempts:
            self.registry.counter("serving/preemptions").inc(
                sched.preemptions - self._counted_preempts)
            self._counted_preempts = sched.preemptions
        pc = sched.prefix_cache
        if pc is not None:
            if pc.hits > self._counted_hits:
                self.registry.counter("serving/prefix_cache_hits").inc(
                    pc.hits - self._counted_hits)
                self._counted_hits = pc.hits
            if pc.evictions > self._counted_evictions:
                self.registry.counter("serving/evictions").inc(
                    pc.evictions - self._counted_evictions)
                self._counted_evictions = pc.evictions

    def run_until_drained(self, max_steps: int = 100_000) -> None:
        """Drive :meth:`step` until no request is waiting or running
        (under drain: until the running ones have delivered) and no call
        is in flight."""
        for _ in range(max_steps):
            if self.scheduler.idle and self._in_flight is None:
                return
            self.step()
        raise RuntimeError(f"not drained after {max_steps} steps")

    # ------------------------------------------------------------ adapters

    def register_adapter(self, adapter_id: str, weights=None, *,
                         seed: Optional[int] = None) -> int:
        """Load (or hot-swap) a LoRA adapter into the arena; returns
        its slot.

        ``weights`` is the ``{proj: (A [L, in, r], B [L, r, out])}``
        dict — typically from :func:`~apex_tpu.serving.lora.
        restore_adapter_for_serving` (the spec-layer restore path) or
        :func:`~apex_tpu.serving.lora.init_adapter_weights`.  ``None``
        builds a deterministic fixture seeded by ``seed`` (default: a
        hash of the id, so the same id loads the same adapter on every
        replica).  A resident id re-registers **in place** — the
        hot-swap path: one donated row update, in-flight requests keep
        decoding (the swap lands between ticks, never mid-step).  A new
        id LRU-evicts the coldest unpinned adapter when the arena is
        full; all-pinned raises
        :class:`~apex_tpu.serving.lora.OutOfAdapterSlotsError`.
        """
        if self.adapter_arena is None:
            raise RuntimeError(
                "ServingConfig.lora is None; this engine serves the "
                "bare checkpoint only")
        if weights is None:
            if seed is None:
                seed = zlib.crc32(str(adapter_id).encode())
            weights = init_adapter_weights(self.model.cfg, self.lora,
                                           seed=int(seed))
        vals = pack_adapter_values(self.model.cfg, self.lora, weights,
                                   self._adapter_dtype)
        slot, evicted = self.adapter_arena.register(adapter_id)
        self.adapters = self._adapter_set(
            self.adapters, np.int32(slot), vals)
        self.registry.counter("serving/adapter_loads").inc()
        if evicted is not None:
            self.registry.counter("serving/adapter_evictions").inc()
        self.registry.gauge("serving/adapter_active").set(
            self.adapter_arena.active)
        timeline.emit(
            "adapter_load", adapter_id=str(adapter_id), slot=int(slot),
            evicted=(str(evicted) if evicted is not None else None))
        return int(slot)

    def unregister_adapter(self, adapter_id: str) -> None:
        """Drop an adapter from the registry: new submits naming it are
        REJECTED; in-flight pinners keep their slot until they finish
        (the rows are only reused after the last pin releases)."""
        if self.adapter_arena is None:
            raise RuntimeError(
                "ServingConfig.lora is None; this engine serves the "
                "bare checkpoint only")
        slot = self.adapter_arena.unregister(adapter_id)
        timeline.emit("adapter_unload", adapter_id=str(adapter_id),
                      slot=int(slot))

    def _adapter_slot_array(self) -> np.ndarray:
        """Each slot's arena row for this tick ([max_batch] DATA; idle
        and ``adapter_id=None`` slots gather the zero adapter)."""
        slots = np.zeros((self.serving.max_batch,), np.int32)
        for req in self.scheduler.running():
            slots[req.slot] = self.adapter_arena.pinned_slot(req.rid)
        return slots

    # ------------------------------------------------------------- prefill

    def _refresh_tables(self) -> None:
        """Rebuild the slot -> physical-block table rows from the live
        requests (preemption and growth both rewrite block lists; the
        rebuild is max_batch * max_blocks ints — noise next to a device
        step)."""
        for table in self._group_tables:
            table[:] = 0
        for req in self.scheduler.running():
            for table, held in zip(self._group_tables, req.group_blocks()):
                table[req.slot, :len(held)] = held
        if self._windowed:
            # an entry handed back behind the window still has to index
            # the arena; no kernel reaches it
            for table in self._group_tables:
                np.maximum(table, 0, out=table)

    def _device_tables(self):
        """The block tables as the compiled steps take them: group 0's
        array, or for a model with cache groups a tuple of them.  Copies:
        the host rewrites its tables in place for the next plan while a
        call may still be in flight, and on the CPU ``jnp.asarray`` of a
        numpy array aliases its memory, so the call would read the next
        plan's tables (a preempted or reassigned slot's rows)."""
        if not self.hybrid:
            return self._jnp.array(self._tables)
        return tuple(self._jnp.array(t) for t in self._group_tables)

    def _note_routed(self, phase: spans.span, pairs, reached) -> None:
        """Record what a call routed to the held experts (``pairs [expert
        layers, held experts]``; ``reached [expert layers]``, the live
        tokens whose router kept a group held here) on its fetch span and
        the counters."""
        total, hit = int(pairs.sum()), int(np.count_nonzero(pairs))
        tokens = int(reached.sum())
        phase.note(moe_pairs=total, moe_experts_hit=hit,
                   moe_peak_pairs=int(pairs.max()) if pairs.size else 0,
                   moe_group_tokens=tokens)
        self._counters.moe_pairs.inc(total)
        self._counters.moe_experts_hit.inc(hit)
        self._counters.moe_group_tokens.inc(tokens)

    def _sampling_arrays(self, phase: spans.span):
        """Per-slot sampling-policy data ([max_batch] each, rebuilt per
        call — policies are data, never shape).  The slots of temperature
        > 0 are what the call's in-graph draw is decided by: their count
        goes on ``phase`` (the call's plan span) as ``drawn`` and into
        the ``serving/drawn_*`` counters."""
        B = self.serving.max_batch
        temp = np.zeros((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        seeds = np.zeros((B,), np.uint32)
        steps = np.zeros((B,), np.int32)
        for req in self.scheduler.running():
            s = req.sampling
            temp[req.slot] = s.temperature
            top_k[req.slot] = s.top_k
            top_p[req.slot] = s.top_p
            seeds[req.slot] = s.seed & 0xFFFFFFFF
            # step_offset rebases the draw counter for fleet failover
            # replays (prompt already carries the emitted prefix); a token
            # in flight counts: it will have been emitted
            steps[req.slot] = (s.step_offset + len(req.output_tokens)
                               + self._ahead(req))
        drawn = int(np.count_nonzero(temp > 0.0))
        phase.note(drawn=drawn)
        if drawn:
            self._counters.drawn_calls.inc()
            self._counters.drawn_rows.inc(drawn)
        return temp, top_k, top_p, seeds, steps

    def _prefill_tick(self, tick: spans.span) -> None:
        """Advance every prefilling slot by at most one chunk
        (``prefill_len`` tokens) in ONE fixed-shape device call; slots
        whose prompt completes this chunk sample their first token
        in-graph."""
        cands = [r for r in self.scheduler.running() if r.prefilling]
        if not cands:
            return      # no chunk to plan: the scan is the tick's own time
        B, T = self.serving.max_batch, self.prefill_len
        bs = self.cache.block_size
        with self._span("serving/tick/prefill_plan") as phase:
            cands.sort(key=lambda r: r.admit_seq)
            plan: List[Tuple[Request, int]] = []
            for req in cands:
                if req.slot is None or not req.prefilling:
                    continue    # preempted by an older request's growth
                chunk = min(req.prefill_target - req.cache_len, T)
                if self.live_prefill_chunk is not None:
                    # live retune (ISSUE 18): the cap is data — the device
                    # call keeps its compiled [B, T] shape and fills less
                    chunk = min(chunk, self.live_prefill_chunk)
                if self._windowed:
                    # nothing behind the window of the chunk's first token
                    # is read again
                    self.scheduler.free_behind_window(req, req.cache_len)
                covered = self.scheduler.try_grow_to(
                    req, req.cache_len + chunk)
                chunk = min(chunk, covered - req.cache_len)
                if chunk > 0:
                    plan.append((req, chunk))
            if not plan:
                return

            tokens = np.zeros((B, T), np.int32)
            pos_ids = np.zeros((B, T), np.int32)
            limits = np.zeros((B, T), np.int32)
            lengths = np.zeros((B,), np.int32)
            if not self.hybrid:     # else read from the tables in-graph
                dest_b = np.full((B, T), self.cache.n_blocks,
                                 np.int32)                          # OOB=drop
                dest_o = np.zeros((B, T), np.int32)
            sample_index = np.full((B,), T, np.int32)                # OOB=none
            for req, chunk in plan:
                s = req.slot
                wire = req.sequence_tokens()
                lo = req.cache_len
                tokens[s, :chunk] = wire[lo:lo + chunk]
                pos_ids[s, :chunk] = np.arange(lo, lo + chunk)
                limits[s, :chunk] = np.arange(lo + 1, lo + chunk + 1)
                lengths[s] = lo + chunk
                if not self.hybrid:
                    dest_b[s, :chunk] = [req.blocks[(lo + t) // bs]
                                         for t in range(chunk)]
                    dest_o[s, :chunk] = [(lo + t) % bs
                                         for t in range(chunk)]
                if lo + chunk == req.prefill_target:
                    sample_index[s] = chunk - 1
            self._refresh_tables()
            samp = self._sampling_arrays(phase)
            args = (tokens, pos_ids, self._device_tables(), lengths, limits)
            if self.hybrid:
                args = (self.arenas, self.params) + args \
                    + (sample_index,) + samp
            elif self.adapter_arena is None:
                args = (self.arenas, self.params) + args \
                    + (dest_b, dest_o, sample_index) + samp
            else:
                args = (self.arenas, self.adapters, self.params) + args \
                    + (dest_b, dest_o, sample_index,
                       self._adapter_slot_array()) + samp
            n_tokens = int(sum(c for _, c in plan))
            if not self._prefill_registered:
                self._register_prefill(args)

        with timeline.scope("prefill", rids=[r.rid for r, _ in plan],
                            tokens=n_tokens):
            routed = None
            with self._span("serving/tick/prefill_dispatch"):
                if self.hybrid:
                    (self.arenas, next_tokens, _, routed, chosen,
                     reached) = self._prefill(*args)
                    self._tick_choices.append((chosen, tuple(
                        (req.rid, req.slot * T, req.cache_len, chunk)
                        for req, chunk in plan)))
                elif self.adapter_arena is None:
                    self.arenas, next_tokens, _ = self._prefill(*args)
                else:
                    self.arenas, self.adapters, next_tokens, _ = \
                        self._prefill(*args)
            with self._span("serving/tick/prefill_fetch") as fetch:
                if routed is None:
                    next_np = np.asarray(next_tokens)
                else:
                    # one transfer brings the tokens and the routing counts
                    next_np, routed, reached = self._fetch(
                        (next_tokens, routed, reached))
                    self._note_routed(fetch, routed, reached)

        with self._span("serving/tick/prefill_deliver"):
            if self.hybrid and self._sparse_reads:
                self._note_sparse_prefill(tick, plan)
            self._counters.prefill_calls.inc()
            self._counters.prefill_tokens.inc(n_tokens)
            self._counters.prefill_capacity.inc(B * T)
            tick.note(prefill_rows=len(plan), prefill_tokens=n_tokens,
                      prefill_capacity=B * T)
            now = time.monotonic()
            for req, chunk in plan:
                self.scheduler.note_prefilled(req, chunk)
                if not req.prefilling:
                    # prompt complete: the in-graph sample at its last
                    # prompt position is the request's next output token.
                    # The prefilled marker is the trace walk's prefill →
                    # decode boundary (ISSUE 15) — re-emitted per
                    # admission (a preempted request's recompute prefill
                    # ends here too)
                    timeline.emit("request_prefilled", rid=req.rid,
                                  tokens=req.prefill_target,
                                  **trace_fields(req))
                    self._emit(req, int(next_np[req.slot]), now)

    # -------------------------------------------------------------- decode

    def _propose_drafts(self, req: Request) -> List[int]:
        """Ask the proposer for this tick's drafts, clamped to the
        verify width, the context cap, and the remaining budget (the
        verify's own output covers the final token, so a request one
        token from its budget drafts nothing)."""
        if self.proposer is None:
            return []
        max_k = min(self.spec_width - 1,
                    self.cache.max_seq - (req.cache_len + 1),
                    req.max_new_tokens - len(req.output_tokens) - 1)
        if self.live_spec_k is not None:
            # live retune (ISSUE 18): verify keeps its compiled
            # [B, spec_width] shape; k=0 disables drafting entirely
            max_k = min(max_k, self.live_spec_k)
        if max_k <= 0:
            return []
        return list(self.proposer.propose(req, max_k))[:max_k]

    def _ahead(self, req: Request) -> int:
        """Rows of ``req`` that the call in flight computes: tokens, and
        cache rows, that the host's counts do not hold yet."""
        call = self._in_flight
        return int(call is not None and req.rid in call.rids)

    def _growth_may_preempt(self) -> bool:
        """Whether the blocks this tick's plans ask for (a chunk for each
        prefilling slot, a row for each decoding one) could outrun the free
        ones, so that growing would preempt.  Counts nothing a window hands
        back this tick, so it errs towards yes."""
        blocks_for = self.cache.blocks_for
        cap = self.prefill_len
        if self.live_prefill_chunk is not None:
            cap = min(cap, self.live_prefill_chunk)
        need = 0
        for req in self.scheduler.running():
            if req.prefilling:
                upto = min(req.cache_len + cap, req.prefill_target)
                # a prompt that completes decodes in the same tick
                upto += upto == req.prefill_target
            else:
                upto = req.cache_len + self._ahead(req) + 1
            need += max(0, blocks_for(upto) - len(req.blocks))
        return need > min(a.n_free for a in self.scheduler.allocators)

    def settle(self) -> None:
        """Fetch and deliver the decode call in flight, if there is one:
        afterwards the requests hold every token the device has computed.
        The engine settles by itself before a growth that could preempt,
        before a drain, and when a tick has nothing left to dispatch; call
        it before reading a request's tokens between ticks where the newest
        has to be among them."""
        if self._in_flight is not None:
            self._deliver(*self._fetch_call(self._in_flight))

    def _decode_once(self, tick: spans.span):
        """Plan and dispatch this tick's one decode call, and fetch the
        call that is due: the one just dispatched, or where the engine runs
        one call ahead the call before it (fetched at once when nothing
        could be dispatched).  Returns what :meth:`_deliver` takes, or
        ``None`` where nothing was fetched."""
        before = self._in_flight
        args = None
        if self.scheduler.running():
            with self._span("serving/tick/decode_plan") as phase:
                args = self._plan_decode(phase)
        if args is None:
            return self._fetch_call(before) if before is not None else None
        reqs, drafts, positions, args = args
        with self._span("serving/tick/decode_dispatch") as dispatch:
            routed = reached = selected = None
            if self.hybrid:
                (self.arenas, out_tokens, accepted, logits, routed, chosen,
                 reached, *selected) = self._decode(*args)
                selected = selected[0] if selected else None
                self._tick_choices.append((chosen, tuple(
                    (req.rid, req.slot, int(positions[req.slot]), 1)
                    for req in reqs)))
            elif self.adapter_arena is None:
                self.arenas, out_tokens, accepted, logits = \
                    self._decode(*args)
            else:
                self.arenas, self.adapters, out_tokens, accepted, logits = \
                    self._decode(*args)
            if self._runs_ahead:
                dispatch.note(ahead=int(before is not None))
                if before is not None:
                    self._counters.decode_calls_ahead.inc()
        tick.note(decode_slots=len(reqs))
        call = self._in_flight = _DecodeCall(
            rows=[(req, req.slot) for req in reqs],
            rids=frozenset(req.rid for req in reqs), drafts=drafts,
            out_tokens=out_tokens, accepted=accepted, logits=logits,
            routed=routed, reached=reached, selected=selected,
            dispatch=dispatch,
            step=self._steps)
        if not self._runs_ahead:
            return self._fetch_call(call)
        if before is not None:
            return self._fetch_call(before)
        if self._delivered_at < tick.start:
            # nothing to fetch, and the tick has not settled before it
            # planned: every tick with a dispatch has the span
            with self._span("serving/tick/decode_fetch"):
                pass
        return None

    def _plan_decode(self, phase: spans.span):
        """The ``decode_plan`` phase: grow the decoding slots' blocks and
        build the call's arguments from the host's counts as they will be
        once the call in flight, if any, is delivered.  Returns ``(requests,
        drafts, positions [max_batch], arguments)``, or ``None`` where no
        slot decodes."""
        B, S = self.serving.max_batch, self.spec_width
        preempted = self.scheduler.preemptions
        # a request at the context cap cannot write another token:
        # deliver what it has (truncation is a response, not a hang)
        for req in list(self.scheduler.running()):
            if (not req.prefilling and not self._ahead(req)
                    and req.cache_len >= self.cache.max_seq):
                self._finish(req)
        # grow this tick's write blocks oldest-first (evict cached
        # LRU, then preempt strictly newer requests); a newer request
        # that cannot grow just sits this tick out — it keeps its cache
        decoding = sorted(
            (r for r in self.scheduler.running() if not r.prefilling),
            key=lambda r: r.admit_seq)
        reqs: List[Request] = []
        drafts: dict = {}
        positions = np.zeros((B,), np.int32)
        for req in decoding:
            if (req.slot is None
                    or req.state is not RequestState.RUNNING):
                continue    # preempted by an older request's growth
            ahead = self._ahead(req)
            pos = req.cache_len + ahead     # the row this call writes
            if ahead and (pos >= self.cache.max_seq
                          or len(req.output_tokens) + ahead
                          >= req.max_new_tokens):
                continue    # the call in flight ends it: no further row
            if self._windowed:
                # behind the delivered length: the call in flight reads
                # nothing that is handed back
                self.scheduler.free_behind_window(req, req.cache_len)
            covered = self.scheduler.try_grow_to(req, pos + 1)
            if covered < pos + 1:
                continue
            draft = self._propose_drafts(req)
            if draft:
                # blocks for drafted rows come from the free list or
                # the cache LRU only, NEVER preemption: speculation is
                # an optimization and must not evict a neighbour's real
                # KV.  A short grow just truncates the draft (data, not
                # shape).
                covered = self.scheduler.try_grow_to(
                    req, pos + 1 + len(draft), preempt=False)
                draft = draft[:max(0, covered - (pos + 1))]
            drafts[req.rid] = draft
            positions[req.slot] = pos
            reqs.append(req)
        # what the attention kernel meets this tick: each decoding
        # slot attends its history, the row it writes and its drafts
        bs = self.cache.block_size
        history = [int(positions[req.slot]) + 1 + len(drafts[req.rid])
                   for req in reqs]
        kv_tokens = sum(history)
        kv_pages = sum(-(-h // bs) for h in history)
        phase.note(preempted=self.scheduler.preemptions - preempted,
                   kv_tokens=kv_tokens, kv_pages=kv_pages)
        self._counters.decode_kv_tokens.inc(kv_tokens)
        self._counters.decode_kv_pages.inc(kv_pages)
        if self.hybrid:
            self._note_group_reads(phase, history)
        if not reqs:
            return None
        tokens = np.zeros((B, S), np.int32)
        active = np.zeros((B,), bool)
        n_draft = np.zeros((B,), np.int32)
        from_carried = np.zeros((B,), bool)
        for req in reqs:
            d = drafts[req.rid]
            tokens[req.slot, 0] = req.last_token
            if d:
                tokens[req.slot, 1:1 + len(d)] = d
            active[req.slot] = True
            n_draft[req.slot] = len(d)
            from_carried[req.slot] = self._ahead(req)
        self._refresh_tables()
        samp = self._sampling_arrays(phase)

        tables = self._device_tables()
        rest = (positions, tables, active, n_draft)
        if self._runs_ahead:
            before = self._in_flight
            carried = (self._no_tokens if before is None
                       else before.out_tokens)
            args = (self.arenas, self.params, tokens, carried,
                    from_carried) + rest + samp
        elif self.adapter_arena is None:
            args = (self.arenas, self.params, tokens) + rest + samp
        else:
            args = (self.arenas, self.adapters, self.params, tokens) \
                + rest + (self._adapter_slot_array(),) + samp
        if not self._flops_probed:
            # One-time FLOPs probe for the MFU gauge: lowering traces
            # the decode body (no second XLA compile, no execution —
            # the arenas are not donated by a trace) and the HLO cost
            # pass reports the program's FLOPs.  Must happen BEFORE
            # the dispatch consumes the donated arenas.
            self._probe_decode_flops(args)
        return reqs, drafts, positions, args

    def _fetch_call(self, call: _DecodeCall):
        """The fetch half of a settle, in a ``decode_fetch`` span: the
        call's tokens (and what it routed) to the host in one round trip.
        Returns what :meth:`_deliver` takes; the last of it is the call's
        wall time, that of its two spans when it is fetched in the tick
        that dispatched it, else the time since the delivery before it (or
        since its dispatch, if that came later)."""
        if self._in_flight is call:
            self._in_flight = None
        with self._span("serving/tick/decode_fetch") as fetch:
            if call.routed is None:
                out_np = np.asarray(call.out_tokens)
                acc_np = np.asarray(call.accepted)
            else:
                out_np, acc_np, routed, reached = self._fetch(
                    (call.out_tokens, call.accepted, call.routed,
                     call.reached))
                self._note_routed(fetch, routed, reached)
        if call.step == self._steps:
            decode_ms = call.dispatch.ms + fetch.ms
        else:
            decode_ms = (fetch.end - max(call.dispatch.start,
                                         self._delivered_at)) * 1e3
        self._delivered_at = fetch.end
        return call, out_np, acc_np, decode_ms

    @staticmethod
    def _fetch(arrays):
        """Device arrays to the host in one round trip."""
        import jax

        return jax.device_get(arrays)

    def _note_sparse_prefill(self, tick: spans.span, plan) -> None:
        """What the sparse layers' indexers scored and their queries
        attended in a prefill call: a token at position ``p`` scores the
        ``p + 1`` rows up to itself and attends ``min(p + 1,
        index_topk)``, in each sparse layer."""
        scanned = selected = 0
        for req, chunk in plan:
            first = req.cache_len + 1
            rows = np.arange(first, first + chunk)
            for k, n in self._sparse_reads:
                scanned += n * int(rows.sum())
                selected += n * int(np.minimum(rows, k).sum())
        tick.note(dsa_prefill_rows_scanned=scanned,
                  dsa_prefill_rows_selected=selected)
        self._counters.dsa_prefill_rows_scanned.inc(scanned)
        self._counters.dsa_prefill_rows_selected.inc(selected)

    def _note_group_reads(self, phase: spans.span, history) -> None:
        """What each kind of cache group must read this tick, on the
        ``decode_plan`` span: rows (the history, or what of it lies inside
        the window) and the blocks that hold them, summed over the groups
        of a kind, and what the window groups hold and handed back."""
        bs = self.cache.block_size
        reads = {"full": [0, 0], "window": [0, 0], "latent": [0, 0]}
        for g in self.cache.cache_groups:
            kind = reads["latent" if g.latent else
                         "full" if g.window is None else "window"]
            for h in history:
                first = g.first_needed_block(h - 1, bs)
                kind[0] += h if g.window is None else min(h, g.window)
                kind[1] += -(-h // bs) - first
        if self._sparse_reads:
            # each sparse layer scores every row of a slot's history and
            # attends the index_topk best
            scanned = sum(n * sum(history) for _, n in self._sparse_reads)
            selected = sum(n * sum(min(h, k) for h in history)
                           for k, n in self._sparse_reads)
            phase.note(dsa_rows_scanned=scanned, dsa_rows_selected=selected)
            self._counters.dsa_rows_scanned.inc(scanned)
            self._counters.dsa_rows_selected.inc(selected)
        sched = self.scheduler
        freed = sched.window_blocks_freed - self._counted_window_freed
        self._counted_window_freed = sched.window_blocks_freed
        self._counters.window_blocks_freed.inc(freed)
        phase.note(kv_tokens_full=reads["full"][0],
                   kv_pages_full=reads["full"][1],
                   kv_tokens_window=reads["window"][0],
                   kv_pages_window=reads["window"][1],
                   kv_tokens_latent=reads["latent"][0],
                   kv_pages_latent=reads["latent"][1],
                   window_blocks_held=sched.window_blocks_held(),
                   window_blocks_freed=freed)

    def _deliver(self, call: _DecodeCall, out_np, acc_np,
                 decode_ms: float) -> None:
        """Hand a fetched decode call's tokens to their requests (the
        accepted prefix of each slot's verify) and add them to the tick's
        count.  A row whose request the delivery before ended (on its
        ``eos_id``: the one end the plan cannot foresee) is discarded."""
        rows = [(req, slot) for req, slot in call.rows
                if req.state is RequestState.RUNNING and req.slot == slot]
        if len(rows) < len(call.rows):
            self._counters.decode_rows_discarded.inc(
                len(call.rows) - len(rows))
        drafts = call.drafts
        # replaces, and so frees, the delivered call before's
        self._last_logits = (call.logits, tuple(slot for _, slot in rows))
        self._last_selected = call.selected
        self._decode_calls += 1
        self._slot_steps += len(rows)
        self._counters.decode_calls.inc()
        self._counters.decode_slot_steps.inc(len(rows))
        self._refresh_mfu(decode_ms)

        now = time.monotonic()
        emitted = proposed_total = accepted_total = 0
        for req, slot in rows:
            d = drafts[req.rid]
            acc = int(acc_np[slot])
            if d:
                proposed_total += len(d)
                accepted_total += acc
                if self.proposer is not None:
                    self.proposer.observe(req, len(d), acc)
                aid = getattr(req.sampling, "adapter_id", None)
                if aid is not None and (
                        aid in self.spec_by_adapter
                        or len(self.spec_by_adapter) < 256):
                    # per-adapter acceptance (ISSUE 18 satellite) —
                    # the signal behind LoRA-aware back-off and the
                    # autopilot's spec-k retune; bounded key set
                    row = self.spec_by_adapter.setdefault(aid, [0, 0])
                    row[0] += len(d)
                    row[1] += acc
            # rejection rollback is O(1) by construction: positions past
            # the accepted prefix were written but cache_len simply does
            # not advance over them — pointer/length moves on the host,
            # no KV copies; the rows are overwritten by the next tick
            req.cache_len += 1            # column 0: the real last token
            for j in range(acc + 1):
                if j > 0:
                    req.cache_len += 1    # draft j == the token just
                    #                       emitted — its row is real
                self._emit(req, int(out_np[slot, j]), now)
                emitted += 1
                if req.state is not RequestState.RUNNING:
                    break                 # eos/budget: drop the rest
        if proposed_total:
            self.registry.counter("serving/spec_proposed").inc(
                proposed_total)
            self.spec_proposed += proposed_total
        if accepted_total:
            self.registry.counter("serving/spec_accepted").inc(
                accepted_total)
            self.spec_accepted += accepted_total
        if self.spec_proposed:
            self.registry.gauge("serving/spec_acceptance").set(
                self.spec_accepted / self.spec_proposed)
        self._tick_tokens += emitted

    # ------------------------------------------------------------------ mfu

    def _probe_decode_flops(self, args) -> None:
        """Fill ``self._decode_flops`` (or the reason it is unknown), and
        keep the lowered program for ``spans.program_scopes()``: it holds
        the module and the arguments' shapes, no array and not the engine."""
        self._flops_probed = True
        try:
            lowered = self._decode.lower(*args)
        except Exception as e:  # telemetry never breaks serving
            self._probe_fail_reason = (
                f"decode lowering for cost analysis failed: {e!r}")
            self.mfu_reason = self._probe_fail_reason
            return
        self._decode_flops = compiled_flops(lowered)
        spans.register_program("serving/decode", lowered)

    def _register_prefill(self, args) -> None:
        """The prefill program for ``spans.program_scopes()``, from its
        first call: the jitted function and the call's abstract arguments,
        lowered when someone asks (the model is traced a second time only
        then)."""
        import jax

        self._prefill_registered = True
        prefill = self._prefill
        # a sharding where the call's argument is committed to one, as
        # the call sees it: the same module, so the same compile-cache key
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                np.shape(a), a.dtype, sharding=a.sharding if getattr(
                    a, "committed", False) else None), args)
        spans.register_program("serving/prefill",
                               lambda: prefill.lower(*abstract))

    def _refresh_mfu(self, decode_ms: float) -> None:
        """Derive MFU from the last delivered decode call's wall time
        (:meth:`_fetch_call`: its dispatch and fetch spans, or the time
        between consecutive deliveries where the engine runs one call
        ahead); flush the gauge when defined, keep the None-reason (unknown
        device peak vs missing cost analysis) for ``/statusz`` and logs
        otherwise."""
        self._decode_ms = decode_ms
        if self._probe_fail_reason is not None:
            # keep the specific probe failure — the generic "no
            # cost-analysis FLOPs" message would misdiagnose it
            self.mfu, self.mfu_reason = None, self._probe_fail_reason
            return
        n_devices = self.mesh.devices.size
        value, reason = mfu_or_reason(
            self._decode_flops, decode_ms / 1e3,
            device=self.mesh.devices.flat[0], n_devices=n_devices)
        self.mfu, self.mfu_reason = value, reason
        if value is not None:
            self.registry.gauge("serving/mfu").set(value)

    # ---------------------------------------------------------- introspection

    def introspect(self) -> dict:
        """Live engine state for ``/statusz`` (read-only snapshot; the
        :class:`~apex_tpu.observability.debug_server.DebugServer`
        duck-types this)."""
        sched = self.scheduler
        pc = sched.prefix_cache
        return {
            "steps": self._steps,
            "active_slots": len(sched.running()),
            "free_slots": len(sched.free_slots()),
            "free_blocks": sched.allocator.n_free,
            "total_blocks": sched.allocator.n_blocks,
            "queue_depth": len(sched.waiting),
            "draining": self.draining,
            "decode_compiles": self.decode_compile_count(),
            "admission": sched.admission,
            "kv_occupancy": round(sched.kv_occupancy(), 4),
            "prefix_cached_blocks": (pc.n_blocks if pc is not None
                                     else None),
            "prefix_cache_hits": (pc.hits if pc is not None else None),
            "evictions": (pc.evictions if pc is not None else None),
            "preemptions": sched.preemptions,
            "kv_exports_pinned": len(self.exports),
            "spec_width": self.spec_width,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "spec_acceptance": (
                round(self.spec_accepted / self.spec_proposed, 4)
                if self.spec_proposed else None),
            "spec_by_adapter": {
                aid: {"proposed": int(p), "accepted": int(a),
                      "acceptance": round(a / p, 4) if p else None}
                for aid, (p, a) in sorted(self.spec_by_adapter.items())},
            "knobs": self.knobs(),
            "decode_calls": self._decode_calls,
            "adapters_resident": (
                self.adapter_arena.residents()
                if self.adapter_arena is not None else None),
            "adapter_active": (self.adapter_arena.active
                               if self.adapter_arena is not None
                               else None),
            "adapter_loads": (self.adapter_arena.loads
                              if self.adapter_arena is not None
                              else None),
            "adapter_evictions": (self.adapter_arena.evictions
                                  if self.adapter_arena is not None
                                  else None),
            "cache_dtype": str(np.dtype(self.cache.dtype)),
            "last_decode_ms": (round(self._decode_ms, 3)
                               if self._decode_ms is not None else None),
            "slowest_tick": self._slowest_tick(),
            "mfu": self.mfu,
            "mfu_reason": self.mfu_reason,
        }

    def last_logits(self) -> Optional[Tuple[Any, Tuple[int, ...]]]:
        """The last delivered decode call's logits (the device array
        ``[max_batch, spec_width, vocab]``, as the program returned it)
        and the slots whose row was delivered (the other rows are padding,
        or were discarded); ``None`` before the first delivery.  Each named
        slot's request has read ``sequence_tokens()[:cache_len]`` when the
        row was made.  Replaced at delivery, so where the engine runs one
        call ahead these are the call before's, not those of the call in
        flight.  Held by reference until the next delivery replaces it,
        never copied."""
        return self._last_logits

    def last_index_choices(self):
        """The positions the last delivered decode call's queries attended
        in each sparse latent layer (the device array ``[sparse layers,
        max_batch, index_topk]``, ascending, ``-1`` where no token), the
        same call whose :meth:`last_logits` are held; ``None`` for a model
        with no sparse layer or before the first delivery.  Held by
        reference, never fetched."""
        return self._last_selected

    def last_expert_choices(self) -> List[Tuple[Any, Tuple[Tuple[int, ...],
                                                         ...]]]:
        """What the routers of the last tick's calls chose (a model with
        expert layers; else empty): per call, prefill before decode, the
        device array ``[expert layers, rows of the call, top_k]`` of expert
        ids as the program returned it, and which of its rows were tokens:
        ``(request id, first row, first position, count)`` per request (per
        dispatch: a decode call's position is the one it writes, also
        while the call before it is in flight).
        Held by reference until the next tick, never fetched: a
        comparison with another precision reads them afterwards, since
        scores near the cut lie closer than bfloat16 rounds."""
        return self._tick_choices

    @staticmethod
    def _slowest_tick() -> Optional[dict]:
        """The phase split, in ms, of the longest ``serving/tick`` in the
        span ring (the process's: a replica runs one engine) — what
        places a stalled tick in the scheduler, the dispatch or the
        fetch.  ``own`` is what no phase covers, so the phases sum to
        ``ms``."""
        records = spans.recorded()
        ticks = [s for s in records if s.name == "serving/tick"]
        if not ticks:
            return None
        worst = max(ticks, key=lambda s: s.ms)
        children = [s for s in records if s.parent == worst.id]
        phases = {s.name.rpartition("/")[2]: s.ms for s in children}
        phases["own"] = spans.self_ms([worst] + children)[worst.id]
        return {"step": worst.fields["step"], "ms": round(worst.ms, 3),
                "phases": {k: round(v, 3) for k, v in phases.items()}}

    # ---------------------------------------------------------- bookkeeping

    def _emit(self, req: Request, token: int, now: float) -> None:
        """Record one generated token; finish on eos/budget."""
        if req.t_first_token is None:
            req.t_first_token = now
            self.registry.histogram(
                "serving/ttft_ms", keep_samples=4096).observe(
                    (now - req.t_submit) * 1e3)
        elif req.t_last_token is not None:
            self.registry.histogram(
                "serving/tpot_ms", keep_samples=65536).observe(
                    (now - req.t_last_token) * 1e3)
        req.t_last_token = now
        req.output_tokens.append(token)
        self.registry.counter("serving/tokens_generated").inc()
        n = len(req.output_tokens)
        if n % self.timeline_tick_every == 0:
            timeline.emit("decode_tick", rid=req.rid, tokens=n,
                          **trace_fields(req))
        if (n >= req.max_new_tokens
                or (req.eos_id is not None and token == req.eos_id)):
            self._finish(req)

    def _finish(self, req: Request) -> None:
        for table in self._group_tables:
            table[req.slot][:] = 0
        self.scheduler.finish(req)
        self._unpin_adapter(req)
        self.registry.counter("serving/requests_finished").inc()
        timeline.emit("request_finish", rid=req.rid,
                      tokens=len(req.output_tokens),
                      **trace_fields(req))

    def _unpin_adapter(self, req: Request) -> None:
        """Release a terminal request's adapter pin (no-op for the
        ``adapter_id=None`` majority — every terminal path calls this
        unconditionally)."""
        if self.adapter_arena is not None:
            self.adapter_arena.unpin(req.rid)
            self.registry.gauge("serving/adapter_active").set(
                self.adapter_arena.active)

"""Mixture-of-Experts (Switch) MLP with expert parallelism.

Parity-plus: the reference *stubs* MoE out — ``standalone_transformer_lm.py:675``
asserts ``args.num_experts is None`` with the ``SwitchMLP`` call commented —
and SURVEY §2.5 lists expert parallelism as "absent in reference; optional
extension".  Long-context/distributed being first-class here, EP gets the
same treatment as the other strategies: experts shard over a mesh axis and
tokens move with one ``all_to_all`` each way (the standard TPU MoE
dispatch; the ``cp`` axis or the ``dp`` axis both work — whichever the
caller binds).

Routing is Switch-Transformer top-1 with capacity:

- router in fp32, top-1 expert + gate probability per token;
- capacity ``C = ceil(T/E * capacity_factor)`` per expert; overflow
  tokens are *dropped* (their MoE output is zero — the transformer's
  residual connection carries them, exactly Switch semantics);
- load-balancing aux loss ``E * Σ_e f_e·P_e`` (fraction routed × mean
  router prob), returned to the caller (the module form ``sow``s it into
  the ``"losses"`` collection as ``moe_aux``).

Expert-parallel dataflow (``expert_axis`` bound, ``E % ep == 0``): local
dispatch builds ``[E, C, h]``, one ``all_to_all`` regroups to
``[E/ep, ep*C, h]`` so each rank runs only its experts over everyone's
tokens, and the reverse ``all_to_all`` brings outputs home — numerically
identical to the dense path (tested).

**Top-k dropless routing over a share of the experts**
(:func:`route_topk`, :func:`held_experts_ffn`; ISSUE 27): sigmoid scores,
a selection bias that chooses and does not weigh, the ``top_k`` chosen
scores normalised, no capacity and no token dropped.  The layer is *told
which experts it holds* (``held = (first, count)`` of ``n_experts``, the
chip's share under wide expert parallelism): it routes over all of them,
sorts the tick's ``(token, expert)`` pairs that fall on held experts by
expert, runs one grouped matmul per projection over the held experts'
stacked weights, and scatters the weighted rows back.  What the absent
experts would add is left out; nothing stands in for the other chips or
their exchange.  Shapes are fixed by the token count, so churn in what is
routed where never recompiles.  :class:`SwitchMLP` (top-1, capacity drop,
training dry run) stays as it is beside it.

Memory honesty: under EP the expert stacks are declared at their **local**
shape ``[E/ep, ...]`` (the same rank-folded-init convention as the
tensor-parallel linears), with init rng folded by ``axis_index`` so expert
groups decorrelate; ``infer_param_specs`` ships matching ``P(ep_axis)``
dim-0 specs, so parameters, gradients, and optimizer state all live 1/ep
per rank and expert grads are *not* psummed over the ep axis (each rank
owns its experts).  The router stays replicated.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.parallel import collectives as cc

__all__ = ["SwitchMLP", "collect_moe_aux", "switch_route", "route_topk",
           "held_experts_ffn", "grouped_matmul"]


def collect_moe_aux(mutated_collections) -> jnp.ndarray:
    """Sum every ``moe_aux`` value sown into the ``"losses"`` collection
    (one per MoE layer) — add ``coeff * collect_moe_aux(mut)`` to the
    training loss.  Returns 0.0 when no MoE layer ran."""
    from collections.abc import Mapping

    if not isinstance(mutated_collections, Mapping):
        raise TypeError(
            f"expected the mutated-collections mapping from "
            f"module.apply(..., mutable=['losses']), got "
            f"{type(mutated_collections).__name__}")
    losses = mutated_collections.get("losses", {})
    total = jnp.float32(0.0)
    for path, leaf in jax.tree_util.tree_leaves_with_path(losses):
        if any("moe_aux" in str(getattr(k, "key", k)) for k in path):
            total = total + jnp.sum(jnp.asarray(leaf))
    return total


def switch_route(logits32, capacity: int):
    """Top-1 Switch routing tensors from fp32 router logits ``[T, E]``.

    Returns ``(dispatch [T, E, C] bool, gate [T] f32, aux f32)``.
    """
    T, E = logits32.shape
    probs = jax.nn.softmax(logits32, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=1)[:, 0]

    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [T, E]
    # position of each token within its expert's queue (1-based)
    pos = jnp.cumsum(onehot, axis=0) * onehot
    keep = (pos > 0) & (pos <= capacity)
    cpos = jnp.clip(pos.astype(jnp.int32) - 1, 0, capacity - 1)
    dispatch = keep[:, :, None] & (
        cpos[:, :, None]
        == jnp.arange(capacity, dtype=jnp.int32)[None, None, :])

    # Switch load-balance loss: E * sum_e fraction_e * mean_prob_e
    fraction = onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    aux = E * jnp.sum(fraction * mean_prob)
    return dispatch, gate, aux


class SwitchMLP(nn.Module):
    """Switch-style MoE FFN block, drop-in for the dense MLP position.

    ``expert_axis``: mesh axis to shard experts over (``None`` = all
    experts local).  Experts are dense h→ffn→h MLPs with gelu (tensor
    parallelism *within* an expert is a composition left to the caller —
    Megatron's commented-out SwitchMLP wraps ParallelMLP the same way).
    Input/output ``[s, b, h]``; the aux loss is returned and also sown
    into the ``"losses"`` collection (key ``moe_aux``) — **add it to the
    training objective** (``~1e-2`` coefficient; Switch Transformer
    §2.2), e.g. via :func:`collect_moe_aux` on the mutated collections.

    Under EP the expert params are declared at local shape
    ``[E/ep, ...]`` — init must run inside the ``shard_map`` that binds
    ``expert_axis`` (the tensor-parallel rank-folded-init convention).
    """

    hidden_size: int
    ffn_size: int
    num_experts: int
    capacity_factor: float = 1.25
    expert_axis: Optional[str] = None
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        s, b, h = x.shape
        E = self.num_experts
        T = s * b
        capacity = max(1, int(-(-T * self.capacity_factor // E)))

        ep = cc.bound_axis_size(self.expert_axis)
        if E % ep:
            raise ValueError(
                f"num_experts ({E}) not divisible by expert-parallel "
                f"world ({ep})")
        e_local = E // ep

        def expert_init(base):
            # rank-folded init: each ep rank draws its own experts' weights
            def init(rng, shape, dtype):
                if ep > 1:
                    rng = jax.random.fold_in(
                        rng, cc.axis_index(self.expert_axis))
                return base(rng, shape, dtype)
            return init

        router = self.param("router", nn.initializers.normal(0.02),
                            (h, E), jnp.float32)
        w1 = self.param("w1", expert_init(nn.initializers.normal(0.02)),
                        (e_local, h, self.ffn_size), self.param_dtype)
        b1 = self.param("b1", nn.initializers.zeros,
                        (e_local, self.ffn_size), self.param_dtype)
        w2 = self.param("w2", expert_init(nn.initializers.normal(
            0.02 / (2 * E) ** 0.5)), (e_local, self.ffn_size, h),
            self.param_dtype)
        b2 = self.param("b2", nn.initializers.zeros, (e_local, h),
                        self.param_dtype)

        flat = x.reshape(T, h)
        logits = flat.astype(jnp.float32) @ router
        dispatch, gate, aux = switch_route(logits, capacity)
        dd = dispatch.astype(self.dtype)

        expert_in = jnp.einsum("tec,th->ech", dd,
                               flat.astype(self.dtype))  # [E, C, h]

        def one_expert(xe, w1e, b1e, w2e, b2e):
            hmid = jax.nn.gelu(xe @ w1e.astype(self.dtype)
                               + b1e.astype(self.dtype))
            return hmid @ w2e.astype(self.dtype) + b2e.astype(self.dtype)

        if ep > 1:
            # tokens -> expert owners: [E, C, h] -> [E/ep, ep*C, h]
            regroup = cc.all_to_all(expert_in, self.expert_axis,
                                    split_axis=0, concat_axis=1)
            out_local = jax.vmap(one_expert)(regroup, w1, b1, w2, b2)
            # outputs home: [E/ep, ep*C, h] -> [E, C, h]
            expert_out = cc.all_to_all(out_local, self.expert_axis,
                                       split_axis=1, concat_axis=0)
        else:
            expert_out = jax.vmap(one_expert)(expert_in, w1, b1, w2, b2)

        y = jnp.einsum("tec,ech->th", dd, expert_out)
        y = y * gate.astype(self.dtype)[:, None]
        self.sow("losses", "moe_aux", aux)
        return y.reshape(s, b, h), aux


# ------------------------------------------- top-k routing, held experts


def route_topk(logits32, bias, top_k: int):
    """Sigmoid-scored top-k routing from fp32 router logits ``[T, E]``.

    The ``top_k`` experts with the largest ``sigmoid(logit) + bias`` are
    chosen (``bias [E]`` selects and does not weigh; ties go to the lower
    expert id, ``lax.top_k``'s order); their weights are their scores
    normalised to sum to one.  Returns ``(experts [T, k] int32, weights
    [T, k] f32)``."""
    scores = jax.nn.sigmoid(logits32)
    _, experts = jax.lax.top_k(scores + bias, top_k)
    picked = jnp.take_along_axis(scores, experts, axis=1)
    return (experts.astype(jnp.int32),
            picked / jnp.sum(picked, axis=1, keepdims=True))


# row tile of the Pallas grouped matmul; its k and n tiles are the weight
# block a grid step reads (2 MB of bf16: 2.5 us of HBM time beside 0.35 us
# of step overhead)
_GMM_TILES = (128, 1024, 1024)


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [m, k]`` rows sorted by group, ``rhs [groups, k, n]``,
    ``group_sizes [groups]`` int32 summing to at most ``m`` ->
    ``[m, n]`` in ``lhs``'s dtype (fp32 accumulation); rows past the sum
    hold nothing meaningful.  The Pallas grouped matmul of
    ``jax.experimental.pallas.ops.tpu.megablox``: a tile of rows meets only
    its own group's weights, and tiles past the last group are not visited.
    (``lax.ragged_dot`` computes the same; on a v5e at the decode shape it
    took 1.15 ms where this takes 0.60, PERF.md §6, and is not kept.)"""
    import importlib

    from apex_tpu.observability.spans import named_span
    from apex_tpu.utils import platform

    m, k = lhs.shape
    n = rhs.shape[2]
    tiles = tuple(min(t, d) for t, d in zip(_GMM_TILES, (m, k, n)))
    # the kernel traced in place (not through the library's own jit, whose
    # name would be the instruction's), under the scope that names it in a
    # device trace: ``%moe_experts.<n>``
    megablox = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    with named_span("moe_experts"):
        return megablox.gmm.__wrapped__(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=tiles, interpret=platform.pallas_interpret())


def _chunk_rows(pairs: int, share: float) -> int:
    """Rows of one pass of the grouped matmuls: one and a half times the
    pairs expected on the held experts, in steps of 512, at most all
    (rounded up to the row tile, or to 8 where one tile takes them)."""
    want = -(-int(1.5 * pairs * share) // 512) * 512
    rows = min(pairs, max(512, want))
    step = _GMM_TILES[0] if rows > _GMM_TILES[0] else 8
    return -(-rows // step) * step


def held_experts_ffn(x, router, router_bias, w_gate_up, w_down, *,
                     top_k: int, held: Tuple[int, int],
                     live=None):
    """The held experts' part of a top-k expert feed-forward.

    ``x [T, h]``; ``router [h, E]`` and ``router_bias [E]`` over all ``E``
    experts; ``w_gate_up [count, h, 2 f]`` (gate columns, then up) and
    ``w_down [count, f, h]`` of the ``count`` experts from ``held[0]`` on.
    Returns ``(y [T, h] float32, pairs [count] int32, experts [T, top_k]
    int32)``: ``y`` sums, over each token's chosen experts that are held
    here, weight times ``w_down(silu(gate) * up)``; ``pairs`` counts the
    ``(token, expert)`` pairs routed to each held expert; ``experts`` are
    the ids each token's router chose among all ``E`` (a comparison with
    another precision needs them: scores near the cut lie closer together
    than bfloat16 rounds).  ``live [T]`` bool marks the rows
    that are tokens (a fixed-shape batch carries padding): the others are
    routed nowhere, cost nothing and add nothing.

    The pairs on held experts are sorted by expert and handled
    ``_chunk_rows`` at a time, as many passes as they need (one, unless the
    routing is far more skewed towards this share than its size
    suggests): no pair is dropped, and the work follows the pairs that are
    here, not the ``T * top_k`` there could be."""
    T, h = x.shape
    n_experts = router.shape[1]
    first, count = held
    f = w_down.shape[1]
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        experts, weights = route_topk(
            logits, router_bias.astype(jnp.float32), top_k)
        local = experts - first
        here = (local >= 0) & (local < count)
        if live is not None:
            here = here & live[:, None]
        key = jnp.where(here, local, count).reshape(-1)       # [T * k]
        order = jnp.argsort(key, stable=True)
        pairs = jnp.sum(
            key[:, None] == jnp.arange(count, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32)                          # [count]
        ends = jnp.cumsum(pairs)
        starts = ends - pairs
        n_here = ends[-1]
        token = (order // top_k).astype(jnp.int32)
        weight = weights.reshape(-1)[order]
    P = T * top_k
    rows = _chunk_rows(P, count / n_experts)

    def one_pass(i, y):
        lo = i * rows
        at = jnp.minimum(lo + jnp.arange(rows, dtype=jnp.int32), P - 1)
        live = (lo + jnp.arange(rows, dtype=jnp.int32)) < n_here
        sizes = (jnp.clip(ends, lo, lo + rows)
                 - jnp.clip(starts, lo, lo + rows)).astype(jnp.int32)
        tok = token[at]
        xs = x[tok]
        gate_up = grouped_matmul(xs, w_gate_up, sizes)
        mid = (jax.nn.silu(gate_up[:, :f].astype(jnp.float32))
               * gate_up[:, f:].astype(jnp.float32)).astype(x.dtype)
        out = grouped_matmul(mid, w_down, sizes)
        out = jnp.where(live[:, None],
                        out.astype(jnp.float32) * weight[at][:, None], 0.0)
        # rows past the pairs that are here add nothing, wherever they land
        return y.at[tok].add(out)

    with jax.named_scope("moe_experts"):
        y = jax.lax.fori_loop(0, -(-n_here // rows), one_pass,
                              jnp.zeros((T, h), jnp.float32))
    return y, pairs, experts

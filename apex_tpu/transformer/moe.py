"""Mixture-of-experts feed-forward layers.

**The layer that serves and trains: top-k dropless routing over a share of
the experts** (:func:`route_topk`, :func:`held_experts_ffn`): sigmoid or
softmax scores, a selection bias that chooses and does not weigh, an
optional limit to the best few groups of experts, the ``top_k`` chosen
scores normalised or not (and scaled), no capacity and no token dropped,
an optional shared expert that every token meets.  The layer is *told
which experts it holds* (``held = (first, count)`` of ``n_experts``, the
chip's share under wide expert parallelism): it routes over all of them,
sorts the ``(token, expert)`` pairs that fall on held experts by expert,
runs one grouped matmul per projection over the held experts' stacked
weights, and scatters the weighted rows back.  What the absent experts
would add is left out; nothing stands in for the other chips or their
exchange.  Shapes are fixed by the token count, so churn in what is routed
where never recompiles.  It is differentiable: :func:`grouped_matmul` has
a VJP from the same Pallas library (the rows' gradient is a grouped matmul
over the transposed weights, the weights' gradient the transposed grouped
matmul), the passes over the sorted pairs have one of their own that walks
the same passes again (both follow the pairs that are here), and gradients
reach the router through the chosen scores, not through the choice.

**Beside it, Switch-Transformer top-1 routing with capacity**
(:class:`SwitchMLP`, :func:`switch_route`; a training dry run, ROADMAP R3
has its removal).  Parity-plus: the reference *stubs* MoE out,
``standalone_transformer_lm.py:675`` asserts ``args.num_experts is None``
with the ``SwitchMLP`` call commented, and SURVEY §2.5 lists expert
parallelism as "absent in reference; optional extension".  Experts shard
over a mesh axis and tokens move with one ``all_to_all`` each way:

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

Memory honesty: under EP the expert stacks are declared at their **local**
shape ``[E/ep, ...]`` (the same rank-folded-init convention as the
tensor-parallel linears), with init rng folded by ``axis_index`` so expert
groups decorrelate; ``infer_param_specs`` ships matching ``P(ep_axis)``
dim-0 specs, so parameters, gradients, and optimizer state all live 1/ep
per rank and expert grads are *not* psummed over the ep axis (each rank
owns its experts).  The router stays replicated.
"""

from __future__ import annotations

import functools
import importlib
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.observability.spans import named_span
from apex_tpu.parallel import collectives as cc
from apex_tpu.utils import platform

__all__ = ["SwitchMLP", "collect_moe_aux", "switch_route", "route_topk",
           "kept_groups", "held_experts_ffn", "grouped_matmul", "swiglu"]


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


def kept_groups(choose_by, n_groups: int, topk_groups: int):
    """``[T, n_groups]`` bool: the ``topk_groups`` groups of consecutive
    experts whose best entry of ``choose_by [T, E]`` is largest (ties to
    the lower group)."""
    T, E = choose_by.shape
    best = jnp.max(choose_by.reshape(T, n_groups, E // n_groups), axis=-1)
    _, kept = jax.lax.top_k(best, topk_groups)
    return jnp.any(kept[:, :, None] == jnp.arange(n_groups)[None, None, :],
                   axis=1)


def _scores(logits32, bias, scoring: str):
    """``(scores, what the choice goes by)``: the second adds the bias."""
    scores = (jax.nn.sigmoid(logits32) if scoring == "sigmoid"
              else jax.nn.softmax(logits32, axis=-1))
    return scores, scores if bias is None else scores + bias


def route_topk(logits32, bias, top_k: int, eps: float = 0.0,
               scale: float = 1.0, *, scoring: str = "sigmoid",
               groups: Tuple[int, int] = (1, 1), normalize: bool = True):
    """Top-k routing from fp32 router logits ``[T, E]``, scored by
    ``scoring``: ``"sigmoid"`` of each logit, or ``"softmax"`` over the
    experts.

    The ``top_k`` experts with the largest ``score + bias`` are chosen
    (``bias [E]`` selects and does not weigh, ``None`` is no bias; ties go
    to the lower expert id, ``lax.top_k``'s order).  ``groups = (n_groups,
    topk_groups)`` limits the choice to the experts of the ``topk_groups``
    groups that :func:`kept_groups` names.  The weights are the chosen
    scores over their sum plus ``eps`` (the scores as they are where
    ``normalize`` is false), times ``scale``.  Gradients flow through the
    chosen scores and not through the choice.  Returns ``(experts [T, k]
    int32, weights [T, k] f32)``."""
    scores, choose_by = _scores(logits32, bias, scoring)
    if groups[0] > 1:
        kept = kept_groups(choose_by, *groups)
        choose_by = jnp.where(
            jnp.repeat(kept, scores.shape[1] // groups[0], axis=1),
            choose_by, -jnp.inf)
    _, experts = jax.lax.top_k(choose_by, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=1)
    if normalize:
        total = jnp.sum(weights, axis=1, keepdims=True)
        weights = weights / (total + eps if eps else total)
    return experts.astype(jnp.int32), (weights * scale if scale != 1.0
                                       else weights)


# row tile of the Pallas grouped matmul; its k and n tiles are the weight
# block a grid step reads (2 MB of bf16: 2.5 us of HBM time beside 0.35 us
# of step overhead)
_GMM_TILES = (128, 1024, 1024)


def _megablox():
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _tiles(m, k, n):
    return tuple(min(t, d) for t, d in zip(_GMM_TILES, (m, k, n)))


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [m, k]`` rows sorted by group, ``rhs [groups, k, n]``,
    ``group_sizes [groups]`` int32 summing to at most ``m`` ->
    ``[m, n]`` in ``lhs``'s dtype (fp32 accumulation); rows past the sum
    hold nothing meaningful.  The Pallas grouped matmul of
    ``jax.experimental.pallas.ops.tpu.megablox``: a tile of rows meets only
    its own group's weights, and tiles past the last group are not visited.
    (``lax.ragged_dot`` computes the same; on a v5e at the decode shape it
    took 1.15 ms where this takes 0.60, PERF.md §6, and is not kept.)

    Differentiable in ``lhs`` and ``rhs``: the rows' gradient is the same
    kernel over the transposed weights, the weights' gradient the library's
    transposed grouped matmul (a group no row fell on gets zeros); the
    gradient of rows past the sum holds nothing meaningful either."""
    m, k = lhs.shape
    # the kernel traced in place (not through the library's own jit, whose
    # name would be the instruction's), under the scope that names it in a
    # device trace: ``%moe_experts.<n>``
    with named_span("moe_experts"):
        return _megablox().gmm.__wrapped__(
            lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=_tiles(m, k, rhs.shape[2]),
            interpret=platform.pallas_interpret())


def _grouped_matmul_fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(kept, g):
    lhs, rhs, group_sizes = kept
    m, k = lhs.shape
    n = rhs.shape[2]
    lib, interpret = _megablox(), platform.pallas_interpret()
    with named_span("moe_experts_bwd"):
        d_lhs = lib.gmm.__wrapped__(
            g, rhs, group_sizes, preferred_element_type=lhs.dtype,
            tiling=_tiles(m, n, k), transpose_rhs=True, interpret=interpret)
        d_rhs = lib.tgmm.__wrapped__(
            lhs.swapaxes(0, 1), g, group_sizes,
            preferred_element_type=rhs.dtype, tiling=_tiles(m, k, n),
            num_actual_groups=rhs.shape[0], interpret=interpret)
    return d_lhs, d_rhs, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _chunk_rows(pairs: int, share: float) -> int:
    """Rows of one pass of the grouped matmuls: one and a half times the
    pairs expected on the held experts, in steps of 512, at most all
    (rounded up to the row tile, or to 8 where one tile takes them)."""
    want = -(-int(1.5 * pairs * share) // 512) * 512
    rows = min(pairs, max(512, want))
    step = _GMM_TILES[0] if rows > _GMM_TILES[0] else 8
    return -(-rows // step) * step


def swiglu(x, w_gate_up, w_down):
    """``w_down(silu(gate) * up)`` of ``x [T, h]`` with ``w_gate_up [h, 2
    f]`` (gate columns, then up) and ``w_down [f, h]``: float32 out of
    matmuls on operands in ``x``'s dtype."""
    f = w_down.shape[0]
    gate_up = jnp.dot(x, w_gate_up, preferred_element_type=jnp.float32)
    mid = jax.nn.silu(gate_up[:, :f]) * gate_up[:, f:]
    return jnp.dot(mid.astype(x.dtype), w_down,
                   preferred_element_type=jnp.float32)


# The passes over the sorted pairs.  A pass takes ``rows`` of them
# (``_chunk_rows``: one and a half times what the held experts expect, so
# that one pass is the rule and more come only under a skew towards this
# share), gathers their tokens' rows, runs the two grouped matmuls and adds
# the weighted results to their tokens; as many passes run as the pairs that
# are here need, so none is dropped and none that is elsewhere is paid for.
# That trip count is traced, which reverse differentiation cannot take, so
# the passes have a VJP of their own: it walks the same passes again, each
# differentiated by itself (through ``grouped_matmul``'s VJP), and adds up
# what they give.


def _pass_rows(i, rows, x, w_gate_up, w_down, weight, token, ends, masked):
    """Pass ``i``: the tokens of its ``rows`` sorted pairs and their
    weighted expert outputs ``[rows, h]`` in float32, nought past the pairs
    that are here.  ``masked`` also noughts the gathered rows there, so
    that what the kernels leave in rows they do not visit reaches no
    gradient."""
    P = token.shape[0]
    f = w_down.shape[1]
    n_here = ends[-1]
    starts = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends[:-1]])
    lo = i * rows
    at = jnp.minimum(lo + jnp.arange(rows, dtype=jnp.int32), P - 1)
    live = (lo + jnp.arange(rows, dtype=jnp.int32)) < n_here
    sizes = (jnp.clip(ends, lo, lo + rows)
             - jnp.clip(starts, lo, lo + rows)).astype(jnp.int32)
    tok = token[at]
    xs = x[tok]
    if masked:
        xs = jnp.where(live[:, None], xs, 0)
    gate_up = grouped_matmul(xs, w_gate_up, sizes)
    mid = (jax.nn.silu(gate_up[:, :f].astype(jnp.float32))
           * gate_up[:, f:].astype(jnp.float32)).astype(x.dtype)
    out = grouped_matmul(mid, w_down, sizes).astype(jnp.float32)
    if masked:
        # the weight's gradient is a product with these rows: nought first
        out = jnp.where(live[:, None], out, 0.0) * weight[at][:, None]
    else:
        out = jnp.where(live[:, None], out * weight[at][:, None], 0.0)
    return tok, out


def _n_passes(ends, rows):
    return -(-ends[-1] // rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_passes(rows, x, w_gate_up, w_down, weight, token, ends):
    """``y [T, h]`` float32: every pass's rows added to their tokens."""
    def one_pass(i, y):
        tok, out = _pass_rows(i, rows, x, w_gate_up, w_down, weight, token,
                              ends, masked=False)
        # rows past the pairs that are here add nothing, wherever they land
        return y.at[tok].add(out)

    return jax.lax.fori_loop(0, _n_passes(ends, rows), one_pass,
                             jnp.zeros(x.shape, jnp.float32))


def _held_passes_fwd(rows, x, w_gate_up, w_down, weight, token, ends):
    return (_held_passes(rows, x, w_gate_up, w_down, weight, token, ends),
            (x, w_gate_up, w_down, weight, token, ends))


def _held_passes_bwd(rows, kept, dy):
    x, w_gate_up, w_down, weight, token, ends = kept

    def one_pass(i, grads):
        def rows_of(x, w_gate_up, w_down, weight):
            tok, out = _pass_rows(i, rows, x, w_gate_up, w_down, weight,
                                  token, ends, masked=True)
            return out, tok

        _, pull, tok = jax.vjp(rows_of, x, w_gate_up, w_down, weight,
                               has_aux=True)
        return tuple(g + d.astype(g.dtype)
                     for g, d in zip(grads, pull(dy[tok])))

    zeros = (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(w_gate_up),
             jnp.zeros_like(w_down), jnp.zeros_like(weight))
    dx, d_gate_up, d_down, d_weight = jax.lax.fori_loop(
        0, _n_passes(ends, rows), one_pass, zeros)
    return dx.astype(x.dtype), d_gate_up, d_down, d_weight, None, None


_held_passes.defvjp(_held_passes_fwd, _held_passes_bwd)


def _group_tokens(logits32, bias, held, live, scoring: str = "sigmoid",
                  groups: Tuple[int, int] = (1, 1), **_):
    """How many live tokens' routers kept a group that holds one of the
    ``held`` experts (:func:`held_experts_ffn`)."""
    T, E = logits32.shape
    reached = jnp.ones((T,), bool)
    if groups[0] > 1:
        kept = kept_groups(_scores(logits32, bias, scoring)[1], *groups)
        size = E // groups[0]
        mine = jnp.arange(groups[0])
        mine = (mine >= held[0] // size) & (
            mine <= (held[0] + held[1] - 1) // size)
        reached = jnp.any(kept & mine[None, :], axis=1)
    if live is not None:
        reached = reached & live
    return jnp.sum(reached, dtype=jnp.int32)


def held_experts_ffn(x, router, router_bias, w_gate_up, w_down, *,
                     top_k: int, held: Tuple[int, int],
                     live=None, route_eps: float = 0.0,
                     route_scale: float = 1.0, shared=None, **routing):
    """The held experts' part of a top-k expert feed-forward.

    ``x [T, h]``; ``router [h, E]`` and ``router_bias [E]`` over all ``E``
    experts; ``w_gate_up [count, h, 2 f]`` (gate columns, then up) and
    ``w_down [count, f, h]`` of the ``count`` experts from ``held[0]`` on.
    ``router_bias`` may be ``None`` (a family without one).
    Returns ``(y [T, h] float32, pairs [count] int32, experts [T, top_k]
    int32, group_tokens int32)``: ``y`` sums, over each token's chosen
    experts that are held here, weight times ``w_down(silu(gate) * up)``;
    ``pairs`` counts the ``(token, expert)`` pairs routed to each held
    expert; ``experts`` are
    the ids each token's router chose among all ``E`` (a comparison with
    another precision needs them: scores near the cut lie closer together
    than bfloat16 rounds); ``group_tokens`` counts the live tokens whose
    router kept a group that holds a held expert (all of them without a
    group limit: only such a token can bring a pair here).  ``live [T]``
    bool marks the rows
    that are tokens (a fixed-shape batch carries padding): the others are
    routed nowhere, cost nothing and add nothing.  ``route_eps`` and
    ``route_scale`` as :func:`route_topk` takes them, as are ``scoring``,
    ``groups`` and ``normalize`` (``routing``, by keyword).  ``shared``, a
    pair
    ``(w_gate_up [h, 2 f'], w_down [f', h])``, is a shared expert: a dense
    SwiGLU every token meets on its own chip, added to ``y``.

    The pairs on held experts are sorted by expert and handled
    ``_chunk_rows`` at a time, as many passes as they need (one, unless the
    routing is far more skewed towards this share than its size
    suggests): no pair is dropped, and the work follows the pairs that are
    here, not the ``T * top_k`` there could be.  Differentiable in ``x``
    and every weight but ``router_bias`` (which only chooses)."""
    T, h = x.shape
    n_experts = router.shape[1]
    first, count = held
    with named_span("moe_router"):
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        # a family with neither epsilon nor scale keeps the three-argument
        # call that every earlier caller, and whatever wraps this name,
        # makes
        extra = (route_eps, route_scale) if (
            route_eps or route_scale != 1.0) else ()
        bias = (None if router_bias is None
                else router_bias.astype(jnp.float32))
        experts, weights = route_topk(logits, bias, top_k, *extra, **routing)
        local = experts - first
        here = (local >= 0) & (local < count)
        if live is not None:
            here = here & live[:, None]
        group_tokens = _group_tokens(logits, bias, held, live, **routing)
        key = jnp.where(here, local, count).reshape(-1)       # [T * k]
        order = jnp.argsort(key, stable=True)
        pairs = jnp.sum(
            key[:, None] == jnp.arange(count, dtype=key.dtype)[None, :],
            axis=0, dtype=jnp.int32)                          # [count]
        ends = jnp.cumsum(pairs)
        token = (order // top_k).astype(jnp.int32)
        weight = weights.reshape(-1)[order]
    rows = _chunk_rows(T * top_k, count / n_experts)
    with named_span("moe_experts"):
        y = _held_passes(rows, x, w_gate_up, w_down, weight, token, ends)
    if shared is not None:
        with named_span("moe_shared"):
            y = y + swiglu(x, *shared)
    return y, pairs, experts, group_tokens

"""chip_smoke.py — does GPT-124M still train and serve on the chip?

One process, no arguments, seeds only.  It drives the two entry points a
user calls (``build_gpt_3d`` -> train step, ``ServingEngine`` -> requests)
at the full width and depth of the repo's ``gpt_flash`` configuration, on
whatever TPU devices JAX reports (one chip, or the four of a host), and
runs every Pallas kernel once against its reference twin.  Any failed
check raises, so the process exits non-zero; the last line of stdout is
the JSON verdict only when every phase passed.

It is not a benchmark: the compile and step times it prints say that the
path ran and how long the smoke takes, nothing about speed.

    python chip_smoke.py            # through the chip tool

The phases are plain functions over a :class:`Sizes` table so that
``tests/test_kernels_compile_tpu.py`` runs the same code at a toy size on
the CPU mesh and compiles the same kernel cases for a v5e topology.
"""

import dataclasses
import glob
import json
import os
import time
from typing import Callable, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    # model: the gpt_flash configuration (GPT-124M, bf16, flash attention)
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    vocab: int = 50304
    positions: int = 1024
    # trainer: one fixed seeded batch
    batch: int = 8
    microbatches: int = 2
    train_steps: int = 8
    lr: float = 1e-4
    # server
    max_batch: int = 8
    max_seq: int = 1024
    prefill_len: int = 256
    # (arrival tick, prompt tokens): several prefill chunks and KV blocks,
    # arriving over a few ticks; the first request is the sampled one
    requests: Tuple[Tuple[int, int], ...] = (
        (0, 700), (0, 40), (1, 555), (2, 130), (4, 410), (6, 260))
    new_tokens: int = 24
    # kernels off the default path, checked at these widths
    verify_k: int = 3
    lora_rank: int = 8


FULL = Sizes()


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


def gpt_config(sizes: Sizes, tp: int, sequence_parallel: bool):
    """A GPT-2-shaped flash-attention configuration, tensor parallel when
    the layout has a tp axis wider than one."""
    import jax.numpy as jnp

    from apex_tpu.transformer.testing import TransformerConfig

    return TransformerConfig(
        hidden_size=sizes.hidden, num_layers=sizes.layers,
        num_attention_heads=sizes.heads, padded_vocab_size=sizes.vocab,
        max_position_embeddings=sizes.positions, hidden_dropout=0.0,
        attention_dropout=0.0, use_flash_attention=True,
        dtype=jnp.bfloat16, tensor_axis="tp" if tp > 1 else None,
        sequence_parallel=sequence_parallel)


def assert_compiled_kernels(lowered_text: str, what: str) -> None:
    """The program holds Mosaic custom calls exactly when the platform
    compiles Pallas (TPU); in interpret mode (CPU) it holds none."""
    from apex_tpu.utils import platform

    want = not platform.pallas_interpret()
    check(("tpu_custom_call" in lowered_text) == want,
          f"{what}: tpu_custom_call {'missing from' if want else 'found in'}"
          " the lowered program")
    log(f"{what}: tpu_custom_call present={want}")


def check_spread(tree, specs, mesh, what: str) -> None:
    """Every leaf has a shard on every device of the mesh, and as many
    distinct shards as its PartitionSpec names mesh positions."""
    import jax
    from jax.sharding import PartitionSpec as P

    leaves = jax.tree_util.tree_leaves_with_path(tree)
    spec_leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    check(len(leaves) == len(spec_leaves),
          f"{what}: {len(leaves)} leaves vs {len(spec_leaves)} specs")
    n_dev = mesh.devices.size
    for (path, leaf), spec in zip(leaves, spec_leaves):
        name = jax.tree_util.keystr(path)
        axes = [a for part in spec if part is not None
                for a in ((part,) if isinstance(part, str) else part)]
        want = int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))
        shards = leaf.addressable_shards
        on = len({s.device for s in shards})
        check(on == n_dev,
              f"{what}{name}: shards on {on} of {n_dev} devices")
        got = len({str(s.index) for s in shards})
        check(got == want,
              f"{what}{name}: {got} distinct shards, spec {spec} names "
              f"{want}")
    log(f"{what}: {len(leaves)} leaves spread over {n_dev} device(s) as "
        "their specs say")


def device_memory(devices, what: str) -> dict:
    """Per-device ``bytes_in_use`` / ``peak_bytes_in_use``; every device
    must hold something.  The CPU backend reports no statistics, which is
    said and not checked; a TPU must report them."""
    stats = [d.memory_stats() for d in devices]
    if not all(stats):
        check(devices[0].platform != "tpu",
              f"{what}: a TPU device reports no memory_stats")
        log(f"{what}: this backend reports no memory_stats")
        return {}
    out = {"bytes_in_use": [int(s["bytes_in_use"]) for s in stats],
           "peak_bytes_in_use": [int(s["peak_bytes_in_use"])
                                 for s in stats]}
    log(f"{what}: {out}")
    check(min(out["bytes_in_use"]) > 0, f"{what}: a device holds no memory")
    return out


class CompileClock:
    """Sums JAX's own compile events, so cold and warm runs of the smoke
    can be told apart (a persistent-cache hit skips the backend compile)."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "backend_s",
              "/jax/compilation_cache/cache_retrieval_time_sec":
                  "cache_read_s"}
    COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax

        self.totals = {v: 0.0 for v in self.EVENTS.values()}
        self.totals.update({v: 0 for v in self.COUNTS.values()})
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._count)

    def _dur(self, event, secs, **_):
        if event in self.EVENTS:
            self.totals[self.EVENTS[event]] += secs

    def _count(self, event, **_):
        if event in self.COUNTS:
            self.totals[self.COUNTS[event]] += 1

    def snapshot(self) -> dict:
        return {k: round(v, 2) if isinstance(v, float) else v
                for k, v in self.totals.items()}


def native_helpers() -> list:
    """Built native helper libraries in the tree (git ignores them, so a
    checkout has none; the GPT path loads none)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return sorted(os.path.basename(p) for p in glob.glob(
        os.path.join(here, "apex_tpu", "_native", "*.so")))


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------


def train_phase(sizes: Sizes, devices) -> dict:
    """``build_gpt_3d`` + FusedAdam + the non-finite sentinel, a few steps
    on one fixed batch: finite, falling loss and no skipped step."""
    import jax

    from __graft_entry__ import factor
    from apex_tpu import parallel
    from apex_tpu.amp.scaler import DynamicLossScale
    from apex_tpu.observability import MetricRegistry, TrainStatsLogger
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.resilience import sentinel_init
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    # one chip is (1, 1, 1); the four-chip host pp2 x tp2
    dp, pp, tp = factor(len(devices))
    vpp = sizes.layers // pp
    log(f"trainer: dp{dp} x pp{pp} (x{vpp} virtual) x tp{tp}"
        f"{' + sequence parallel' if tp > 1 else ''}, "
        f"batch {sizes.batch}x{sizes.positions}, "
        f"{sizes.microbatches} microbatches")
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=tp, pipeline_model_parallel_size=pp,
        virtual_pipeline_model_parallel_size=vpp if pp > 1 else None,
        devices=devices)
    try:
        cfg = gpt_config(sizes, tp, sequence_parallel=tp > 1)
        init_fn, _, make_train_step = build_gpt_3d(
            cfg, num_chunks=vpp, num_microbatches=sizes.microbatches,
            mesh=mesh)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (sizes.batch, sizes.positions), 0,
            sizes.vocab)
        params, specs = init_fn(jax.random.PRNGKey(0), tokens)
        n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
        check_spread(params, specs, mesh, "trainer params")

        scaler = DynamicLossScale()
        opt = FusedAdam(lr=sizes.lr)
        state = opt.init(params)
        sent = sentinel_init(scaler)
        jax.block_until_ready((params, state))
        device_memory(devices, "trainer after init")

        step = jax.jit(make_train_step(opt, specs, scaler=scaler,
                                       collect_stats=True))
        lowered = step.lower(params, state, tokens, sent)
        assert_compiled_kernels(lowered.as_text(), "train step")
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0

        losses, step_s = [], []
        stats_logger = TrainStatsLogger(MetricRegistry())
        for i in range(sizes.train_steps):
            t0 = time.perf_counter()
            params, state, sent, loss, stats = compiled(
                params, state, tokens, sent)
            loss = float(jax.block_until_ready(loss))
            step_s.append(time.perf_counter() - t0)
            fetched = stats_logger.log(i, stats)
            check(np.isfinite(loss), f"step {i}: non-finite loss {loss}")
            check(fetched["nonfinite_leaves"] == 0,
                  f"step {i}: non-finite gradient leaves: {fetched}")
            losses.append(loss)
        skipped = int(sent.skipped_steps)
        check(skipped == 0, f"{skipped} train steps skipped by the sentinel")
        check(losses[-1] < losses[0],
              f"loss did not fall over {sizes.train_steps} steps: {losses}")
        memory = device_memory(devices, "trainer after steps")
    finally:
        parallel.destroy_model_parallel()
    out = {"layout": {"dp": dp, "pp": pp, "vpp": vpp, "tp": tp},
           "params": int(n_params),
           "losses": [round(x, 4) for x in losses],
           "skipped_steps": skipped,
           "compile_s": round(compile_s, 2),
           "step_s": [round(x, 3) for x in step_s],
           "peak_bytes_in_use": memory.get("peak_bytes_in_use")}
    log(f"trainer: {out}")
    return out


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------


def serve_phase(sizes: Sizes, devices) -> dict:
    """``ServingEngine`` with its default (fused) configuration over
    tp = all devices: staggered requests through chunked prefill and
    continuous decode, one of them sampled, all finishing in full with
    one compile per program."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import parallel
    from apex_tpu.serving import SamplingParams, ServingConfig, ServingEngine
    from apex_tpu.serving.scheduler import RequestState
    from apex_tpu.transformer.testing.gpt_parallel_train import build_gpt_3d

    tp = len(devices)
    log(f"server: tp{tp}, max_batch {sizes.max_batch}, max_seq "
        f"{sizes.max_seq}, prefill chunk {sizes.prefill_len}")
    mesh = parallel.initialize_model_parallel(
        tensor_model_parallel_size=tp, devices=devices)
    try:
        # a decode step has no sequence dimension to shard
        cfg = gpt_config(sizes, tp, sequence_parallel=False)
        # params as serving/replica._build_engine makes them
        init_fn, _, _ = build_gpt_3d(cfg, num_chunks=cfg.num_layers,
                                     num_microbatches=1, mesh=mesh)
        params, _ = init_fn(jax.random.PRNGKey(0),
                            jnp.zeros((2, 2), jnp.int32))
        engine = ServingEngine(
            cfg, ServingConfig(max_batch=sizes.max_batch,
                               max_seq=sizes.max_seq,
                               prefill_len=sizes.prefill_len),
            params, mesh=mesh)
        check(engine.serving.fused_attention and engine.serving.fuse_epilogue,
              "the default ServingConfig is no longer the fused one")
        check_spread(engine.params, engine.param_specs, mesh,
                     "server params")
        jax.block_until_ready((engine.params, engine.arenas))
        device_memory(devices, "server after init")

        rng = np.random.RandomState(0)
        pending = [
            (tick, rng.randint(0, sizes.vocab, size=n).tolist(),
             SamplingParams(temperature=0.8, top_k=40, top_p=0.95, seed=7)
             if i == 0 else None)
            for i, (tick, n) in enumerate(sizes.requests)]
        requests, tick_s = [], []
        tick = 0
        while pending or not engine.scheduler.idle:
            while pending and pending[0][0] <= tick:
                _, prompt, sampling = pending.pop(0)
                requests.append(engine.submit(
                    prompt, sizes.new_tokens, sampling=sampling))
            t0 = time.perf_counter()
            engine.step()
            tick_s.append(time.perf_counter() - t0)
            tick += 1
            check(tick < 100 * len(sizes.requests) * sizes.new_tokens,
                  f"server not drained after {tick} ticks")

        for req in requests:
            check(req.state is RequestState.FINISHED,
                  f"request {req.rid} ended {req.state}")
            check(len(req.output_tokens) == sizes.new_tokens,
                  f"request {req.rid}: {len(req.output_tokens)} of "
                  f"{sizes.new_tokens} tokens")
            check(all(0 <= t < sizes.vocab for t in req.output_tokens),
                  f"request {req.rid}: token outside the vocabulary")
        compiles = {"decode": engine.decode_compile_count(),
                    "prefill": engine.prefill_compile_count()}
        check(compiles == {"decode": 1, "prefill": 1},
              f"each program must compile exactly once: {compiles}")
        engine.scheduler.allocator.check()
        check(engine.scheduler.allocator.n_free + (
            engine.scheduler.prefix_cache.n_blocks
            if engine.scheduler.prefix_cache is not None else 0)
            == engine.cache.n_blocks,
            "KV blocks leaked after the drain")

        # the programs the engine ran, lowered again at their only shapes
        # (the analysis/entries.py recipe): they must hold the kernels
        B, S, T = sizes.max_batch, engine.spec_width, engine.prefill_len
        tables = jnp.zeros((B, engine.cache.max_blocks_per_request),
                           jnp.int32)
        zi = lambda *shape: np.zeros(shape, np.int32)  # noqa: E731
        sampling = (np.zeros((B,), np.float32), zi(B),
                    np.ones((B,), np.float32), np.zeros((B,), np.uint32),
                    zi(B))
        decode_args = (engine.arenas, engine.params, zi(B, S), zi(B),
                       tables, np.zeros((B,), bool), zi(B)) + sampling
        prefill_args = (engine.arenas, engine.params, zi(B, T), zi(B, T),
                        tables, zi(B), zi(B, T), zi(B, T), zi(B, T),
                        np.full((B,), T, np.int32)) + sampling
        assert_compiled_kernels(
            engine._decode.lower(*decode_args).as_text(), "decode step")
        assert_compiled_kernels(
            engine._prefill.lower(*prefill_args).as_text(), "prefill step")
        memory = device_memory(devices, "server after drain")
    finally:
        parallel.destroy_model_parallel()
    warm = sorted(tick_s[len(tick_s) // 2:])
    out = {"tp": tp, "requests": len(requests),
           "prompt_tokens": [n for _, n in sizes.requests],
           "tokens_out": sum(len(r.output_tokens) for r in requests),
           "ticks": tick,
           "first_tick_s": round(tick_s[0], 2),
           "late_tick_median_s": round(warm[len(warm) // 2], 4),
           "compiles": compiles,
           "peak_bytes_in_use": memory.get("peak_bytes_in_use")}
    log(f"server: {out}")
    return out


# ---------------------------------------------------------------------------
# kernels against their twins
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelCase:
    name: str
    kernel: Callable            # (*args, **kwargs) -> array(s)
    twin: Callable              # same signature
    make_args: Callable         # () -> (args, kwargs), from fixed seeds
    rtol: float
    atol: float
    why: str                    # the reason for the tolerance
    # normalise the error by the twin's largest magnitude (attention
    # gradients span orders of magnitude; bf16 error scales with them)
    relative_to_max: bool = False


# fp32 in and out: kernel and twin both contract in fp32 (HIGHEST), so
# they differ by summation order and the exp implementation only.  A
# single-pass bf16 contraction would miss this by two orders of magnitude.
_F32 = dict(rtol=1e-4, atol=1e-4,
            why="fp32 math on both sides: summation order only")
# bf16 out: both sides round an fp32 result to bf16, and two nearly equal
# fp32 values can land one bf16 ulp (2^-8 relative) apart.
_BF16_OUT = dict(rtol=2.0 ** -7, atol=2.0 ** -7,
                 why="outputs rounded to bf16: one ulp of rounding skew")
# flash attention in bf16: QK^T is exact in fp32, but p, ds and the
# outputs are each rounded to bf16 (2^-9 relative) before the next
# matmul; fp8 operands would miss this by 3x or more.
_FLASH_BF16 = dict(rtol=0.0, atol=2e-2, relative_to_max=True,
                   why="bf16 operands: p, ds and outputs rounded to bf16; "
                       "error bounded at 2% of the reference's largest "
                       "value")


def _paged_case_args(sizes: Sizes, heads: int, q_dtype, cache_dtype,
                     chunk: Optional[int]):
    """Seeded operands of one paged-attention call at the engine's
    shapes.  ``chunk=None`` is the one-token decode; otherwise q is
    ``[b, chunk, n, d]`` with per-token limits over ragged histories
    (an empty slot, a slot mid-prompt, a slot at the context cap)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serving import ServingConfig

    b, bs = sizes.max_batch, ServingConfig.block_size
    d = sizes.hidden // sizes.heads
    mb = -(-sizes.max_seq // bs)
    n_blocks = b * mb
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    T = chunk or 1
    q = jax.random.normal(ks[0], (b, T, heads, d), jnp.float32)
    shape = (n_blocks, bs, heads, d)
    kwargs = {}
    if cache_dtype == jnp.int8:
        ka = jax.random.randint(ks[1], shape, -127, 128, jnp.int8)
        va = jax.random.randint(ks[2], shape, -127, 128, jnp.int8)
        kwargs["k_scales"], kwargs["v_scales"] = (jax.random.uniform(
            k, shape[:-1], jnp.float32, 0.004, 0.02) for k in ks[3:5])
    else:
        ka = jax.random.normal(ks[1], shape, jnp.float32)
        va = jax.random.normal(ks[2], shape, jnp.float32)
    tables = jax.random.permutation(ks[5], n_blocks).reshape(b, mb)
    # per slot, as fractions: tokens already cached, tokens of this call
    frac = np.array([1.0, 0.0, 0.4, 0.05, 0.75, 0.3, 0.6, 0.9])
    new = np.array([1.0, 0.0, 0.15, 1.0, 1.0, 0.5, 1.0, 0.02])
    idx = np.arange(b) % len(frac)
    fresh = np.minimum(np.ceil(new[idx] * T), T).astype(np.int32)
    hist = np.floor(frac[idx] * (sizes.max_seq - T)).astype(np.int32)
    hist[0] = sizes.max_seq - T          # the slot at the context cap
    t = np.arange(T)[None, :]
    limits = np.where(t < fresh[:, None], hist[:, None] + t + 1, 0)
    lengths = np.where(fresh > 0, hist + fresh, 0)
    q = q.astype(q_dtype)
    if chunk is None:
        q = q[:, 0]
    else:
        kwargs["limits"] = jnp.asarray(limits, jnp.int32)
    return (q, ka.astype(cache_dtype), va.astype(cache_dtype),
            tables.astype(jnp.int32),
            jnp.asarray(lengths, jnp.int32)), kwargs


def kernel_cases(sizes: Sizes, heads: Optional[int] = None) -> list:
    """Every Pallas kernel of the repo at the smoke's shapes, with its
    twin and the tolerance of the comparison.  ``heads`` is one tensor-
    parallel rank's share (default: all heads, the one-chip engine)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.normalization import (
        fused_layer_norm_affine, fused_rms_norm_affine)
    from apex_tpu.ops.flash_attention import flash_attention
    from apex_tpu.ops.pallas_norm import pallas_layer_norm, pallas_rms_norm
    from apex_tpu.serving.fused_ops import (
        fused_residual_norm, residual_norm_unfused)
    from apex_tpu.serving.lora import lora_delta_fused, lora_delta_unfused
    from apex_tpu.serving import paged_attention as pa
    from apex_tpu.utils import platform

    heads = heads or sizes.heads
    d = sizes.hidden // sizes.heads
    f32, bf16, i8 = jnp.float32, jnp.bfloat16, jnp.int8
    cases = []

    def paged(kind, q_dtype, cache_dtype, tol):
        chunk = {"decode": None, "prefill": sizes.prefill_len,
                 "verify": sizes.verify_k + 1}[kind]
        fused, twin = {
            "decode": (pa.paged_attention_decode,
                       pa.paged_attention_decode_unfused),
            "prefill": (pa.paged_prefill_attention,
                        pa.paged_prefill_attention_unfused),
            # the k+1 verify: the decode entry point with a 4-D q
            "verify": (pa.paged_attention_decode,
                       pa.paged_attention_decode_unfused)}[kind]
        cases.append(KernelCase(
            name=f"paged_{kind}[q={jnp.dtype(q_dtype).name},"
                 f"cache={jnp.dtype(cache_dtype).name}]",
            kernel=fused, twin=twin,
            make_args=lambda: _paged_case_args(
                sizes, heads, q_dtype, cache_dtype, chunk),
            **tol))

    # fp32 end to end pins the math; bf16 queries over the engine's
    # default (param-dtype, fp32) cache, a bf16 and an int8 cache are the
    # compiled paths a deployment runs
    for kind in ("decode", "prefill"):
        paged(kind, f32, f32, _F32)
        paged(kind, bf16, f32, _BF16_OUT)
        paged(kind, bf16, bf16, _BF16_OUT)
        paged(kind, bf16, i8, _BF16_OUT)
    paged("verify", f32, f32, _F32)
    paged("verify", bf16, i8, _BF16_OUT)

    def norm_args(rows, dtype, n_vec):
        def make():
            ks = jax.random.split(jax.random.PRNGKey(5), 2 + n_vec)
            mats = tuple(jax.random.normal(k, (rows, sizes.hidden),
                                           f32).astype(dtype)
                         for k in ks[:2])
            vecs = tuple(1.0 + 0.1 * jax.random.normal(
                k, (sizes.hidden,), f32) for k in ks[2:])
            return mats + vecs, {}
        return make

    # rows: one decode tick, one prefill chunk
    for rows in (sizes.max_batch, sizes.max_batch * sizes.prefill_len):
        for dtype, tol in ((f32, dict(_F32, rtol=1e-5, atol=1e-5)),
                           (bf16, _BF16_OUT)):
            cases.append(KernelCase(
                name=f"fused_residual_norm[rows={rows},"
                     f"{jnp.dtype(dtype).name}]",
                kernel=lambda x, r, w, b, sb: fused_residual_norm(
                    x, r, w, b, bias=sb),
                twin=lambda x, r, w, b, sb: residual_norm_unfused(
                    x, r, w, b, bias=sb),
                make_args=norm_args(rows, dtype, 3), **tol))
    rows = sizes.max_batch * sizes.prefill_len
    interpret = platform.pallas_interpret()
    cases.append(KernelCase(
        name=f"pallas_layer_norm[rows={rows},bfloat16]",
        kernel=lambda x, _, w, b: pallas_layer_norm(
            x, w, b, 1e-5, 256, interpret),
        twin=lambda x, _, w, b: fused_layer_norm_affine(
            x, w, b, (sizes.hidden,), 1e-5),
        make_args=norm_args(rows, bf16, 2), **_BF16_OUT))
    cases.append(KernelCase(
        name=f"pallas_rms_norm[rows={rows},bfloat16]",
        kernel=lambda x, _, w: pallas_rms_norm(x, w, 1e-5, 256, interpret),
        twin=lambda x, _, w: fused_rms_norm_affine(
            x, w, (sizes.hidden,), 1e-5),
        make_args=norm_args(rows, bf16, 1), **_BF16_OUT))

    def lora_args(S, dtype):
        def make():
            ks = jax.random.split(jax.random.PRNGKey(9), 3)
            n_slots, r = 5, sizes.lora_rank
            x = jax.random.normal(
                ks[0], (S, sizes.max_batch, sizes.hidden), f32)
            a = jax.random.normal(ks[1], (n_slots, sizes.hidden, r), f32)
            b = jax.random.normal(
                ks[2], (n_slots, r, 3 * sizes.hidden), f32) * 0.05
            slots = jnp.arange(sizes.max_batch, dtype=jnp.int32) % n_slots
            return (x.astype(dtype), a, b, slots), {}
        return make

    # the qkv projection's delta: one decode tick (fp32: the math) and
    # one prefill chunk (bf16 activations over fp32 adapters, the engine's
    # dtypes)
    cases.append(KernelCase(
        name="lora_delta[S=1,float32]", kernel=lora_delta_fused,
        twin=lora_delta_unfused, make_args=lora_args(1, f32), **_F32))
    cases.append(KernelCase(
        name=f"lora_delta[S={sizes.prefill_len},bfloat16]",
        kernel=lora_delta_fused, twin=lora_delta_unfused,
        make_args=lora_args(sizes.prefill_len, bf16), **_BF16_OUT))

    def flash_args():
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        shape = (sizes.batch // sizes.microbatches, heads, sizes.positions,
                 d)
        return tuple(jax.random.normal(k, shape, f32).astype(bf16)
                     for k in ks), {}

    def with_grads(attend):
        def run(q, k, v, do):
            out, vjp = jax.vjp(attend, q, k, v)
            return (out,) + vjp(do.astype(out.dtype))
        return run

    def reference_attention(q, k, v):
        qf, kf, vf = (x.astype(f32) for x in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / np.sqrt(d)
        mask = jnp.tril(jnp.ones(s.shape[-2:], bool))
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vf).astype(q.dtype)

    # one microbatch of the train step: causal, forward and backward
    cases.append(KernelCase(
        name=f"flash_attention fwd+bwd[s={sizes.positions},bfloat16]",
        kernel=with_grads(lambda q, k, v: flash_attention(
            q, k, v, causal=True)),
        twin=with_grads(reference_attention), make_args=flash_args,
        **_FLASH_BF16))
    return cases


def kernel_phase(sizes: Sizes, heads: Optional[int] = None) -> dict:
    """Run each kernel once on the default device beside its twin.  The
    twins run under ``default_matmul_precision("highest")``: on a TPU a
    float32 einsum is otherwise a single bf16 pass, less exact than the
    kernels it is the reference for."""
    import jax
    import jax.numpy as jnp

    errors = {}
    for case in kernel_cases(sizes, heads):
        args, kwargs = case.make_args()
        t0 = time.perf_counter()
        got = jax.jit(case.kernel)(*args, **kwargs)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(case.twin)(*args, **kwargs)
        got, want = (jax.tree_util.tree_leaves(jax.block_until_ready(x))
                     for x in (got, want))
        check(len(got) == len(want), f"{case.name}: output count differs")
        worst = 0.0
        for i, (g, w) in enumerate(zip(got, want)):
            check(g.shape == w.shape and g.dtype == w.dtype,
                  f"{case.name}[{i}]: {g.shape} {g.dtype} vs twin "
                  f"{w.shape} {w.dtype}")
            g, w = (np.asarray(x.astype(jnp.float32)) for x in (g, w))
            check(np.isfinite(g).all(), f"{case.name}[{i}]: non-finite")
            check(np.abs(w).max() > 0, f"{case.name}[{i}]: twin is all 0")
            err = np.abs(g - w)
            if case.relative_to_max:
                err = err / np.abs(w).max()
            excess = err - (case.atol + case.rtol * np.abs(w))
            check(excess.max() <= 0,
                  f"{case.name}[{i}]: error {err.max():.3e} over "
                  f"atol={case.atol:g} rtol={case.rtol:g} ({case.why})")
            worst = max(worst, float(err.max()))
        errors[case.name] = worst
        log(f"kernel {case.name}: max error {worst:.3e} within "
            f"atol={case.atol:g} rtol={case.rtol:g} [{case.why}] "
            f"({time.perf_counter() - t0:.1f}s)")
    return errors


# ---------------------------------------------------------------------------


def main() -> None:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"jax {jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind!r} device_count={device['count']}")
    # the gate: JAX itself falls back to the CPU when it finds no chip
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (platform={dev.platform!r}); this smoke "
            "only means something on the chip")

    from apex_tpu.utils import platform

    cache_dir = platform.enable_compilation_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    log(f"compile cache at {cache_dir} ({entries} entries at start)")
    before = native_helpers()
    clock = CompileClock()
    devices = jax.devices()

    summary = {"jax": jax.__version__, "device": device}
    t0 = time.perf_counter()
    # kernels first, at one tensor-parallel rank's share of the heads: a
    # wrong kernel is named here, not met later as a bad loss or token
    summary["kernels"] = kernel_phase(FULL, FULL.heads // len(devices))
    summary["train"] = train_phase(FULL, devices)
    summary["serve"] = serve_phase(FULL, devices)
    summary["compile_events"] = clock.snapshot()
    summary["wall_s"] = round(time.perf_counter() - t0, 1)
    after = native_helpers()
    summary["native_helpers"] = {"found": before,
                                 "built": sorted(set(after) - set(before))}
    log(f"native helpers found in the tree: {before or 'none'}; built by "
        f"this run: {summary['native_helpers']['built'] or 'none'} "
        "(the GPT path loads none)")
    summary["claim"] = None
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

"""Cache-group decode attention microbenchmark: the two kernels timed alone.

Times ``paged_attention_decode`` over a cache group's flat arenas
(``apex_tpu/serving/paged_attention.py``, ``paged_decode_full`` and
``paged_decode_window``) at the shapes of the
``mimo-v2-flash.serve-long-answer`` cell: 64 slots, 64 query heads, q/k 192
beside v 128, blocks of 16, a table of 384 blocks, bfloat16; full attention
on 4 KV heads over histories uniform in 128..4200, window attention (128,
sinks) on 8 KV heads; and ``paged_decode_latent`` at the shapes of the
``deepseek-v2.serve-long-context`` cell: 64 slots, 128 heads over rows of
576 channels in 640 lanes (values the leading 512), blocks of 16, a table of
1,280 blocks, histories uniform in 4,096..14,336, on a table in runs of 8
adjacent blocks (as the cell's set-up allocates them) and on one scattered
over the arena.  A timing is one jitted program of ``--reps`` calls in
a chain (the step plan is built once in it, as in a model whose layers share
a table), so a call's time carries an eighth of the plan.  Beside each time:
the share of the HBM roofline that the rows read stand for (rows, not padded
pages: ``benchmark/kernels/paged_attention_groups.py``, and for the latent
kernel the larger of its FLOP and bytes as ``benchmark/kernels/
latent_attention.py`` counts them), us a grid step (the call over the steps
its plan has), the gauges the module records at trace time, and the largest
gap to the unfused twin at a smaller batch.  ``--pages`` sweeps the full
kernel's pages per key tile (the module's ``_MAX_FLAT_PAGES``; the window
and latent kernels' follow from their window and ``_MAX_LATENT_PAGES``).
No benchmark cell runs this: it is the sweep of ROADMAP S11, kept so that
it can be repeated.

    python examples/bench_paged_decode_groups.py --pages default,8,16,32
    python examples/bench_paged_decode_groups.py --tree .archive_check/parent
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES_PER_S = 819e9          # one v5e chip (benchmark/peaks.json)
FLOPS_PER_S = 197e12


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=ROOT,
                    help="the checkout whose apex_tpu is timed")
    ap.add_argument("--pages", default="default",
                    help="comma-separated pages per key tile of the full "
                         "kernel; 'default' is what the module derives")
    ap.add_argument("--kinds", default="full,window,latent")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        from apex_tpu.utils.platform import pin_cpu

        pin_cpu()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.observability.metrics import default_registry
    from apex_tpu.serving import paged_attention as pa

    on_tpu = jax.devices()[0].platform == "tpu"
    n, dk, dv, bs = 64, 192, 128, 16
    b, max_blocks, longest = (64, 384, 4200) if on_tpu else (3, 24, 300)
    reps, steps = (args.reps, args.steps) if on_tpu else (2, 1)
    kinds = {"full": dict(g=4, window=None, n_blocks=16384 if on_tpu else 96),
             "window": dict(g=8, window=128, n_blocks=17 * b)}
    rng = np.random.default_rng(0)

    def case(kind, b, max_blocks, lengths):
        """Arenas of random rows, a table whose live pages are distinct
        blocks (a window group's pages behind the window are handed back)."""
        g, window, n_blocks = (kinds[kind][x]
                               for x in ("g", "window", "n_blocks"))
        keys = jax.random.split(jax.random.PRNGKey(1), 4)
        q = jax.random.normal(keys[0], (b, n, dk), jnp.bfloat16)
        k = jax.random.normal(keys[1], (n_blocks, bs, g * dk), jnp.bfloat16)
        v = jax.random.normal(keys[2], (n_blocks, bs, g * dv), jnp.bfloat16)
        tables = np.zeros((b, max_blocks), np.int32)
        blocks = iter(rng.permutation(n_blocks))
        for i, length in enumerate(lengths):
            last = -(-int(length) // bs)
            first = 0 if window is None else max(int(length) - window, 0) // bs
            tables[i, :first] = -1 if window is not None else 0
            tables[i, first:last] = [next(blocks) for _ in range(first, last)]
        sinks = (None if window is None else
                 4.0 + jax.random.normal(keys[3], (n,), jnp.float32))
        return dict(args=(q, k, v, jnp.asarray(tables),
                          jnp.asarray(lengths, jnp.int32)),
                    kw=dict(kv_heads=g, window=window, sinks=sinks))

    # the latent group: heads, rows in lanes, values, table, histories
    ln, lanes, lv = 128, 640, 512
    lb, lmax, lhist, lblocks = ((64, 1280, (4096, 14336), 53248) if on_tpu
                                else (3, 24, (40, 300), 96))

    def latent_case(b, lengths, runs):
        """An arena of random rows (the padding lanes zero), a table whose
        live pages are distinct blocks: in runs of ``runs`` adjacent ones,
        the runs in random order (1: scattered)."""
        keys = jax.random.split(jax.random.PRNGKey(2), 2)
        pad = (jnp.arange(lanes) < 576).astype(jnp.bfloat16)
        q = jax.random.normal(keys[0], (b, ln, lanes), jnp.bfloat16) * pad
        rows = jax.random.normal(keys[1], (lblocks, bs, lanes),
                                 jnp.bfloat16) * pad
        order = (rng.permutation(lblocks // runs)[:, None] * runs
                 + np.arange(runs)).reshape(-1)
        tables = np.zeros((b, lmax), np.int32)
        blocks = iter(order)
        for i, length in enumerate(lengths):
            tables[i, :-(-int(length) // bs)] = [
                next(blocks) for _ in range(-(-int(length) // bs))]
        return dict(args=(q, rows, jnp.asarray(tables),
                          jnp.asarray(lengths, jnp.int32)),
                    kw=dict(v_dim=lv, scale=0.1147))

    def decode(q, k, v, tables, lengths, **kw):
        if v is None:
            return pa.paged_decode_latent(q, k, tables, lengths, **kw)
        return pa.paged_attention_decode(q, k, v, tables, lengths, **kw)

    def chain(q, k, v, tables, lengths, **kw):
        total = jnp.zeros((), jnp.float32)
        for i in range(reps):
            out = decode(q + jnp.asarray(i / 64, q.dtype), k, v, tables,
                         lengths, **kw)
            total = total + out[0, 0, 0].astype(jnp.float32)
        return total

    def steps_of(kind, lengths, window):
        """The grid steps of one call: a slot's live pages over the key
        tile's (the gauge), one at least."""
        pages = gauges(kind)[f"paged_decode/keys_per_step/{kind}"] // bs
        live = -(-np.asarray(lengths) // bs)
        if window is not None:
            live = live - np.maximum(np.asarray(lengths) - window, 0) // bs
        return int(np.maximum(-(-live // pages), 1).sum())

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps * 1e3

    def gauges(kind):
        snap = default_registry().snapshot()
        return {k: v for k, v in snap.items()
                if k.startswith("paged_decode/") and k.endswith(f"/{kind}")}

    def latent_rows():
        out = []
        lengths = rng.integers(lhist[0], lhist[1] + 1, lb)
        rows = int(lengths.sum())
        flops = rows * 2 * ln * (576 + lv)
        least_ms = max(flops / FLOPS_PER_S,
                       rows * 576 * 2 / HBM_BYTES_PER_S) * 1e3
        check = np.asarray([0, 1, 15, 16, 17, 300])
        for table, runs in (("runs_of_8", 8), ("scattered", 1)):
            c = latent_case(lb, lengths, runs)
            q, rows_, tables, lens = c["args"]
            ms = timed(jax.jit(lambda q, r, t, n: chain(
                q, r, None, t, n, **c["kw"])), q, rows_, tables, lens) / reps
            small = latent_case(len(check), check, runs)
            fused = jax.jit(lambda *xs: pa.paged_decode_latent(
                *xs, **small["kw"]))(*small["args"])
            twin = jax.jit(lambda *xs: pa.paged_decode_latent_unfused(
                *xs, **small["kw"]))(*small["args"])
            row = {"kind": "latent", "table": table,
                   "ms_a_call": round(ms, 4),
                   "us_a_step": round(
                       1e3 * ms / steps_of("latent", lengths, None), 4),
                   "rows": rows, "roofline_pct": round(100 * least_ms / ms, 2),
                   "gap_to_unfused": float(jnp.abs(
                       fused.astype(jnp.float32)
                       - twin.astype(jnp.float32)).max()),
                   "gauges": gauges("latent")}
            out.append(row)
            print(json.dumps(row), flush=True)
        return out

    results = []
    lengths = rng.integers(128, longest + 1, b)
    check = np.asarray([0, 1, 15, 16, 17, 127, 128, 129, 143, 144, 145,
                        511, 512, 513, 1000, 1536][:max(b // 4, 3)])
    kinds_asked = args.kinds.split(",")
    for pages in args.pages.split(","):
        if pages != "default":
            pa._MAX_FLAT_PAGES = int(pages)
        for kind in kinds_asked:
            if kind != "full" and pages != args.pages.split(",")[0]:
                continue
            if kind == "latent":
                results += latent_rows()
                continue
            c = case(kind, b, max_blocks, lengths)
            ms = timed(jax.jit(lambda *xs: chain(*xs, **c["kw"])),
                       *c["args"]) / reps
            g, window = kinds[kind]["g"], kinds[kind]["window"]
            rows = int(np.minimum(lengths, window or longest).sum())
            least_ms = rows * g * (dk + dv) * 2 / HBM_BYTES_PER_S * 1e3
            small = case(kind, len(check), min(max_blocks, 96), check)
            fused = jax.jit(lambda *xs: pa.paged_attention_decode(
                *xs, **small["kw"]))(*small["args"])
            twin = jax.jit(lambda *xs: pa.paged_attention_decode_unfused(
                *xs, **small["kw"]))(*small["args"])
            row = {"kind": kind, "pages": pages, "ms_a_call": round(ms, 4),
                   "us_a_step": round(
                       1e3 * ms / steps_of(kind, lengths, window), 4),
                   "rows": rows, "roofline_pct": round(100 * least_ms / ms, 2),
                   "gap_to_unfused": float(jnp.abs(
                       fused.astype(jnp.float32)
                       - twin.astype(jnp.float32)).max()),
                   "gauges": gauges(kind)}
            results.append(row)
            print(json.dumps(row), flush=True)

    print(json.dumps({
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "tree": os.path.abspath(args.tree), "reps": reps, "steps": steps,
        "results": results}))


if __name__ == "__main__":
    main()

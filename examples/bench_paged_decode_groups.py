"""Cache-group decode attention microbenchmark: the two kernels timed alone.

Times ``paged_attention_decode`` over a cache group's flat arenas
(``apex_tpu/serving/paged_attention.py``, ``paged_decode_full`` and
``paged_decode_window``) at the shapes of the
``mimo-v2-flash.serve-long-answer`` cell: 64 slots, 64 query heads, q/k 192
beside v 128, blocks of 16, a table of 384 blocks, bfloat16; full attention
on 4 KV heads over histories uniform in 128..4200, window attention (128,
sinks) on 8 KV heads.  A timing is one jitted program of ``--reps`` calls in
a chain (the step plan is built once in it, as in a model whose layers share
a table), so a call's time carries an eighth of the plan.  Beside each time:
the share of the HBM roofline that the rows read stand for (rows, not padded
pages: ``benchmark/kernels/paged_attention_groups.py``), the gauges the
module records at trace time, and the largest gap to the unfused twin at a
smaller batch.  ``--pages`` sweeps the full kernel's pages per key tile (the
module's ``_MAX_FLAT_PAGES``; the window kernel's follow from its window).
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=ROOT,
                    help="the checkout whose apex_tpu is timed")
    ap.add_argument("--pages", default="default",
                    help="comma-separated pages per key tile of the full "
                         "kernel; 'default' is what the module derives")
    ap.add_argument("--kinds", default="full,window")
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

    def chain(q, k, v, tables, lengths, **kw):
        total = jnp.zeros((), jnp.float32)
        for i in range(reps):
            out = pa.paged_attention_decode(
                q + jnp.asarray(i / 64, q.dtype), k, v, tables, lengths, **kw)
            total = total + out[0, 0, 0].astype(jnp.float32)
        return total

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps * 1e3

    def gauges():
        snap = default_registry().snapshot()
        return {k: v for k, v in snap.items()
                if k.startswith("paged_decode/")}

    results = []
    lengths = rng.integers(128, longest + 1, b)
    check = np.asarray([0, 1, 15, 16, 17, 127, 128, 129, 143, 144, 145,
                        511, 512, 513, 1000, 1536][:max(b // 4, 3)])
    for pages in args.pages.split(","):
        if pages != "default":
            pa._MAX_FLAT_PAGES = int(pages)
        for kind in args.kinds.split(","):
            if kind == "window" and pages != args.pages.split(",")[0]:
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
                   "rows": rows, "roofline_pct": round(100 * least_ms / ms, 2),
                   "gap_to_unfused": float(jnp.abs(
                       fused.astype(jnp.float32)
                       - twin.astype(jnp.float32)).max()),
                   "gauges": gauges()}
            results.append(row)
            print(json.dumps(row), flush=True)

    print(json.dumps({
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "tree": os.path.abspath(args.tree), "reps": reps, "steps": steps,
        "results": results}))


if __name__ == "__main__":
    main()

"""Flash attention microbenchmark: the three Pallas calls timed apart.

Times the forward call, ``dq_chunk`` and ``dkv_chunk`` of
``apex_tpu/ops/flash_attention.py`` alone, causal, at the shape of a
``gpt2-medium.train`` microbatch (``6, 16, 1024, 64``) and at head 128 /
sequence 2048 (``4, 8, 2048, 128``), for each pair of fetched-tile sizes
asked for, and beside them the unfused BMM+softmax+BMM core (what
``CoreAttention`` runs when ``use_flash_attention=False``), forward and
backward in one.  It prints the tiling gauges the module records at trace
time.  No benchmark cell runs this: it is the block sweep of ROADMAP S1,
kept so that the sweep can be repeated.

    python examples/bench_flash_attention.py                 # defaults
    python examples/bench_flash_attention.py --blocks 256x512,1024x1024
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--blocks", default="default",
                    help="comma-separated BLOCK_QxBLOCK_K pairs; 'default' "
                         "is what resolve_default_blocks gives")
    ap.add_argument("--no-xla", action="store_true",
                    help="skip the unfused comparison")
    args = ap.parse_args()

    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        from apex_tpu.utils.platform import pin_cpu

        pin_cpu()
    import jax
    import jax.numpy as jnp

    from apex_tpu.observability.metrics import default_registry
    from apex_tpu.ops.flash_attention import (
        dkv_chunk,
        dq_chunk,
        flash_attention_with_lse,
        resolve_default_blocks,
    )

    on_tpu = jax.devices()[0].platform == "tpu"
    dtype = jnp.dtype(args.dtype)
    shapes = ([(6, 16, 1024, 64), (4, 8, 2048, 128)]
              if on_tpu else [(1, 2, 256, 64)])
    steps = args.steps if on_tpu else 2
    pairs = [resolve_default_blocks() if p == "default"
             else tuple(int(x) for x in p.split("x"))
             for p in args.blocks.split(",")]

    def timed(fn, *xs):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*xs))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*xs)
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / steps * 1e3, 4)

    def gauges():
        snap = default_registry().snapshot()
        return {k: v for k, v in snap.items() if k.startswith("flash/")}

    def xla_attn(q, k, v):
        d = q.shape[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
        s = s / (d ** 0.5)
        sq, sk = s.shape[-2:]
        mask = jnp.tril(jnp.ones((sq, sk), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    results = []
    for shape in shapes:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, do = (jax.random.normal(kk, shape, dtype) for kk in ks)
        out, lse = flash_attention_with_lse(q, k, v, True)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)
        t_xla = None
        if not args.no_xla:
            try:
                t_xla = timed(jax.grad(
                    lambda q, k, v: jnp.sum(
                        xla_attn(q, k, v).astype(jnp.float32) ** 2),
                    argnums=(0, 1, 2)), q, k, v)
            except Exception as e:  # O(s^2) scores can OOM at long seqlens
                print(f"xla path failed at {shape}: {e!r}", file=sys.stderr)
        for bq, bk in pairs:
            kw = dict(causal=True, block_q=bq, block_k=bk)
            row = {
                "shape": list(shape), "block_q": bq, "block_k": bk,
                "fwd_ms": timed(lambda q, k, v: flash_attention_with_lse(
                    q, k, v, True, None, bq, bk), q, k, v),
                "dq_ms": timed(lambda *xs: dq_chunk(*xs, **kw),
                               q, k, v, do, lse, delta),
                "dkv_ms": timed(lambda *xs: dkv_chunk(*xs, **kw),
                                q, k, v, do, lse, delta),
                "xla_fwd_bwd_ms": t_xla,
                "gauges": gauges(),
            }
            # a train step runs the forward twice (jax.checkpoint)
            row["fwd_x2_dq_dkv_ms"] = round(
                2 * row["fwd_ms"] + row["dq_ms"] + row["dkv_ms"], 4)
            results.append(row)
            print(json.dumps(row), flush=True)

    print(json.dumps({
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "dtype": str(dtype),
        "steps": steps,
        "results": results,
    }))


if __name__ == "__main__":
    main()

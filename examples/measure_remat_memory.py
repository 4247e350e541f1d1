"""Grouped-remat memory measurement on the real chip (VERDICT r2 item 9).

Round 2's 4-10x live-memory cut for ``pipeline_apply(remat_ticks=...)``
was measured only on the virtual CPU mesh
(``tests/test_pipeline_perf.py::test_grouped_remat_cuts_live_memory``).
This harness compiles the same interleaved forward+backward program **for
the attached TPU** (pp=1 on a single chip — the rotation scan, virtual
stages, and remat grouping are all still present) and records the
compiled executable's XLA memory analysis.  Compile-only: nothing runs.

    python examples/measure_remat_memory.py            # default shapes
    python examples/measure_remat_memory.py --width 1024 --m 64

Prints one JSON record, which carries its ``platform``: a compile for the
CPU says nothing about the chip's memory (not measured there; ROADMAP R10).
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--width", type=int, default=1024)
    p.add_argument("--mb", type=int, default=8)
    p.add_argument("--vpp", type=int, default=8)
    p.add_argument("--m", type=int, default=32)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        from apex_tpu.utils.platform import pin_cpu

        pin_cpu()

    from apex_tpu import parallel
    from apex_tpu.transformer.pipeline_parallel import stack_stage_params
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_pipelining_with_interleaving as fb_interleaved,
    )

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    width, mb, vpp, m = args.width, args.mb, args.vpp, args.m
    if not on_tpu:
        width, m = min(width, 128), min(m, 8)

    parallel.initialize_model_parallel(
        pipeline_model_parallel_size=1, devices=jax.devices()[:1])

    def stage_fn(params, x):
        h = jnp.tanh(x @ params["w1"])
        return h @ params["w2"] + x

    ks = jax.random.split(jax.random.PRNGKey(0), vpp)
    stages = [
        {"w1": jax.random.normal(k, (width, width)) * 0.1,
         "w2": jax.random.normal(jax.random.fold_in(k, 1),
                                 (width, width)) * 0.1}
        for k in ks
    ]
    stacked = stack_stage_params(stages)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, width))
    tgt = jax.random.normal(jax.random.PRNGKey(2), (m, mb, width))

    def loss_fn(o, t):
        return jnp.sum((o - t) ** 2)

    def analyze(remat_ticks):
        def fb(params):
            _, grads = fb_interleaved(
                stage_fn, loss_fn, params, x, tgt, num_chunks=vpp,
                remat_ticks=remat_ticks)
            return grads

        t0 = time.perf_counter()
        ma = jax.jit(fb).lower(stacked).compile().memory_analysis()
        return {
            "temp_bytes": int(ma.temp_size_in_bytes),
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "compile_s": round(time.perf_counter() - t0, 1),
        }

    flat = analyze(None)
    grouped = analyze(True)
    rec = {
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "width": width, "mb": mb, "vpp": vpp, "m": m,
        "flat": flat, "grouped": grouped,
        "temp_cut": round(flat["temp_bytes"]
                          / max(grouped["temp_bytes"], 1), 2),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(rec))


if __name__ == "__main__":
    main()

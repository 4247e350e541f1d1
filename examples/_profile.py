"""Shared jax.profiler capture harness for the profile_* examples.

One place for the backend bring-up (CPU pin honor, persistent compile
cache), the warm-compile convention, the timestamped
``bench_results/profiles/<workload>_<stamp>/`` trace layout, and the
``summary.jsonl`` record schema (every row carries ``workload`` so
consumers never field-sniff).
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def init_bench_backend():
    """Backend + bench module with the tuning harnesses' conventions.
    Returns ``(jax, bench, dev, on_tpu)``."""
    import jax

    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        from apex_tpu.utils.platform import pin_cpu

        pin_cpu()

    import bench
    from apex_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()
    dev = jax.devices()[0]
    return jax, bench, dev, dev.platform == "tpu"


def profile_capture(workload: str, jax, bench, step_fn, st0, steps: int,
                    record_fields: dict) -> dict:
    """Warm-compile ``step_fn`` (two calls), trace ``steps`` timed steps,
    append the summary record, and return it.

    ``record_fields``: workload-specific fields merged into the record
    (callables receive the measured ``dt`` — e.g. MFU derivations)."""
    st = step_fn(*st0)
    st = step_fn(*st)
    jax.block_until_ready(st)

    stamp = time.strftime("%Y%m%d_%H%M%S")
    trace_dir = os.path.join(REPO, "bench_results", "profiles",
                             f"{workload}_{stamp}")
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        dt, st = bench._timeit(jax, step_fn, st, steps)

    dev = jax.devices()[0]
    rec = {
        "workload": workload,
        "trace_dir": os.path.relpath(trace_dir, REPO),
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "steps": steps,
        "step_ms": round(dt / steps * 1e3, 2),
        "ts": stamp,
    }
    for k, v in record_fields.items():
        rec[k] = v(dt) if callable(v) else v
    out = os.path.join(REPO, "bench_results", "profiles", "summary.jsonl")
    with open(out, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(json.dumps(rec))
    return rec

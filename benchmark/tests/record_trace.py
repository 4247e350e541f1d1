"""Records the small device trace that ``test_xplane.py`` checks the trace
reduction against, and prints what a trace of this chip looks like.

    chiprun --chips 1 -- python benchmark/tests/record_trace.py

Three steps of a small program (two matmuls and the flash attention kernel
at a small shape), each inside a ``bench/probe.step`` host span, with a
20 ms sleep inside a ``bench/probe.sleep`` span after each, so that the
idle gaps are known; all inside the ``bench/window`` span.  Writes
``chiprun_out/probe.xplane.pb`` and ``chiprun_out/probe_trace.txt``.
"""

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


def main():
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.flash_attention import flash_attention

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)

    @jax.jit
    def step(x, w, q):
        y = jnp.tanh(x @ w) @ w.T
        a = flash_attention(q, q, q, causal=True)
        return y, a

    k = jax.random.PRNGKey(0)
    x = jax.random.normal(k, (1024, 1024), jnp.bfloat16)
    w = jax.random.normal(k, (1024, 1024), jnp.bfloat16)
    q = jax.random.normal(k, (2, 4, 512, 64), jnp.bfloat16)
    jax.block_until_ready(step(x, w, q))

    from lib import xplane

    tdir = os.path.join(out, "probe_trace")
    shutil.rmtree(tdir, ignore_errors=True)
    t0 = time.perf_counter()
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench/probe.step"):
                jax.block_until_ready(step(x, w, q))
            with jax.profiler.TraceAnnotation("bench/probe.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    print("traced", time.perf_counter() - t0, "s")
    (path,) = glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb"))
    shutil.copy(path, os.path.join(out, "probe.xplane.pb"))
    shutil.rmtree(tdir)

    data = jax.profiler.ProfileData.from_file(os.path.join(out, "probe.xplane.pb"))
    lines = []
    for plane in data.planes:
        lines.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            lines.append(f"  LINE {line.name!r} events={len(events)}")
            for ev in events[:12]:
                stats = {k: (str(v)[:80]) for k, v in ev.stats}
                lines.append(f"    {ev.name!r} start={ev.start_ns} "
                             f"dur={ev.duration_ns} stats={stats}")
    with open(os.path.join(out, "probe_trace.txt"), "w") as f:
        f.write("\n".join(lines))
    print("\n".join(lines[:60]))
    print(os.path.getsize(os.path.join(out, "probe.xplane.pb")), "bytes")


if __name__ == "__main__":
    main()

"""Flash-attention block-size sweep on hardware (VERDICT r2 item 3).

Round 2 shipped DEFAULT_BLOCK_Q=256 / DEFAULT_BLOCK_K=512 unswept; GPT-124M
MFU stalled at 0.436 while BERT hit 0.488.  This harness times the *actual
flagship train step* (the ``gpt_flash`` bench config) across a
(block_q, block_k) grid, each point in its own subprocess (a hang or OOM
cannot kill the sweep) with the persistent compilation cache on.

    python examples/tune_flash_blocks.py                 # full grid
    python examples/tune_flash_blocks.py --seq 2048      # long-seq grid
    python examples/tune_flash_blocks.py --one 256 512   # single point

Results append to ``bench_results/flash_block_sweep.jsonl``.  A TPU
sweep at the flagship seq (1024) auto-lands its winner in
``bench_results/flash_blocks_tuned.json``, which the kernel consults
lazily at first call and adopts only on a matching ``device_kind`` —
no manual default-picking needed (env overrides still win).
"""

import argparse
import itertools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "bench_results", "flash_block_sweep.jsonl")
if REPO not in sys.path:  # runnable as `python examples/tune_flash_blocks.py`
    sys.path.insert(0, REPO)

from examples._sweep import run_sweep  # noqa: E402

# jax's reference TPU flash kernel defaults to 128/128 (BlockSizes.
# get_default, with an open TODO for a real heuristic); cover that corner
# of the space as well as the larger tiles our defaults use.
GRID_Q = (128, 256, 512)
GRID_K = (128, 256, 512, 1024)


def run_point(block_q: int, block_k: int, seq: int, steps: int) -> None:
    """Child: one grid point — compile + time the gpt_flash train step
    (the exact workload of ``bench.gpt_flash_setup``, so sweep results
    transfer 1:1 to the bench/profile numbers)."""
    import jax

    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        from apex_tpu.utils.platform import pin_cpu

        pin_cpu()

    import bench
    from apex_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu:  # CPU smoke: tiny shapes, still exercises the plumbing
        steps = min(steps, 2)

    cfg, step, st, batch, seq, n_params = bench.gpt_flash_setup(
        jax, on_tpu, seq=seq)

    t0 = time.perf_counter()
    st = step(*st)
    jax.block_until_ready(st)
    compile_s = time.perf_counter() - t0

    dt, _ = bench._timeit(jax, step, st, steps)

    tps = batch * seq * steps / dt
    flops = bench._lm_train_flops(cfg, n_params, batch, seq) * steps / dt
    rec = {
        "block_q": block_q, "block_k": block_k, "seq": seq,
        "batch": batch, "tokens_per_sec": round(tps, 1),
        "mfu": round(flops / bench._peak_flops(dev), 4) if on_tpu else None,
        "compile_s": round(compile_s, 1),
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(rec), flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--one", nargs=2, type=int, default=None,
                   metavar=("BLOCK_Q", "BLOCK_K"))
    p.add_argument("--timeout", type=float, default=420.0)
    args = p.parse_args()

    if args.one:
        grid = [tuple(args.one)]
    else:
        grid = list(itertools.product(GRID_Q, GRID_K))

    best, records = run_sweep(
        grid,
        env_for=lambda p: {"APEX_TPU_FLASH_BLOCK_Q": str(p[0]),
                           "APEX_TPU_FLASH_BLOCK_K": str(p[1])},
        child_args_for=lambda p: [
            os.path.abspath(__file__), "--child",
            str(p[0]), str(p[1]), str(args.seq), str(args.steps)],
        label_for=lambda p: (
            f"block_q={p[0]} block_k={p[1]} seq={args.seq}"),
        out_path=OUT, timeout=args.timeout)
    if best:
        print(json.dumps({"best": best}))
        # Land the winner automatically: a TPU sweep at the flagship seq
        # (1024) writes the tuned-defaults file that
        # apex_tpu.ops.flash_attention consults lazily at first kernel
        # call, gated on matching device_kind (env overrides still win).
        # >1 successful point required: a lone survivor (others
        # hung/OOMed) is no comparison.
        if (best["platform"] == "tpu" and args.seq == 1024
                and not args.one and len(records) > 1):
            tuned_path = os.path.join(REPO, "bench_results",
                                      "flash_blocks_tuned.json")
            with open(tuned_path, "w") as f:
                json.dump(best, f)
            print(f"tuned defaults written to {tuned_path}",
                  file=sys.stderr, flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        run_point(int(sys.argv[2]), int(sys.argv[3]),
                  int(sys.argv[4]), int(sys.argv[5]))
    else:
        main()

"""Per-chip batch-size sweep for the flagship gpt_flash workload.

The r4 first TPU window measured gpt_flash MFU 0.4155 at the shipped
batch 8 while BERT-large crossed 0.5059 on the same stack — batch is the
one shape knob the block sweep (tune_flash_blocks.py) does not touch,
and at 124M params the activation memory for batch 16/32 is far inside
a v5e's HBM.  This harness times the real train step
(``bench.gpt_flash_setup`` via ``APEX_TPU_GPT_BATCH``) across a batch
grid, each point in its own subprocess with the persistent compile
cache on.

    python examples/tune_gpt_batch.py                # 8, 16, 32
    python examples/tune_gpt_batch.py --batches 16 48 --seq 8192

Results append to ``bench_results/gpt_batch_sweep.jsonl``; each record
carries both the requested ``base_batch`` (the knob) and the effective
``batch`` (above seq 1024 the workload token-budget-rescales it).  MFU
is batch-honest, so a better point justifies bumping the shipped
default *with* the recorded sweep as provenance — the policy the
``APEX_TPU_GPT_BATCH`` comment in bench.py states.

Off-TPU the knob is inert (``gpt_flash_setup`` pins tiny CPU smoke
shapes), so the driver runs a single smoke point and says so.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "bench_results", "gpt_batch_sweep.jsonl")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from examples._sweep import run_sweep  # noqa: E402


def run_point(base_batch: int, seq: int, steps: int) -> None:
    """Child: one batch point of the exact gpt_flash workload.  The knob
    is set here too, so a hand-run child honors its argv."""
    os.environ["APEX_TPU_GPT_BATCH"] = str(base_batch)

    import jax

    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        from apex_tpu.utils.platform import pin_cpu

        pin_cpu()

    import bench
    from apex_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu:
        steps = min(steps, 2)

    cfg, step, st, got_batch, seq, n_params = bench.gpt_flash_setup(
        jax, on_tpu, seq=seq)

    t0 = time.perf_counter()
    st = step(*st)
    jax.block_until_ready(st)
    compile_s = time.perf_counter() - t0

    dt, _ = bench._timeit(jax, step, st, steps)
    tps = got_batch * seq * steps / dt
    flops = bench._lm_train_flops(cfg, n_params, got_batch, seq) * steps / dt
    rec = {
        "base_batch": base_batch, "batch": got_batch, "seq": seq,
        "tokens_per_sec": round(tps, 1),
        "mfu": round(flops / bench._peak_flops(dev), 4) if on_tpu else None,
        "compile_s": round(compile_s, 1),
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", ""),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(rec), flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batches", nargs="+", type=int, default=[8, 16, 32])
    p.add_argument("--seq", type=int, default=1024)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--timeout", type=float, default=600.0)
    p.add_argument("--no-land", action="store_true",
                   help="exploratory sweep: never write "
                        "bench_results/gpt_batch_tuned.json (by default a "
                        "TPU sweep at seq 1024 with >1 surviving point "
                        "auto-lands its winner as the bench default)")
    args = p.parse_args()

    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        print("CPU pin detected: the batch knob is inert off-TPU "
              "(gpt_flash_setup uses fixed smoke shapes); running a "
              "single smoke point", file=sys.stderr, flush=True)
        batches = args.batches[:1]
    else:
        # dedupe points whose *effective* batch collapses (above seq 1024
        # the workload rescales base*1024//seq)
        batches, seen = [], set()
        for b in args.batches:
            eff = b if args.seq <= 1024 else max(1, b * 1024 // args.seq)
            if eff in seen:
                print(f"--- batch={b}: effective batch {eff} duplicates "
                      f"an earlier point; skipped",
                      file=sys.stderr, flush=True)
                continue
            seen.add(eff)
            batches.append(b)

    def eff(b):
        return b if args.seq <= 1024 else max(1, b * 1024 // args.seq)

    best, records = run_sweep(
        batches,
        env_for=lambda b: {"APEX_TPU_GPT_BATCH": str(b)},
        child_args_for=lambda b: [
            os.path.abspath(__file__), "--child",
            str(b), str(args.seq), str(args.steps)],
        label_for=lambda b: (
            f"batch={b} seq={args.seq}" if eff(b) == b
            else f"batch={b} (effective {eff(b)}) seq={args.seq}"),
        out_path=OUT, timeout=args.timeout)
    if best:
        print(json.dumps({"best": best}))
        # Auto-land the winner (flash-blocks pattern): a TPU sweep at the
        # flagship seq writes the tuned file bench.gpt_flash_setup
        # consults, gated on device_kind (env override still wins) — so
        # an unattended capture upgrades the bench batch with the sweep
        # itself as recorded provenance.  Gated on >1 *successful* point:
        # a lone survivor (others hung/OOMed) is no comparison.
        if (best["platform"] == "tpu" and args.seq == 1024
                and len(records) > 1 and not args.no_land):
            tuned = os.path.join(REPO, "bench_results",
                                 "gpt_batch_tuned.json")
            with open(tuned, "w") as f:
                json.dump(best, f)
            print(f"tuned batch written to {tuned}",
                  file=sys.stderr, flush=True)


if __name__ == "__main__":
    if len(sys.argv) >= 5 and sys.argv[1] == "--child":
        run_point(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    else:
        main()

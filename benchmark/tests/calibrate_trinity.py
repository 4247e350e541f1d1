"""Reads, on the chip and at the cell's own size, the numbers that
``correct`` compares in ``trinity-mini.train-8k``, by ``tests/calibrate.py``'s
method: the program's over the seeds (the lower reading of each limit) and,
on the first ``--control-seeds`` of them, the control's and the planted
faults' (the upper).

    chiprun --chips 1 --timeout 3000 -- python benchmark/tests/calibrate_trinity.py \
        --seeds 6 --control-seeds 2 --seconds 2

One process: every seed is a whole run of the cell's driver (set-up,
warm-up, a window of ``--seconds``, the reference), so the seeds it passes
count as runs with ``correct`` true.  Each fault of ``faults_trinity.FAULTS``
is planted in the program and the driver run again on the same seed.  The
control is the plain reference with every layer GEMM's operands rounded to
fp8 (e4m3 forward, e5m2 for the gradient that enters the backward GEMMs),
the nearest precision below the configuration's bfloat16, put in the
program's place: its losses, its first gradient's and its change's leaf
norms, and its own expert choices for the reference to follow.  Every
variant's numbers go through the harness's ``check.training`` and
``check.passed`` with the cell's limits.  Writes
``chiprun_out/calibrate_<workload>_<first seed>_<n>.json``.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)
sys.path.insert(2, os.path.join(HERE, "tests"))

import faults_trinity            # noqa: E402
import run as harness            # noqa: E402
from drivers import trinity_program  # noqa: E402
from lib import check            # noqa: E402


def numbers(result):
    return dict({k: v["value"] for k, v in result["compared"].items()},
                correct=result["correct"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="trinity-mini.train-8k")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--first-seed", type=int, default=2147483000)
    ap.add_argument("--faults", default=",".join(faults_trinity.FAULTS))
    ap.add_argument("--skip-sound", action="store_true",
                    help="faults and control only (a second call that "
                         "finishes what one call's time limit cut)")
    ap.add_argument("--skip-control", action="store_true")
    ap.add_argument("--presets", default=None,
                    help="a directory of presets in place of the "
                         "benchmark's own cells: a rehearsal on the CPU")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    harness.enable_compile_cache(jax)
    where = ({} if args.presets is None
             else {"root": args.presets, "data": args.presets})
    bench = harness.load_json(args.presets or ROOT, "BENCHMARK.json")
    bench.setdefault("end_to_end", [])
    bench.setdefault("per_layer", [])
    devices = jax.devices()[:1]
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i

        def run_once():
            cell = harness.Cell(bench, args.workload, seed, args.seconds,
                                False, devices, **where)
            return cell, harness.run_cell(cell)

        t0 = time.perf_counter()
        row = {"seed": seed}
        if args.skip_sound:
            cell = harness.Cell(bench, args.workload, seed, args.seconds,
                                False, devices, **where)
        else:
            cell, result = run_once()
            row.update(
                program=numbers(result),
                metrics={k: v["value"] for k, v in result["metrics"].items()},
                observed=result["observed"],
                memory_peak_bytes=result["device"]["memory_peak_bytes"],
                wall_s=time.perf_counter() - t0)
            print(json.dumps(row), flush=True)
        if i < args.control_seeds:
            for name in filter(None, args.faults.split(",")):
                with faults_trinity.FAULTS[name]():
                    row["fault_" + name] = numbers(run_once()[1])
                print(json.dumps({name: row["fault_" + name]}), flush=True)
        if i < args.control_seeds and not args.skip_control:
            # the control in the program's place
            driver, ref, traffic = cell.driver, cell.reference, cell.traffic
            sz = ref.sizes_of(cell.config)
            key = trinity_program.seed_key(seed)
            batches = [jnp.asarray(driver.batch_of(seed, s, traffic,
                                                   sz["vocab"]))
                       for s in range(traffic["checked_steps"])]
            low = ref.train(key, batches, sz, traffic["adam"], quant=ref.FP8)
            want = ref.train(key, batches, sz, traffic["adam"],
                             chosen=low["own"])
            compared = check.training(low, want, cell.limits)
            compared["router_choice_margin"] = (
                want["router_choice_margin"],
                cell.limits["router_choice_margin"])
            row["control_fp8"] = dict(
                {k: v for k, (v, _) in compared.items()},
                correct=check.passed(compared),
                loss_gaps=check.loss_gaps(low, want),
                choices_flipped=want["choices_flipped"])
        rows.append(row)
        print(json.dumps(row), flush=True)

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    name = f"calibrate_{args.workload}_{args.first_seed}_{len(args.faults)}.json"
    with open(os.path.join(out, name), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()

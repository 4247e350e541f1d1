"""Reads, on the chip and at a cell's own size, the numbers that ``correct``
compares: the program's over many seeds (the lower reading of each limit)
and, on the first few, the control's and the planted faults' (the upper).

    chiprun --chips 1 -- python benchmark/tests/calibrate.py \
        --workload gpt2-medium.train --seeds 12 --control-seeds 3 --seconds 2

One process: every seed is a whole run of the cell's driver (set-up, warm-up,
a window of ``--seconds``, the reference), so the seeds it passes count as
runs with ``correct`` true.  The control is the plain reference computed
with its layer GEMMs' operands rounded to fp8 (e4m3, per-tensor scaled),
the nearest precision below the configuration's bfloat16, put in the
program's place.  ``half_batch`` is planted in the reference put in the
program's place; ``--program-faults`` plants faults in the program itself.
Writes ``chiprun_out/calibrate_<workload>.json``.
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

import faults                    # noqa: E402
import run as harness            # noqa: E402
from lib import check            # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--first-seed", type=int, default=2147483000)
    ap.add_argument("--program-faults", default="",
                    help="faults of tests/faults.py, comma separated, to "
                         "plant in the program on the control seeds: one "
                         "more run of the driver each")
    ap.add_argument("--presets", default=None,
                    help="a directory of presets (tests/presets) in place of "
                         "the benchmark's own cells: a rehearsal on the CPU")
    args = ap.parse_args()

    import jax
    import numpy as np

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    where = {}
    if args.presets:
        presets = harness.load_json(args.presets, "BENCHMARK.json")
        bench = dict(bench, configs=presets["configs"],
                     workloads=presets["workloads"])
        where = {"root": args.presets, "data": args.presets}
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    devices = jax.devices()[:entry["chips"]]
    harness.enable_compile_cache(jax)
    rows = []

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        cell = harness.Cell(bench, args.workload, seed, args.seconds, False,
                            devices, **where)
        ref_mod = cell.reference
        with_control = i < args.control_seeds
        row = {"seed": seed}

        # -- training: the reference's steps again in fp8 and with a fault
        real_train, real_training = ref_mod.train, check.training

        def train(w0, batches, sz, hyper, rows_per_block, **kw):
            ref = real_train(w0, batches, sz, hyper, rows_per_block, **kw)
            row["reference_leaves"] = ref
            if with_control:
                ctl = real_train(w0, batches, sz, hyper, rows_per_block,
                                 quant=ref_mod.FP8)
                row["control_leaves"] = ctl
                row["control"] = {k: v for k, (v, _) in real_training(
                    ctl, ref, cell.limits).items()}
                half = real_train(w0, batches, sz, hyper, rows_per_block,
                                  fault="half_batch")
                row["fault_half_batch"] = {
                    k: v for k, (v, _) in real_training(
                        half, ref, cell.limits).items()}
            return ref

        # -- serving: the fp8 forward pass's first tokens at the same places
        real_gaps = ref_mod.served_token_gaps

        def gaps(w, prompt, served, sz, pad):
            out = real_gaps(w, prompt, served, sz, pad)
            if with_control:
                ctl = ref_mod.control_token_gaps(w, prompt, served, sz, pad,
                                                 ref_mod.FP8)
                row.setdefault("control_gaps", []).append(float(np.max(ctl)))
                row.setdefault("control_flips", []).append(
                    int(np.sum(np.asarray(ctl) > 0)))
            row.setdefault("served_gaps", []).append(float(np.max(out)))
            row.setdefault("served_flips", []).append(
                int(np.sum(np.asarray(out) > 0)))
            row.setdefault("served_tokens", []).append(len(served))
            return out

        real_logits = ref_mod.last_logits

        def logits(w, sequences, sz, pad):
            out = real_logits(w, sequences, sz, pad)
            if with_control:
                row["control_logit_rms_gap"] = check.logit_rms_gap(
                    real_logits(w, sequences, sz, pad, ref_mod.FP8), out)
            return out

        def training(seen, ref, limits):
            row["program_leaves"] = seen
            return real_training(seen, ref, limits)

        ref_mod.train, ref_mod.served_token_gaps = train, gaps
        ref_mod.last_logits, check.training = logits, training
        t0 = time.perf_counter()
        try:
            result = harness.run_cell(cell)
        finally:
            ref_mod.train, ref_mod.served_token_gaps = real_train, real_gaps
            ref_mod.last_logits, check.training = real_logits, real_training
        row["wall_s"] = time.perf_counter() - t0
        row["correct"] = result["correct"]
        row["program"] = {k: v["value"] for k, v in result["compared"].items()}
        row["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
        row["observed"] = result["observed"]
        for fault in filter(None, args.program_faults.split(",")):
            if with_control:
                again = harness.Cell(bench, args.workload, seed, args.seconds,
                                     False, devices, **where)
                with faults.FAULTS[fault]():
                    broken = harness.run_cell(again)
                row["fault_" + fault] = {
                    k: v["value"] for k, v in broken["compared"].items()}
                row["fault_" + fault]["loss_gaps"] = broken["observed"].get(
                    "loss_gaps")
        rows.append(row)
        print(json.dumps(row), flush=True)

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"calibrate_{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()

"""Reads, on the chip and at the cell's own size, the numbers that
``correct`` compares in ``deepseek-v2.serve-long-context``, by
``tests/calibrate_mimo.py``'s method, which this repeats for another
reference's signatures and faults: the program's over the seeds (the lower
reading of each limit) and, on the first ``--control-seeds`` of them, the
control's and the planted faults' (the upper).

    chiprun --chips 1 -- python benchmark/tests/calibrate_deepseek.py \
        --seeds 6 --control-seeds 1 --altered-seeds 1 --seconds 3

One process: every seed is a whole run of the cell's driver (set-up,
warm-up, a window of ``--seconds``, the reference), so the seeds it passes
count as runs with ``correct`` true.  The control is the plain reference
with its layer GEMMs' operands rounded to fp8 (e4m3, per-tensor scaled),
the nearest precision below the configuration's bfloat16, put in the
program's place on the sequences the run checked: its logits, its own
expert choices for the reference to follow, its first choices where the
program served a token.  Each fault of ``FAULTS`` is the reference with
that fault planted (``fault=``), put in the program's place likewise.
Each variant's numbers go through the driver's own ``numbers`` and
``held`` and the harness's ``check.passed`` with the cell's limits.  A
variant costs two passes a sequence of ten thousand tokens, so the variants
read the first ``--control-rows`` of the checked rows (the longest history
among them) and not all.  On the
first ``--altered-seeds`` the driver runs once more with a token altered
where the engine emits it (``tests/faults.token_altered``).  Writes
``chiprun_out/calibrate_<workload>.json``.
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
from drivers import serve_hybrid  # noqa: E402
from lib import check            # noqa: E402

FAULTS = ("rope_term_left_out", "yarn_scale_left_out",
          "latent_norm_left_out", "group_limit_left_out",
          "route_scale_left_out", "shared_left_out", "values_from_whole_row")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="deepseek-v2.serve-long-context")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--control-rows", type=int, default=2)
    ap.add_argument("--altered-seeds", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-seed", type=int, default=2147483000)
    ap.add_argument("--presets", default=None,
                    help="a directory of presets in place of the "
                         "benchmark's own cells: a rehearsal on the CPU")
    args = ap.parse_args()

    import jax
    import numpy as np

    bench = harness.load_json(ROOT, "BENCHMARK.json")
    where = {}
    if args.presets:
        presets = harness.load_json(args.presets, "BENCHMARK.json")
        serving = ("decode_tokens_per_s", "tpot_ms_p95", "setup_s")
        bench = dict(bench, configs=presets["configs"],
                     workloads=presets["workloads"], per_layer=[],
                     end_to_end=[{k: v for k, v in m.items()
                                  if k != "workloads"}
                                 for m in bench["end_to_end"]
                                 if m["name"] in serving])
        where = {"root": args.presets, "data": args.presets}
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    devices = jax.devices()[:entry["chips"]]
    harness.enable_compile_cache(jax)
    rows = []

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        cell = harness.Cell(bench, args.workload, seed, args.seconds, False,
                            devices, **where)
        ref, driver = cell.reference, cell.driver
        with_control = i < args.control_seeds
        row = {"seed": seed}
        real_gaps, real_logits = ref.served_token_gaps, ref.last_logits
        real_rows = serve_hybrid.row_gaps
        variants = [("control_fp8", ref.FP8, None)] + [
            ("fault_" + f, None, f) for f in FAULTS]
        low_served = {}             # variant -> (gap, margin)

        def follow(records, lengths):
            return [{"chosen": r["own"][:, :n]}
                    for r, n in zip(records, lengths)]

        def gaps(w, prompt, served, sz, pad, routing=None):
            out = real_gaps(w, prompt, served, sz, pad, routing)
            row.setdefault("served_gaps", []).append(float(np.max(out)))
            row.setdefault("served_flips", []).append(
                int(np.sum(np.asarray(out) > 0)))
            row.setdefault("served_tokens", []).append(len(served))
            if with_control and len(row["served_tokens"]) == 1:
                # the longest checked request: each variant's first choices
                # at the same places, its expert choices followed
                n = len(prompt) + len(served) - 1
                for name, quant, fault in variants:
                    theirs = {}
                    low = ref.served_logits(w, prompt, served, sz, pad,
                                            quant, fault, theirs)
                    ours = follow([theirs], [n])[0]
                    rows = ref.served_logits(w, prompt, served, sz, pad,
                                             routing=ours)
                    picked = np.take_along_axis(
                        np.asarray(rows),
                        np.asarray(low).argmax(-1)[:, None], 1)[:, 0]
                    low_served[name] = (
                        float(np.max(np.asarray(rows).max(-1) - picked)),
                        ours["margin"])
            return out

        def logits(w, sequences, sz, pad, routing=None):
            out = real_logits(w, sequences, sz, pad, routing=routing)
            lengths = [len(s) for s in sequences]
            row["sequence_lengths"] = lengths
            if with_control:
                some = sequences[:args.control_rows]
                for name, quant, fault in variants:
                    theirs = [{} for _ in some]
                    low = real_logits(w, some, sz, pad, quant, fault, theirs)
                    ours = follow(theirs, lengths)
                    want = real_logits(w, some, sz, pad, routing=ours)
                    gap, margin = low_served.get(name, (float("nan"), 0.0))
                    values = driver.numbers(
                        np.asarray(low), np.asarray(want),
                        max([margin] + [r["margin"] for r in ours]), gap)
                    values["logit_row_gaps"] = sorted(
                        map(float, real_rows(low, want)))
                    values["correct"] = check.passed(
                        driver.held(values, cell.limits))
                    row[name] = values
            return out

        def rows_logged(got, want):
            out = real_rows(got, want)
            row["logit_row_gaps"] = sorted(map(float, out))
            return out

        ref.served_token_gaps, ref.last_logits = gaps, logits
        serve_hybrid.row_gaps = rows_logged
        t0 = time.perf_counter()
        try:
            result = harness.run_cell(cell)
        finally:
            ref.served_token_gaps, ref.last_logits = real_gaps, real_logits
            serve_hybrid.row_gaps = real_rows
        row["wall_s"] = time.perf_counter() - t0
        row["correct"] = result["correct"]
        row["program"] = {k: v["value"] for k, v in result["compared"].items()}
        row["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
        row["observed"] = result["observed"]
        if i < args.altered_seeds:
            again = harness.Cell(bench, args.workload, seed, args.seconds,
                                 False, devices, **where)
            with faults.token_altered(every=7,
                                      vocab=again.config["vocab_size"]):
                broken = harness.run_cell(again)
            row["fault_token_altered"] = dict(
                {k: v["value"] for k, v in broken["compared"].items()},
                correct=broken["correct"])
        rows.append(row)
        print(json.dumps(row), flush=True)

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"calibrate_{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()

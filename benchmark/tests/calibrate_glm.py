"""Reads, on the chip and at the cell's own size, the numbers that
``correct`` compares in ``glm-5.serve-sparse-long-context``, by
``tests/calibrate_deepseek.py``'s method, which this repeats for another
reference's signatures and faults and for the selection's margin: the
program's over the seeds (the lower reading of each limit) and, on the
first ``--control-seeds`` of them, the control's and the planted faults'
(the upper).

    chiprun --chips 1 -- python benchmark/tests/calibrate_glm.py \
        --seeds 2 --control-seeds 1 --altered-seeds 0 --seconds 3

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
``held`` and the harness's ``check.passed`` with the cell's limits.  The
driver reads one forward pass a checked request; a variant costs two
passes over the first of them, and is read at the first
``--control-rows`` of that request's tapped rows and at every token it
served.  A variant's selection at those rows (its own forward's,
``index=``) is held to the sound reference's scores there by
``reference.selection_margin``, as the driver holds the program's.  On the
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
from reference import glm_5      # noqa: E402

# the faults an output can show (``tests/faults_glm.INVISIBLE`` cannot)
FAULTS = tuple(f for f in glm_5.FAULTS if f != "index_weights_unscaled")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="glm-5.serve-sparse-long-context")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--control-rows", type=int, default=2)
    ap.add_argument("--altered-seeds", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-seed", type=int, default=2147483000)
    ap.add_argument("--served-variants", default=None,
                    help="comma-separated variants whose served gaps are "
                         "read (each costs two passes over the longest "
                         "checked request); all where not given")
    ap.add_argument("--trace", type=int, default=0,
                    help="trace the first seed's window as run.py --trace 1 "
                         "does (its line then has the per-layer metrics)")
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
    served_only = (set(args.served_variants.split(","))
                   if args.served_variants else None)

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        cell = harness.Cell(bench, args.workload, seed, args.seconds,
                            bool(args.trace) and i == 0, devices, **where)
        ref, driver = cell.reference, cell.driver
        with_control = i < args.control_seeds
        row = {"seed": seed}
        real_served = ref.served_logits
        real_rows = serve_hybrid.row_gaps
        variants = [("control_fp8", ref.FP8, None)] + [
            ("fault_" + f, None, f) for f in FAULTS]

        def served(w, prompt, tokens, sz, pad, quant=None, fault=None,
                   routing=None, index=None):
            out = real_served(w, prompt, tokens, sz, pad, quant, fault,
                              routing, index)
            n = len(prompt) + len(tokens) - 1
            pick = np.take_along_axis(np.asarray(out),
                                      np.asarray(tokens)[:, None], 1)[:, 0]
            gap = np.asarray(out).max(-1) - pick
            row.setdefault("served_gaps", []).append(float(np.max(gap)))
            row.setdefault("served_flips", []).append(int(np.sum(gap > 0)))
            row.setdefault("served_tokens", []).append(len(tokens))
            row.setdefault("sequence_lengths", []).append(n)
            if not with_control or len(row["served_tokens"]) > 1:
                return out
            # the first checked request: each variant in the program's
            # place, its expert choices followed, at its tapped rows (the
            # last position where none was tapped) and at every served one
            at = (index or {}).get("at") or [n - 1]
            at = at[:args.control_rows]
            k = sz["kinds"][0]["index_topk"]
            for name, quant, fault in variants:
                if served_only and name not in served_only:
                    continue
                theirs, picked = {}, {"at": at}
                low = np.asarray(real_served(w, prompt, tokens, sz, pad,
                                             quant, fault, theirs, picked))
                ours = {"chosen": theirs["own"][:, :n]}
                seen = {"at": at}
                want = np.asarray(real_served(w, prompt, tokens, sz, pad,
                                              routing=ours, index=seen))
                first = np.take_along_axis(want, low.argmax(-1)[:, None],
                                           1)[:, 0]
                rows = [a - (len(prompt) - 1) for a in at]
                values = driver.numbers(low[rows], want[rows], ours["margin"],
                                        float(np.max(want.max(-1) - first)))
                values["index_choice_margin"] = max(
                    glm_5.selection_margin(
                        seen["scores"][layer, j],
                        np.where(picked["selected"][layer, j] <= a,
                                 picked["selected"][layer, j], -1), k)
                    for j, a in enumerate(at)
                    for layer in range(sz["layers"]))
                values["logit_row_gaps"] = sorted(
                    map(float, real_rows(low[rows], want[rows])))
                values["correct"] = check.passed(
                    driver.held(values, cell.limits))
                row[name] = values
            return out

        def rows_logged(got, want):
            out = real_rows(got, want)
            row["logit_row_gaps"] = sorted(map(float, out))
            return out

        ref.served_logits = served
        serve_hybrid.row_gaps = rows_logged
        t0 = time.perf_counter()
        try:
            result = harness.run_cell(cell)
        finally:
            ref.served_logits = real_served
            serve_hybrid.row_gaps = real_rows
        row["wall_s"] = time.perf_counter() - t0
        row["correct"] = result["correct"]
        row["program"] = {k: v["value"] for k, v in result["compared"].items()}
        row["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
        row["device"] = result["device"]
        row["setup_s"] = cell.setup_s
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

#!/usr/bin/env python
"""Bench regression gate (ISSUE 10): newest BENCH/MULTICHIP record vs
history, exit nonzero on regression.

The driver rounds still in the repo (``BENCH_r02..r05.json``,
``MULTICHIP_r02..r05.json``; all predate PR 1 and ran on the CPU) are the
history this gate reads.  It compares **compact records**: a header
(``metric``/``value``/``unit``/``platform``) plus ``rows`` of per-bench
``{value, unit, platform, vs_*}`` dicts — the form those rounds' last
stdout line has.  ``bench.py`` no longer prints that form (PR 21 removed
it with the CPU fallback), so the gate judges recorded rounds only, until
ROADMAP S1 moves its floors and gates into per-cell ledger bounds:

- each round's compact record is taken from the driver's ``parsed``
  field, falling back to the last parseable JSON line of the stdout
  ``tail`` (a round with no usable record contributes no baseline,
  exactly like an errored row);
- rows are compared **only against history measured on the same
  platform** (a CPU fallback round must never be judged against a TPU
  round);
- the baseline per row is the **median** of its history values, and
  each row gets a **noise tolerance** (CPU fallback rows on a shared
  host are noisy: the observed round-to-round spread of the headline is
  ~15%, so the default tolerance is deliberately wide; per-row
  overrides in ``TOLERANCES``).  Direction comes from the unit:
  ``*/sec*`` rows regress downward, ``us/step``/``ms/*`` rows regress
  upward;
- three regression classes are noise-free and always fatal: the newest
  round's driver ``rc`` going nonzero while history succeeded, a row
  that now ``error``s but previously produced a value, and a hard
  **gate** field exceeding its standing ceiling
  (``telemetry_overhead.vs_bare`` ≤ 1.05 — the free-telemetry
  acceptance from ISSUE 5/10);
- MULTICHIP records regress when the newest round's ``ok`` flag drops
  (or ``rc`` goes nonzero) while any historical round passed.

Exit status: 0 = no regression, 1 = regression (each printed with its
row, baseline, and tolerance), 2 = usage/IO error.  Wired fast-tier in
``tests/test_bench_regress.py``: exit 0 on the real r02→r05 history,
nonzero on a fixture with an injected >tolerance regression.

Usage::

    python scripts/bench_regress.py                     # repo history
    python scripts/bench_regress.py --dir /path/to/dir  # a fixture dir
    python scripts/bench_regress.py --tolerance 0.5     # override default
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
from typing import List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Default fractional tolerance: CPU fallback rounds on a shared host
# show ~15% round-to-round drift on the headline alone; 0.4 keeps the
# gate quiet on noise while still catching the 2x-class regressions
# that matter.
DEFAULT_TOLERANCE = 0.4

# Per-row overrides (fraction of the baseline).  Rows with tiny absolute
# values or known environment sensitivity get more room.
TOLERANCES = {
    "headline": 0.5,          # resnet50_o2 CPU throughput, host-load bound
    "real_data_rn50": 0.8,    # ~0.6 images/sec absolute on CPU
    "input_pipeline": 0.7,    # scales with the host's free cores
    "tp_gpt": 0.6,            # 8-way shard_map on a shared CPU
    # preemption/recompute cadence is host-load sensitive on CPU (the
    # interpret-mode prefill dominates the recompute cost)
    "serving_occupancy": 0.6,
    # acceptance length couples throughput to the model's greedy
    # cycling, which shifts with any model/config change; the ratio
    # vs_baseline is the stable signal, the absolute rate is not
    "serving_spec": 0.6,
    # 3 replica processes + the loopback socket leg (wire_vs_inproc)
    # on a shared CPU host: process scheduling noise dominates both
    # the absolute rate and the transport ratio
    "serving_fleet": 0.6,
    # absolute decode p99 on a shared CPU host is scheduling-noise
    # bound; the gated signal is the vs_colocated floor below
    "serving_disagg": 0.6,
    # absolute wave rate on a shared CPU host is noisy; the gated
    # signal is the vs_bare ceiling above, not the rate
    "serving_trace_overhead": 0.6,
    # same A/B discipline as serving_trace_overhead: the rate is
    # noise, vs_bare is the gated signal
    "serving_slo_overhead": 0.6,
    # the delta kernel runs interpret-mode Pallas on CPU, so the
    # absolute rate couples to host load twice over; the gated signal
    # is the vs_bare_1adapter floor below
    "serving_lora": 0.6,
    # four replica processes timesharing a CPU host: the absolute
    # burst token rate is scheduling-noise bound; the gated signal is
    # the vs_static floor below
    "serving_autopilot": 0.6,
}

# Hard ceilings on whitelist fields — standing acceptance gates, not
# noise comparisons ((row, field) -> max allowed value).
GATES = {
    ("telemetry_overhead", "vs_bare"): 1.05,
    # ISSUE 15: the distributed-tracing plane armed on the serving hot
    # path must ride inside the same free-telemetry budget
    ("serving_trace_overhead", "vs_bare"): 1.05,
    # ISSUE 20: the longitudinal history + SLO burn-rate plane, armed
    # at a hotter-than-shipped cadence, rides the same budget
    ("serving_slo_overhead", "vs_bare"): 1.05,
}

# Hard floors, same idea in the other direction ((row, field) -> min
# allowed value).  serving_spec.vs_baseline is the ISSUE 13 acceptance
# bar: speculation must never make serving slower than the plain
# engine, even on CPU where the verify's FLOPs are not free.
FLOORS = {
    ("serving_spec", "vs_baseline"): 1.0,
    # ISSUE 16: disaggregating prefill from decode must protect the
    # decode tail — co-located p99 / disaggregated p99 under the same
    # prefill flood at equal pool size
    ("serving_disagg", "vs_colocated"): 1.0,
    # ISSUE 17: a single resident adapter may cost at most ~10% of the
    # bare engine's decode rate — the gathered delta rides the tick,
    # it must not own it
    ("serving_lora", "vs_bare_1adapter"): 0.9,
    # ISSUE 18: the SLO autopilot must beat the static fleet it
    # operates on the burst tail it exists to protect — paired
    # median-of-ratios of p99 TTFT, static / autopilot
    ("serving_autopilot", "vs_static"): 1.0,
}


def lower_is_better(unit: Optional[str]) -> Optional[bool]:
    """Regression direction from the row's unit; ``None`` (skip) when
    the unit is unknown."""
    if not unit:
        return None
    return "/sec" not in unit


def parse_compact(record: dict) -> Optional[dict]:
    """The round's compact record: the driver's ``parsed`` field, else
    the last parseable JSON object line in the stdout tail."""
    parsed = record.get("parsed")
    if isinstance(parsed, dict) and "metric" in parsed:
        return parsed
    tail = record.get("tail", "")
    for line in reversed(tail.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            return obj
    return None


def load_rounds(paths: List[str]) -> List[dict]:
    """``[{path, n, rc, compact}]`` sorted oldest→newest (by the
    driver's round number when present, else by filename)."""
    rounds = []
    for path in paths:
        with open(path) as f:
            rec = json.load(f)
        rounds.append({
            "path": path,
            "n": rec.get("n"),
            "rc": rec.get("rc"),
            "ok": rec.get("ok"),
            "compact": parse_compact(rec),
            "raw": rec,
        })
    rounds.sort(key=lambda r: (r["n"] if isinstance(r["n"], int)
                               else 10**9, r["path"]))
    return rounds


def _rows_of(compact: Optional[dict]) -> dict:
    """Whitelist rows of one compact record, with the headline folded in
    as a pseudo-row so it is gated like everything else."""
    if not isinstance(compact, dict):
        return {}
    rows = dict(compact.get("rows") or {})
    if compact.get("value") is not None:
        rows["headline"] = {
            "value": compact["value"],
            "unit": compact.get("unit"),
            "platform": compact.get("platform"),
        }
    return rows


def check_bench(rounds: List[dict], tolerance: float,
                failures: List[str], notes: List[str]) -> None:
    if not rounds:
        notes.append("bench: no records found (nothing to gate)")
        return
    newest, history = rounds[-1], rounds[:-1]
    label = os.path.basename(newest["path"])

    rc_history_ok = any(h["rc"] == 0 for h in history)
    if newest["rc"] not in (0, None) and rc_history_ok:
        failures.append(
            f"bench {label}: driver rc={newest['rc']} but history has "
            "successful rounds")
    if newest["compact"] is None:
        if newest["rc"] in (0, None) and any(
                h["compact"] is not None for h in history):
            failures.append(
                f"bench {label}: no parseable compact record (the "
                "driver-contract last-line guarantee broke) though "
                "history has them")
        else:
            notes.append(f"bench {label}: no compact record (round "
                         f"failed, rc={newest['rc']}) — skipping rows")
        return

    new_rows = _rows_of(newest["compact"])
    hist_rows = [_rows_of(h["compact"]) for h in history]

    for name, row in sorted(new_rows.items()):
        # hard gates first: a ceiling/floor needs no history
        for (gname, field), ceiling in GATES.items():
            if name == gname and row.get(field) is not None:
                if float(row[field]) > ceiling:
                    failures.append(
                        f"bench {label}: {name}.{field}="
                        f"{row[field]} exceeds the {ceiling} gate")
                else:
                    notes.append(f"bench {label}: gate {name}.{field}="
                                 f"{row[field]} <= {ceiling} ok")
        for (gname, field), floor in FLOORS.items():
            if name == gname and row.get(field) is not None:
                if float(row[field]) < floor:
                    failures.append(
                        f"bench {label}: {name}.{field}="
                        f"{row[field]} below the {floor} floor")
                else:
                    notes.append(f"bench {label}: floor {name}.{field}="
                                 f"{row[field]} >= {floor} ok")

        platform = row.get("platform")
        prior = [h[name] for h in hist_rows if name in h]
        prior_clean = [
            p for p in prior
            if p.get("value") is not None and "error" not in p
            and (platform is None or p.get("platform") in (None, platform))]
        if "error" in row:
            if prior_clean:
                failures.append(
                    f"bench {label}: row {name} now errors "
                    f"({row['error']!r}) but history has clean values")
            continue
        value = row.get("value")
        if value is None or not prior_clean:
            continue
        baseline = statistics.median(
            float(p["value"]) for p in prior_clean)
        unit = row.get("unit") or next(
            (p.get("unit") for p in prior_clean if p.get("unit")), None)
        direction = lower_is_better(unit)
        if direction is None or baseline == 0:
            notes.append(f"bench {label}: row {name} has no unit/"
                         "baseline — direction unknown, skipped")
            continue
        tol = TOLERANCES.get(name, tolerance)
        ratio = float(value) / baseline
        if direction:
            regressed = ratio > 1.0 + tol
        else:
            regressed = ratio < 1.0 - tol
        verdict = "REGRESSION" if regressed else "ok"
        line = (f"bench {label}: {name} {value} {unit or ''} vs median "
                f"{baseline:g} (x{ratio:.3f}, tol ±{tol:.0%}, "
                f"{'lower' if direction else 'higher'}-is-better, "
                f"n={len(prior_clean)}) {verdict}")
        (failures if regressed else notes).append(line)


def check_multichip(rounds: List[dict], failures: List[str],
                    notes: List[str]) -> None:
    if not rounds:
        notes.append("multichip: no records found")
        return
    newest, history = rounds[-1], rounds[:-1]
    label = os.path.basename(newest["path"])
    ever_ok = any(h["raw"].get("ok") for h in history)
    new_ok = bool(newest["raw"].get("ok")) and newest["rc"] in (0, None)
    if ever_ok and not new_ok:
        failures.append(
            f"multichip {label}: ok={newest['raw'].get('ok')} "
            f"rc={newest['rc']} but history has passing rounds")
    else:
        notes.append(f"multichip {label}: ok={new_ok}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="bench/multichip regression gate over the driver "
                    "record history")
    ap.add_argument("--dir", default=_REPO,
                    help="directory holding the record files "
                         "(default: the repo root)")
    ap.add_argument("--bench-glob", default="BENCH_r*.json")
    ap.add_argument("--multichip-glob", default="MULTICHIP_r*.json")
    ap.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE,
                    help="default fractional noise tolerance "
                         f"(default {DEFAULT_TOLERANCE}; per-row "
                         "overrides in TOLERANCES)")
    ap.add_argument("--quiet", action="store_true",
                    help="print only regressions")
    args = ap.parse_args(argv)

    bench_paths = sorted(glob.glob(os.path.join(args.dir, args.bench_glob)))
    multi_paths = sorted(glob.glob(
        os.path.join(args.dir, args.multichip_glob)))
    if not bench_paths and not multi_paths:
        print(f"bench_regress: no records match {args.bench_glob} / "
              f"{args.multichip_glob} under {args.dir}", file=sys.stderr)
        return 2

    failures: List[str] = []
    notes: List[str] = []
    try:
        check_bench(load_rounds(bench_paths), args.tolerance,
                    failures, notes)
        check_multichip(load_rounds(multi_paths), failures, notes)
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_regress: cannot read records: {e!r}",
              file=sys.stderr)
        return 2

    if not args.quiet:
        for line in notes:
            print(line)
    for line in failures:
        print(f"FAIL {line}")
    if failures:
        print(f"bench_regress: {len(failures)} regression(s)")
        return 1
    print("bench_regress: no regressions "
          f"({len(bench_paths)} bench + {len(multi_paths)} multichip "
          "rounds)")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Shared subprocess-sweep driver for the hardware tuning harnesses.

Each grid point runs the real flagship train step in its own subprocess
(it holds the chip alone, and a hang or OOM cannot kill the sweep; this
parent never touches JAX) with the persistent XLA compile cache on; the child prints one JSON record line, which the
driver appends to a jsonl and ranks by ``tokens_per_sec``.  Used by
``tune_flash_blocks.py`` (block_q/block_k knob) and ``tune_gpt_batch.py``
(batch knob).
"""

import json
import os
import subprocess
import sys


def run_sweep(points, *, env_for, child_args_for, label_for, out_path,
              timeout):
    """Run each point; return ``(best, records)`` — the top record by
    ``tokens_per_sec`` (None if every point failed) and the list of all
    successful records, so callers can gate decisions (e.g. auto-landing
    a tuned default) on how many points actually survived.

    ``env_for(pt)``: extra env vars for the child;
    ``child_args_for(pt)``: argv after ``sys.executable``;
    ``label_for(pt)``: stderr progress label.
    """
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    best, records = None, []
    for pt in points:
        env = dict(os.environ)
        env.update(env_for(pt))
        print(f"--- {label_for(pt)}", file=sys.stderr, flush=True)
        try:
            proc = subprocess.run(
                [sys.executable] + child_args_for(pt),
                env=env, capture_output=True, timeout=timeout)
        except subprocess.TimeoutExpired:
            print(f"    timeout after {timeout:.0f}s",
                  file=sys.stderr, flush=True)
            continue
        if proc.returncode != 0:
            print("    rc=%d %s" % (
                proc.returncode,
                proc.stderr.decode(errors="replace")[-400:]),
                file=sys.stderr, flush=True)
            continue
        lines = proc.stdout.decode(errors="replace").strip().splitlines()
        if not lines:
            print("    rc=0 but empty stdout", file=sys.stderr, flush=True)
            continue
        line = lines[-1]
        try:
            rec = json.loads(line)
        except ValueError:
            print(f"    unparseable record: {line[-200:]}",
                  file=sys.stderr, flush=True)
            continue
        with open(out_path, "a") as f:
            f.write(line + "\n")
        print(f"    {rec.get('tokens_per_sec')} tok/s  mfu={rec.get('mfu')}",
              file=sys.stderr, flush=True)
        records.append(rec)
        if best is None or (rec.get("tokens_per_sec") or 0) > (
                best.get("tokens_per_sec") or 0):
            best = rec
    return best, records

"""One run of one benchmark cell.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data that ``BENCHMARK.json`` names:
the configuration (``configs/<config>.json`` with its plain reference in
``reference/``), the traffic mix (``traffic/<traffic>.json``, whose
``driver`` names the module of ``drivers/`` that feeds the program), the
limits of the output check (``limits/<workload>.json``) and one reader per
per-layer metric (``metrics/<metric>.py``).  The last line of standard
output is the result.
"""

import time

T_START = time.perf_counter()           # set-up counts from here

import argparse                         # noqa: E402
import gc                               # noqa: E402
import importlib                        # noqa: E402
import json                             # noqa: E402
import os                               # noqa: E402
import sys                              # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)

from lib import check, peaks            # noqa: E402


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def workload(bench, name):
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    return entry


class Cell:
    """One run's inputs, and what the drivers ask of the harness."""

    def __init__(self, bench, name, seed, seconds, trace, devices, root=ROOT,
                 data=HERE, t_start=None):
        """``root`` holds the files ``bench`` names; ``data`` the traffic
        mixes and limits (the tests keep tiny presets of their own)."""
        entry = workload(bench, name)
        conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
        self.bench, self.name = bench, name
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices = devices
        self.config = load_json(root, conf["file"])
        self.traffic = load_json(data, "traffic", entry["traffic"] + ".json")
        self.limits = load_json(data, "limits", name + ".json")
        self.reference = importlib.import_module(
            "reference." + self.config["reference"])
        self.driver = importlib.import_module(
            "drivers." + self.traffic["driver"])
        self.scratch = os.path.join(ROOT, ".bench_cache")
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.setup_s = None
        self.marks = {}
        self.collections = []            # (start, seconds) of each

    def mark(self, what: str):
        """Seconds since the start at which a part of set-up was done."""
        self.marks[what] = round(time.perf_counter() - self.t_start, 3)

    def setup_done(self):
        """Set-up (loading, compiling, warming up) ends here.  What set-up
        left on the heap (the traced programs of 24 layers: millions of
        objects) is collected once and frozen, as a long-lived server does:
        a full collection inside the window then walks only what the window
        made, not the whole heap.  Each collection from here on is timed."""
        gc.collect()
        gc.freeze()
        began = []

        def timed(phase, info):
            if phase == "start":
                began.append(time.perf_counter())
            elif began:
                t = began.pop()
                self.collections.append((t, time.perf_counter() - t))

        gc.callbacks.append(timed)
        self.setup_s = time.perf_counter() - self.t_start

    def gc_pause_ms(self, t0: float, t1: float) -> float:
        """Milliseconds the collector held the host between ``t0`` and
        ``t1``."""
        return 1e3 * sum(s for t, s in self.collections if t0 <= t <= t1)

    def read_memory(self) -> dict:
        """Peak bytes per chip, once the window has closed and before the
        reference runs.  This runtime counts what arrays hold
        (``peak_bytes_in_use``) apart from what it reserves for the compiled
        programs' temporaries (``peak_bytes_reserved``); a step needs both at
        once, so the peak is their sum.  The CPU backend reports none; a TPU
        must."""
        stats = [d.memory_stats() for d in self.devices]
        if not all(stats):
            if self.devices[0].platform == "tpu":
                raise RuntimeError("a TPU device reports no memory_stats")
            return {"peak_bytes": [0 for _ in stats]}
        return {"peak_bytes": [int(s["peak_bytes_in_use"])
                               + int(s.get("peak_bytes_reserved", 0))
                               for s in stats]}


def metrics_of(bench, cell_name, group):
    """The metrics of ``group`` that the cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def run_cell(cell) -> dict:
    """Drive one cell and assemble the contract's result."""
    out = cell.driver.run(cell)
    dev = cell.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(cell.devices),
              "memory_peak_bytes": max(out["observed"]["memory"]["peak_bytes"])}
    values = dict(out["end_to_end"], setup_s=cell.setup_s)
    result = {"correct": check.passed(out["compared"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    if not cell.trace:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(cell.bench, cell.name, "end_to_end")}
    else:
        trace = out["trace"]
        view = {"trace": trace, "observed": out["observed"],
                "reference": cell.reference, "traffic": cell.traffic,
                "chips": len(cell.devices),
                "peaks": peaks.of(dev.device_kind) if dev.platform == "tpu"
                else None}
        result["metrics"] = {}
        for m in metrics_of(cell.bench, cell.name, "per_layer"):
            reader = importlib.import_module("metrics." + m["name"])
            value = reader.read(view)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    result["device"] = device
    result["observed"] = {k: v for k, v in out["observed"].items()
                          if isinstance(v, (int, float, str))}
    result["observed"]["setup_marks"] = cell.marks
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in out["compared"].items()}
    return result


def enable_compile_cache(jax):
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment places it; every program is cached, however quick."""
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".bench_cache", "xla")
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    bench = load_json(ROOT, "BENCHMARK.json")

    import apex_tpu  # noqa: F401  (no program beside the benchmark: stop)
    import jax

    devices = jax.devices()
    want = workload(bench, args.workload)["chips"]
    if devices[0].platform != "tpu" or len(devices) < want:
        raise SystemExit(
            f"benchmark: {args.workload} needs {want} TPU chip(s); JAX found "
            f"{len(devices)} device(s) of platform {devices[0].platform!r}")
    peaks.of(devices[0].device_kind)
    enable_compile_cache(jax)

    cell = Cell(bench, args.workload, args.seed, args.seconds,
                bool(args.trace), devices[:want], t_start=T_START)
    cell.mark("devices")
    result = run_cell(cell)
    for name, rec in result["compared"].items():
        print(f"compared {name}: {rec['value']!r} limit {rec['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

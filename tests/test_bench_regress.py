"""The bench regression gate (ISSUE 10): ``scripts/bench_regress.py``
must exit 0 on the repo's real BENCH_r02→r05 / MULTICHIP_r02→r05
history and nonzero on a fixture with an injected >tolerance
regression."""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "bench_regress.py")

sys.path.insert(0, os.path.join(REPO, "scripts"))
import bench_regress  # noqa: E402


def _run(*args):
    return subprocess.run(
        [sys.executable, SCRIPT, *args], capture_output=True, text=True,
        cwd=REPO)


def _copy_history(tmp_path):
    for name in sorted(os.listdir(REPO)):
        if name.startswith(("BENCH_r", "MULTICHIP_r")) and \
                name.endswith(".json"):
            shutil.copy(os.path.join(REPO, name), tmp_path / name)


def _newest_bench(tmp_path):
    names = sorted(p for p in os.listdir(tmp_path)
                   if p.startswith("BENCH_r"))
    with open(tmp_path / names[-1]) as f:
        rec = json.load(f)
    return names[-1], rec


def _write_round(tmp_path, name, rec, n):
    rec = copy.deepcopy(rec)
    rec["n"] = n
    with open(tmp_path / name, "w") as f:
        json.dump(rec, f)
    return rec


class TestRealHistory:
    def test_exit_zero_on_repo_records(self):
        """The standing acceptance: the real r02→r05 evidence is not a
        regression against itself."""
        proc = _run()
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "no regressions" in proc.stdout

    def test_no_records_is_a_usage_error(self, tmp_path):
        proc = _run("--dir", str(tmp_path))
        assert proc.returncode == 2


class TestInjectedRegression:
    def test_value_drop_beyond_tolerance_fails(self, tmp_path):
        """A >tolerance drop on a higher-is-better whitelist row in a
        new round exits nonzero and names the row."""
        _copy_history(tmp_path)
        _, newest = _newest_bench(tmp_path)
        assert newest["parsed"], "fixture expects r05's parsed compact"
        bad = copy.deepcopy(newest)
        # 70% drop >> the 40% default tolerance
        bad["parsed"]["rows"]["gpt_flash"]["value"] *= 0.3
        _write_round(tmp_path, "BENCH_r06.json", bad, n=6)
        proc = _run("--dir", str(tmp_path))
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "gpt_flash" in proc.stdout and "REGRESSION" in proc.stdout

    def test_within_tolerance_noise_passes(self, tmp_path):
        """A 10% dip is CPU noise, not a regression."""
        _copy_history(tmp_path)
        _, newest = _newest_bench(tmp_path)
        ok = copy.deepcopy(newest)
        ok["parsed"]["rows"]["gpt_flash"]["value"] *= 0.9
        _write_round(tmp_path, "BENCH_r06.json", ok, n=6)
        proc = _run("--dir", str(tmp_path))
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_lower_is_better_direction(self, tmp_path):
        """us/step rows regress UPWARD: a 2x slower fused_adam_step
        fails, a 2x faster one does not."""
        _copy_history(tmp_path)
        _, newest = _newest_bench(tmp_path)
        slow = copy.deepcopy(newest)
        slow["parsed"]["rows"]["fused_adam_step"]["value"] *= 2.0
        _write_round(tmp_path, "BENCH_r06.json", slow, n=6)
        assert _run("--dir", str(tmp_path)).returncode == 1
        fast = copy.deepcopy(newest)
        fast["parsed"]["rows"]["fused_adam_step"]["value"] *= 0.5
        _write_round(tmp_path, "BENCH_r06.json", fast, n=6)
        assert _run("--dir", str(tmp_path)).returncode == 0

    def test_row_turning_error_fails(self, tmp_path):
        """A row that errors where history has clean values is fatal
        regardless of tolerance (noise-free signal)."""
        _copy_history(tmp_path)
        _, newest = _newest_bench(tmp_path)
        bad = copy.deepcopy(newest)
        bad["parsed"]["rows"]["bert_large"] = {"error": "rc=1: boom"}
        _write_round(tmp_path, "BENCH_r06.json", bad, n=6)
        proc = _run("--dir", str(tmp_path))
        assert proc.returncode == 1
        assert "bert_large" in proc.stdout

    def test_vs_bare_gate_ceiling(self, tmp_path):
        """The free-telemetry acceptance (vs_bare <= 1.05) is a hard
        ceiling, no history needed."""
        _copy_history(tmp_path)
        _, newest = _newest_bench(tmp_path)
        bad = copy.deepcopy(newest)
        bad["parsed"]["rows"]["telemetry_overhead"] = {
            "value": 180000.0, "unit": "us/step", "platform": "cpu",
            "vs_bare": 1.31}
        _write_round(tmp_path, "BENCH_r06.json", bad, n=6)
        proc = _run("--dir", str(tmp_path))
        assert proc.returncode == 1
        assert "vs_bare" in proc.stdout and "1.05" in proc.stdout

    def test_serving_spec_vs_baseline_floor(self, tmp_path):
        """The ISSUE 13 acceptance bar (speculation never slower than
        the plain engine) is a hard floor, no history needed — and a
        passing ratio is not flagged."""
        _copy_history(tmp_path)
        _, newest = _newest_bench(tmp_path)
        bad = copy.deepcopy(newest)
        bad["parsed"]["rows"]["serving_spec"] = {
            "value": 900.0, "unit": "tokens/sec", "platform": "cpu",
            "vs_baseline": 0.82, "mean_accept_len": 1.1}
        _write_round(tmp_path, "BENCH_r06.json", bad, n=6)
        proc = _run("--dir", str(tmp_path))
        assert proc.returncode == 1
        assert "vs_baseline" in proc.stdout and "floor" in proc.stdout
        ok = copy.deepcopy(newest)
        ok["parsed"]["rows"]["serving_spec"] = {
            "value": 2100.0, "unit": "tokens/sec", "platform": "cpu",
            "vs_baseline": 2.26, "mean_accept_len": 4.0}
        _write_round(tmp_path, "BENCH_r06.json", ok, n=6)
        assert _run("--dir", str(tmp_path)).returncode == 0

    def test_multichip_ok_drop_fails(self, tmp_path):
        _copy_history(tmp_path)
        rec = {"n_devices": 8, "rc": 1, "ok": False, "skipped": False,
               "tail": "boom"}
        with open(tmp_path / "MULTICHIP_r06.json", "w") as f:
            json.dump(rec, f)
        proc = _run("--dir", str(tmp_path))
        assert proc.returncode == 1
        assert "multichip" in proc.stdout

    def test_driver_rc_regression_fails(self, tmp_path):
        _copy_history(tmp_path)
        _, newest = _newest_bench(tmp_path)
        bad = copy.deepcopy(newest)
        bad["rc"] = 137
        bad["parsed"] = None
        bad["tail"] = "killed"
        _write_round(tmp_path, "BENCH_r06.json", bad, n=6)
        proc = _run("--dir", str(tmp_path))
        assert proc.returncode == 1


class TestRecordParsing:
    def test_parse_compact_prefers_parsed_field(self):
        rec = {"parsed": {"metric": "m", "value": 1.0},
               "tail": '{"metric": "other", "value": 9.0}'}
        assert bench_regress.parse_compact(rec)["value"] == 1.0

    def test_parse_compact_falls_back_to_tail(self):
        rec = {"parsed": None, "tail":
               'noise\n{"not": "a record"}\n'
               '{"metric": "m", "value": 3.0, "rows": {}}'}
        assert bench_regress.parse_compact(rec)["value"] == 3.0

    def test_parse_compact_none_when_tail_is_garbage(self):
        assert bench_regress.parse_compact(
            {"parsed": None, "tail": "Traceback ... mid-json {\"val"}) \
            is None

    def test_direction_from_unit(self):
        assert bench_regress.lower_is_better("us/step") is True
        assert bench_regress.lower_is_better("ms/reshard-restore") is True
        assert bench_regress.lower_is_better("tokens/sec/chip") is False
        assert bench_regress.lower_is_better(None) is None

    def test_pseudo_headline_row(self):
        rows = bench_regress._rows_of(
            {"metric": "m", "value": 5.0, "unit": "images/sec/chip",
             "platform": "cpu", "rows": {"a": {"value": 1.0}}})
        assert rows["headline"]["value"] == 5.0
        assert rows["a"] == {"value": 1.0}


@pytest.mark.parametrize("platform_mix", ["cross", "same"])
def test_platform_isolation(tmp_path, platform_mix):
    """A CPU round is never judged against TPU history (and vice
    versa): an apparent 100x 'regression' across platforms is not
    compared at all."""
    hist = {"n": 1, "rc": 0, "tail": "", "parsed": {
        "metric": "m", "value": 8000.0, "unit": "images/sec/chip",
        "platform": "tpu", "rows": {
            "gpt_flash": {"value": 90000.0, "unit": "tokens/sec/chip",
                          "platform": "tpu"}}}}
    new_platform = "tpu" if platform_mix == "same" else "cpu"
    newest = {"n": 2, "rc": 0, "tail": "", "parsed": {
        "metric": "m", "value": 9.0, "unit": "images/sec/chip",
        "platform": new_platform, "rows": {
            "gpt_flash": {"value": 15000.0, "unit": "tokens/sec/chip",
                          "platform": new_platform}}}}
    for name, rec in (("BENCH_r01.json", hist), ("BENCH_r02.json", newest)):
        with open(tmp_path / name, "w") as f:
            json.dump(rec, f)
    with open(tmp_path / "MULTICHIP_r01.json", "w") as f:
        json.dump({"n_devices": 8, "rc": 0, "ok": True, "tail": ""}, f)
    rc = _run("--dir", str(tmp_path)).returncode
    # same-platform: 15000 vs 90000 tokens/sec is a real regression;
    # cross-platform: no comparison, no failure
    assert rc == (1 if platform_mix == "same" else 0)

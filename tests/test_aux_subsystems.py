"""Aux subsystems: timers export, autoresume protocol, rank logger
(SURVEY §5 tracing / failure-detection / observability rows), the
input-pipeline smoke script (ISSUE 8 CI satellite), the serving smoke
script (ISSUE 9 CI satellite), the fleet-serving smoke script
(ISSUE 11 CI satellite), and the APX305 jit-stability sweep over the
registered serving programs (ISSUE 19 tier gate)."""

import json
import logging
import os
import subprocess
import sys

import pytest

from apex_tpu.log_util import get_transformer_logger, set_logging_level
from apex_tpu.transformer.testing.global_vars import (
    AutoResume,
    check_autoresume_termination,
    get_args,
    set_args,
    set_autoresume,
)
from apex_tpu.utils.timers import Timers


def test_timers_write_jsonl(tmp_path):
    t = Timers()
    t("fwd").start()
    t("fwd").stop()
    path = tmp_path / "timers.jsonl"
    t.write(["fwd", "missing"], str(path), iteration=3)
    rec = json.loads(path.read_text().strip())
    assert rec["iteration"] == 3
    assert "fwd" in rec["timers"] and rec["timers"]["fwd"] >= 0
    assert "missing" not in rec["timers"]


def test_timers_write_tensorboard_ducktype():
    calls = []

    class Writer:
        def add_scalar(self, tag, value, step):
            calls.append((tag, value, step))

    t = Timers()
    t("step").start()
    t("step").stop()
    t.write(["step"], Writer(), iteration=7)
    assert calls and calls[0][0] == "timers/step" and calls[0][2] == 7


def test_autoresume_file_protocol(tmp_path):
    sig = tmp_path / "preempt"
    ar = AutoResume(signal_file=str(sig), min_poll_interval=0.0)
    set_autoresume(ar)
    saved = []
    assert not check_autoresume_termination(1, saved.append)
    sig.write_text("now")
    assert check_autoresume_termination(2, saved.append)
    assert saved == [2]
    assert not sig.exists()  # request_resume cleared the sentinel
    set_autoresume(None)


def test_autoresume_env_protocol(monkeypatch):
    monkeypatch.setenv("APEX_TPU_AUTORESUME_TERMINATE", "1")
    ar = AutoResume(min_poll_interval=0.0)
    assert ar.termination_requested()
    # falsy strings mean "disabled", not "requested"
    for off in ("0", "false", "no", ""):
        monkeypatch.setenv("APEX_TPU_AUTORESUME_TERMINATE", off)
        ar.init()
        assert not ar.termination_requested(), off
    monkeypatch.delenv("APEX_TPU_AUTORESUME_TERMINATE")
    ar.init()
    assert not ar.termination_requested()


def test_global_args_registry():
    set_args(None)
    with pytest.raises(RuntimeError):
        get_args()
    set_args({"lr": 0.1})
    assert get_args()["lr"] == 0.1
    set_args(None)


def test_rank_logger_stamps_rank_info():
    import io

    import apex_tpu

    lg = get_transformer_logger(__name__)
    assert lg.name.startswith("apex_tpu.")
    set_logging_level(logging.INFO)
    root = logging.getLogger("apex_tpu")
    # capture through the installed rank-stamped formatter
    buf = io.StringIO()
    cap = logging.StreamHandler(buf)
    cap.setFormatter(root.handlers[0].formatter)
    root.addHandler(cap)
    try:
        lg.info("hello from the library logger")
    finally:
        root.removeHandler(cap)
    out = buf.getvalue()
    assert "hello from the library logger" in out
    assert "[0/1]" in out  # rank info stamped by RankInfoFormatter


def test_data_pipeline_smoke_script(tmp_path):
    """scripts/data_pipeline_smoke.sh end to end (the telemetry_smoke
    wiring pattern): process-pool decode + double-buffered prefetch must
    have every batch ready ahead of the step that takes it (a count, not
    a comparison of two stall timings), the packed LM stream must flow
    through a DataService, and shutdown must leak no worker processes.
    Subprocess
    because the process-pool spawn re-imports __main__ and the smoke
    owns its own platform pinning."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHON"] = sys.executable
    proc = subprocess.run(
        ["bash", os.path.join(repo, "scripts", "data_pipeline_smoke.sh"),
         str(tmp_path / "work")],
        cwd=repo, env=env, capture_output=True, timeout=240)
    assert proc.returncode == 0, (
        f"data_pipeline_smoke.sh rc={proc.returncode}\n"
        f"stderr tail:\n{proc.stderr.decode(errors='replace')[-3000:]}")
    assert b"PASS" in proc.stderr


def test_serving_smoke_script():
    """scripts/serving_smoke.sh end to end (ISSUE 9): continuously-
    batched greedy decode token-identical to the per-request
    full-forward reference across staggered request churn, exactly one
    decode compile, int8 + speculative drafting with the k+1 verify at
    occupancy pressure (A2 — ISSUE 12/13), and a clean SIGTERM drain
    (in-flight delivered, queue cancelled).  Subprocess because the
    smoke sends itself a real SIGTERM and owns its own platform/mesh
    pinning."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHON"] = sys.executable
    proc = subprocess.run(
        ["bash", os.path.join(repo, "scripts", "serving_smoke.sh")],
        cwd=repo, env=env, capture_output=True, timeout=300)
    assert proc.returncode == 0, (
        f"serving_smoke.sh rc={proc.returncode}\n"
        f"stderr tail:\n{proc.stderr.decode(errors='replace')[-3000:]}")
    assert b"PASS" in proc.stderr
    assert b"phase A OK" in proc.stderr and b"phase B OK" in proc.stderr
    assert b"phase A2 OK" in proc.stderr


def test_fleet_smoke_script():
    """scripts/fleet_smoke.sh end to end (ISSUE 11): the 3-replica
    fault matrix with real processes and real signals — SIGKILL one
    replica mid-decode and the replayed streams stay bitwise identical
    to the uninterrupted greedy reference; overload sheds with typed
    REJECTED + serving/requests_rejected; a staggered SIGTERM-drain
    weight rollout under load restores the newest VERIFIED checkpoint
    (corrupt newest falls back), finishes every request, and keeps p99
    TPOT bounded; /healthz answers on live replicas and refuses on the
    killed one.  Phase D (ISSUE 14): the same fleet contract over
    framed loopback TCP — replica_serve daemons behind ChaosProxy, one
    wire partitioned and one host SIGKILLed mid-decode, every stream
    token-identical.  Subprocess because the smoke spawns replica
    processes and owns its own platform pinning (the serving-smoke
    pattern).

    Fast tier runs phases A-C only (FLEET_SMOKE_PHASES=ABC): phase D
    stands up a second 3-daemon socket fleet and the whole script was
    the single heaviest fast-tier item (550s of the aux tier's 783s) —
    the slow-tier twin below runs all phases (ISSUE 18 tier budget
    satellite, the trace-smoke precedent).  The fast tier still asserts
    the demoted phase's artifact: the script must *say* it skipped D
    (so a silently-dropped phase can never pass as a skip)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHON"] = sys.executable
    env["FLEET_SMOKE_PHASES"] = "ABC"
    proc = subprocess.run(
        ["bash", os.path.join(repo, "scripts", "fleet_smoke.sh")],
        cwd=repo, env=env, capture_output=True, timeout=700)
    assert proc.returncode == 0, (
        f"fleet_smoke.sh rc={proc.returncode}\n"
        f"stderr tail:\n{proc.stderr.decode(errors='replace')[-3000:]}")
    assert b"PASS" in proc.stderr
    for phase in (b"phase A OK", b"phase B OK", b"phase C OK"):
        assert phase in proc.stderr
    assert b"phase D skipped" in proc.stderr


@pytest.mark.slow
def test_fleet_smoke_script_socket_chaos():
    """The full fleet smoke including phase D (the second socket-daemon
    fleet behind ChaosProxy wires: a partition + a SIGKILL mid-decode
    over framed TCP) — slow tier: it spawns three more engine hosts on
    top of the phase A-C fleet (ISSUE 18 tier budget satellite)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHON"] = sys.executable
    env["FLEET_SMOKE_PHASES"] = "ABCD"
    proc = subprocess.run(
        ["bash", os.path.join(repo, "scripts", "fleet_smoke.sh")],
        cwd=repo, env=env, capture_output=True, timeout=900)
    assert proc.returncode == 0, (
        f"fleet_smoke.sh rc={proc.returncode}\n"
        f"stderr tail:\n{proc.stderr.decode(errors='replace')[-3000:]}")
    assert b"PASS" in proc.stderr
    for phase in (b"phase A OK", b"phase B OK", b"phase C OK",
                  b"phase D OK"):
        assert phase in proc.stderr


def test_trace_smoke_script():
    """scripts/trace_smoke.sh end to end (ISSUE 15 CI satellite): a
    3-replica loopback socket fleet with tracing armed in every
    process — one replica SIGKILLed mid-decode yields ONE merged trace
    spanning both replicas with failover_replay attributed and the
    per-request hop books exactly closed (overcommit 0, unattributed
    0); every request's hop sum matches the router-side stopwatch
    within 2%; /fleet/statusz serves the per-tenant SLO plane; and
    scripts/trace_report.py parses the spill dir strictly.  Subprocess
    because the smoke spawns replica daemons and owns its platform
    pinning (the fleet-smoke pattern).

    Fast tier runs phases A-C only (TRACE_SMOKE_PHASES=ABC): phase D
    stands up a second 4-daemon fleet and was the slowest fast-tier
    phase — the slow-tier twin below runs all phases (ISSUE 17 tier
    budget satellite)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHON"] = sys.executable
    env["TRACE_SMOKE_PHASES"] = "ABC"
    proc = subprocess.run(
        ["bash", os.path.join(repo, "scripts", "trace_smoke.sh")],
        cwd=repo, env=env, capture_output=True, timeout=600)
    assert proc.returncode == 0, (
        f"trace_smoke.sh rc={proc.returncode}\n"
        f"stderr tail:\n{proc.stderr.decode(errors='replace')[-3000:]}")
    assert b"PASS" in proc.stderr
    for phase in (b"phase A OK", b"phase B OK", b"phase C OK"):
        assert phase in proc.stderr


@pytest.mark.slow
def test_trace_smoke_script_disagg():
    """The full trace smoke including phase D (the disaggregated
    2-prefill/2-decode fleet with kv_migrate hops on real daemons) —
    slow tier: it stands up a second fleet of four daemons on top of
    the phase A-C fleet."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHON"] = sys.executable
    env["TRACE_SMOKE_PHASES"] = "ABCD"
    proc = subprocess.run(
        ["bash", os.path.join(repo, "scripts", "trace_smoke.sh")],
        cwd=repo, env=env, capture_output=True, timeout=900)
    assert proc.returncode == 0, (
        f"trace_smoke.sh rc={proc.returncode}\n"
        f"stderr tail:\n{proc.stderr.decode(errors='replace')[-3000:]}")
    assert b"PASS" in proc.stderr
    for phase in (b"phase A OK", b"phase B OK", b"phase C OK",
                  b"phase D OK"):
        assert phase in proc.stderr


def test_obs_smoke_script(tmp_path):
    """scripts/obs_smoke.sh end to end (ISSUE 10 CI satellite): the
    driver dryrun with the FLIGHT RECORDER armed — the spilled timeline
    parses under strict torn-tail semantics, the goodput buckets close
    the books against an independent stopwatch (exhaustive + disjoint),
    online accounting matches the offline recompute, and the debug
    server's /metrics + /statusz scrape.  2-device mesh to keep the XLA
    compile in the fast tier (the telemetry_smoke wiring pattern)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the script's dryrun pins its own
    proc = subprocess.run(
        ["bash", os.path.join(repo, "scripts", "obs_smoke.sh"),
         "2", str(tmp_path / "out")],
        cwd=repo, env=env, capture_output=True, timeout=560)
    assert proc.returncode == 0, (
        f"obs_smoke.sh rc={proc.returncode}\n"
        f"stdout: {proc.stdout.decode(errors='replace')[-2000:]}\n"
        f"stderr tail:\n{proc.stderr.decode(errors='replace')[-2000:]}")
    assert b"obs_smoke OK" in proc.stdout


def test_stability_lint_decode_fast():
    """APX305 over the flagship program (ISSUE 19 tier gate, fast
    tier): the no-LoRA decode step traced at 3 distinct churn configs
    — the all-zeros entry shape plus two randomized live mixes — must
    hash to one jaxpr structure.  One engine build, trace-only (no XLA
    compile), so this rides the fast tier; the slow twin below sweeps
    every registered program at 4 configs."""
    from apex_tpu.analysis.stability import run_stability

    report, n = run_stability(programs=["decode"], n_configs=3)
    assert n == 1
    assert report.ok and not report.findings, report.format()


@pytest.mark.slow
def test_stability_lint_full_sweep_slow():
    """APX305 full sweep (ISSUE 19 acceptance): every registered
    serving program — decode, prefill, speculative, LoRA — at 4 churn
    configs each, identical structure hash across all of them."""
    from apex_tpu.analysis.stability import run_stability

    report, n = run_stability(n_configs=4)
    assert n == 4
    assert report.ok and not report.findings, report.format()

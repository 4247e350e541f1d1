"""Multi-process / multi-host bring-up.

Behavioral spec: ``apex/parallel/multiproc.py:1-35`` (spawn ``world_size``
local ranks with ``--rank i``) and the hybrid process-group construction of
``apex/transformer/parallel_state.py:83-153``.  The JAX analog is one call
per process to :func:`jax.distributed.initialize`; afterwards
``jax.devices()`` spans every process and the mesh builder
(:func:`apex_tpu.parallel.mesh.initialize_model_parallel`) lays the ``dcn``
axis across the process boundary, so no group bookkeeping survives.

Two entry points:

- :func:`initialize_distributed` — call at the top of each rank's script
  (env-var defaults match the common launchers: ``COORDINATOR_ADDRESS`` /
  ``JAX_COORDINATOR_ADDRESS``, ``NUM_PROCESSES``, ``PROCESS_ID``, plus
  SLURM/TPU-pod autodetection inherited from ``jax.distributed``).
- :func:`run_multiprocess` — the ``multiproc`` launcher analog for tests
  and single-host experiments: spawn N copies of a script on local CPU
  devices, each with the right coordinator/rank env, and wait.

CPU ranks use the Gloo cross-process collective backend (JAX's default for
CPU), which is how the 2-process integration test
(``tests/test_multiprocess.py``) runs collectives without hardware —
SURVEY.md §4's "multi-node without a cluster" translation.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Optional, Sequence

__all__ = ["initialize_distributed", "run_multiprocess", "free_port"]

_INITIALIZED = False


def free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
) -> None:
    """Join (or trivially skip, single-process) the distributed job.

    Must run before any other JAX backend use in the process — like the
    reference's requirement that ``init_process_group`` precede CUDA work.
    Arguments default from the environment (``COORDINATOR_ADDRESS``,
    ``NUM_PROCESSES``, ``PROCESS_ID``); on managed platforms (TPU pods,
    SLURM) ``jax.distributed.initialize()`` autodetects everything and this
    wrapper passes straight through.
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS") or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])

    import jax

    if num_processes is not None and num_processes <= 1:
        _INITIALIZED = True
        return
    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        # Pin at the *config* level too, and enable the Gloo cross-process
        # collective backend — without it multi-process CPU collectives
        # deadlock.
        from apex_tpu.utils.platform import pin_cpu

        pin_cpu()
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        local_device_ids=local_device_ids,
    )
    _INITIALIZED = True


def run_multiprocess(
    script: str,
    num_processes: int = 2,
    devices_per_process: int = 4,
    timeout: float = 600.0,
    extra_env: Optional[dict] = None,
    script_args: Optional[Sequence[str]] = None,
):
    """Spawn ``num_processes`` CPU ranks of ``script`` on this host and wait
    (the ``python -m apex.parallel.multiproc`` analog; per-rank output is
    returned rather than written to ``GPU_i.log``).

    Each rank gets ``JAX_PLATFORMS=cpu``, ``devices_per_process`` forced
    host devices, and coordinator/rank env consumed by
    :func:`initialize_distributed`; ``script_args`` are appended to every
    rank's argv.  Returns the list of ``CompletedProcess`` results; raises
    if any rank fails.
    """
    port = free_port()
    procs = []
    for rank in range(num_processes):
        env = dict(os.environ)
        env.update(extra_env or {})
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices_per_process}"
        ).strip()
        env["COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["NUM_PROCESSES"] = str(num_processes)
        env["PROCESS_ID"] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, script, *(script_args or ())],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        ))
    results = []
    failed = []
    for rank, proc in enumerate(procs):
        try:
            out, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
            failed.append((rank, "timeout", err))
            continue
        results.append(subprocess.CompletedProcess(
            proc.args, proc.returncode, out, err))
        if proc.returncode != 0:
            failed.append((rank, proc.returncode, err))
    if failed:
        msgs = "\n".join(
            f"rank {r}: {rc}\n{e.decode(errors='replace')[-2000:]}"
            for r, rc, e in failed)
        raise RuntimeError(f"multiprocess launch failed:\n{msgs}")
    return results

"""Test configuration: run everything on a virtual 8-device CPU mesh.

The reference runs distributed tests by spawning world_size processes on one
host over NCCL (``apex/transformer/testing/distributed_test_base.py:22-93``,
``MultiProcessTestCase``).  The JAX analog (SURVEY.md §4) is a single process
with ``--xla_force_host_platform_device_count=N`` so every collective runs on
a real N-device mesh without hardware.

This must happen before any JAX backend is initialized, so the env var is
set before the import and the config is pinned right after it.
"""

import os

# Must precede jax import / backend init.
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_parallel_state():
    """Reset the global mesh registry between tests (the analog of the
    reference's per-test ``destroy_model_parallel`` teardown)."""
    yield
    from apex_tpu.parallel import mesh

    mesh.destroy_model_parallel()


@pytest.fixture
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running distributed/model tests (deselect with "
        "-m 'not slow' for the fast tier)",
    )


# The graph-lint fixture (apex_tpu.analysis): importing it here registers
# it for every test module, so suites can lint any model they already
# trace against the shared rulebook (docs/analysis.md).
from apex_tpu.analysis.fixtures import graph_lint  # noqa: E402,F401

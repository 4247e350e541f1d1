#!/usr/bin/env bash
# Input-pipeline smoke (ISSUE 8 satellite): drive every layer of
# apex_tpu.data end to end — synthetic JPEG tree through the
# process-pool ImageFolderLoader + double-buffered prefetch_to_device,
# and a packed LM token stream through a DataService loader process —
# asserting OVERLAP (every batch pulled and placed ahead of the step
# that takes it; none with the same loader pulled synchronously) and
# CLEAN SHUTDOWN (no leaked worker/service processes).  Wired into the fast tier like telemetry_smoke.sh
# (tests/test_aux_subsystems.py::test_data_pipeline_smoke_script).
#
# Usage: scripts/data_pipeline_smoke.sh [WORK_DIR]
set -euo pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
WORK="${1:-$(mktemp -d /tmp/apex_tpu_data_smoke.XXXXXX)}"
PYTHON="${PYTHON:-python}"

echo "data_pipeline_smoke: -> ${WORK}" >&2
cd "$REPO"
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
  "$PYTHON" apex_tpu/testing/data_pipeline_smoke.py "$WORK"
echo "PASS" >&2

#!/usr/bin/env bash
# Launch a bigdl_tpu training script on TPU hosts (the reference's
# scripts/spark-submit-with-bigdl.sh role: one wrapper that wires the
# runtime's environment so user scripts stay deployment-agnostic).
#
# Single host (one TPU VM):
#   scripts/tpu-host-run.sh train.py --batch-size 1024
#
# Multi-host (a TPU pod slice): run the SAME command on every host, with
# the coordinator address and this host's index set — jax.distributed
# picks them up through Engine.init(distributed=True):
#   BIGDL_TPU_COORDINATOR=10.0.0.2:8476 BIGDL_TPU_NUM_HOSTS=4 \
#   BIGDL_TPU_HOST_INDEX=0 scripts/tpu-host-run.sh train.py
#
# GKE/managed runtimes usually set MEGASCALE/JAX_* variables themselves;
# this wrapper only fills what is missing, never overrides.
set -euo pipefail

if [ $# -lt 1 ]; then
    echo "usage: $(basename "$0") <script.py> [args...]" >&2
    exit 1
fi

BIGDL_TPU_HOME="${BIGDL_TPU_HOME:-$(cd "$(dirname "$0")/.." && pwd)}"

# the package must be importable: installed wheel, or the repo checkout
if ! python -c "import bigdl_tpu" 2>/dev/null; then
    export PYTHONPATH="${BIGDL_TPU_HOME}${PYTHONPATH:+:${PYTHONPATH}}"
fi
if ! python -c "import bigdl_tpu" 2>/dev/null; then
    echo "Cannot import bigdl_tpu (looked at ${BIGDL_TPU_HOME});" \
         "install the wheel from scripts/make_dist.sh or set" \
         "BIGDL_TPU_HOME to the repo checkout" >&2
    exit 1
fi

# TPU backend unless the caller pinned one (CPU dev boxes keep working)
export JAX_PLATFORMS="${JAX_PLATFORMS:-tpu}"

# multi-host wiring for jax.distributed (Engine.init(distributed=True));
# all three must come together or not at all
if [ -n "${BIGDL_TPU_COORDINATOR:-}" ]; then
    : "${BIGDL_TPU_NUM_HOSTS:?set BIGDL_TPU_NUM_HOSTS with COORDINATOR}"
    : "${BIGDL_TPU_HOST_INDEX:?set BIGDL_TPU_HOST_INDEX with COORDINATOR}"
    export JAX_COORDINATOR_ADDRESS="${BIGDL_TPU_COORDINATOR}"
    export JAX_NUM_PROCESSES="${BIGDL_TPU_NUM_HOSTS}"
    export JAX_PROCESS_ID="${BIGDL_TPU_HOST_INDEX}"
fi

# persistent XLA compile cache: a ResNet-50 step costs most of a minute
# to compile; keep it across restarts (checkpoint resume makes restarts
# routine). Where the variable is set it is left alone; where it is not,
# the one fixed default of bigdl_tpu/utils/compile_cache.py is used.
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$(python -m bigdl_tpu.utils.compile_cache)}"

exec python "$@"

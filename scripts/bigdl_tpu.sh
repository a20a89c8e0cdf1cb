#!/usr/bin/env bash
# Environment bootstrap + launcher (ref scripts/bigdl.sh: the reference
# exports its MKL/OMP contract then execs the user command; here the
# contract is the JAX/TPU runtime configuration, SURVEY.md §5.6).
#
#   ./scripts/bigdl_tpu.sh [--platform cpu|tpu] [--hosts N] -- <cmd...>
#
# Exports:
#   JAX_PLATFORMS            the JAX platform (--platform sets it)
#   BIGDL_TPU_CHECK_SINGLETON one trainer per process guard (default on)
#   XLA_FLAGS                 host-device count for CPU simulation
set -euo pipefail

PLATFORM=""
HOSTS=""
while [[ $# -gt 0 ]]; do
  case "$1" in
    --platform) PLATFORM="$2"; shift 2 ;;
    --hosts)    HOSTS="$2"; shift 2 ;;
    --) shift; break ;;
    *) break ;;
  esac
done

if [[ -n "$PLATFORM" ]]; then
  export JAX_PLATFORMS="$PLATFORM"
  if [[ "$PLATFORM" == "cpu" && -n "$HOSTS" ]]; then
    # simulate an N-device mesh on CPU (the test/dry-run configuration)
    export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=${HOSTS}"
  fi
fi
export BIGDL_TPU_CHECK_SINGLETON="${BIGDL_TPU_CHECK_SINGLETON:-1}"

if [[ $# -eq 0 ]]; then
  echo "usage: $0 [--platform cpu|tpu] [--hosts N] -- <command...>" >&2
  exit 2
fi
exec "$@"

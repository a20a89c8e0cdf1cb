#!/usr/bin/env bash
# Run the test suite as N parallel pytest processes, splitting by file
# (the image has no pytest-xdist; test files are independent — each
# process gets its own jax CPU backend and tmp dirs).
#
#     bash scripts/run_tests_sharded.sh            # default profile, N=3
#     N=4 bash scripts/run_tests_sharded.sh --full # CI-full in 4 shards
set -u
cd "$(dirname "$0")/.."
N=${N:-3}

# Fast resilience gate first (FAULTS_GATE=0 skips): the fault matrix is
# small and tier-1, and a broken retry/failover/resume path should fail
# the run in seconds, before the full shards spend their minutes.
# test_kvcache.py carries the pool-exhaustion faults (typed rejection
# vs deferral) — KV memory pressure is a first-class fault domain.
# test_spec_decode.py carries the serving.verify site (a transient
# demotes speculating slots instead of killing streams) and the
# acceptance-collapse demotion matrix.
# test_disagg.py carries the serving.migrate site (a transient retries
# the KV-chain export; a lost payload re-prefills on the decode
# replica — zero accepted-request loss either way).
# Observability gate first (OBS_GATE=0 skips): tracing, the metric
# registry, the telemetry sampler, and the flight recorder are the
# instruments every OTHER failure is diagnosed with — a broken
# instrument should fail the run in seconds, before anything else
# burns minutes producing evidence nothing can read.
if [ "${OBS_GATE:-1}" = "1" ]; then
  python -m pytest tests/test_obs.py tests/test_flight.py \
    tests/test_memledger.py -q -m "not slow" || exit 1
fi

if [ "${FAULTS_GATE:-1}" = "1" ]; then
  python -m pytest tests/test_resilience.py tests/test_traffic.py \
    tests/test_kvcache.py tests/test_spec_decode.py tests/test_disagg.py \
    tests/test_router.py \
    -q -m faults || exit 1
fi

# Artifact schema lint: TRACE_*/FLIGHT_* files — a truncated or
# key-drifted one fails silently downstream (resume identity never
# matches, a forensic bundle reads as empty), so it should fail loudly
# here, in seconds.
python scripts/validate_artifact.py || exit 1

# Placement gate: mesh-sliced serving is agreement-critical (a wrong
# sharding rule serves silently wrong numbers from every TP slot) and
# the whole file runs on the fake 8-device CPU mesh in seconds.
if [ "${PLACEMENT_GATE:-1}" = "1" ]; then
  python -m pytest tests/test_placement.py -q -m "not slow" || exit 1
fi

files=(tests/test_*.py)
pids=()
for i in $(seq 0 $((N - 1))); do
  subset=()
  for j in "${!files[@]}"; do
    if [ $((j % N)) -eq "$i" ]; then subset+=("${files[$j]}"); fi
  done
  python -m pytest "${subset[@]}" -q "$@" &
  pids+=($!)
done
rc=0
for p in "${pids[@]}"; do
  wait "$p" || rc=1
done
exit $rc

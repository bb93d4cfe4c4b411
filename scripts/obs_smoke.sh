#!/usr/bin/env bash
# Observability smoke: prove the diagnostics plane end to end (ISSUE 6).
#
# Drives tests/test_obs_chaos.py (`-m chaos`): boot the Event Server and
# the Engine Server, inject a seeded PIO_FAULTS `corrupt=` (NaN) fault
# into a fold tick, and assert that
#   - the guard layer's rejection automatically captured an incident
#     bundle under <PIO_FS_BASEDIR>/incidents/ whose flight records,
#     trace links and registry lineage reconstruct the
#     event -> fold -> gate -> reject chain (`pio incidents show`),
#   - GET /health.json flips the affected SLO (the guarded-deploys
#     event budget) within one fast burn window,
#   - the flight recorder stayed non-blocking throughout (drop-on-full,
#     fsync-light — serving queries kept answering 200).
# Chaos-marked, so the tier-1 `-m 'not slow'` lane never runs it; this
# script is the CI/operator entry point, next to chaos_smoke.sh.
#
# Determinism: seeded injectors, CPU jax, pinned hash seed.
set -euo pipefail
cd "$(dirname "$0")/.."

# CPU on purpose: a behaviour smoke, not a device run (chip_smoke.py is)
export JAX_PLATFORMS=cpu
export PYTHONHASHSEED=0
# never inherit ambient chaos, a PIO_GUARD kill switch that would
# disarm the layer producing the incident, or a PIO_FLIGHT/PIO_INCIDENTS
# off-switch that would mute the very plane under test
unset PIO_FAULTS 2>/dev/null || true
unset PIO_GUARD 2>/dev/null || true
unset PIO_FLIGHT 2>/dev/null || true
unset PIO_INCIDENTS 2>/dev/null || true

exec python -m pytest tests/test_obs_chaos.py -q -m chaos \
    -p no:cacheprovider -p no:randomly \
    --continue-on-collection-errors "$@"

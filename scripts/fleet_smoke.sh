#!/usr/bin/env bash
# Fleet observability smoke: prove the cross-process obs plane end to
# end (ISSUE 13).
#
# Drives tests/test_fleet_chaos.py (`-m chaos`): boot the Event Server,
# the Engine Server, and a `pio update --follow` scheduler as THREE OS
# processes sharing one PIO_FS_BASEDIR, SIGKILL the event server, and
# assert that
#   - `pio fleet status` reports the death within ONE heartbeat (the
#     same-host pid probe closes the fresh-heartbeat window a SIGKILL
#     leaves; no mtime guessing anywhere),
#   - federation of the SURVIVORS keeps working: the merged
#     /fleet/metrics scrape still carries the engine server's series
#     under {role,pid} labels and the /health.json rollup still
#     answers,
#   - no member ever deregistered itself — the registry's record of
#     the corpse IS the report.
# Chaos-marked, so the tier-1 `-m 'not slow'` lane never runs it; this
# script is the CI/operator entry point, next to obs_smoke.sh.
#
# Determinism: CPU jax, pinned hash seed, no ambient chaos/kill
# switches.
set -euo pipefail
cd "$(dirname "$0")/.."

# CPU on purpose: a behaviour smoke, not a device run (chip_smoke.py is)
export JAX_PLATFORMS=cpu
export PYTHONHASHSEED=0
# never inherit an ambient fleet/flight/incidents off-switch that would
# mute the very plane under test, nor chaos aimed elsewhere
unset PIO_FAULTS 2>/dev/null || true
unset PIO_FLEET 2>/dev/null || true
unset PIO_FLIGHT 2>/dev/null || true
unset PIO_INCIDENTS 2>/dev/null || true
unset PIO_FLEET_HEARTBEAT_S 2>/dev/null || true

exec python -m pytest tests/test_fleet_chaos.py -q -m chaos \
    -p no:cacheprovider -p no:randomly \
    --continue-on-collection-errors "$@"

#!/usr/bin/env bash
# Fleet tenant failover smoke: prove the placement control plane end
# to end (ISSUE 18).
#
# Drives tests/test_failover_chaos.py (`-m chaos`): boot TWO serving
# hosts plus the event server as separate OS processes on one
# PIO_FS_BASEDIR, admit two tenants onto host A (one with a fold
# scheduler following the event tail), SIGKILL host A, and assert that
#   - the placement controller re-places EVERY stranded tenant onto
#     host B within 60s, reloaded from registry lineage with the
#     scheduler's cursor resumed from the published lineage (fresh
#     events keep becoming published instances on the survivor),
#   - clients hammering through the TenantRouter for the whole episode
#     see added latency but ZERO errors — stale routes 409 off the
#     generation fence and connection failures retry under the stock
#     backoff policy onto the survivor,
#   - the episode lands as exactly ONE host_failover incident bundle
#     naming the dead member and each re-placed tenant.
# Chaos-marked, so the tier-1 `-m 'not slow'` lane never runs it; this
# script is the CI/operator entry point, next to fleet_smoke.sh.
#
# Determinism: CPU jax, pinned hash seed, no ambient chaos/kill
# switches.
set -euo pipefail
cd "$(dirname "$0")/.."

# CPU on purpose: a behaviour smoke, not a device run (chip_smoke.py is)
export JAX_PLATFORMS=cpu
export PYTHONHASHSEED=0
# never inherit an ambient fleet/flight/incidents off-switch that would
# mute the very plane under test, nor chaos or auth aimed elsewhere
unset PIO_FAULTS 2>/dev/null || true
unset PIO_FLEET 2>/dev/null || true
unset PIO_FLIGHT 2>/dev/null || true
unset PIO_INCIDENTS 2>/dev/null || true
unset PIO_FLEET_HEARTBEAT_S 2>/dev/null || true
unset PIO_FLEET_LIVENESS_S 2>/dev/null || true
unset PIO_AUTH 2>/dev/null || true
unset PIO_HBM_BUDGET 2>/dev/null || true

exec python -m pytest tests/test_failover_chaos.py -q -m chaos \
    -p no:cacheprovider -p no:randomly \
    --continue-on-collection-errors "$@"

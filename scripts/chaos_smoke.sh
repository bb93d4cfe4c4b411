#!/usr/bin/env bash
# Chaos smoke: run the seeded fault-injection suite deterministically.
#
# The chaos tests (`-m chaos`) drive the real failure paths against
# seeded fault injection:
#   - tests/test_chaos.py        — infrastructure faults (ISSUE 3):
#     ingest -> spill -> replay zero-loss, breaker cycling, saturation
#     shed, degraded serving, scheduler supervision.
#   - tests/test_guard_chaos.py  — MODEL faults (ISSUE 5): `corrupt=`
#     (NaN) injection into a fold tick, proving end-to-end that the
#     sentinel aborts a poisoned tick, the pre-swap gates refuse a
#     poisoned publish, and — with gates off — the canary confines the
#     poisoned version to its traffic fraction and the watchdog rolls
#     back to last-known-good within one window with zero non-canary
#     5xx.
# They are excluded from the tier-1 `-m 'not slow'` lane (the chaos
# marker implies slow — tests/conftest.py); this script is their entry
# point for CI and for an operator rehearsing failure modes locally.
#
# Determinism: every injector in the suite is seeded (specs carry
# seed=...), jax runs on CPU, and hash randomization is pinned, so a
# red run reproduces byte-for-byte.
set -euo pipefail
cd "$(dirname "$0")/.."

# CPU on purpose: a behaviour smoke, not a device run (chip_smoke.py is)
export JAX_PLATFORMS=cpu
export PYTHONHASHSEED=0
# never inherit ambient chaos into the suite's own controlled specs —
# and never inherit a PIO_GUARD kill switch that would disarm the very
# layer the corruption scenario proves
unset PIO_FAULTS 2>/dev/null || true
unset PIO_GUARD 2>/dev/null || true

exec python -m pytest tests/ -q -m chaos -p no:cacheprovider \
    -p no:randomly --continue-on-collection-errors "$@"

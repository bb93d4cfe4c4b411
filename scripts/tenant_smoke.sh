#!/usr/bin/env bash
# Multi-tenant serving host smoke (ISSUE 15): the packing acceptance
# scenario — three REAL engine tenants (recommendation, similarproduct,
# classification/naive_bayes) trained through the normal pipeline and
# packed on one device behind a tenancy.ServingHost under a
# forced-small PIO_TABLE_BUDGET_BYTES (set inside the test):
#   - queries route by /engines/<tenant>/ key, each family answers
#     correctly through its own slot;
#   - pio_engine_hbm_bytes{tenant} sums to the measured per-tenant
#     resident bytes (the serving-only naive_bayes tenant reads 0);
#   - budget pressure fires real LRU evictions, and an evicted
#     tenant's readmission serves BYTE-IDENTICAL responses (the host
#     mirrors are the truth; re-upload rides the budget-checked
#     cached_put_rows / ShardedTable.device cold paths);
#   - rolling back one tenant's canary leaves the other tenants'
#     models, result-cache namespaces and last-known-good pins
#     untouched;
#   - steady-state multi-tenant serving compiles NOTHING after the
#     per-tenant AOT warm (tenants share one compile-plane ladder);
#   - GET /tenants/signals.json (ISSUE 17) attributes the device:
#     per-tenant deviceTimeShare sums to <= 1.0 across the whole map
#     (incl. the "" untenanted share), occupancy shares stay in
#     [0, 1], and each row's hbmBytes equals the budget gauges.
#
# The test is slow-marked (never tier-1); this script is its CI /
# operator entry point.
set -euo pipefail
cd "$(dirname "$0")/.."

# CPU on purpose: a behaviour smoke, not a device run (chip_smoke.py is)
export JAX_PLATFORMS=cpu
export PYTHONHASHSEED=0
# hermetic: no ambient chaos, guard kill switch, stale budget, or a
# disabled serve cache (the isolation assertions exercise it)
unset PIO_FAULTS 2>/dev/null || true
unset PIO_GUARD 2>/dev/null || true
unset PIO_TABLE_BUDGET_BYTES 2>/dev/null || true
unset PIO_SERVE_CACHE 2>/dev/null || true

exec python -m pytest tests/test_tenant_scale.py -q -m slow \
    -p no:cacheprovider -p no:randomly "$@"

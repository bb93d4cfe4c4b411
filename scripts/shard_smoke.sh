#!/usr/bin/env bash
# Sharded online plane smoke (ISSUE 12): the over-budget acceptance
# scenario on the suite's virtual 8-device CPU mesh (JAX_PLATFORMS=cpu;
# tests/conftest.py sets jax_num_cpu_devices=8).
#
# tests/test_sharded_scale.py trains, folds >= 3 consecutive ticks and
# serves a vocabulary whose factor-table bytes exceed the enforced
# per-device table budget (PIO_TABLE_BUDGET_BYTES, set inside the
# test) — possible only because the tables are model-sharded:
#   - replicated upload/fold paths REFUSE the budget violation;
#   - the sharded layout pays table/N per device and proceeds;
#   - steady-state ticks move O(touched-row plans) over the host link
#     (no full-table h2d/d2h), asserted via the thread-h2d counter
#     behind pio_fold_upload_bytes_total;
#   - pio_hbm_table_bytes reads exactly 1/N of the tables per shard;
#   - serve answers come from per-shard top-k + cross-shard merge
#     with exact parity vs a host-numpy reference ranking;
#   - the tail of the tick chain compiles nothing (PR 9 acceptance
#     extended to the sharded executables).
#
# The test is slow-marked (never tier-1); this script is its CI /
# operator entry point.
set -euo pipefail
cd "$(dirname "$0")/.."

# CPU on purpose: a behaviour smoke, not a device run (chip_smoke.py is)
export JAX_PLATFORMS=cpu
export PYTHONHASHSEED=0
# hermetic: no ambient chaos, guard kill switch, or stale budget
unset PIO_FAULTS 2>/dev/null || true
unset PIO_GUARD 2>/dev/null || true
unset PIO_TABLE_BUDGET_BYTES 2>/dev/null || true

exec python -m pytest tests/test_sharded_scale.py -q -m slow \
    -p no:cacheprovider -p no:randomly "$@"

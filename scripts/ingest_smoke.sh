#!/usr/bin/env bash
# Ingest smoke (ISSUE 7): prove the overhauled write path keeps the
# durability contract under concurrent fire.
#
# Runs the chaos-marked concurrent-ingest burst: 8 writers through the
# admission micro-batcher + nativelog-style group commit, plus a
# columnar bulk write (/events/columnar.json), against a store with
# seeded 30% write-fault injection. The bar is the acceptance
# criterion verbatim — every acked event is either in the store or
# replayed from the spill WAL after recovery: zero loss, zero
# duplicates. Also re-runs the PR 3 single-event zero-loss acceptance
# so a group-commit regression against the OLD path cannot hide.
#
# Chaos tests imply the slow marker (tests/conftest.py), so none of
# this is in the tier-1 lane; this script is the CI / operator entry
# point. Determinism: seeded injectors, CPU jax, pinned hash seed.
set -euo pipefail
cd "$(dirname "$0")/.."

# CPU on purpose: a behaviour smoke, not a device run (chip_smoke.py is)
export JAX_PLATFORMS=cpu
export PYTHONHASHSEED=0
# never inherit ambient chaos or ingest tuning into the controlled run
unset PIO_FAULTS 2>/dev/null || true
unset PIO_INGEST_GROUP_COMMIT_MS 2>/dev/null || true

exec python -m pytest -q -m chaos -p no:cacheprovider -p no:randomly \
    --continue-on-collection-errors \
    tests/test_chaos.py::TestConcurrentIngestBurstChaos \
    tests/test_chaos.py::TestSpillReplayAcceptance \
    "$@"

#!/usr/bin/env bash
# Cold-start smoke: prove the persistent compile cache + AOT warm path
# end to end across a REAL process restart (ISSUE 9).
#
# Two fresh interpreters share one persistent-cache directory and each
# run the deploy-shaped workload: train a small ALS model (compiles the
# sweep executables), AOT-warm the serving ladder (compiles
# batch_predict buckets), then serve the first query. Asserts:
#   - process 1 (cold cache) pays real XLA backend compiles
#     (pcache misses > 0, compile seconds substantial);
#   - process 2 (warm cache) answers EVERY compile from disk
#     (pcache hits >= process 1's misses, zero misses) and its
#     attributed XLA compile seconds are >= 5x smaller.
#
# The >= 5x bar is asserted on `pio_compile_executable_seconds_total`
# (the wall the cache exists to eliminate) rather than process wall:
# on the CPU container, trace/lowering — which the XLA cache does not
# cover, by design — dominates these small programs, capping the
# end-to-end wall gain near 2-3x; on a TPU backend compile dominates
# both, and the same mechanism carries the full deploy-to-first-query
# ratio (chip_smoke.py reports the deploy process's cache hits there).
# Both walls are printed for the log.
#
# Chaos-class tooling: never part of the tier-1 lane; this script is
# the CI/operator entry point next to chaos_smoke.sh / obs_smoke.sh.
set -euo pipefail
cd "$(dirname "$0")/.."

# CPU on purpose: a behaviour smoke, not a device run (chip_smoke.py is)
export JAX_PLATFORMS=cpu
export PYTHONHASHSEED=0
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
unset PIO_XLA_CACHE 2>/dev/null || true
unset PIO_AOT 2>/dev/null || true
unset JAX_COMPILATION_CACHE_DIR 2>/dev/null || true

WORK=$(mktemp -d)
trap 'rm -rf "$WORK"' EXIT
CACHE="$WORK/xla_cache"
export PIO_FS_BASEDIR="$WORK/store"

PROBE="$WORK/probe.py"
cat > "$PROBE" <<'EOF'
import json, sys, time
import numpy as np
from predictionio_tpu.compile.cache import cache_status, \
    enable_persistent_cache
from predictionio_tpu.obs import costmon
enable_persistent_cache(root=sys.argv[1])
from predictionio_tpu.compile.aot import warm_models
from predictionio_tpu.data.bimap import EntityIdIxMap
from predictionio_tpu.models.recommendation import (ALSAlgorithm,
    ALSAlgorithmParams, RecommendationModel)
from predictionio_tpu.ops.als import ALSConfig, als_train
from predictionio_tpu.ops.ratings import RatingsCOO

t_deploy = time.perf_counter()
rng = np.random.default_rng(0)
nnz, n_u, n_i, rank = 8000, 400, 500, 48
coo = RatingsCOO(rng.integers(0, n_u, nnz).astype(np.int32),
                 rng.integers(0, n_i, nnz).astype(np.int32),
                 rng.integers(1, 6, nnz).astype(np.float32), n_u, n_i)
als = als_train(coo, ALSConfig(rank=rank, iterations=1))
model = RecommendationModel(
    als, EntityIdIxMap.build(["u%d" % i for i in range(n_u)]),
    EntityIdIxMap.build(["i%d" % i for i in range(n_i)]))
algo = ALSAlgorithm(ALSAlgorithmParams(rank=rank))
warm_models([algo], [model], batch_hint=16)
q = algo.query_class.from_dict({"user": "u1", "num": 10})
t_q = time.perf_counter()
out = algo.batch_predict(model, [(0, q)])
first_ms = (time.perf_counter() - t_q) * 1000
assert out and out[0][1].item_scores, "first query answered nothing"
pc = costmon.pcache_totals()
print(json.dumps({
    "deploy_to_first_query_s": time.perf_counter() - t_deploy,
    "first_query_ms": first_ms,
    "compile_s": sum(costmon.compile_seconds_by_executable().values()),
    "pcache_hits": pc["hits"], "pcache_misses": pc["misses"],
    "cache_entries": cache_status()["entries"]}))
EOF

echo "== process 1 (cold cache) =="
COLD=$(python "$PROBE" "$CACHE" | tail -1)
echo "$COLD"
echo "== process 2 (warm cache) =="
WARM=$(python "$PROBE" "$CACHE" | tail -1)
echo "$WARM"

COLD="$COLD" WARM="$WARM" python - <<'EOF'
import json, os
cold = json.loads(os.environ["COLD"])
warm = json.loads(os.environ["WARM"])
assert cold["pcache_misses"] > 0, "cold process compiled nothing?"
assert cold["cache_entries"] > 0, "cold process wrote no cache entries"
assert warm["pcache_misses"] == 0, (
    f"warm process missed the cache {warm['pcache_misses']} time(s)")
assert warm["pcache_hits"] >= cold["pcache_misses"], (warm, cold)
ratio = cold["compile_s"] / max(warm["compile_s"], 1e-9)
print(f"XLA compile seconds: cold {cold['compile_s']:.2f}s, "
      f"warm {warm['compile_s']:.2f}s -> {ratio:.1f}x")
print(f"deploy-to-first-query wall: cold "
      f"{cold['deploy_to_first_query_s']:.2f}s, warm "
      f"{warm['deploy_to_first_query_s']:.2f}s")
assert ratio >= 5.0, (
    f"warm-cache compile seconds only {ratio:.1f}x better (< 5x)")
print("AOT SMOKE OK")
EOF

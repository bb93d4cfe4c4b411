#!/usr/bin/env python3
"""`pio train`'s ALS at a benchmark configuration's published size, through
the normal path and nothing else: ratings from the configuration's file and
the seed (benchmark/lib/datagen, as the cells make them), then `als_train`
with the parameters `ALSAlgorithm.train` (models/recommendation.py) hands it
for this host: the mesh (`model_mesh` over all the chips under
`factor_sharding: "model"`), `keep_sharded`, the sentinel ON (the default),
host-side init, the final fetch. What the benchmark's train cells leave out
is exactly what this runs.

    python3 scripts/train_at_size.py \
        --config benchmark/configs/rec-amazon14-all-r200.json \
        --seed 3401 --iterations 2 [--scale 0.001]

Every phase is appended to `chiprun_out/train_at_size.jsonl` as it ends, so
a run that is cut still says how far it came. The last record holds
als_train's telemetry, each chip's `peak_bytes_in_use`, and a check of the
result that needs nothing of the program: the last half-sweep solved every
item row from the user table it returned, so 64 item rows and the heaviest
are solved again here in float64 numpy from those user rows and compared
(bfloat16 Gram operands: expect 1e-3 to 3e-3, the cell's `item_end_err_*`).
`--scale` cuts the three counts for a CPU run of the control flow."""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK_ROWS = 64
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--iterations", type=int, default=2)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out"),
                    help="directory of train_at_size.jsonl")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, "train_at_size.jsonl"), "a")
    t_start = time.perf_counter()

    def record(phase: str, **what) -> None:
        line = json.dumps({"phase": phase, "seed": args.seed,
                           "at_s": round(time.perf_counter() - t_start, 2),
                           **what})
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    import jax

    from benchmark.lib import datagen
    from predictionio_tpu.compile.cache import enable_persistent_cache
    from predictionio_tpu.ops import als
    from predictionio_tpu.ops.ratings import RatingsCOO
    from predictionio_tpu.parallel.mesh import current_mesh, model_mesh
    from predictionio_tpu.parallel.sharded_table import table_rows
    enable_persistent_cache()
    with open(args.config) as f:
        c = json.load(f)
    for key in ("n_users", "n_items", "n_ratings"):
        c[key] = max(int(c[key] * args.scale), 64)
    if args.scale != 1.0:          # the caps follow, as a tiny tree's do
        c["assumed"] = dict(c["assumed"],
                            user_degree_cap=c["n_items"] // 2,
                            item_degree_cap=c["n_users"] // 2)
    devices = jax.devices()
    record("start", config=c["name"], scale=args.scale,
           n_users=c["n_users"], n_items=c["n_items"],
           n_ratings=c["n_ratings"], platform=devices[0].platform,
           device_kind=devices[0].device_kind, chips=len(devices))

    t0 = time.perf_counter()
    user_idx, item_idx, value = datagen.ratings(c, args.seed)
    coo = RatingsCOO(user_idx, item_idx, value, c["n_users"], c["n_items"])
    record("generate", seconds=time.perf_counter() - t0, nnz=int(coo.nnz))

    # ALSAlgorithm.train's mesh and ALSConfig, field for field
    sharded = c.get("factor_sharding", "replicated") == "model"
    mesh = model_mesh(len(devices)) if sharded else current_mesh()
    cfg = als.ALSConfig(
        rank=int(c["rank"]), iterations=args.iterations,
        lam=float(c["lam"]), sweep_chunk=int(c.get("sweep_chunk", 0)),
        seed=args.seed % (2 ** 31),
        compute_dtype=als.default_compute_dtype(),
        factor_sharding="model" if sharded else "replicated",
        keep_sharded=sharded)
    assert cfg.sentinel, "the normal path runs with the sentinel on"
    telemetry: dict = {}

    def progress() -> None:        # als_train fills telemetry as it goes
        seen = 0
        while "fetch_s" not in telemetry:
            if len(telemetry) > seen:
                seen = len(telemetry)
                record("als_train so far", telemetry=dict(telemetry))
            time.sleep(2.0)

    threading.Thread(target=progress, daemon=True).start()
    t0 = time.perf_counter()
    model = als.als_train(coo, cfg, mesh=mesh, telemetry=telemetry)
    record("als_train", seconds=time.perf_counter() - t0,
           telemetry=telemetry)

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    U, V = model.user_factors, model.item_factors
    # the last item half-sweep's systems, solved again from the user rows
    # als_train returned
    rng = np.random.default_rng([args.seed, 9])
    degree = np.bincount(item_idx, minlength=c["n_items"])
    rated = np.flatnonzero(degree)
    picked = np.unique(np.concatenate([
        rng.choice(rated, min(CHECK_ROWS, rated.size), replace=False),
        [int(np.argmax(degree))]]))
    of_picked = np.flatnonzero(np.isin(item_idx, picked))
    errs = []
    for i in picked:
        sel = of_picked[item_idx[of_picked] == i]
        Y = np.asarray(table_rows(U, user_idx[sel]), np.float64)
        r = value[sel].astype(np.float64)
        A = Y.T @ Y + float(c["lam"]) * sel.size * np.eye(cfg.rank)
        want = np.linalg.solve(A, Y.T @ r)
        got = np.asarray(table_rows(V, np.array([i])), np.float64)[0]
        errs.append(float(np.linalg.norm(got - want)
                          / np.linalg.norm(want)))
    finite = bool(np.isfinite(table_rows(V, picked)).all())
    record("done", memory_peak_bytes=peaks,
           user_table=[type(U).__name__, list(U.shape)],
           item_table=[type(V).__name__, list(V.shape)],
           rows_checked=int(picked.size), heaviest_item=int(degree.max()),
           item_row_err_p50=float(np.median(errs)),
           item_row_err_max=float(np.max(errs)), finite=finite,
           total_s=time.perf_counter() - t_start)
    return 0 if finite and max(errs) < 0.02 else 1


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness: ALS training throughput + REST predict latency.

The reference publishes no numbers (BASELINE.md), so this harness defines
the measurement: synthetic MovieLens-20M-shaped ratings (138,493 users x
26,744 items x 20M ratings, power-law popularity), explicit ALS rank=200 —
the BASELINE.json north-star workload — timed per full iteration (user
sweep + item sweep, MLlib's iteration unit). Secondary: p50 latency of
POST /queries.json against the trained model behind the real engine server.

vs_baseline compares against SPARK_CPU_BASELINE_RATINGS_PER_SEC, an assumed
single-node Spark-1.3 MLlib ALS figure for this workload (the reference's
substrate; it cannot be measured in this environment). The north-star
">=10x Spark-on-CPU" therefore corresponds to vs_baseline >= 10.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import json
import os
import sys
import threading
import time
from contextlib import contextmanager

import numpy as np

SPARK_CPU_BASELINE_RATINGS_PER_SEC = 2.0e5

# Peaks per device kind, one table: dense-matmul throughput (flop/s, bf16
# with f32 accumulation) and HBM bandwidth (bytes/s). Sources: Google
# Cloud TPU documentation per generation (v5e: 197 TFLOP/s, 819 GB/s;
# v4: 275, 1228; v5p: 459, 2765; v6e: 918, 1640). The flop/s peak
# SELF-VALIDATES the measurement: a benched number implying more than
# the chip can physically do is a timing bug, and the harness refuses to
# report it. A device that is not in the table is an error, not a
# default. "cpu" is the named entry --tiny runs resolve to: nominal host
# figures, only ever printed next to platform=cpu.
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),    # v5e
    "TPU v5": (459e12, 2765e9),        # v5p
    "TPU v4": (275e12, 1228e9),
    "TPU v6 lite": (918e12, 1640e9),   # v6e
    "cpu": (2e12, 50e9),
}


def _device_lookup() -> tuple:
    import jax
    kind = jax.devices()[0].device_kind
    # longest prefix first: "TPU v5 lite" must not resolve as "TPU v5"
    for prefix in sorted(DEVICE_PEAKS, key=len, reverse=True):
        if kind.startswith(prefix):
            return DEVICE_PEAKS[prefix]
    raise KeyError(
        f"device_kind {kind!r} is not in bench.DEVICE_PEAKS "
        f"({sorted(DEVICE_PEAKS)}); add its published peaks with their "
        f"source before benchmarking on it")


def device_peak_flops() -> float:
    return _device_lookup()[0]


def device_hbm_bw() -> float:
    return _device_lookup()[1]


def als_iteration_flops(user_plan, item_plan, rank: int) -> float:
    """Counted device work per full ALS iteration (both half-sweeps), from
    the actual padded batch shapes: Gram einsum 2*B*K*R^2 + rhs 2*B*K*R per
    batch, Cholesky B*R^3/3, two triangular solves 2*B*R^2 each."""
    total = 0.0
    for plan in (user_plan, item_plan):
        for b in plan.batches:
            B, K = b.shape
            total += 2.0 * B * K * rank * rank   # Gram
            total += 2.0 * B * K * rank          # rhs
            total += B * rank ** 3 / 3.0         # Cholesky
            total += 2.0 * 2.0 * B * rank ** 2   # tri solves
    return total


def als_iteration_hbm_bytes(user_plan, item_plan, rank: int,
                            compute_dtype: str,
                            factor_dtype: str = "float32") -> float:
    """Memory traffic per full ALS iteration, from the actual padded batch
    shapes — the numerator of the memory-bound roofline the measured
    s/iteration is compared against. Per batch [B, K]: counterpart factor
    row gathers B*K*R at the STORAGE dtype (the dominant term; random
    access, so full rows — rounds 1-3 priced this at the compute dtype,
    understating the bound 2x whenever bf16 einsums read f32 tables),
    ratings val+mask+idx reads, one write + one read of the normal
    matrices (min(K, R)-dim — the dual/Woodbury route solves K x K when
    K < R; CG re-reads stay in VMEM), rhs write+read, result scatter."""
    db = 2.0 if compute_dtype == "bfloat16" else 4.0
    fb = 2.0 if factor_dtype == "bfloat16" else 4.0
    total = 0.0
    for plan in (user_plan, item_plan):
        for b in plan.batches:
            B, K = b.shape
            S = min(K, rank)
            total += B * K * rank * fb           # factor-row gathers
            total += B * K * (4.0 + 4.0 + 4.0)   # val + mask + idx (f32/i32)
            total += 2.0 * B * S * S * db        # normal-matrix write+read
            total += 2.0 * B * rank * fb         # rhs write+read
            total += B * rank * fb               # solved rows scatter
    return total

# persistent XLA compilation cache: warmup compiles are paid once per
# machine, not per run — the product's one rule (compile/cache.py:
# $JAX_COMPILATION_CACHE_DIR, else <checkout>/.xla_cache)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from predictionio_tpu.compile.cache import \
    enable_persistent_cache  # noqa: E402


def _stage(msg: str) -> None:
    """Progress mark on stderr: what the run was doing when it died."""
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def synthetic_ml20m(n_users, n_items, nnz, seed=0):
    """Power-law popularity + lognormal user activity, ML-20M shaped."""
    rng = np.random.default_rng(seed)
    # user activity: lognormal, scaled to sum ~ nnz
    raw = rng.lognormal(mean=0.0, sigma=1.1, size=n_users)
    counts = np.maximum(1, (raw / raw.sum() * nnz)).astype(np.int64)
    diff = nnz - counts.sum()
    counts[0] += max(diff, 1 - counts[0])
    user_idx = np.repeat(np.arange(n_users, dtype=np.int32),
                         counts).astype(np.int32)
    total = user_idx.shape[0]
    # item popularity: zipf-ish
    pop = 1.0 / np.arange(1, n_items + 1) ** 1.1
    pop /= pop.sum()
    item_idx = rng.choice(n_items, size=total, p=pop).astype(np.int32)
    rating = rng.integers(1, 6, size=total).astype(np.float32)
    return user_idx, item_idx, rating


def hard_sync(x) -> float:
    """Close a timed region with a one-element host fetch: it cannot
    complete before the device finished the enqueued chain."""
    import jax
    return float(np.asarray(jax.device_get(x[:1, :1]))[0, 0])


def prepare_als_run(mesh, ratings, cfg, seed: int = 1,
                    batch_multiple: int = 1):
    """The shared scaffold of every timed ALS benchmark: build both
    solve plans, upload them (sweep-chunk merged), init device-resident
    factors and hyperparameter scalars. Returns a dict so callers pick
    what they need."""
    from predictionio_tpu.ops import als as A
    from predictionio_tpu.ops.ratings import plan_for_items, plan_for_users

    user_plan = plan_for_users(ratings, work_budget=cfg.work_budget,
                               batch_multiple=batch_multiple,
                               bucket_ratio=cfg.bucket_ratio)
    item_plan = plan_for_items(ratings, work_budget=cfg.work_budget,
                               batch_multiple=batch_multiple,
                               bucket_ratio=cfg.bucket_ratio)
    chunk = A.resolve_sweep_chunk(cfg.sweep_chunk, mesh.n_devices)
    return {
        "user_plan": user_plan, "item_plan": item_plan,
        "user_batches": A._upload_plan(mesh, user_plan, chunk),
        "item_batches": A._upload_plan(mesh, item_plan, chunk),
        "U": mesh.put_replicated(
            A._init_factors(ratings.n_users, cfg.rank, seed, 1)),
        "V": mesh.put_replicated(
            A._init_factors(ratings.n_items, cfg.rank, seed, 2)),
        "lam": mesh.put_replicated(np.float32(cfg.lam)),
        "alpha": mesh.put_replicated(np.float32(cfg.alpha)),
    }


def bench_als(full_scale: bool):
    import jax
    from predictionio_tpu.ops import als as A
    from predictionio_tpu.ops.als import ALSConfig, ALSModel, als_rmse
    from predictionio_tpu.ops.ratings import RatingsCOO
    from predictionio_tpu.parallel.mesh import current_mesh

    if full_scale:
        n_users, n_items, nnz, rank = 138_493, 26_744, 20_000_000, 200
        iters_timed = 4
    else:  # CPU smoke mode — nnz >= 1M so the fixed dispatch overhead is
        # a small fraction of an iteration and scale_check_ratio ~ 1.0
        # actually validates the timing (at the old 60k, a 27 ms
        # iteration was mostly overhead and the 0.6..1.67 gate was loose)
        n_users, n_items, nnz, rank = 20_000, 4_000, 1_200_000, 32
        iters_timed = 4

    _stage("bench_als: datagen")
    t0 = time.perf_counter()
    ui, ii, vv = synthetic_ml20m(n_users, n_items, nnz)
    ratings = RatingsCOO(ui, ii, vv, n_users, n_items)
    gen_s = time.perf_counter() - t0

    enable_persistent_cache()

    mesh = current_mesh()
    from predictionio_tpu.ops.solve import resolve_solver
    cfg = ALSConfig(rank=rank, iterations=1, lam=0.05, seed=1,
                    compute_dtype=("bfloat16" if full_scale else "float32"),
                    work_budget=(1 << 20),
                    # resolve with the real device count: _run_side is
                    # called directly here, bypassing als_train's own
                    # resolution (pallas can't take GSPMD-sharded operands)
                    solver=resolve_solver("auto", mesh.n_devices))

    # host prep + one-time HBM residency for the solve plans
    _stage("bench_als: prep/upload")
    t0 = time.perf_counter()
    run = prepare_als_run(mesh, ratings, cfg, seed=cfg.seed)
    user_plan, item_plan = run["user_plan"], run["item_plan"]
    user_batches, item_batches = run["user_batches"], run["item_batches"]
    prep_s = time.perf_counter() - t0

    U, V = run["U"], run["V"]
    lam_dev, alpha_dev = run["lam"], run["alpha"]

    def run_iters(k):
        """k full iterations dispatched back-to-back, closed by hard_sync
        so the wall-clock includes execution."""
        nonlocal U, V
        t0 = time.perf_counter()
        for _ in range(k):
            U = A._run_side(user_batches, U, V, cfg, None, lam_dev, alpha_dev)
            V = A._run_side(item_batches, V, U, cfg, None, lam_dev, alpha_dev)
        hard_sync(V)
        return time.perf_counter() - t0

    # warmup compiles the two sweep programs (one per side)
    _stage("bench_als: warmup compile")
    warm_s = run_iters(1)

    # scaling check: doubled work must take ~2x wall-clock, else the timer
    # is not measuring execution and the run is invalid
    _stage("bench_als: timed iterations (half)")
    t_half = run_iters(max(1, iters_timed // 2))
    _stage("bench_als: timed iterations (full)")
    t_full = run_iters(iters_timed)
    best = t_full / iters_timed
    scale_ratio = t_full / t_half / (iters_timed / max(1, iters_timed // 2))

    flops_iter = als_iteration_flops(user_plan, item_plan, rank)
    implied_flops = flops_iter / best
    peak = device_peak_flops()
    mfu = implied_flops / peak
    # memory-bound roofline from the actual plan: the primary efficiency
    # metric (mfu undercounts by design — it credits neither CG work nor
    # padding — so roofline_fraction is what tracks optimization progress;
    # 1.0 = measured time equals the HBM-traffic lower bound)
    hbm_bytes = als_iteration_hbm_bytes(user_plan, item_plan, rank,
                                        cfg.compute_dtype, cfg.factor_dtype)
    roofline_s = hbm_bytes / device_hbm_bw()
    roofline_fraction = roofline_s / best
    timing_valid = (implied_flops <= peak) and (0.6 <= scale_ratio <= 1.67)
    if not timing_valid:
        raise RuntimeError(
            f"benchmark self-validation failed: implied {implied_flops:.3e} "
            f"flop/s vs peak {peak:.3e} (mfu {mfu:.3f}), iteration-doubling "
            f"ratio {scale_ratio:.2f} (want ~1.0) — refusing to report a "
            f"non-physical number")
    ratings_per_sec = ratings.nnz / best
    _stage("bench_als: model fetch + rmse sample")

    model = ALSModel(np.asarray(U)[:n_users], np.asarray(V)[:n_items], rank)
    # sanity: the factorization actually fits the data
    sample = np.random.default_rng(0).choice(ratings.nnz,
                                             min(200_000, ratings.nnz),
                                             replace=False)
    sub = RatingsCOO(ui[sample], ii[sample], vv[sample], n_users, n_items)
    rmse = als_rmse(model, sub)

    return {
        "ratings_per_sec_per_chip": ratings_per_sec,
        "train_s_per_iteration": best,
        "mfu": round(mfu, 4),
        "roofline_fraction": round(roofline_fraction, 4),
        "roofline_s_per_iteration": round(roofline_s, 4),
        "hbm_gb_per_iteration": round(hbm_bytes / 1e9, 2),
        "counted_flops_per_iteration": flops_iter,
        "scale_check_ratio": round(scale_ratio, 3),
        # combined padded/real gather-position ratio across both sweeps
        # (rounds 1-3 reported the SUM of the two per-side ratios, which
        # read as a ~2.4x tax when the real inflation was ~1.2x/side)
        "padding_overhead": round(
            (user_plan.padded_work + item_plan.padded_work)
            / max(user_plan.nnz + item_plan.nnz, 1), 3),
        "padding_overhead_user": round(user_plan.padding_overhead, 3),
        "padding_overhead_item": round(item_plan.padding_overhead, 3),
        "warmup_s": warm_s,
        "prep_s": round(prep_s, 3),
        "datagen_s": gen_s,
        "nnz": ratings.nnz,
        "rank": rank,
        "train_rmse_sample": rmse,
    }, model


def mllib_solver(rank: int):
    """Pick the faster dense SPD solver on this machine — LAPACK LU via
    np.linalg.solve (lower per-call overhead, wins at small rank) or
    scipy Cholesky (half the flops, wins at large rank). The baseline
    deserves its best foot, so calibrate once per run."""
    try:
        from scipy.linalg import cho_factor, cho_solve

        def chol_solve(A, b):
            # SPD Cholesky (n^3/3 flops); check_finite off — the scans
            # cost more than the factorization at small rank
            return cho_solve(
                cho_factor(A, lower=True, check_finite=False), b,
                check_finite=False)
    except ImportError:      # scipy is optional: LU arm still measures
        chol_solve = np.linalg.solve

    A0 = np.eye(rank) * 2.0 + 0.1
    b0 = np.ones(rank)
    t0 = time.perf_counter()
    for _ in range(20):
        np.linalg.solve(A0, b0)
    t_lu = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(20):
        chol_solve(A0, b0)
    t_ch = time.perf_counter() - t0
    return chol_solve if t_ch < t_lu else np.linalg.solve


def mllib_half_sweep(group_idx, counter_idx, vals, n_groups, counter, out,
                     rank, lam, solve, n_workers=1):
    """One MLlib-shaped ALS half-sweep: per-entity normal equations
    A = V_S^T V_S + lambda*n_ratings*I in float64 (ALS-WR, MLlib 1.3's
    default; reference semantics: examples/scala-parallel-recommendation/
    custom-prepartor/src/main/scala/ALSAlgorithm.scala:55 `ALS.train`).
    Grouping is CSR via one argsort; each entity's solve is a dense
    numpy call, mirroring the per-block dense solves MLlib runs inside
    a partition. Optionally fanned out over a thread pool the way Spark
    fans entity blocks over executor cores (reference entry:
    core/src/main/scala/io/prediction/workflow/WorkflowContext.scala:
    25-45) — per-entity Gram+solve is BLAS, which releases the GIL, so
    threads scale on real cores. Shared by the timing baseline and the
    rank-200 math-parity job so the two can't diverge."""
    order = np.argsort(group_idx, kind="stable")
    g, c, r = group_idx[order], counter_idx[order], vals[order]
    counts = np.bincount(g, minlength=n_groups)
    starts = np.concatenate([[0], np.cumsum(counts)])
    eye = np.eye(rank)

    def run_range(e_lo, e_hi):
        for e in range(e_lo, e_hi):
            lo, hi = starts[e], starts[e + 1]
            if lo == hi:
                continue
            Vs = counter[c[lo:hi]].astype(np.float64)
            A = Vs.T @ Vs + lam * (hi - lo) * eye
            b = Vs.T @ r[lo:hi].astype(np.float64)
            out[e] = solve(A, b)

    if n_workers <= 1:
        run_range(0, n_groups)
        return
    from concurrent.futures import ThreadPoolExecutor
    # contiguous entity ranges, one per worker: same locality a Spark
    # partition gets, no per-entity task overhead
    bounds = np.linspace(0, n_groups, n_workers + 1).astype(int)
    with ThreadPoolExecutor(max_workers=n_workers) as pool:
        futs = [pool.submit(run_range, bounds[i], bounds[i + 1])
                for i in range(n_workers)]
        for f in futs:
            f.result()


def mllib_shaped_cpu_baseline(full_scale: bool):
    """MEASURED single-node CPU baseline: one
    iteration of the MLlib-shaped explicit ALS (`mllib_half_sweep`),
    timed at 1 core and at every core this host exposes.

    Runs on a 1/20-scale sample of the north-star workload — users,
    items, and nnz all scaled together so per-entity densities match —
    at the SAME rank (per-rating work is rank-dominated, so ratings/s
    transfers); the reported number turns the assumed
    SPARK_CPU_BASELINE constant into same-machine arithmetic. ~1 min per
    timed configuration at rank 200, x3 reps (best-of) per core-count —
    a few minutes total, still a small fraction of a bench session."""
    if full_scale:
        n_users, n_items, nnz, rank = 6_924, 1_337, 1_000_000, 200
    else:
        n_users, n_items, nnz, rank = 2_000, 800, 120_000, 32
    lam = 0.05
    ui, ii, vv = synthetic_ml20m(n_users, n_items, nnz, seed=3)
    rng = np.random.default_rng(7)
    U = np.abs(rng.standard_normal((n_users, rank))) / np.sqrt(rank)
    V = np.abs(rng.standard_normal((n_items, rank))) / np.sqrt(rank)
    solve = mllib_solver(rank)

    ncores = len(os.sched_getaffinity(0)) if hasattr(
        os, "sched_getaffinity") else (os.cpu_count() or 1)

    def timed_iteration(n_workers, reps=3):
        # best-of-reps: scheduling hiccups on a busy host only ever ADD
        # time, and the baseline is the north-star denominator — its
        # fastest observed iteration is the generous (fair) number
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            mllib_half_sweep(ui, ii, vv, n_users, V, U, rank, lam, solve,
                             n_workers)
            mllib_half_sweep(ii, ui, vv, n_items, U, V, rank, lam, solve,
                             n_workers)
            best = min(best, time.perf_counter() - t0)
        return best

    dt1 = timed_iteration(1)
    out = {"baseline_measured_ratings_per_sec_1core": round(nnz / dt1, 1),
           "baseline_measured_s_per_iteration_1core": round(dt1, 2),
           "baseline_measured_ncores": ncores,
           "baseline_measured_nnz": nnz, "baseline_measured_rank": rank}
    if ncores > 1:
        dtn = timed_iteration(ncores)
        out["baseline_measured_ratings_per_sec_ncore"] = round(nnz / dtn, 1)
        out["baseline_measured_s_per_iteration_ncore"] = round(dtn, 2)
    else:
        # single-core host: the pooled path measures nothing extra; the
        # 1-core number IS the whole machine (noted so the artifact is
        # honest about what "ncore" means here)
        out["baseline_measured_ratings_per_sec_ncore"] = round(nnz / dt1, 1)
        out["baseline_measured_s_per_iteration_ncore"] = round(dt1, 2)
    # the number the north-star ratio divides by: everything this host
    # can do, i.e. the n-core rate
    out["baseline_measured_ratings_per_sec"] = (
        out["baseline_measured_ratings_per_sec_ncore"])
    return out


def math_parity_report(out_path="MATH_PARITY.json", iters=6,
                       n_users=6_924, n_items=1_337, nnz=1_000_000,
                       rank=200):
    """Rank-200 end-to-end math parity (round-4 verdict item 3): train
    the production `als_train` path — bucket ladder, dual/Woodbury
    solves, with bf16 factor tables OFF and ON — and the MLlib-shaped
    float64 baseline (`mllib_half_sweep`, the `ALS.train` semantics of
    examples/scala-parallel-recommendation/custom-prepartor/src/main/
    scala/ALSAlgorithm.scala:55) on IDENTICAL data at the north-star
    operating point (rank 200, the 1M-nnz 1/20-scale sample), then
    compare held-out prediction RMSE. ALS is non-convex and the inits
    differ, so the parity claim is predictive equivalence within
    tolerance, not factor equality. A CPU job.
    Run: python bench.py --math-parity
    (The size parameters exist so the test suite can smoke the harness
    at toy scale; the committed artifact uses the defaults.)"""
    from predictionio_tpu.ops.als import ALSConfig, als_train
    from predictionio_tpu.ops.ratings import RatingsCOO

    lam = 0.05
    ui, ii, vv = synthetic_ml20m(n_users, n_items, nnz, seed=3)
    # held-out split: 2% of ratings never seen by any trainer
    rng = np.random.default_rng(11)
    test_mask = np.zeros(nnz, dtype=bool)
    test_mask[rng.choice(nnz, nnz // 50, replace=False)] = True
    tr = ~test_mask
    ui_tr, ii_tr, vv_tr = ui[tr], ii[tr], vv[tr]
    ui_te, ii_te, vv_te = ui[test_mask], ii[test_mask], vv[test_mask]

    def heldout_rmse(U, V):
        pred = np.einsum("ij,ij->i", U[ui_te].astype(np.float64),
                         V[ii_te].astype(np.float64))
        return float(np.sqrt(np.mean((pred - vv_te) ** 2)))

    results = {}

    t0 = time.perf_counter()
    rng_b = np.random.default_rng(7)
    U = np.abs(rng_b.standard_normal((n_users, rank))) / np.sqrt(rank)
    V = np.abs(rng_b.standard_normal((n_items, rank))) / np.sqrt(rank)
    solve = mllib_solver(rank)
    for _ in range(iters):
        mllib_half_sweep(ui_tr, ii_tr, vv_tr, n_users, V, U, rank, lam,
                         solve)
        mllib_half_sweep(ii_tr, ui_tr, vv_tr, n_items, U, V, rank, lam,
                         solve)
    results["mllib_shaped_float64"] = {
        "heldout_rmse": round(heldout_rmse(U, V), 4),
        "train_s": round(time.perf_counter() - t0, 1)}

    ratings_tr = RatingsCOO(ui_tr, ii_tr, vv_tr, n_users, n_items)
    variants = (
        ("als_train_f32_tables", {}),
        ("als_train_bf16_tables", {"factor_dtype": "bfloat16"}),
        # accuracy side of the ablation's dualcap16 speed row, at the
        # full rank-200 regime (cap = ~8% of the K+8 budget). solver
        # 'cg' explicitly: the CPU default resolves to cholesky, which
        # ignores iteration budgets and would test nothing. The cap
        # scales down at toy rank so the suite's smoke run still binds
        # it — PROVIDED the smoke rank is >= 16: the dual route needs
        # K < rank and the bucket ladder's minimum K is 8, so at rank 8
        # the Woodbury branch never fires and the cap is only plumbing-
        # tested (tests/test_bench_harness.py runs rank 16: the K=8
        # bucket takes the dual route with budget K+8=16 > cap 8);
        # at rank >= 32 this is exactly 16
        ("als_train_dualcap16_cg",
         {"solver": "cg", "dual_iters_cap": min(16, max(1, rank // 2))}),
    )
    for label, extra_cfg in variants:
        t0 = time.perf_counter()
        model = als_train(ratings_tr, ALSConfig(
            rank=rank, iterations=iters, lam=lam, seed=1,
            work_budget=(1 << 20), **extra_cfg))
        results[label] = {
            "heldout_rmse": round(heldout_rmse(
                np.asarray(model.user_factors, dtype=np.float64),
                np.asarray(model.item_factors, dtype=np.float64)), 4),
            "train_s": round(time.perf_counter() - t0, 1)}

    base_rmse = results["mllib_shaped_float64"]["heldout_rmse"]
    tol = 0.05
    deltas = {k: round(v["heldout_rmse"] - base_rmse, 4)
              for k, v in results.items() if k != "mllib_shaped_float64"}
    out = {
        "artifact": "rank200_math_parity",
        "workload": {"n_users": n_users, "n_items": n_items,
                     "nnz_train": int(tr.sum()),
                     "nnz_heldout": int(test_mask.sum()), "rank": rank,
                     "lam": lam, "iterations": iters},
        "backend": "cpu",
        "results": results,
        "rmse_delta_vs_mllib": deltas,
        "tolerance": tol,
        "parity_ok": bool(all(abs(d) <= tol for d in deltas.values())),
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["parity_ok"] else 1


def _populate_columnar(ev, app_id, ui, ii, vv, beat_label="populate",
                       ts0: int = 1000, user_prefix: str = "u"):
    """Bulk import through the PRODUCT columnar write path (ISSUE 7
    insert_columnar: minted ids, vectorized hashing/templating,
    group-committed blocks) — the same route an operator's
    /events/columnar.json import takes, so store population exercises
    and times real product code on every backend instead of a
    bench-only raw append. eventTime carries the day component so
    timestamps stay parseable past 24h of millis (31 days covers nnz
    up to 2.67e9)."""
    from predictionio_tpu.data.columnar import ColumnarBatch

    def time_str(ts):
        sec, ms = divmod(ts, 1000)
        mi, sec = divmod(sec, 60)
        hh, mi = divmod(mi, 60)
        dd, hh = divmod(hh, 24)
        assert dd < 31, "bench populate: ts exceeds January 1970"
        return "1970-01-%02dT%02d:%02d:%02d.%03dZ" % (
            dd + 1, hh, mi, sec, ms)

    nnz = len(vv)
    chunk = 500_000   # bound host memory; heartbeat per chunk
    for lo in range(0, nnz, chunk):
        if lo:
            _stage(f"{beat_label}: populate row {lo}")
        hi = min(lo + chunk, nnz)
        ev.insert_columnar(ColumnarBatch(
            hi - lo, "rate", "user",
            [f"{user_prefix}{int(u)}" for u in ui[lo:hi]],
            target_entity_type="item",
            target_entity_id=[f"i{int(it)}" for it in ii[lo:hi]],
            properties=[{"rating": round(float(v), 1)}
                        for v in vv[lo:hi]],
            event_time=[time_str(ts0 + j) for j in range(lo, hi)]),
            app_id)


def bench_product_path(full_scale: bool):
    """`pio train`-equivalent timing: events already in the store (the
    realistic starting state) -> DataSource columnar scan -> Preparator
    vocab/dedup -> ALS training. Validates that the product path, not just
    the kernel, sustains the throughput (reference contract:
    core/src/main/scala/io/prediction/controller/Engine.scala:621-708).

    Store population is setup, not measurement: rows go straight into the
    backing store the way an operator's bulk import would have left them.

    PIO_BENCH_PRODUCT_BACKEND selects the event store: `nativelog`
    (default — the scalable C++ store, hash-partitioned with parallel
    shard scans) or `sqlite` (the embedded operator default).
    """
    import tempfile

    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.models import recommendation as R

    if full_scale:
        n_users, n_items, nnz, rank, iters = 138_493, 26_744, 5_000_000, 200, 2
    else:
        n_users, n_items, nnz, rank, iters = 2_000, 500, 60_000, 16, 2

    backend = os.environ.get("PIO_BENCH_PRODUCT_BACKEND", "nativelog")
    base = tempfile.mkdtemp(prefix="pio_bench_store_")
    with bench_storage_env(backend, base):
        from predictionio_tpu.data.storage.registry import Storage
        app_id = Storage.get_meta_data_apps().insert(App(0, "benchapp"))
        ev = Storage.get_events()
        ev.init(app_id)

        ui, ii, vv = synthetic_ml20m(n_users, n_items, nnz)
        _stage("bench_product_path: populate")
        t0 = time.perf_counter()
        _populate_columnar(ev, app_id, ui, ii, vv,
                           beat_label="bench_product_path")
        populate_s = time.perf_counter() - t0

        ds = R.RecommendationDataSource(
            R.DataSourceParams(app_name="benchapp"))
        _stage("bench_product_path: datasource read")
        t0 = time.perf_counter()
        td = ds.read_training()
        read_s = time.perf_counter() - t0

        prep = R.RecommendationPreparator()
        _stage("bench_product_path: prepare")
        t0 = time.perf_counter()
        pd = prep.prepare(td)
        prepare_s = time.perf_counter() - t0

        algo = R.ALSAlgorithm(R.ALSAlgorithmParams(
            rank=rank, num_iterations=iters, lam=0.05, seed=1))
        _stage("bench_product_path: cold train")
        t0 = time.perf_counter()
        algo.train(pd)
        train_s = time.perf_counter() - t0
        _stage("bench_product_path: warm train")

        # warm re-train: same shapes, compiled programs now cached — the
        # total cost of an operator retrain (plan build + upload + iters).
        # The per-phase split comes from the train's own telemetry (hard-
        # synced in ops/als.py): `s_per_iter` is the steady-state sweep
        # cost, directly comparable to the kernel bench's s/iteration,
        # without differencing two noisy totals.
        t0 = time.perf_counter()
        algo.train(pd)
        train_warm_s = time.perf_counter() - t0
        tel = getattr(algo, "last_train_telemetry", {})

        e2e = read_s + prepare_s + train_s
        out = {
            "product_backend": backend,
            "product_nnz": int(pd.ratings_coo.nnz),
            "product_read_s": round(read_s, 3),
            "product_prepare_s": round(prepare_s, 3),
            "product_train_s": round(train_s, 3),
            "product_train_warm_s": round(train_warm_s, 3),
            "product_e2e_s": round(e2e, 3),
            "product_events_per_sec_read": round(nnz / read_s, 1),
            "product_setup_populate_s": round(populate_s, 3),
        }
        for k, v in tel.items():
            out[f"product_train_{k}"] = (round(v, 4)
                                         if isinstance(v, float) else v)
        if tel.get("s_per_iter"):
            out["product_ratings_per_sec_steady"] = round(
                pd.ratings_coo.nnz / tel["s_per_iter"], 1)
        return out


def _ingest_event(j):
    return {"event": "rate", "entityType": "user",
            "entityId": f"u{j % 997}",
            "targetEntityType": "item",
            "targetEntityId": f"i{j % 499}",
            "properties": {"rating": float(j % 5 + 1)}}


def ingest_load_driver(spec: dict) -> None:
    """Body of the ``--ingest-driver`` subprocess: generate HTTP ingest
    load against the parent's Event Server from OUTSIDE its process.
    An in-process load generator shares the server's GIL, so the
    concurrent-8 shape measured an 8-client + 8-handler thread brawl
    in one interpreter — the load generator's own serialization work
    was charged against the server, which is how an earlier
    concurrent-8 read *slower* than serial even after the storage
    convoy was fixed (a real ingest plane never hosts its clients).
    Protocol on stdout/stdin: after warmup the driver prints WARMED
    and waits for a GO line so the parent can baseline the lock-wait
    probe; the final line is ``RESULT {json}``.

    The four shapes INTERLEAVE within each rep (single, batch,
    columnar, concurrent-8, repeat) rather than running as
    consecutive blocks: on a noisy shared box the run-to-run swing is
    ~1.4x, so consecutive blocks hand whichever shape runs last the
    drift (page-cache state, log growth, ambient load) — exactly the
    single-vs-concurrent8 comparison this bench exists to make
    honestly. Interleaving spreads every shape's reps across the
    run's lifetime; the median per shape then compares windows from
    the same epochs."""
    port = spec["port"]
    reps = spec["reps"]
    n_single = spec["n_single"]
    n_batch_events = spec["n_batch"]
    n_columnar = spec["n_columnar"]
    n_conc = spec["n_conc"]
    max_batch = spec["max_batch"]
    path = "/events.json?accessKey=benchkey"
    event = _ingest_event

    def timed_rate(run, n_events):
        t0 = time.perf_counter()
        run()
        return n_events / (time.perf_counter() - t0)

    c = _Client(port)
    for j in range(20):  # warm the connection + code paths
        resp = json.loads(c.post(event(j), path=path))
        assert "eventId" in resp, f"ingest rejected: {resp}"
    # one warm batch, per-event statuses verified — a batch endpoint
    # returns 200 around per-event failures, which would otherwise
    # count as ingested (_Client only raises on transport-level >=400)
    statuses = json.loads(c.post(
        [event(j) for j in range(max_batch)],
        path="/batch/events.json?accessKey=benchkey"))
    bad = [s for s in statuses if s.get("status") != 201]
    assert not bad, f"batch ingest rejected events: {bad[:3]}"

    def run_singles():
        for j in range(n_single):
            c.post(event(j), path=path)

    def run_batches():
        for lo in range(0, n_batch_events, max_batch):
            c.post([event(j) for j in
                    range(lo, min(lo + max_batch, n_batch_events))],
                   path="/batch/events.json?accessKey=benchkey")

    # columnar bulk write (ISSUE 7 tentpole b): parallel arrays in ONE
    # POST /events/columnar.json — one parse, one id-mint pass, one
    # group-committed bulk insert. The body dict is built once outside
    # the clock; the timed region is client dumps + wire + server
    # parse/validate/insert + ack, i.e. everything a real bulk loader
    # pays per request.
    col_body = {
        "event": "rate", "entityType": "user",
        "entityId": [f"u{j % 997}" for j in range(n_columnar)],
        "targetEntityType": "item",
        "targetEntityId": [f"i{j % 499}" for j in range(n_columnar)],
        "properties": [{"rating": float(j % 5 + 1)}
                       for j in range(n_columnar)],
    }

    def run_columnar():
        resp = json.loads(c.post(
            col_body, path="/events/columnar.json?accessKey=benchkey",
            timeout=600))
        assert resp.get("eventsCreated") == n_columnar, resp

    def run_conc(workers):
        # concurrent-8 window: one GO/DONE round trip for the whole
        # window keeps the parent's bookkeeping off the timed region
        for p in workers:
            p.stdin.write("GO\n")
            p.stdin.flush()
        for p in workers:
            assert p.stdout.readline().strip() == "DONE"

    # concurrent-8 load: EIGHT worker PROCESSES, one connection each.
    # Worker threads in this process would share one GIL — the "8
    # concurrent clients" would throttle each other's serialization
    # and add their own wakeup latency to every request, understating
    # the server. Real concurrent clients are independent processes.
    import subprocess
    workers = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--ingest-driver",
         json.dumps({"shape": "conc_worker", "port": port,
                     "n": n_conc // 8, "worker": w})],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        for w in range(8)]
    try:
        for p in workers:
            assert p.stdout.readline().strip() == "READY"
        print("WARMED", flush=True)
        sys.stdin.readline()  # parent baselines lock probe, says GO
        rates = {"single": [], "batch": [], "columnar": [],
                 "concurrent8": []}
        for _ in range(reps):
            rates["single"].append(timed_rate(run_singles, n_single))
            rates["batch"].append(
                timed_rate(run_batches, n_batch_events))
            rates["columnar"].append(
                timed_rate(run_columnar, n_columnar))
            rates["concurrent8"].append(
                timed_rate(lambda: run_conc(workers),
                           n_conc // 8 * 8))
        res = {k: float(np.median(v)) for k, v in rates.items()}
    finally:
        for p in workers:
            try:
                p.stdin.close()
            except OSError:
                pass
            p.wait(timeout=30)
    c.close()
    print("RESULT " + json.dumps(res), flush=True)


def _ingest_conc_worker(spec: dict) -> None:
    """One concurrent-8 client: a keep-alive connection posting
    singles, gated per rep by GO/DONE lines on stdin/stdout."""
    c = _Client(spec["port"])
    base = spec["worker"] * 100_000
    for j in range(8):  # warm connection + code paths
        c.post(_ingest_event(base + j),
               path="/events.json?accessKey=benchkey")
    print("READY", flush=True)
    while sys.stdin.readline().strip() == "GO":
        for j in range(spec["n"]):
            c.post(_ingest_event(base + j),
                   path="/events.json?accessKey=benchkey")
        print("DONE", flush=True)
    c.close()


def bench_ingest(full_scale: bool):
    """Ingest throughput through the real Event Server over loopback
    HTTP, load generated by a SEPARATE driver process (see
    ingest_load_driver — in-process clients share the server's GIL and
    invert the concurrent ordering). Four client shapes per backend:
    serial single events, /batch/events.json at the 50-event wire cap,
    one-POST columnar bulk writes (/events/columnar.json, ISSUE 7),
    and 8 concurrent keep-alive clients posting singles. Backends:
    nativelog (the scalable C++ store) and sqlite (the embedded
    operator default). (reference ingest path:
    data/src/main/scala/io/prediction/data/api/EventServer.scala:226-260)
    """
    import subprocess
    import tempfile

    from predictionio_tpu.data.api.event_server import (MAX_BATCH_SIZE,
                                                        EventServer,
                                                        EventServerConfig)

    spec_base = {
        "n_single": 2_000 if full_scale else 500,
        "n_batch": 20_000 if full_scale else 5_000,
        "n_columnar": 100_000 if full_scale else 20_000,
        "n_conc": 2_000 if full_scale else 500,
        # median of 3 reps per shape: single timed passes on a 1-core
        # host swung ~1.4x run-to-run on scheduler noise
        "reps": 3,
        "max_batch": MAX_BATCH_SIZE,
    }

    out = {}
    for backend in ("nativelog", "sqlite"):
        base = tempfile.mkdtemp(prefix=f"pio_bench_ingest_{backend}_")
        server = None
        driver = None
        with bench_storage_env(backend, base):
            try:
                from predictionio_tpu.data.storage.base import (AccessKey,
                                                                App)
                from predictionio_tpu.data.storage.registry import Storage
                app_id = Storage.get_meta_data_apps().insert(
                    App(0, "benchapp"))
                Storage.get_events().init(app_id)
                Storage.get_meta_data_access_keys().insert(
                    AccessKey("benchkey", app_id, []))
                server = EventServer(
                    EventServerConfig(ip="127.0.0.1", port=0))
                server.start()

                # contention probe (ISSUE 6): p99 writer wait on the
                # nativelog per-handle lock during the concurrent-8
                # phase — the number that localizes a concurrent-8
                # regression to the append convoy
                lock_wait = None
                lw_before = None
                if backend == "nativelog":
                    from predictionio_tpu.obs.slo import lock_probe
                    lock_wait = lock_probe("nativelog_append")

                spec = dict(spec_base, port=server.config.port)
                driver = subprocess.Popen(
                    [sys.executable, os.path.abspath(__file__),
                     "--ingest-driver", json.dumps(spec)],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True)
                result = None
                for line in driver.stdout:
                    line = line.strip()
                    if line == "WARMED":
                        # baseline AFTER the warm phase: cold-path
                        # waits (first-contact contention, lazy init)
                        # must not pollute the p99. The window covers
                        # every warmed shape (they interleave), so
                        # this is the whole ingest run's writer-wait
                        # p99 — concurrent-8 windows included
                        if lock_wait is not None:
                            lw_before = lock_wait.bucket_counts()
                        driver.stdin.write("GO\n")
                        driver.stdin.flush()
                    elif line.startswith("RESULT "):
                        result = json.loads(line[len("RESULT "):])
                rc = driver.wait(timeout=120)
                if rc != 0 or result is None:
                    raise RuntimeError(
                        f"ingest load driver failed (rc={rc}, "
                        f"result={'yes' if result else 'no'}) for "
                        f"{backend}")

                if lock_wait is not None:
                    p99 = lock_wait.percentile_since(lw_before, 99)
                    if p99 is not None:
                        out["lock_wait_p99_ms_ingest"] = round(
                            p99 * 1000, 4)

                for shape in ("single", "batch", "columnar",
                              "concurrent8"):
                    out[f"ingest_events_per_sec_{shape}_{backend}"] = \
                        round(result[shape], 1)
                # registry-derived write-latency percentiles (ISSUE 2):
                # per-server histogram, so per-backend isolation is free
                wh = server.metrics.get("pio_event_write_seconds")
                if wh is not None and wh.count:
                    out[f"ingest_write_p50_ms_{backend}"] = round(
                        (wh.percentile(50) or 0.0) * 1000, 4)
                    out[f"ingest_write_p99_ms_{backend}"] = round(
                        (wh.percentile(99) or 0.0) * 1000, 4)
            finally:
                if driver is not None and driver.poll() is None:
                    driver.kill()
                if server is not None:
                    server.stop()
    return out


def bench_fold_tick(full_scale: bool):
    """Online fold-tick scenario (ISSUE 4): a deployed model absorbs a
    ~1%-touched burst of fresh events per tick. Reports
    ``fold_tick_p50_ms`` (tick wall, p50 over the steady-state ticks),
    ``fold_read_rows`` (rows the entity-filtered tail read actually
    pulled vs ``fold_read_rows_full`` = the corpus it avoided scanning)
    and ``fold_h2d_bytes`` (per-tick instrumented upload bytes on the
    SECOND consecutive tick, when the factor tables are device-resident
    and only touched-row plans cross the link)."""
    import datetime as dt
    import tempfile

    from predictionio_tpu.core import EngineParams
    from predictionio_tpu.data import DataMap, Event
    from predictionio_tpu.models import recommendation as R
    from predictionio_tpu.online.scheduler import (SchedulerConfig,
                                                   attach_scheduler)
    from predictionio_tpu.serving import EngineServer, ServerConfig
    from predictionio_tpu.workflow import run_train

    UTC = dt.timezone.utc
    n_users = 20_000 if full_scale else 1_500
    per_user = 50 if full_scale else 20
    n_items = 2_000 if full_scale else 300
    touched_users = max(8, n_users // 100)
    base = tempfile.mkdtemp(prefix="pio_bench_fold_")
    out = {}
    with bench_storage_env("sqlite", base):
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.data.storage.registry import Storage
        app_id = Storage.get_meta_data_apps().insert(App(0, "foldapp"))
        ev = Storage.get_events()
        ev.init(app_id)
        t0 = dt.datetime.now(UTC) - dt.timedelta(days=1)
        rng = np.random.default_rng(11)
        batch, corpus_rows = [], 0
        for u in range(n_users):
            for k, i in enumerate(rng.integers(0, n_items, per_user)):
                batch.append(Event(
                    event="rate", entity_type="user",
                    entity_id=f"u{u}", target_entity_type="item",
                    target_entity_id=f"i{i}",
                    properties=DataMap(
                        {"rating": float(1 + (u + int(i)) % 5)}),
                    event_time=t0 + dt.timedelta(
                        milliseconds=corpus_rows + k)))
            corpus_rows += per_user
            if len(batch) >= 20_000:
                ev.insert_batch(batch, app_id)
                batch = []
        if batch:
            ev.insert_batch(batch, app_id)
        ep = EngineParams(
            data_source_params=("", R.DataSourceParams(
                app_name="foldapp")),
            preparator_params=("", R.PreparatorParams()),
            algorithm_params_list=[("als", R.ALSAlgorithmParams(
                rank=16 if full_scale else 8, num_iterations=2,
                lam=0.1, seed=1))],
            serving_params=("", None))
        engine = R.RecommendationEngineFactory.apply()
        run_train(engine, ep, engine_id="foldbench",
                  engine_version="1", engine_variant="v1",
                  engine_factory="recommendation")
        server = EngineServer(ServerConfig(
            ip="127.0.0.1", port=0, engine_id="foldbench",
            engine_version="1", engine_variant="v1"))
        server.load()
        sched = attach_scheduler(server, SchedulerConfig(
            app_name="foldapp", max_deltas=1))

        def burst(tick_no):
            t = dt.datetime.now(UTC)
            for j in range(touched_users):
                u = (tick_no * touched_users + j) % n_users
                ev.insert(Event(
                    event="rate", entity_type="user",
                    entity_id=f"u{u}", target_entity_type="item",
                    target_entity_id=f"i{j % n_items}",
                    properties=DataMap({"rating": 5.0}),
                    event_time=t + dt.timedelta(milliseconds=j)), app_id)

        # obs tax (ISSUE 6, measured like guard_overhead_ms — from the
        # instruments' own cumulative wall, not a subtractive rerun):
        # flight-recorder record() time + SLO evaluation time per tick
        from predictionio_tpu.obs import costmon as _costmon
        from predictionio_tpu.obs.flight import FLIGHT as _FLIGHT
        walls, reads, h2ds, guards, obs_ms = [], [], [], [], []
        n_ticks = 3
        for tick_no in range(n_ticks):
            burst(tick_no)
            o0 = _FLIGHT.spent_s + server.slo.spent_s
            w0 = time.perf_counter()
            report = sched.tick(force=True)
            # the tick wall stays tick-only (comparable with PR 4/5
            # artifacts); the /health.json poll a tick sees runs
            # outside it but inside the obs-tax window
            walls.append((time.perf_counter() - w0) * 1000)
            server.slo.evaluate()
            obs_ms.append((_FLIGHT.spent_s + server.slo.spent_s - o0)
                          * 1000)
            assert report and report["readPath"] == "entity_filtered", \
                report
            reads.append(report["readRows"])
            h2ds.append(report["h2dBytes"])
            guards.append(report.get("guardOverheadMs", 0.0))
        out["fold_tick_p50_ms"] = round(float(np.median(walls[1:])), 2)
        out["fold_read_rows"] = int(np.median(reads))
        out["fold_read_rows_full"] = corpus_rows
        # second consecutive tick: resident tables, plans-only uploads
        out["fold_h2d_bytes"] = int(h2ds[1])
        # guard tax (ISSUE 5, schema-additive): wall spent in the
        # numerical sentinels + pre-swap gates per tick, instrumented
        # at the call sites (scheduler report guardOverheadMs) rather
        # than diffed between runs — per-tick solve-plan recompiles
        # make a subtractive measurement pure noise. Steady-state p50;
        # acceptance: <= 5% of fold_tick_p50_ms on a clean tick.
        out["guard_overhead_ms"] = round(float(np.median(guards[1:])), 2)
        # recorder+SLO tax per tick (acceptance: <=1% of serve p99 and
        # fold-tick p50; schema-additive)
        out["obs_overhead_ms"] = round(float(np.median(obs_ms[1:])), 3)
        # compile attribution (ISSUE 6): per-executable compile seconds
        # and jit-cache hit/miss counts accumulated across this bench's
        # train + fold + probe work — the evidence the AOT/compile-
        # cache ROADMAP item starts from (schema-additive)
        comp = _costmon.compile_seconds_by_executable()
        if comp:
            out["compile_s_by_executable"] = comp
        cache = _costmon.cache_counts()
        if cache["hits"] or cache["misses"]:
            out["compile_cache_hits"] = {
                k: int(v) for k, v in cache["hits"].items()}
            out["compile_cache_misses"] = {
                k: int(v) for k, v in cache["misses"].items()}
        # device-time attribution (ISSUE 11, schema-additive): the
        # acceptance check that serve + fold executables both own
        # non-zero estimated device seconds after one bench run
        dev = _costmon.device_time_by_executable()
        if dev:
            out["device_time_s_by_executable"] = dev
    return out


def bench_sharded(full_scale: bool):
    """Sharded online plane (ISSUE 12, schema-additive): fold-tick and
    serve cost with the factor tables model-sharded across every local
    device, next to the replicated numbers the rest of the artifact
    carries. Emits ``fold_tick_p50_ms_sharded`` (steady-state sharded
    fold_in_coo wall), ``serve_p50_ms_sharded`` (batched sharded top-k
    wall), ``hbm_table_bytes_per_shard`` (per-device bytes of the
    resident tables — ~1/N of the replicated footprint) and
    ``fold_h2d_bytes_sharded`` (tick-2 uploads: touched-row plans
    only, the no-full-table-round-trip claim as a number). Skips —
    emitting nothing — on a single-device backend."""
    import jax

    from predictionio_tpu.obs import jaxmon
    from predictionio_tpu.online.fold_in import FoldInConfig, fold_in_coo
    from predictionio_tpu.ops.als import (ALSConfig, als_train,
                                          users_topk_serve)
    from predictionio_tpu.ops.ratings import RatingsCOO
    from predictionio_tpu.parallel.mesh import model_mesh
    from predictionio_tpu.utils import device_cache

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {}
    n_users = 20_000 if full_scale else 2_000
    n_items = 50_000 if full_scale else 8_000
    rank = 32 if full_scale else 16
    nnz = 400_000 if full_scale else 60_000
    rng = np.random.default_rng(101)
    coo = RatingsCOO(rng.integers(0, n_users, nnz),
                     rng.integers(0, n_items, nnz),
                     rng.uniform(1, 5, nnz).astype(np.float32),
                     n_users, n_items)
    mesh = model_mesh(n_dev)
    model = als_train(coo, ALSConfig(rank=rank, iterations=2, seed=5,
                                     factor_sharding="model",
                                     keep_sharded=True), mesh=mesh)
    cfg = FoldInConfig(sweeps=1, factor_sharding="model")
    touched = max(8, n_users // 100)
    walls, h2ds = [], []
    cur = model
    for tick in range(4):
        tu = rng.integers(0, n_users, touched)
        ti = rng.integers(0, n_items, touched)
        h0 = jaxmon.thread_h2d_total()
        t0 = time.perf_counter()
        cur, st = fold_in_coo(cur, coo, tu, ti, cfg,
                              resident_key="bench_sharded")
        walls.append((time.perf_counter() - t0) * 1000)
        h2ds.append(jaxmon.h2d_delta(h0))
    out = {
        "fold_tick_p50_ms_sharded": round(float(np.median(walls[1:])),
                                          2),
        "fold_h2d_bytes_sharded": int(h2ds[1]),
        "sharded_n_shards": n_dev,
    }
    sizes = device_cache.resident_sizes()
    if "bench_sharded" in sizes:
        out["hbm_table_bytes_per_shard"] = int(sizes["bench_sharded"])
    users = list(rng.integers(0, n_users, 16))
    users_topk_serve(cur, users, 10)   # warm the serve bucket
    serve_walls = []
    for _ in range(30):
        t0 = time.perf_counter()
        users_topk_serve(cur, users, 10)
        serve_walls.append((time.perf_counter() - t0) * 1000)
    out["serve_p50_ms_sharded"] = round(float(np.median(serve_walls)),
                                        3)
    device_cache.drop_resident("bench_sharded")
    return out


def bench_multitenant(full_scale: bool):
    """Multi-tenant serving host (ISSUE 15, schema-additive): three
    engine tenants of different vocab sizes packed on one device
    behind a ServingHost, served by a 16-way closed loop round-robin
    across tenants, under an HBM budget sized to hold only TWO
    tenants' padded tables — so steady traffic exercises the
    LRU-eviction + readmission path, not just routing. Emits
    ``serve_p50_ms_multitenant`` / ``serve_p99_ms_multitenant`` (mixed
    workload latency through the host's per-tenant routing),
    ``tenant_evictions`` (budget evictions during the timed window)
    and ``hbm_bytes_by_tenant`` (the per-tenant gauge at the end).

    ISSUE 17 additions: ``serve_p99_ms_by_tenant`` (the same timed
    window split per tenant), ``device_time_share_by_tenant`` (costmon
    attribution at the end of the run) and ``tenant_obs_overhead_ms``
    (the per-request cost of the tenant observability additions —
    scope entry, contextvar reads, labeled-child bookkeeping — which
    must stay under 1% of serve p50)."""
    import datetime as dt
    import threading

    from predictionio_tpu.core import FirstServing
    from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
    from predictionio_tpu.data.storage.base import EngineInstance
    from predictionio_tpu.models import recommendation as R
    from predictionio_tpu.ops.als import ALSModel
    from predictionio_tpu.serving import EngineServer, ServerConfig
    from predictionio_tpu.tenancy import (HostConfig, ServingHost,
                                          TenantSpec,
                                          estimate_padded_bytes)

    rank = 32 if full_scale else 8
    vocabs = ([(30_000, 60_000), (20_000, 40_000), (10_000, 20_000)]
              if full_scale else [(600, 1200), (400, 800), (200, 400)])
    rng = np.random.default_rng(7)

    def make_server(key, n_users, n_items):
        als = ALSModel(
            user_factors=rng.standard_normal(
                (n_users, rank)).astype(np.float32),
            item_factors=rng.standard_normal(
                (n_items, rank)).astype(np.float32),
            rank=rank)
        user_ix = EntityIdIxMap(
            BiMap({str(i): i for i in range(n_users)}))
        item_ix = EntityIdIxMap(
            BiMap({str(i): i for i in range(n_items)}))
        srv = EngineServer(
            ServerConfig(ip="127.0.0.1", port=0, micro_batch=16),
            engine=R.RecommendationEngineFactory.apply(), tenant=key,
            shared_result_cache=host.result_cache)
        now = dt.datetime.now(dt.timezone.utc)
        srv.engine_instance = EngineInstance(
            id=f"bench-{key}", status="COMPLETED", start_time=now,
            end_time=now, engine_id=key, engine_version="0",
            engine_variant="bench", engine_factory="recommendation")
        srv.algorithms = [R.ALSAlgorithm(R.ALSAlgorithmParams(
            rank=rank))]
        srv.models = [R.RecommendationModel(als, user_ix, item_ix)]
        srv.serving = FirstServing()
        return srv

    # budget: the two largest tenants' padded tables fit, all three
    # don't — mixed traffic must evict to keep serving
    host = ServingHost(HostConfig(ip="127.0.0.1", port=0,
                                  budget_bytes=1))
    servers = {}
    expected = []
    for k, (nu, ni) in zip(("t0", "t1", "t2"), vocabs):
        servers[k] = make_server(k, nu, ni)
        expected.append(estimate_padded_bytes(servers[k].models))
    host.budget.budget_bytes = int(expected[0] + expected[1]
                                   + expected[2] // 2)
    for k in servers:
        host.admit_server(TenantSpec(key=k, engine_id=k), servers[k])
    host.start()
    port = host.config.port
    keys = list(servers)
    sizes = {k: v[0] for k, v in zip(keys, vocabs)}
    try:
        # warm every tenant's serve bucket (compiles excluded from the
        # timed window, like every other serve bench here)
        warm_client = _Client(port)
        for k in keys:
            for i in range(8):
                warm_client.post({"user": str(i), "num": 10},
                                 timeout=600,
                                 path=f"/engines/{k}/queries.json")
        warm_client.close()
        ev0 = sum(t["evictions"] for t in
                  host.budget.snapshot()["tenants"].values())
        n_threads, per_thread = 16, (40 if full_scale else 25)
        lat, errors, lock = [], [], threading.Lock()

        def worker(seed):
            # failures are COLLECTED, not printed-and-dropped: a dead
            # thread's missing samples would silently skew the
            # published percentiles toward the survivors
            try:
                c = _Client(port)
                r = np.random.default_rng(seed)
                mine = []
                for j in range(per_thread):
                    k = keys[(seed + j) % len(keys)]
                    u = int(r.integers(0, sizes[k]))
                    t0 = time.perf_counter()
                    c.post({"user": str(u), "num": 10}, timeout=600,
                           path=f"/engines/{k}/queries.json")
                    mine.append((k, time.perf_counter() - t0))
                c.close()
                with lock:
                    lat.extend(mine)
            except Exception as e:
                with lock:
                    errors.append(repr(e))

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors or len(lat) < n_threads * per_thread:
            raise RuntimeError(
                f"multitenant bench lost samples: "
                f"{len(lat)}/{n_threads * per_thread} completed, "
                f"errors={errors[:3]}")
        snap = host.budget.snapshot()
        evictions = sum(t["evictions"]
                        for t in snap["tenants"].values()) - ev0
        all_lat = [d for _, d in lat]
        by_tenant = {k: [d for kk, d in lat if kk == k] for k in keys}

        # tenant obs tax (ISSUE 17): the per-request additions are one
        # scope entry + the contextvar/registered-set reads + one
        # labeled-child inc — measured standalone, best-of-3, and held
        # to <= 1% of serve p50 by tests/test_obs_overhead.py
        from predictionio_tpu.obs import MetricsRegistry
        from predictionio_tpu.obs.tenantctx import (current_tenant,
                                                    metric_tenant_label,
                                                    tenant_scope)
        reg = MetricsRegistry()
        fam = reg.counter("bench_tenant_obs", "x",
                          labelnames=("tenant",))

        def _tenant_obs_once():
            with tenant_scope("t0"):
                current_tenant()
                fam.labels(tenant=metric_tenant_label()).inc()

        n = 2000
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n):
                _tenant_obs_once()
            d = time.perf_counter() - t0
            best = d if best is None else min(best, d)
        obs_ms = best / n * 1000.0
        from predictionio_tpu.obs import costmon
        dev_share = costmon.tenant_device_time_share()
        return {
            "serve_p50_ms_multitenant": round(
                float(np.percentile(all_lat, 50)) * 1000, 3),
            "serve_p99_ms_multitenant": round(
                float(np.percentile(all_lat, 99)) * 1000, 3),
            "serve_p99_ms_by_tenant": {
                k: round(float(np.percentile(v, 99)) * 1000, 3)
                for k, v in sorted(by_tenant.items()) if v},
            "multitenant_qps": round(len(lat) / wall, 1),
            "tenant_evictions": int(evictions),
            "hbm_bytes_by_tenant": {
                k: int(v["hbmBytes"])
                for k, v in sorted(snap["tenants"].items())},
            "device_time_share_by_tenant": {
                k: dev_share.get(k, 0.0) for k in sorted(keys)},
            "tenant_obs_overhead_ms": round(obs_ms, 6),
        }
    finally:
        host.stop()


def bench_backfill(full_scale: bool):
    """Bulk data plane (ISSUE 16, schema-additive): streamed backfill —
    chunked store cursors + double-buffered H2D staging — against the
    serial drain (per-event ``find()`` iteration, then one monolithic
    blocking upload). Also times the snapshot tenant bootstrap end to
    end on the nativelog backend (restore -> streamed train -> fold
    catch-up), reporting ``bootstrap_catchup_s``.

    ``backfill_speedup_vs_serial`` compares per-row rates: the serial
    drain is capped at ``backfill_serial_rows`` on full scale (minutes
    of per-event Python otherwise) and the cap is REPORTED, never
    silent."""
    import tempfile

    from predictionio_tpu.data.event import to_millis
    from predictionio_tpu.data.storage.base import App

    if full_scale:
        n_users, n_items, nnz = 138_493, 26_744, 5_000_000
        serial_cap = 500_000
    else:
        n_users, n_items, nnz = 2_000, 500, 60_000
        serial_cap = 60_000

    backend = os.environ.get("PIO_BENCH_PRODUCT_BACKEND", "nativelog")
    base = tempfile.mkdtemp(prefix="pio_bench_backfill_")
    with bench_storage_env(backend, base):
        import jax

        from predictionio_tpu.data.storage.registry import Storage
        from predictionio_tpu.dataplane import BulkLoadExecutor
        from predictionio_tpu.models import recommendation as R

        app_id = Storage.get_meta_data_apps().insert(App(0, "backfillapp"))
        ev = Storage.get_events()
        ev.init(app_id)
        ui, ii, vv = synthetic_ml20m(n_users, n_items, nnz)
        _stage("bench_backfill: populate")
        _populate_columnar(ev, app_id, ui, ii, vv,
                           beat_label="bench_backfill")

        p = R.DataSourceParams(app_name="backfillapp")

        # serial drain baseline: the pre-dataplane shape — one event at
        # a time through find(), per-row Python conversion, then a
        # single blocking upload once everything is on the host
        _stage("bench_backfill: serial drain")
        t0 = time.perf_counter()
        users, items, vals, ts = [], [], [], []
        for e in ev.find(app_id=app_id, entity_type="user",
                         target_entity_type="item",
                         event_names=["rate", "buy"], limit=serial_cap):
            users.append(e.entity_id)
            items.append(e.target_entity_id)
            vals.append(float(e.properties.fields.get("rating", 4.0))
                        if e.event == "rate" else 4.0)
            ts.append(to_millis(e.event_time))
        n_serial = len(vals)
        dev = (jax.device_put(np.asarray(vals, np.float32)),
               jax.device_put(np.asarray(ts, np.int64)))
        jax.block_until_ready(dev)
        serial_s = time.perf_counter() - t0
        del users, items, vals, ts, dev

        # streamed pipeline: read thread -> per-chunk decode -> staged
        # double-buffered uploads
        _stage("bench_backfill: streamed pipeline")
        t0 = time.perf_counter()
        result = BulkLoadExecutor().run(
            "backfillapp", property_field="rating",
            decode=lambda c: R.RecommendationDataSource
            ._ratings_from_cols(c, p),
            encode=lambda rd: {"vals": rd.vals, "t": rd.ts},
            entity_type="user", target_entity_type="item",
            event_names=["rate", "buy"])
        stream_s = time.perf_counter() - t0
        st = result.stats
        del result

        out = {
            "backfill_rows": int(st.rows),
            "backfill_chunks": int(st.chunks),
            "backfill_wall_s": round(stream_s, 3),
            "backfill_read_mb_s": round(st.read_mb_s, 1),
            "backfill_h2d_overlap_frac": round(st.h2d_overlap_frac, 3),
            "backfill_steady_compiles": int(st.steady_compiles),
            "backfill_steady_compile_s": round(st.steady_compile_s, 3),
            "backfill_serial_rows": n_serial,
            "backfill_serial_wall_s": round(serial_s, 3),
        }
        if n_serial and st.rows and stream_s > 0:
            out["backfill_speedup_vs_serial"] = round(
                (serial_s / n_serial) / (stream_s / st.rows), 2)

        if backend == "nativelog":
            # snapshot tenant bootstrap, end to end (restore ->
            # streamed train -> fold-tail catch-up; no host admission
            # here — the bench has no serving host to admit into)
            _stage("bench_backfill: bootstrap")
            from predictionio_tpu.core import EngineParams
            from predictionio_tpu.data.storage import snapshot as S
            from predictionio_tpu.dataplane import bootstrap_from_snapshot

            snap_uri = "file://" + os.path.join(base, "backups")
            S.create_snapshot(app_id, snap_uri, name="bench")

            def fresh_events(_manifest):
                # post-restore live traffic the catch-up must fold
                from predictionio_tpu.data.columnar import ColumnarBatch
                from predictionio_tpu.data.event import (format_event_time,
                                                         utcnow)
                k = 512
                now = format_event_time(utcnow())
                ev.insert_columnar(ColumnarBatch(
                    k, "rate", "user",
                    [f"fresh_u{j % 97}" for j in range(k)],
                    target_entity_type="item",
                    target_entity_id=[f"i{j % n_items}" for j in range(k)],
                    properties=[{"rating": 5.0}] * k,
                    event_time=now), app_id)

            params = EngineParams(
                data_source_params=("", R.DataSourceParams(
                    app_name="backfillapp", stream=True)),
                preparator_params=("", R.PreparatorParams()),
                algorithm_params_list=[("als", R.ALSAlgorithmParams(
                    rank=8, num_iterations=2, lam=0.05, seed=1))],
                serving_params=("", None))
            report = bootstrap_from_snapshot(
                "bench-tenant", snap_uri, "bench",
                R.RecommendationEngineFactory.apply(), params,
                force=True, engine_factory="recommendation",
                on_restored=fresh_events)
            out["bootstrap_restore_s"] = round(report.restore_s, 3)
            out["bootstrap_train_s"] = round(report.train_s, 3)
            out["bootstrap_catchup_s"] = round(
                report.bootstrap_catchup_s, 3)
            out["bootstrap_catchup_events"] = int(
                report.catchup_events)
        return out


def bench_rest_latency(model, n_queries=200, wait_ms=None, reps=3,
                       openloop=True, result_cache=True,
                       inflight=None):
    """p50 of POST /queries.json against the trained model via the real
    engine server (loopback HTTP). `wait_ms` sets the micro-batcher's
    coalescing window — swept by main() to pick the default from data;
    None means "whatever ServerConfig ships", so the headline row always
    characterizes the configuration a `pio deploy` user actually gets
    (round-4 verdict: the old 2.0 default measured a config nobody ran).

    The concurrent phase runs an untimed warm burst first (the scorer
    pads batch dims to powers of two, so the first burst compiles each
    new shape — timing it mixes compilation into qps and produced the
    round-4 3x main-block-vs-sweep spread), then `reps` timed bursts,
    reporting the median as qps_concurrent16 with min/max alongside."""
    import urllib.request

    from predictionio_tpu.core import EngineParams, FirstServing
    from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
    from predictionio_tpu.data.storage.base import EngineInstance
    from predictionio_tpu.models import recommendation as R
    from predictionio_tpu.serving import EngineServer, ServerConfig
    import datetime as dt

    n_users = model.user_factors.shape[0]
    n_items = model.item_factors.shape[0]
    user_ix = EntityIdIxMap(
        BiMap({str(i): i for i in range(n_users)}))
    item_ix = EntityIdIxMap(
        BiMap({str(i): i for i in range(n_items)}))
    rec_model = R.RecommendationModel(model, user_ix, item_ix)
    algo = R.ALSAlgorithm(R.ALSAlgorithmParams(rank=model.rank))

    if wait_ms is None:
        wait_ms = ServerConfig.micro_batch_wait_ms  # the shipped default
    engine = R.RecommendationEngineFactory.apply()
    server = EngineServer(ServerConfig(ip="127.0.0.1", port=0,
                                       micro_batch=32,
                                       micro_batch_wait_ms=wait_ms,
                                       result_cache=result_cache,
                                       serve_inflight=inflight),
                          engine=engine)
    now = dt.datetime.now(dt.timezone.utc)
    server.engine_instance = EngineInstance(
        id="bench", status="COMPLETED", start_time=now, end_time=now,
        engine_id="bench", engine_version="0", engine_variant="bench",
        engine_factory="recommendation")
    server.algorithms = [algo]
    server.models = [rec_model]
    server.serving = FirstServing()
    server.start()
    client = _Client(server.config.port)
    try:
        rng = np.random.default_rng(0)
        users = rng.integers(0, n_users, n_queries)
        # warmup (first call compiles the serve kernel on-device)
        for u in users[:10]:
            client.post({"user": str(int(u)), "num": 10}, timeout=600)
        # registry-histogram window marker: percentiles derived below
        # must cover the TIMED traffic only, not the compile-dominated
        # warmup observations already in the cumulative buckets
        q_hist = server.metrics.get("pio_engine_query_seconds")
        q_hist_pre = q_hist.bucket_counts()
        # runtime attribution window markers (ISSUE 11): estimated
        # device seconds + sampling-profiler wall spent DURING the
        # timed traffic only
        from predictionio_tpu.obs import costmon as _costmon
        from predictionio_tpu.obs.profiler import PROFILER as _PROF
        dev_pre = sum(_costmon.device_time_by_executable().values())
        prof_pre = _PROF.spent_s
        t_window0 = time.perf_counter()
        lat = []
        for u in users:
            t0 = time.perf_counter()
            client.post({"user": str(int(u)), "num": 10})
            lat.append(time.perf_counter() - t0)
        lat = np.array(lat)

        # concurrent throughput: 16 keep-alive clients (serial p50 pays
        # every per-request fixed cost in full; the path pipelines, so
        # concurrency recovers throughput)
        from concurrent.futures import ThreadPoolExecutor
        n_workers, n_total = 16, 320
        # pre-framed request bytes + raw-socket round trips: the load
        # phases measure the SERVER; a fat client on a shared-core
        # container steals the core from it (PR 7 methodology lesson)
        pool = _PerThreadClients(server.config.port, fast=True)
        frames = {int(u): _FastClient.frame(
            {"user": str(int(u)), "num": 10}) for u in set(users)}

        def worker(uid):
            pool.get().roundtrip(frames[int(uid)])
        jobs = [users[i % len(users)] for i in range(n_total)]
        with ThreadPoolExecutor(n_workers) as ex:
            # untimed warm burst: compiles every power-of-two batch shape
            # the 16-client load can produce, so the timed reps measure
            # steady state, not compilation (the round-4 3x spread)
            list(ex.map(worker, jobs[:64]))
            # snapshot batcher counters so the coalescing number covers
            # ONLY the timed bursts (warmup + the serial loop run
            # hundreds of single-query batches that would dilute a
            # cumulative average)
            pre = json.loads(client.get("/stats.json"))
            # readback-plane window marker (ISSUE 19): overlap frac +
            # bytes/window over the timed concurrent bursts only (the
            # serial loop's windows never have a neighbor to hide
            # their d2h wall behind)
            from predictionio_tpu.ops import readback as _readback
            rb_pre = _readback.stats_snapshot()
            qps_reps = []
            for _ in range(max(1, reps)):
                t0 = time.perf_counter()
                list(ex.map(worker, jobs))
                qps_reps.append(n_total / (time.perf_counter() - t0))
            rb_post = _readback.stats_snapshot()
        pool.close_all()
        # server-side latency split: device/score time vs serve+HTTP
        stats = json.loads(client.get("/stats.json"))
        d_q = (stats.get("batchedQueries", 0)
               - pre.get("batchedQueries", 0))
        d_b = stats.get("batches", 0) - pre.get("batches", 0)
        out = {"p50_ms": float(np.percentile(lat, 50) * 1000),
               "p95_ms": float(np.percentile(lat, 95) * 1000),
               "p99_ms": float(np.percentile(lat, 99) * 1000),
               "qps_serial": float(1.0 / lat.mean()),
               "qps_concurrent16": float(np.median(qps_reps)),
               "qps_concurrent16_min": float(min(qps_reps)),
               "qps_concurrent16_max": float(max(qps_reps)),
               "server_avg_total_ms": stats["avgServingSec"] * 1000,
               "server_avg_predict_ms": stats["avgPredictSec"] * 1000,
               # realized coalescing DURING the timed bursts — the
               # datum for tuning micro_batch_wait_ms
               "serve_avg_batch_size": (d_q / d_b if d_b else 0.0),
               "serve_max_batch_size": float(
                   stats.get("maxBatchSize", 0))}
        # pipelined executor + result cache attribution (ISSUE 14,
        # schema-additive): what fraction of the headline throughput
        # the cache answered, and whether windows actually overlapped
        # readback plane (ISSUE 19, schema-additive): how much of the
        # serve d2h span hid behind neighboring windows' work, and the
        # payload each window actually moved (packed: k x batch x 6
        # bytes; the d2h floor is latency-bound, so small+overlapped
        # is the whole win)
        rb_windows = rb_post["windows"] - rb_pre["windows"]
        if rb_windows > 0:
            out["serve_d2h_overlap_frac"] = round(
                _readback.overlap_frac(rb_post, rb_pre), 4)
            out["serve_readback_bytes_per_window"] = round(
                (rb_post["bytes"] - rb_pre["bytes"]) / rb_windows, 1)
        rc = stats.get("resultCache") or {}
        if rc.get("hitRate") is not None:
            out["serve_cache_hit_rate"] = round(float(rc["hitRate"]), 4)
        if stats.get("pipelined") is not None:
            out["serve_pipelined"] = bool(stats.get("pipelined"))
            out["serve_pipeline_stalls"] = float(
                stats.get("pipelineStalls", 0))
        # registry-derived per-phase percentiles (ISSUE 2): the same
        # bucketed histograms /metrics scrapes, in place of further
        # ad-hoc min/mean keys. Additive — the schema above is stable.
        # Windowed from the post-warmup marker so the compile-dominated
        # warmup queries (first serve kernel + every batch shape) don't
        # masquerade as steady-state tail latency.
        for q, suffix in ((50, "p50_ms"), (99, "p99_ms")):
            v = q_hist.percentile_since(q_hist_pre, q)
            if v is not None:
                out[f"serve_hist_{suffix}"] = float(v * 1000)
        wait_hist = getattr(server.batcher, "wait_hist", None)
        if wait_hist is not None and wait_hist.count:
            for q, suffix in ((50, "p50_ms"), (99, "p99_ms")):
                v = wait_hist.percentile(q)
                if v is not None:
                    out[f"batch_wait_hist_{suffix}"] = float(v * 1000)
        # runtime attribution (ISSUE 11, schema-additive): where the
        # serve window's time went — estimated device seconds over the
        # timed wall (the ALX-style occupancy number), the queue-vs-
        # device p99 decomposition, and the always-on profiler's own
        # cost over the same window
        window_s = time.perf_counter() - t_window0
        dev_s = sum(_costmon.device_time_by_executable().values()) \
            - dev_pre
        if window_s > 0:
            out["device_time_fraction"] = round(
                min(dev_s / window_s, 1.0), 4)
        if wait_hist is not None and wait_hist.count:
            v = wait_hist.percentile(99)
            if v is not None:
                out["serve_queue_p99_ms"] = float(v * 1000)
        dev_pct = _costmon.device_time_percentiles(
            _costmon.BATCH_PREDICT)
        if dev_pct is not None:
            out["serve_device_p99_ms"] = dev_pct["p99_ms"]
        out["profiler_overhead_ms"] = round(
            (_PROF.spent_s - prof_pre) * 1000.0, 3)
        # open-loop phase (ISSUE 14 satellite — the bench-honesty fix):
        # the closed-loop 16-client loop above hides coordinated
        # omission — a slow response delays that client's NEXT request,
        # so queue delay never accumulates into the measured p99. Here
        # requests fire on a FIXED arrival schedule regardless of
        # completions, and each latency is measured from the request's
        # SCHEDULED instant — a response that kept the schedule waiting
        # is charged its full queue time. Keys are schema-additive
        # (serve_*_openloop) next to the closed-loop ones; banked
        # artifacts are never rewritten.
        if openloop:
            out.update(_serve_openloop(
                server.config.port, users,
                target_qps=0.7 * out["qps_concurrent16"]))
        return out
    finally:
        client.close()
        server.stop()


def _serve_openloop(port, users, target_qps: float,
                    duration_s: float = 4.0, workers: int = 32) -> dict:
    """Fixed-arrival-rate load against a running engine server: one
    scheduler thread submits on the tick, a worker pool executes, and
    latency runs scheduled-send -> completion (coordinated-omission-
    free). The target defaults to 0.7x the measured closed-loop
    throughput — below saturation, so the p99 reflects service + queue
    jitter rather than an intentionally overloaded queue."""
    from concurrent.futures import ThreadPoolExecutor

    target_qps = max(target_qps, 5.0)
    n = int(min(max(target_qps * duration_s, 50), 4000))
    interval = 1.0 / target_qps
    pool = _PerThreadClients(port, fast=True)
    frames = {int(u): _FastClient.frame(
        {"user": str(int(u)), "num": 10}) for u in set(users)}
    lat = [None] * n

    def fire(i, t_sched):
        # the schedule, not the send, anchors the measurement
        pool.get().roundtrip(frames[int(users[i % len(users)])])
        lat[i] = time.perf_counter() - t_sched

    t0 = time.perf_counter()
    with ThreadPoolExecutor(workers) as ex:
        futures = []
        for i in range(n):
            t_sched = t0 + i * interval
            delay = t_sched - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            futures.append(ex.submit(fire, i, t_sched))
        errors = sum(1 for f in futures if f.exception() is not None)
    wall = time.perf_counter() - t0
    pool.close_all()
    done = np.array([v for v in lat if v is not None])
    if not len(done):
        return {}
    out = {
        "serve_openloop_target_qps": float(round(target_qps, 1)),
        "serve_qps_openloop": float(len(done) / wall),
        "serve_p50_ms_openloop": float(np.percentile(done, 50) * 1000),
        "serve_p99_ms_openloop": float(np.percentile(done, 99) * 1000),
    }
    if errors:
        out["serve_openloop_errors"] = int(errors)
    return out


class _Client:
    """Keep-alive HTTP client with TCP_NODELAY — stdlib urllib opens a new
    connection per request and writes headers/body separately, so Nagle +
    delayed ACK adds ~40-200 ms per request that has nothing to do with the
    server under test."""

    def __init__(self, port):
        self.port = port
        self.conn = None

    def _connect(self, timeout):
        import http.client
        import socket
        self.conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                               timeout=timeout)
        self.conn.connect()
        self.conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def post(self, body, timeout=30, path="/queries.json"):
        if self.conn is None:
            self._connect(timeout)
        try:
            self.conn.request("POST", path,
                              body=json.dumps(body),
                              headers={"Content-Type": "application/json"})
            resp = self.conn.getresponse()
            out = resp.read()
            if resp.status >= 400:
                # every bench loop expects success; counting error
                # responses (which skip the real work and return fast)
                # would silently inflate the published rate
                raise RuntimeError(
                    f"HTTP {resp.status} from {path}: {out[:200]!r}")
            return out
        except Exception:
            self.close()
            raise

    def get(self, path, timeout=30):
        if self.conn is None:
            self._connect(timeout)
        try:
            self.conn.request("GET", path)
            return self.conn.getresponse().read()
        except Exception:
            self.close()
            raise

    def close(self):
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class _FastClient:
    """wrk-style minimal HTTP/1.1 load client: pre-framed request
    bytes, one sendall + recv-parse per round trip over a keep-alive
    socket with TCP_NODELAY. http.client's per-request header
    assembly and response machinery cost ~100 µs of CLIENT CPU per
    call — on a shared-core bench container that under-reports the
    SERVER's throughput (the PR 7 "client shares the generator's GIL"
    methodology lesson, applied to the serve plane). Still strictly
    closed-loop: one outstanding request per connection."""

    def __init__(self, port):
        import socket
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._buf = b""

    @staticmethod
    def frame(body_obj, path="/queries.json") -> bytes:
        body = json.dumps(body_obj).encode()
        return (f"POST {path} HTTP/1.1\r\nHost: bench\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n"
                f"Connection: keep-alive\r\n\r\n").encode() + body

    def roundtrip(self, framed: bytes) -> bytes:
        self.sock.sendall(framed)
        while b"\r\n\r\n" not in self._buf:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed connection")
            self._buf += chunk
        head, _, rest = self._buf.partition(b"\r\n\r\n")
        status = int(head.split(None, 2)[1])
        clen = 0
        for line in head.split(b"\r\n")[1:]:
            if line[:15].lower() == b"content-length:":
                clen = int(line[15:])
                break
        while len(rest) < clen:
            chunk = self.sock.recv(65536)
            if not chunk:
                raise ConnectionError("server closed connection")
            rest += chunk
        body, self._buf = rest[:clen], rest[clen:]
        if status >= 400:
            raise RuntimeError(f"HTTP {status}: {body[:200]!r}")
        return body

    def close(self):
        try:
            self.sock.close()
        except OSError:
            pass


class _PerThreadClients:
    """One keep-alive client per worker thread (a shared connection
    would interleave concurrent requests on one socket).
    ``fast=True`` hands out _FastClient sockets for the pre-framed
    load phases."""

    def __init__(self, port, fast: bool = False):
        self.port = port
        self.fast = fast
        self._tls = threading.local()
        self._all = []
        self._lock = threading.Lock()

    def get(self):
        c = getattr(self._tls, "client", None)
        if c is None:
            c = _FastClient(self.port) if self.fast \
                else _Client(self.port)
            self._tls.client = c
            with self._lock:
                self._all.append(c)
        return c

    def close_all(self):
        for c in self._all:
            c.close()


@contextmanager
def bench_storage_env(backend: str, base: str):
    """Scoped PIO_STORAGE environment for a bench run: sqlite metadata,
    `backend` ("nativelog"/"sqlite") event data, localfs models, all
    rooted under `base`. Restores the caller's storage env and clears
    the registry cache on exit (shared by the product-path and ingest
    benches so the two can't drift)."""
    from predictionio_tpu.data.storage import registry

    saved = {k: os.environ[k] for k in list(os.environ)
             if k.startswith("PIO_STORAGE")}
    for k in saved:
        del os.environ[k]
    os.environ.update({
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "bench_meta",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "SQLITE",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "bench_event",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": backend.upper(),
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "bench_model",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "LOCALFS",
        "PIO_STORAGE_SOURCES_SQLITE_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_SQLITE_URL": os.path.join(base, "pio.db"),
        "PIO_STORAGE_SOURCES_NATIVELOG_TYPE": "nativelog",
        "PIO_STORAGE_SOURCES_NATIVELOG_PATH": os.path.join(base, "evlog"),
        "PIO_STORAGE_SOURCES_NATIVELOG_PARTITIONS": "8",
        "PIO_STORAGE_SOURCES_LOCALFS_TYPE": "localfs",
        "PIO_STORAGE_SOURCES_LOCALFS_HOSTS": os.path.join(base, "models"),
    })
    registry.clear_cache()
    try:
        yield
    finally:
        registry.clear_cache()
        for k in list(os.environ):
            if k.startswith("PIO_STORAGE"):
                del os.environ[k]
        os.environ.update(saved)
        registry.clear_cache()


def measure_d2h_floor_ms() -> dict:
    """Per-transfer device->host latency vs payload size. A flat profile
    across 40 B..4 MB payloads is the signature of a per-transfer latency
    floor, not bandwidth."""
    import jax
    f = jax.jit(lambda a: a * 2)
    out = {}
    for n in (10, 1000, 100_000, 1_000_000):
        x = jax.device_put(np.arange(n, dtype=np.float32))
        np.asarray(f(x))  # warm compile + cache
        ts = []
        for _ in range(7):
            t0 = time.perf_counter()
            np.asarray(f(x))
            ts.append(time.perf_counter() - t0)
        out[f"d2h_ms_{4 * n}B"] = round(
            float(np.percentile(ts, 50) * 1000), 3)
    out["d2h_floor_ms"] = out["d2h_ms_40B"]
    return out


def require_device(tiny: bool = False) -> dict:
    """The device this run measures, or exit: a benchmark number is a
    chip number, so anything but a TPU ends the run non-zero with the
    reason and no metric. ``--tiny`` is the control-flow run at toy
    sizes on whatever the platform resolver allows (the CPU only under
    an explicit JAX_PLATFORMS=cpu); its output is named for what it
    is, never for a chip metric."""
    from predictionio_tpu.parallel.mesh import (DeviceUnavailable,
                                                device_platform)
    try:
        device = device_platform()
    except DeviceUnavailable as e:
        raise SystemExit(f"bench.py: {e}")
    if device["platform"] != "tpu" and not tiny:
        raise SystemExit(
            f"bench.py: no chip (platform={device['platform']}, "
            f"device_kind={device['device_kind']}): a benchmark number "
            f"is a chip number. `python bench.py --tiny` runs the "
            f"control flow at toy sizes under its own metric name.")
    return device


def main():
    tiny = "--tiny" in sys.argv
    device = require_device(tiny)
    full_scale = not tiny
    _stage("bench_als")
    als_stats, model = bench_als(full_scale)
    _stage("bench_rest_latency")
    rest_stats = bench_rest_latency(model)
    rest_stats.update(measure_d2h_floor_ms())
    # micro-batch coalescing-window sweep: the datum for choosing the
    # micro_batch_wait_ms default (serial p50 pays the window when idle,
    # concurrent throughput gains from coalescing — both reported)
    _stage("serve_sweep")
    serve_sweep = {}
    if not os.environ.get("PIO_BENCH_SKIP_SERVE_SWEEP"):
        for w in (2.0, 5.0, 10.0):
            _stage(f"serve_sweep wait={w:g}")
            # the sweep compares closed-loop coalescing per window
            # setting; the open-loop phase runs once, on the headline
            # configuration
            # cache off: the sweep characterizes the BATCHER per
            # window setting — repeated hot-user queries answering
            # from the result cache would never reach it
            s = bench_rest_latency(model, n_queries=100, wait_ms=w,
                                   openloop=False, result_cache=False)
            serve_sweep[f"{w:g}"] = {
                "p50_ms": round(s["p50_ms"], 3),
                "p99_ms": round(s["p99_ms"], 3),
                "qps_concurrent16": round(s["qps_concurrent16"], 1),
                "qps_concurrent16_min": round(
                    s["qps_concurrent16_min"], 1),
                "qps_concurrent16_max": round(
                    s["qps_concurrent16_max"], 1),
                "avg_batch": round(s["serve_avg_batch_size"], 2)}
            _stage(f"serve_sweep wait={w:g} done")
    # in-flight transfer-depth sweep (ISSUE 19): with d2h copies in
    # flight at dispatch, PIO_SERVE_INFLIGHT is the number of serve
    # windows whose readback walls may overlap — the knob that beats
    # the fixed d2h floor on a real chip. Swept closed-loop on the
    # headline wait; each point carries its measured overlap fraction.
    inflight_sweep = {}
    if not os.environ.get("PIO_BENCH_SKIP_INFLIGHT_SWEEP"):
        for depth in (1, 2, 3, 4):
            _stage(f"serve_inflight_sweep depth={depth}")
            s = bench_rest_latency(model, n_queries=100,
                                   openloop=False, result_cache=False,
                                   inflight=depth)
            row = {"p50_ms": round(s["p50_ms"], 3),
                   "qps_concurrent16": round(s["qps_concurrent16"], 1)}
            if "serve_d2h_overlap_frac" in s:
                row["d2h_overlap_frac"] = s["serve_d2h_overlap_frac"]
            inflight_sweep[str(depth)] = row
            _stage(f"serve_inflight_sweep depth={depth} done")
    product_stats = {}
    if not os.environ.get("PIO_BENCH_SKIP_PRODUCT"):
        _stage("bench_product_path")
        product_stats = bench_product_path(full_scale)
    _stage("product done")
    baseline_stats = {}
    if not os.environ.get("PIO_BENCH_SKIP_BASELINE"):
        _stage("mllib_shaped_cpu_baseline")
        baseline_stats = mllib_shaped_cpu_baseline(full_scale)
    _stage("baseline done")
    ingest_stats = {}
    if not os.environ.get("PIO_BENCH_SKIP_INGEST"):
        _stage("bench_ingest")
        ingest_stats = bench_ingest(full_scale)
    fold_stats = {}
    if not os.environ.get("PIO_BENCH_SKIP_FOLD"):
        # online fold-tick scenario (ISSUE 4): the BENCH_*.json
        # trajectory finally covers the online path (schema-additive)
        _stage("bench_fold_tick")
        fold_stats = bench_fold_tick(full_scale)
    sharded_stats = {}
    if not os.environ.get("PIO_BENCH_SKIP_SHARDED"):
        # sharded online plane (ISSUE 12): model-sharded fold/serve
        # rows next to the replicated ones (schema-additive; no-op on
        # a single-device backend)
        _stage("bench_sharded")
        sharded_stats = bench_sharded(full_scale)
    multitenant_stats = {}
    if not os.environ.get("PIO_BENCH_SKIP_MULTITENANT"):
        # multi-tenant serving host (ISSUE 15): three tenants packed
        # under a forced-tight HBM budget (schema-additive)
        _stage("bench_multitenant")
        multitenant_stats = bench_multitenant(full_scale)
    backfill_stats = {}
    if not os.environ.get("PIO_BENCH_SKIP_BACKFILL"):
        # bulk data plane (ISSUE 16): streamed backfill vs serial
        # drain + snapshot tenant bootstrap (schema-additive)
        _stage("bench_backfill")
        backfill_stats = bench_backfill(full_scale)
    value = als_stats["ratings_per_sec_per_chip"]
    out = {
        # a toy-size run is named for what it is: the chip metric's name
        # is only ever printed by a full-scale run on a TPU
        "metric": ("als_ml20m_rank200_ratings_per_sec_per_chip"
                   if full_scale else "als_tiny_ratings_per_sec"),
        "value": round(value, 1),
        "unit": "ratings/s/chip" if full_scale else "ratings/s",
        "vs_baseline": round(value / SPARK_CPU_BASELINE_RATINGS_PER_SEC, 3),
        "platform": device["platform"],
        "device_kind": device["device_kind"],
        "device_count": device["n"],
        "full_scale": full_scale,
        **{k: (round(v, 4) if isinstance(v, float) else v)
           for k, v in als_stats.items() if k != "ratings_per_sec_per_chip"},
        **{k: round(v, 3) for k, v in rest_stats.items()},
        **product_stats,
        **baseline_stats,
        **ingest_stats,
        **fold_stats,
        **sharded_stats,
        **multitenant_stats,
        **backfill_stats,
    }
    if baseline_stats:
        # the north-star ratio computed from two numbers measured on
        # this machine, next to the assumed-constant version
        out["vs_baseline_measured"] = round(
            value / baseline_stats["baseline_measured_ratings_per_sec"], 3)
    if serve_sweep:
        out["serve_wait_sweep_ms"] = serve_sweep
        # regression guard (ISSUE 19 satellite): surface the sweep's
        # winner so a capture where the configured default loses to
        # another window setting is visible in one key
        best = max(serve_sweep,
                   key=lambda w: serve_sweep[w]["qps_concurrent16"])
        out["serve_wait_best_ms"] = float(best)
        out["serve_wait_best_qps"] = serve_sweep[best]["qps_concurrent16"]
    if inflight_sweep:
        out["serve_inflight_sweep"] = inflight_sweep
        best_d = max(inflight_sweep,
                     key=lambda d: inflight_sweep[d]["qps_concurrent16"])
        out["serve_inflight_best"] = int(best_d)
    print(json.dumps(out))


def solver_ablation():
    """Reproduce the solver ablation table (docs/benchmarks.md): time one
    full ML-20M iteration per solver configuration on the current
    backend. Run: python bench.py --ablation"""
    import jax
    from predictionio_tpu.ops import als as A
    from predictionio_tpu.ops.als import ALSConfig
    from predictionio_tpu.ops.ratings import (RatingsCOO, plan_for_items,
                                              plan_for_users)
    from predictionio_tpu.parallel.mesh import current_mesh

    full = "--tiny" not in sys.argv
    if full:
        n_users, n_items, nnz, rank = 138_493, 26_744, 20_000_000, 200
        # Ordered decision-first; rows print as they complete. Row 1 is
        # the production config whose compiles the headline bench
        # already left in the persistent cache; rows 2-3 are the
        # stage-split diagnostic that locates the 1.36 s/iteration of
        # TPU_BENCH_CAPTURE_latest.json (vs the 0.056 s roofline); then
        # the candidate levers; history/slow rows last.
        configs = [
            ("cg_pallas + dual + chunk4",
             dict(solver="cg_pallas", dual_solve="auto", sweep_chunk=4)),
            # stage split (diagnostic solvers, wrong math by design):
            # gather+scatter only, then +Gram without solve — differences
            # against row 1 split the iteration into gather / Gram /
            # solve shares
            ("DIAG gather+scatter (no gram/solve)",
             dict(solver="diag_gather", dual_solve="auto", sweep_chunk=4)),
            ("DIAG gather+gram (no solve)",
             dict(solver="diag_nosolve", dual_solve="auto",
                  sweep_chunk=4)),
            # ladder coarseness: at full scale the ladder size IS the
            # solver-call count (FULLSCALE_CPU.json: 47+78 uniquely-
            # shaped batches = 125 solver calls/iter at 1.125); ratio
            # 1.5/2.0 cut calls ~3x/5x at the cost of padding (gather
            # bytes + Gram flops). Round 2 measured coarser=worse in the
            # old per-batch-dispatch code; these re-measure on current
            # code where calls, not bytes, are the suspect
            ("cg_pallas + dual + ratio2.0",
             dict(solver="cg_pallas", dual_solve="auto",
                  bucket_ratio=2.0)),
            ("cg_pallas + dual + ratio1.5",
             dict(solver="cg_pallas", dual_solve="auto",
                  bucket_ratio=1.5)),
            # ratio x budget: work_budget splits cap the step reduction
            # (ratio2.0 alone is 67 steps because coarse buckets split;
            # with a 4M budget the host-side plan counts are 48 steps at
            # 1.5 / 35 at 2.0 vs the default plan's 125)
            ("cg_pallas + dual + ratio2.0 + budget4M",
             dict(solver="cg_pallas", dual_solve="auto",
                  bucket_ratio=2.0, work_budget=(1 << 22))),
            ("cg_pallas + dual + ratio1.5 + budget4M",
             dict(solver="cg_pallas", dual_solve="auto",
                  bucket_ratio=1.5, work_budget=(1 << 22))),
            ("cg_pallas + dual + ratio2.0 + budget4M + dualcap16",
             dict(solver="cg_pallas", dual_solve="auto",
                  bucket_ratio=2.0, work_budget=(1 << 22),
                  dual_iters_cap=16)),
            # does dual-solve time scale with CG depth or is it per-call
            # fixed? SPEED measurement only here; accuracy at the full
            # rank-200 regime is pre-cleared (MATH_PARITY.json
            # als_train_dualcap16_cg: heldout RMSE identical to uncapped)
            ("cg_pallas + dual + chunk4 + dualcap16",
             dict(solver="cg_pallas", dual_solve="auto", sweep_chunk=4,
                  dual_iters_cap=16)),
            # the combined candidate default if the two singles above
            # both win
            ("cg_pallas + dual + ratio2.0 + dualcap16",
             dict(solver="cg_pallas", dual_solve="auto",
                  bucket_ratio=2.0, dual_iters_cap=16)),
            # if the ~20-30 ms/solver-call fixed cost is Pallas launch
            # overhead, XLA-native CG dodges it at the cost of slower
            # matvecs
            ("cg (XLA) + dual + chunk4",
             dict(solver="cg", dual_solve="auto", sweep_chunk=4)),
            # once per-call costs are amortized, the f32 factor-row
            # gathers are the roofline numerator (45.5 GB/iter) — bf16
            # tables halve it
            ("cg_pallas + dual + chunk4 + bf16 tables",
             dict(solver="cg_pallas", dual_solve="auto", sweep_chunk=4,
                  factor_dtype="bfloat16")),
            ("cg_pallas + dual + chunk4 + fused iteration",
             dict(solver="cg_pallas", dual_solve="auto", sweep_chunk=4,
                  fuse_iteration=True)),
            ("cg_pallas + dual + chunk8",
             dict(solver="cg_pallas", dual_solve="auto", sweep_chunk=8)),
            # larger solve batches amortize per-call cost only where a
            # bucket actually split (a handful at budget 1M) — expected
            # marginal; kept to close the hypothesis
            ("cg_pallas + dual + chunk4 + budget4M",
             dict(solver="cg_pallas", dual_solve="auto", sweep_chunk=4,
                  work_budget=(1 << 22))),
            ("cg_pallas + dual + budget4M",
             dict(solver="cg_pallas", dual_solve="auto",
                  work_budget=(1 << 22))),
            ("schulz_pallas + dual + chunk4",
             dict(solver="schulz_pallas", dual_solve="auto",
                  sweep_chunk=4)),
            ("implicit cg_pallas + dual + chunk4",
             dict(solver="cg_pallas", dual_solve="auto", sweep_chunk=4,
                  implicit_prefs=True)),
            # per-solver-call fixed cost amortization curve (chunk1/2
            # complete the 1/2/4/8 sweep)
            ("cg_pallas + dual", dict(solver="cg_pallas",
                                      dual_solve="auto")),
            ("cg_pallas + dual + chunk2",
             dict(solver="cg_pallas", dual_solve="auto", sweep_chunk=2)),
            # MXU-packed panel factorization: the dense-bucket candidate
            # (never compiled under real Mosaic beyond K=8; a FAILED row
            # here is a finding, not an error)
            ("chol_pallas + dual + chunk4",
             dict(solver="chol_pallas", dual_solve="auto",
                  sweep_chunk=4)),
            ("implicit cg_pallas + dual (eig-SMW)",
             dict(solver="cg_pallas", dual_solve="auto",
                  implicit_prefs=True)),
            ("implicit cg_pallas primal",
             dict(solver="cg_pallas", dual_solve="never",
                  implicit_prefs=True)),
            ("cg_pallas primal", dict(solver="cg_pallas",
                                      dual_solve="never")),
            ("cholesky primal", dict(solver="cholesky",
                                     dual_solve="never")),
        ]
    else:
        n_users, n_items, nnz, rank = 2_000, 500, 60_000, 32
        configs = [
            ("cholesky primal", dict(solver="cholesky",
                                     dual_solve="never")),
            ("cg + dual", dict(solver="cg", dual_solve="auto")),
            ("implicit cg + dual", dict(solver="cg", dual_solve="auto",
                                        implicit_prefs=True)),
            ("cg + dual + chunk4",
             dict(solver="cg", dual_solve="auto", sweep_chunk=4)),
            ("cg + dual + chunk4 + fused iteration",
             dict(solver="cg", dual_solve="auto", sweep_chunk=4,
                  fuse_iteration=True)),
            ("DIAG gather+scatter (no gram/solve)",
             dict(solver="diag_gather", dual_solve="auto", sweep_chunk=4)),
            ("DIAG gather+gram (no solve)",
             dict(solver="diag_nosolve", dual_solve="auto",
                  sweep_chunk=4)),
            # exercises the per-budget plan/upload machinery in smoke
            ("cg + dual + budget/4",
             dict(solver="cg", dual_solve="auto",
                  work_budget=(1 << 18))),
            # exercises the per-ratio plan machinery in smoke
            ("cg + dual + ratio2.0",
             dict(solver="cg", dual_solve="auto", bucket_ratio=2.0)),
        ]
    ui, ii, vv = synthetic_ml20m(n_users, n_items, nnz)
    ratings = RatingsCOO(ui, ii, vv, n_users, n_items)
    mesh = current_mesh()
    plans = {}     # (budget, ratio) -> (user_plan, item_plan)
    uploads = {}   # (chunk, budget, ratio) -> (user_batches, item_batches)

    def batches_for(chunk, budget, ratio):
        if (budget, ratio) not in plans:
            # batch_multiple keeps B divisible by the data axis — without
            # it the upload's batch-dim sharding rejects odd-B batches on
            # any mesh with dp > 1
            dp = mesh.data_parallelism
            plans[(budget, ratio)] = (
                plan_for_users(ratings, work_budget=budget,
                               batch_multiple=dp, bucket_ratio=ratio),
                plan_for_items(ratings, work_budget=budget,
                               batch_multiple=dp, bucket_ratio=ratio))
        key = (chunk, budget, ratio)
        if key not in uploads:
            up, ip = plans[(budget, ratio)]
            uploads[key] = (A._upload_plan(mesh, up, chunk),
                            A._upload_plan(mesh, ip, chunk))
        return uploads[key]
    _stage("ablation: replicate scalars")
    lam = mesh.put_replicated(np.float32(0.05))
    alpha = mesh.put_replicated(np.float32(1.0))
    for name, kw in configs:
        _stage(f"ablation: {name}")
        cfg = ALSConfig(rank=rank, iterations=1, lam=0.05, seed=1,
                        compute_dtype=("bfloat16" if full else "float32"),
                        **{"work_budget": (1 << 20), **kw})
        # resolve chunk exactly as als_train would (auto -> 4 on a
        # single-device TPU): rows that omit sweep_chunk must still
        # measure the PRODUCTION chunking, else every ratio/budget/
        # candidate row silently conflates its lever with a chunk=1
        # downgrade vs the chunk4 baseline row
        user_batches, item_batches = batches_for(
            A.resolve_sweep_chunk(cfg.sweep_chunk, mesh.n_devices),
            cfg.work_budget, cfg.bucket_ratio)
        fdt = cfg.factor_dtype
        import jax.numpy as jnp
        dt = jnp.bfloat16 if fdt == "bfloat16" else np.float32
        U = mesh.put_replicated(
            A._init_factors(n_users, rank, 1, 1).astype(dt))
        V = mesh.put_replicated(
            A._init_factors(n_items, rank, 1, 2).astype(dt))
        imp = cfg.implicit_prefs
        gram_of = ((A._gram_eig if cfg.dual_solve == "auto" else A._gram)
                   if imp else None)

        def run_iter(U, V):
            if cfg.fuse_iteration:
                U, V, _cg_iters = A._solve_iteration(
                    U, V, user_batches, item_batches, lam, alpha,
                    nratings_reg=True, implicit=imp, rank=rank,
                    compute_dtype=cfg.compute_dtype, solver=cfg.solver,
                    dual_solve=cfg.dual_solve,
                    solver_iters=cfg.solver_iters,
                    dual_iters_cap=cfg.dual_iters_cap,
                    n_users=n_users, n_items=n_items)
                return U, V
            # the conditional keeps the explicit timed path free of even
            # the factor-slice dispatch the gram computation needs
            U = A._run_side(user_batches, U, V, cfg,
                            gram_of(V[:n_items]) if imp else None,
                            lam, alpha)
            V = A._run_side(item_batches, V, U, cfg,
                            gram_of(U[:n_users]) if imp else None,
                            lam, alpha)
            return U, V
        try:
            # warmup (compile)
            U, V = run_iter(U, V)
            float(np.asarray(jax.device_get(V[:1, :1]))[0, 0])
            t0 = time.perf_counter()
            for _ in range(2):
                U, V = run_iter(U, V)
            float(np.asarray(jax.device_get(V[:1, :1]))[0, 0])
            dt_s = (time.perf_counter() - t0) / 2
            print(f"{name:34s}: {dt_s * 1000:9.1f} ms/iteration "
                  f"({nnz / dt_s / 1e6:8.2f} M ratings/s)", flush=True)
        except Exception as e:
            print(f"{name:34s}: FAILED {type(e).__name__}: {e}",
                  flush=True)


def mesh_sweep():
    """Multi-chip weak scaling, measured: run the ALS iteration on 1
    device and on the full visible slice, reporting ratings/s/chip for
    each plus the compiled program's collective instructions (the
    GSPMD-emitted ICI traffic). Run: python bench.py --mesh-sweep.
    On a 1-chip host this degrades to the single-chip row — the sweep is
    staged so a multi-chip slice produces the scaling artifact with no
    code changes."""
    import jax
    from predictionio_tpu.ops import als as A
    from predictionio_tpu.ops.als import ALSConfig
    from predictionio_tpu.ops.ratings import RatingsCOO
    from predictionio_tpu.parallel.collective_stats import collective_stats
    from predictionio_tpu.parallel.mesh import make_mesh
    from predictionio_tpu.ops.solve import resolve_solver

    full = "--tiny" not in sys.argv
    if full:
        n_users, n_items, nnz, rank = 138_493, 26_744, 20_000_000, 200
    else:
        n_users, n_items, nnz, rank = 20_000, 4_000, 1_200_000, 32
    ui, ii, vv = synthetic_ml20m(n_users, n_items, nnz)
    ratings = RatingsCOO(ui, ii, vv, n_users, n_items)
    enable_persistent_cache()

    devices = jax.devices()
    rows = []
    for n in sorted({1, len(devices)}):
        _stage(f"mesh_sweep n_devices={n}")
        mesh = make_mesh(devices=devices[:n])
        cfg = ALSConfig(rank=rank, iterations=1, lam=0.05, seed=1,
                        compute_dtype=("bfloat16" if full else "float32"),
                        work_budget=(1 << 20),
                        solver=resolve_solver("auto", n))
        run = prepare_als_run(mesh, ratings, cfg, batch_multiple=n)
        U, V = run["U"], run["V"]
        user_b, item_b = run["user_batches"], run["item_batches"]
        lam, alpha = run["lam"], run["alpha"]

        def run_iter(U, V):
            U = A._run_side(user_b, U, V, cfg, None, lam, alpha)
            V = A._run_side(item_b, V, U, cfg, None, lam, alpha)
            return U, V

        U, V = run_iter(U, V)   # warm/compile
        hard_sync(V)
        t0 = time.perf_counter()
        for _ in range(2):
            U, V = run_iter(U, V)
        hard_sync(V)
        dt = (time.perf_counter() - t0) / 2
        comp = A._solve_sweep.lower(
            U, V, None, user_b, lam, alpha,
            nratings_reg=True, implicit=False, rank=rank,
            compute_dtype=cfg.compute_dtype, solver=cfg.solver).compile()
        rows.append({
            "n_devices": n,
            "s_per_iteration": round(dt, 4),
            "ratings_per_sec_per_chip": round(nnz / dt / n, 1),
            "collective_instructions": collective_stats(comp),
        })
    out = {"metric": "als_mesh_weak_scaling", "backend":
           jax.default_backend(), "full_scale": full, "rows": rows}
    if len(rows) == 2:
        out["weak_scaling_efficiency"] = round(
            rows[1]["ratings_per_sec_per_chip"]
            / rows[0]["ratings_per_sec_per_chip"], 3)
    print(json.dumps(out), flush=True)


def full_scale_cpu_report(out_path="FULLSCALE_CPU.json"):
    """Full-scale evidence that needs no chip: build the REAL ML-20M /
    rank-200 plan (138,493 x 26,744, 20M nnz — BASELINE.json north star),
    run iterations on CPU, and emit plan statistics + convergence to a
    committed artifact. Proves the north-star shape builds, fits in
    memory, and converges without any TPU; the per-iteration *time* is a
    CPU number and is labeled as such. Run: python bench.py --full-scale-cpu
    """
    import resource

    import jax
    from predictionio_tpu.ops import als as A
    from predictionio_tpu.ops.als import ALSConfig, ALSModel, als_rmse
    from predictionio_tpu.ops.ratings import (RatingsCOO, plan_for_items,
                                              plan_for_users)
    from predictionio_tpu.parallel.mesh import current_mesh
    from predictionio_tpu.ops.solve import resolve_solver

    n_users, n_items, nnz, rank = 138_493, 26_744, 20_000_000, 200
    t0 = time.perf_counter()
    ui, ii, vv = synthetic_ml20m(n_users, n_items, nnz)
    ratings = RatingsCOO(ui, ii, vv, n_users, n_items)
    gen_s = time.perf_counter() - t0

    enable_persistent_cache()
    mesh = current_mesh()
    cfg = ALSConfig(rank=rank, iterations=1, lam=0.05, seed=1,
                    work_budget=(1 << 20),
                    solver=resolve_solver("auto", mesh.n_devices))

    t0 = time.perf_counter()
    user_plan = plan_for_users(ratings, work_budget=cfg.work_budget,
                               bucket_ratio=cfg.bucket_ratio)
    item_plan = plan_for_items(ratings, work_budget=cfg.work_budget,
                               bucket_ratio=cfg.bucket_ratio)
    plan_s = time.perf_counter() - t0

    host_plan_bytes = sum(
        b.rows.nbytes + b.idx.nbytes + b.val.nbytes + b.mask.nbytes
        for p in (user_plan, item_plan) for b in p.batches)
    factor_bytes = (n_users + n_items + 2) * rank * 4
    flops_iter = als_iteration_flops(user_plan, item_plan, rank)
    hbm_bytes = als_iteration_hbm_bytes(user_plan, item_plan, rank,
                                        "bfloat16")
    v5e_roofline_s = hbm_bytes / DEVICE_HBM_BW["TPU v5 lite"]

    t0 = time.perf_counter()
    chunk = A.resolve_sweep_chunk(cfg.sweep_chunk, mesh.n_devices)
    user_batches = A._upload_plan(mesh, user_plan, chunk)
    item_batches = A._upload_plan(mesh, item_plan, chunk)
    upload_s = time.perf_counter() - t0

    U = mesh.put_replicated(A._init_factors(n_users, rank, cfg.seed, 1))
    V = mesh.put_replicated(A._init_factors(n_items, rank, cfg.seed, 2))
    lam_dev = mesh.put_replicated(np.float32(cfg.lam))
    alpha_dev = mesh.put_replicated(np.float32(cfg.alpha))

    sample = np.random.default_rng(0).choice(nnz, 200_000, replace=False)
    sub = RatingsCOO(ui[sample], ii[sample], vv[sample], n_users, n_items)

    def rmse_now():
        m = ALSModel(np.asarray(U)[:n_users], np.asarray(V)[:n_items], rank)
        return round(float(als_rmse(m, sub)), 4)

    def run_side_split(groups, factors, counter):
        # one dispatch PER scan group instead of the production
        # single-program sweep: XLA:CPU takes upwards of an hour to
        # compile the ~60-group full-scale mega-program (observed), and
        # this artifact's evidence is the plan/memory/convergence, not
        # CPU dispatch efficiency. The math is identical; the TPU path
        # keeps the one-dispatch sweep.
        for g in groups:
            factors = A._run_side((g,), factors, counter, cfg, None,
                                  lam_dev, alpha_dev)
        return factors

    rmse_by_iter = [rmse_now()]
    iter_s = []
    for _ in range(3):
        t0 = time.perf_counter()
        U = run_side_split(user_batches, U, V)
        V = run_side_split(item_batches, V, U)
        hard_sync(V)
        iter_s.append(round(time.perf_counter() - t0, 2))
        rmse_by_iter.append(rmse_now())

    peak_rss_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    out = {
        "artifact": "full_scale_cpu_evidence",
        "workload": {"n_users": n_users, "n_items": n_items, "nnz": nnz,
                     "rank": rank},
        "backend": jax.default_backend(),
        "plan": {
            "user_batches": len(user_plan.batches),
            "item_batches": len(item_plan.batches),
            "user_scan_groups": len(user_plan.kernel_shapes),
            "item_scan_groups": len(item_plan.kernel_shapes),
            "padding_overhead_user": round(user_plan.padding_overhead, 3),
            "padding_overhead_item": round(item_plan.padding_overhead, 3),
            "padding_overhead": round(
                (user_plan.padded_work + item_plan.padded_work)
                / (user_plan.nnz + item_plan.nnz), 3),
            "host_plan_gb": round(host_plan_bytes / 1e9, 3),
            "factor_tables_gb": round(factor_bytes / 1e9, 4),
            "counted_flops_per_iteration": flops_iter,
            "hbm_gb_per_iteration": round(hbm_bytes / 1e9, 2),
            "v5e_roofline_s_per_iteration": round(v5e_roofline_s, 3),
            "plan_build_s": round(plan_s, 1),
            "upload_s": round(upload_s, 1),
            "datagen_s": round(gen_s, 1),
        },
        "execution": {
            "iterations_run": len(iter_s),
            "cpu_s_per_iteration": iter_s,  # first includes compile
            "rmse_sample_by_iteration": rmse_by_iter,
            "converges": rmse_by_iter[-1] < rmse_by_iter[0],
            "peak_host_rss_gb": round(peak_rss_gb, 2),
        },
    }
    line = json.dumps(out)
    print(line, flush=True)
    if out_path:
        with open(out_path, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    if "--ingest-driver" in sys.argv:
        # load-generator subprocess for bench_ingest: must stay out of
        # the server's process so client-side work never shares the
        # GIL being measured
        _spec = json.loads(
            sys.argv[sys.argv.index("--ingest-driver") + 1])
        if _spec.get("shape") == "conc_worker":
            _ingest_conc_worker(_spec)
        else:
            ingest_load_driver(_spec)
        raise SystemExit(0)
    if "--full-scale-cpu" in sys.argv:
        full_scale_cpu_report()
        raise SystemExit(0)
    if "--math-parity" in sys.argv:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            # parity is a CPU job by design (float64 reference against
            # the f32 trainer; no device number comes out of it)
            raise SystemExit(
                "bench.py --math-parity runs on the CPU: "
                "JAX_PLATFORMS=cpu python bench.py --math-parity")
        raise SystemExit(math_parity_report())
    if "--mesh-sweep" in sys.argv:
        require_device("--tiny" in sys.argv)
        mesh_sweep()
        raise SystemExit(0)
    if "--ablation" in sys.argv:
        require_device("--tiny" in sys.argv)
        solver_ablation()
        raise SystemExit(0)
    main()

"""Fold-in kernels: solve only the touched factor rows of a deployed model.

The ALX observation (PAPERS.md, arxiv 2112.02194): the per-user least-
squares step of ALS — solve (V_S^T C V_S + lam*n*I) x = V_S^T C r with the
counterpart table FIXED — is exactly the bucketed batched-solve shape the
training sweep already runs, so absorbing fresh events costs one
mini-sweep over the touched entities instead of a full retrain. This
module reuses the whole training stack for that mini-sweep: the
ragged->fixed bucketing of ``ops/ratings.build_solve_plan``, the stacked
device upload of ``ops/als._upload_plan``, the single-dispatch scan sweep
``ops/als._solve_sweep`` and its backend-resolved solvers
(``ops/solve.spd_solve`` — LAPACK cholesky on CPU, the VMEM-resident CG
Pallas kernel on TPU).

Math parity with the training sweep is by construction — both paths call
the identical ``_solve_batch`` kernel:

  explicit  — ALS-WR: x = argmin sum_S (r - x.v)^2 + lam * n |x|^2
              (per-entity regularizer lam * n ratings, MLlib 1.3).
  implicit  — Hu-Koren: (G + V_S^T (C_S - I) V_S + lam*n*I) x = V_S^T C_S p
              with G = V^T V over the FULL counterpart table.

Device residency (the ALX keep-shards-on-device discipline): a tick
uploads the grown U/V tables at most once — the solve plans upload once
per side, both solve sides and every sweep read the tables where they
already live, and solved rows scatter on-device between sides. The
implicit Gram is carried alongside its table and updated by the rank-k
correction G += sum(v_new v_new^T - v_old v_old^T) over the scattered
rows (recomputed from the table on upload and every
``_GRAM_REFRESH_EVERY`` incremental ticks, bounding float drift).
With a ``resident_key``, the tick's final device tables stay resident
in ``utils/device_cache`` keyed by the published model's host arrays,
so the NEXT tick uploads only its touched-row solve plans — per-tick
``pio_fold_upload_bytes_total`` is O(touched), not O(model).

Exactness caveat: a folded row is the exact least-squares solution GIVEN
the current counterpart factors; counterpart rows not in the touched set
keep their deployed values, so the folded model is one Gauss-Seidel
half-step from the retrain fixed point, not the fixed point itself. The
scheduler's drift bound (fold-in loss vs anchor loss) decides when that
gap has grown enough to warrant a real retrain.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np

from predictionio_tpu.guard.sentinels import (SweepSentinel, guard_enabled,
                                              host_max_norm)
from predictionio_tpu.ops.als import (ALSConfig, _gram, _gram_eig,
                                      ALSModel, _run_side, _upload_plan,
                                      default_compute_dtype,
                                      resolve_sweep_chunk)
from predictionio_tpu.ops.ratings import RatingsCOO, build_solve_plan
from predictionio_tpu.ops.solve import resolve_solver
from predictionio_tpu.parallel.mesh import MeshContext, current_mesh, \
    host_fetch
from predictionio_tpu.utils import device_cache


@dataclass(frozen=True)
class FoldInConfig:
    """Hyperparameters of the touched-row solves. Defaults mirror
    ``ops/als.ALSConfig`` so a fold-in against a model trained with
    default params reproduces the training math exactly."""
    lam: float = 0.01
    implicit_prefs: bool = False
    alpha: float = 1.0
    lambda_scaling: str = "nratings"   # 'nratings' (ALS-WR) | 'constant'
    solver: str = "auto"               # ops/solve.resolve_solver's names
    compute_dtype: Optional[str] = None  # None = bf16 on TPU, f32 on CPU
    work_budget: int = 1 << 20
    sweep_chunk: int = 0
    # pow2 segment-length ladder (train defaults to 1.125): the fold
    # tick's K classes must be a SMALL, quickly-saturated set so
    # consecutive ticks re-dispatch compiled programs instead of
    # minting near-duplicate shapes (ISSUE 9 zero-recompile contract);
    # the extra padded gather work is noise at touched-row scale
    bucket_ratio: float = 2.0
    dual_solve: str = "auto"
    solver_iters: Optional[int] = None
    dual_iters_cap: Optional[int] = None
    # one sweep = user side then item side. 2 sweeps let a brand-new
    # (user, item) PAIR bootstrap: the first user-side solve sees only
    # zero rows for a brand-new item, so its solution is refined once the
    # item side has produced a real row.
    sweeps: int = 1
    # sharded online plane (ISSUE 12): 'model' keeps the factor
    # tables device-resident under a NamedSharding over the mesh model
    # axis for the whole tick — solves gather touched counterpart rows
    # cross-shard (GSPMD collectives over ICI), solved rows scatter
    # back to their owning shard on-device, and the publish patches
    # only the touched rows into the per-shard host mirrors. The
    # layout is normally inferred from the model's tables
    # (ShardedTable -> 'model'); the config field records intent and
    # lets parity harnesses force a layout.
    factor_sharding: str = "replicated"
    # numerical sentinels (ISSUE 5): after each side's solve, the
    # touched rows are checked on-device for finiteness and norm
    # explosion (> max(floor, ratio * incumbent max row norm)). A breach
    # rolls back to the last clean sweep's checkpointed device tables,
    # or — with no clean sweep — aborts the tick with NumericalFault so
    # the scheduler's delta-restore machinery requeues the events.
    # PIO_GUARD=off disables at runtime.
    sentinel: bool = True
    sentinel_norm_ratio: float = 1e3
    sentinel_norm_floor: float = 1e4


@dataclass
class FoldInStats:
    """What one fold-in call touched (exported by the serving counters)."""
    n_user_rows: int = 0
    n_item_rows: int = 0
    n_new_users: int = 0
    n_new_items: int = 0
    nnz_user_side: int = 0
    nnz_item_side: int = 0
    sweeps: int = 0
    wall_s: float = 0.0
    # ISSUE 12: the tick ran the model-sharded layout (tables resident
    # under a model-axis NamedSharding; publish patched host mirrors)
    sharded: bool = False
    # True when the tick reused device-resident tables from the previous
    # tick (no full-table upload happened)
    resident_hit: bool = False
    # ISSUE 5 guard outcomes: the tick was a clean no-op (nothing
    # solvable — empty touched set or all-zero ratings), or a sentinel
    # breach rolled the tables back to the last clean sweep
    degenerate: bool = False
    sentinel_rollback: bool = False
    # wall seconds spent in sentinel work (baseline norm + per-side row
    # checks, including the device sync each check forces — an upper
    # bound on the tax).
    guard_wall_s: float = 0.0


#: incremental Gram updates tolerated before a full recompute from the
#: table — bounds accumulated float32 error across long tick chains
_GRAM_REFRESH_EVERY = 64


def _degenerate_counter():
    from predictionio_tpu.obs import get_registry
    return get_registry().counter(
        "pio_guard_fold_degenerate_total",
        "Fold ticks that no-opped cleanly (empty touched set after "
        "filtering, or all-zero ratings) instead of building an empty "
        "solve plan")


def _als_config(cfg: FoldInConfig, rank: int, solver: str) -> ALSConfig:
    return ALSConfig(
        rank=rank, iterations=1, lam=cfg.lam,
        implicit_prefs=cfg.implicit_prefs, alpha=cfg.alpha,
        lambda_scaling=cfg.lambda_scaling, solver=solver,
        compute_dtype=cfg.compute_dtype or default_compute_dtype(),
        work_budget=cfg.work_budget, sweep_chunk=cfg.sweep_chunk,
        bucket_ratio=cfg.bucket_ratio, dual_solve=cfg.dual_solve,
        solver_iters=cfg.solver_iters, dual_iters_cap=cfg.dual_iters_cap)


# -- small jitted helpers (resolved from the compile plane) -----------------
#
# ISSUE 9: the fold tick resolves its jitted helpers from the AOT
# registry's shared-jit surface instead of a module-local cache — one
# process-wide jit per key, visible in `pio status --telemetry` /
# /stats.json, and the idiom the JAX003/JAX005 lint rules recognize.

def _jitted(name: str, impl):
    from predictionio_tpu.compile.aot import shared_jit
    return shared_jit("fold." + name, impl)


#: scatter-target sentinel for bucket padding: far out of range for any
#: factor table, so `.at[dst].set(mode="drop")` discards the entry (a
#: negative pad would WRAP under jax indexing and corrupt a real row)
_DROP = np.int32(2**31 - 1)


def _scatter_impl(table, solved, src, dst):
    # padded dst entries carry _DROP (out of bounds) -> dropped
    return table.at[dst].set(solved[src], mode="drop")


def _scatter_gram_impl(table, gram, solved, src, dst):
    import jax.numpy as jnp
    n = table.shape[0]
    valid = dst < n                       # bucket padding -> False
    rows = jnp.where(valid[:, None], solved[src], 0.0)
    old = jnp.where(valid[:, None], table[jnp.minimum(dst, n - 1)], 0.0)
    return (table.at[dst].set(rows, mode="drop"),
            gram + rows.T @ rows - old.T @ old)


def _eigh_impl(G):
    import jax.numpy as jnp
    return jnp.linalg.eigh(G)


def _solver_gram(G, dual_auto: bool):
    """The solver-facing gram the sweep kernels expect: (G, w, q) when
    the eig-SMW dual route applies, else G alone. The eigendecomposition
    is rank x rank — recomputing it per solve from the carried G costs
    nothing next to re-deriving G from the full table."""
    if G is None:
        return None
    if dual_auto:
        w, q = _jitted("eigh", _eigh_impl)(G)
        return (G, w, q)
    return G


def _grown_dev(table, n_new: int):
    """Zero-append rows ON DEVICE so vocabulary growth never round-trips
    the table through the host."""
    grow = n_new - int(table.shape[0])
    if grow <= 0:
        return table
    import jax.numpy as jnp
    return jnp.pad(table, ((0, grow), (0, 0)))


def _record_h2d(nbytes: int):
    from predictionio_tpu.obs import jaxmon
    jaxmon.record_h2d(int(nbytes))


# -- sharded-layout helpers (ISSUE 12) --------------------------------------

def _take_rows_impl(table, idx):
    return table[idx]


def _sharded_jit(name: str, impl, mesh: MeshContext, out_shardings):
    """Per-mesh shared jit for the sharded tick's scatter/gather
    programs: the explicit ``out_shardings`` pin the updated table to
    its model-axis layout (GSPMD propagation alone may re-replicate a
    scatter output), and the AOT-adopt key includes the mesh so two
    meshes never share a latched sharding."""
    import jax
    from predictionio_tpu.compile.aot import get_aot
    key = (f"fold.{name}.sharded:{id(mesh.mesh)}:"
           f"{mesh.model_parallelism}")
    return get_aot().adopt(key, jax.jit(impl,
                                        out_shardings=out_shardings))


def _pad_pow2_idx(idx: np.ndarray) -> np.ndarray:
    """Pad a row-index vector to its compile-plane row bucket (floored
    at the touched-row floor so tiny ticks share ONE gather program —
    bare pow2 would mint classes 1/2/4/8 and recompile across steady
    ticks) by repeating its first entry (duplicate fetches are
    harmless)."""
    from predictionio_tpu.compile.buckets import bucket_rows
    n = int(idx.size)
    m = bucket_rows(n, floor=_TOUCHED_FLOOR)
    if m == n:
        return idx
    out = np.empty(m, dtype=np.int32)
    out[:n] = idx
    out[n:] = idx[0] if n else 0
    return out


def _fetch_rows(table_dev, idx: np.ndarray, mesh: MeshContext
                ) -> np.ndarray:
    """Device->host fetch of the touched rows only — the ONLY d2h a
    steady-state sharded tick pays (the publish patches these into the
    host shard mirrors; the table itself never crosses the link)."""
    from predictionio_tpu.obs import jaxmon
    if idx.size == 0:
        return np.zeros((0, table_dev.shape[1]), dtype=np.float32)
    padded = _pad_pow2_idx(np.asarray(idx, dtype=np.int32))
    take = _sharded_jit("take_rows", _take_rows_impl, mesh,
                        mesh.replicated())
    rows = np.asarray(host_fetch(take(table_dev, padded)),
                      dtype=np.float32)[:idx.size]
    jaxmon.record_d2h(rows.nbytes)
    return rows


def solve_rows(counter_factors: np.ndarray,
               owner_compact: np.ndarray,
               counter_idx: np.ndarray,
               values: np.ndarray,
               n_rows: int,
               cfg: FoldInConfig,
               mesh: Optional[MeshContext] = None) -> np.ndarray:
    """One-sided normal-equation solve for ``n_rows`` entities, host in /
    host out — the per-side-upload path (the counterpart table crosses
    the link on every call; ``fold_in_coo`` is the device-resident tick
    built from the same kernels). Kept as the reference implementation
    the parity tests compare against, and for ad-hoc callers.

    ``owner_compact`` [nnz] holds compacted 0..n_rows-1 owner ids,
    ``counter_idx``/``values`` the counterpart index and rating of each
    entry. Returns the solved [n_rows, rank] float32 rows; rows with no
    entries come back zero (callers keep the deployed row for those).
    """
    mesh = mesh or current_mesh()
    counter_factors = np.ascontiguousarray(counter_factors,
                                           dtype=np.float32)
    rank = counter_factors.shape[1]
    solver = resolve_solver(cfg.solver, mesh.n_devices)
    plan = build_solve_plan(
        np.asarray(owner_compact, dtype=np.int64),
        np.asarray(counter_idx, dtype=np.int32),
        np.asarray(values, dtype=np.float32),
        n_rows, work_budget=cfg.work_budget,
        batch_multiple=mesh.data_parallelism,
        bucket_ratio=cfg.bucket_ratio)
    if not plan.batches:
        return np.zeros((n_rows, rank), dtype=np.float32)
    chunk = resolve_sweep_chunk(cfg.sweep_chunk, mesh.n_devices)
    groups = _upload_plan(mesh, plan, chunk, rank)
    # +1 dummy tail row: the scatter target for batch padding (rows = -1)
    out_dev = mesh.put_replicated(
        np.zeros((n_rows + 1, rank), dtype=np.float32))
    counter_dev = mesh.put_replicated(counter_factors)
    _record_h2d(counter_factors.nbytes)   # the per-side upload cost
    als_cfg = _als_config(cfg, rank, solver)
    gram = None
    if cfg.implicit_prefs:
        gram_of = _gram_eig if cfg.dual_solve == "auto" else _gram
        gram = gram_of(counter_dev)
    from predictionio_tpu.obs import costmon
    with costmon.executable(costmon.FOLD_SIDE):
        solved = costmon.device_timed(
            costmon.FOLD_SIDE, _run_side, groups, out_dev, counter_dev,
            als_cfg, gram)
    return np.asarray(host_fetch(solved)[:n_rows], dtype=np.float32)


def _grown_table(table: np.ndarray, n_new: int) -> np.ndarray:
    """Old rows keep their indices; appended rows start at zero (a zero
    factor row scores 0 everywhere — inert until its first solve)."""
    rank = table.shape[1]
    out = np.zeros((n_new, rank), dtype=np.float32)
    out[:table.shape[0]] = table
    return out


@dataclass
class _SidePrep:
    """One side's per-tick constants: the touched-row selection, solve
    plan and scatter targets are identical across sweeps (the satellite
    fix for the per-sweep np.isin recompute), so they are built — and
    their plan uploaded — exactly once per tick.

    Shape-bucketed (ISSUE 9): ``n_rows`` is the touched-row BUCKET (the
    solved-table height), ``src``/``dst`` are padded to their own pow2
    bucket with ``_DROP`` targets, and the plan's same-shape batch
    groups are padded to pow2 counts — so consecutive ticks whose
    touched sets differ in size (within a bucket) re-dispatch the
    exact programs of the previous tick: zero recompiles."""
    groups: tuple          # device-resident stacked plan groups
    src: np.ndarray        # rows of the solved [bucket+1] table to take
    dst: np.ndarray        # rows of the full table those land on
    dst_real: np.ndarray   # unpadded dst (sentinel checks, stats)
    n_rows: int            # touched-row bucket (solved height minus pad)
    nnz: int


#: touched-row / scatter-length bucket floor: small ticks share one
#: program class without inflating the solve beyond a few dozen rows
_TOUCHED_FLOOR = 16


def _pad_batch_rows(b, target: int):
    """Pad one batch's entity dim to ``target`` rows with the kernel's
    established padding convention (rows = -1 scatters to the dummy
    tail, mask = 0 solves the pure-regularizer system to x = 0)."""
    from predictionio_tpu.ops.ratings import SolveBatch
    B, K = b.shape
    if target <= B:
        return b
    pad = target - B
    return SolveBatch(
        rows=np.concatenate([b.rows,
                             np.full(pad, -1, dtype=b.rows.dtype)]),
        idx=np.vstack([b.idx, np.zeros((pad, K), dtype=b.idx.dtype)]),
        val=np.vstack([b.val, np.zeros((pad, K), dtype=b.val.dtype)]),
        mask=np.vstack([b.mask, np.zeros((pad, K), dtype=b.mask.dtype)]))


def _pad_plan_batches(plan, batch_multiple: int = 1):
    """Shape-stabilize a fold solve plan: pad every batch's entity dim
    B to its pow2 bucket (floored so tiny ticks share one class), then
    pad every same-shape batch GROUP to a pow2 count with fully inert
    batches — so ticks whose touched-count histograms differ (within
    buckets) re-dispatch byte-identical program shapes: zero
    recompiles. Fold-tick only — a train pays this (< 2x, trivially
    solved) padding nowhere."""
    from predictionio_tpu.compile.buckets import bucket_batch
    from predictionio_tpu.ops.ratings import SolveBatch, SolvePlan
    by_shape = {}
    dp = max(int(batch_multiple), 1)
    for b in plan.batches:
        target = max(bucket_batch(b.shape[0], floor=_TOUCHED_FLOOR), dp)
        # the stacked upload shards the entity dim over the mesh data
        # axis: the padded B must stay a MULTIPLE of it (a pow2 bucket
        # alone breaks non-pow2 axes, e.g. dp=3)
        target = ((target + dp - 1) // dp) * dp
        b = _pad_batch_rows(b, target)
        by_shape.setdefault(b.shape, []).append(b)
    out = []
    for shape in sorted(by_shape):
        bs = by_shape[shape]
        out.extend(bs)
        target = bucket_batch(len(bs))
        if target > len(bs):
            B, K = shape
            inert = SolveBatch(
                rows=np.full(B, -1, dtype=np.int32),
                idx=np.zeros((B, K), dtype=np.int32),
                val=np.zeros((B, K), dtype=np.float32),
                mask=np.zeros((B, K), dtype=np.float32))
            out.extend([inert] * (target - len(bs)))
    return SolvePlan(batches=out, n_entities=plan.n_entities,
                     nnz=plan.nnz)


def _prep_side(owner_idx: np.ndarray, counter_idx: np.ndarray,
               values: np.ndarray, touched: np.ndarray,
               cfg: FoldInConfig, mesh: MeshContext, rank: int
               ) -> Optional[_SidePrep]:
    from predictionio_tpu.compile.buckets import bucket_rows
    if touched.size == 0:
        return None
    sel = np.isin(owner_idx, touched)
    nnz = int(np.count_nonzero(sel))
    if nnz == 0:
        return None
    compact = np.searchsorted(touched, owner_idx[sel])
    # touched-row bucket: the solved-table height (and so the sweep's
    # scatter-output shape) quantizes to pow2, so tick-to-tick touched
    # counts inside a bucket re-use every compiled program
    n_slot = bucket_rows(int(touched.size), floor=_TOUCHED_FLOOR)
    plan = build_solve_plan(
        np.asarray(compact, dtype=np.int64),
        np.asarray(counter_idx[sel], dtype=np.int32),
        np.asarray(values[sel], dtype=np.float32),
        n_slot, work_budget=cfg.work_budget,
        batch_multiple=mesh.data_parallelism,
        bucket_ratio=cfg.bucket_ratio)
    if not plan.batches:
        return None
    plan = _pad_plan_batches(plan, batch_multiple=mesh.data_parallelism)
    chunk = resolve_sweep_chunk(cfg.sweep_chunk, mesh.n_devices)
    groups = _upload_plan(mesh, plan, chunk, rank)
    # only scatter rows that actually had data: a touched entity whose
    # entries all vanished (e.g. deleted events) keeps its deployed row
    # rather than being zeroed
    has_data = np.bincount(compact, minlength=touched.size) > 0
    src_real = np.nonzero(has_data)[0].astype(np.int32)
    dst_real = touched[has_data].astype(np.int32)
    # scatter-index bucket: padded entries point src at row 0 (any
    # valid row — their contribution is masked) and dst at _DROP (out
    # of bounds -> dropped by the scatter, excluded from the Gram)
    plen = bucket_rows(max(int(src_real.size), 1), floor=_TOUCHED_FLOOR)
    src = np.zeros(plen, dtype=np.int32)
    src[:src_real.size] = src_real
    dst = np.full(plen, _DROP, dtype=np.int32)
    dst[:dst_real.size] = dst_real
    return _SidePrep(groups=groups, src=src, dst=dst,
                     dst_real=dst_real, n_rows=n_slot, nnz=nnz)


def _solve_side(prep: _SidePrep, counter_dev, counter_gram, out_dev,
                out_gram, als_cfg: ALSConfig, cfg: FoldInConfig,
                mesh: MeshContext, rank: int, sharded: bool = False):
    """One side of one sweep, entirely on device: solve the touched rows
    against the resident counterpart table, scatter them into the
    resident owned table, and (implicit) apply the rank-k Gram
    correction for the rows that moved. Returns the updated
    (out_dev, out_gram).

    Sharded layout: the counterpart gathers and the scatter run
    against model-axis-sharded tables — GSPMD inserts the cross-shard
    row gathers (O(touched) rows over ICI, never a table gather), and
    the scatter's explicit ``out_shardings`` keeps the updated table
    on its owning shards (the ``.at[].set(mode="drop")`` OOB-sentinel
    padding convention is layout-independent)."""
    from predictionio_tpu.obs import costmon
    zeros = mesh.put_replicated(
        np.zeros((prep.n_rows + 1, rank), dtype=np.float32))
    with costmon.executable(costmon.FOLD_SIDE):
        # device-time attribution (ISSUE 11): the fold solve is the
        # other big device consumer next to serving — a sampled sync
        # here is what lets `pio_device_time_seconds_total` compare
        # fold_side against batch_predict honestly
        solved = costmon.device_timed(
            costmon.FOLD_SIDE, _run_side, prep.groups, zeros,
            counter_dev, als_cfg,
            _solver_gram(counter_gram, cfg.dual_solve == "auto"))
    if sharded:
        scatter = _sharded_jit("scatter", _scatter_impl, mesh,
                               mesh.model_sharded(2))
        scatter_gram = _sharded_jit(
            "scatter_gram", _scatter_gram_impl, mesh,
            (mesh.model_sharded(2), mesh.replicated()))
    else:
        scatter = _jitted("scatter", _scatter_impl)
        scatter_gram = _jitted("scatter_gram", _scatter_gram_impl)
    if out_gram is None:
        return scatter(out_dev, solved, prep.src, prep.dst), None
    return scatter_gram(out_dev, out_gram, solved, prep.src, prep.dst)


def fold_in_coo(als: ALSModel, coo: RatingsCOO,
                touched_users: Sequence[int],
                touched_items: Sequence[int],
                cfg: FoldInConfig,
                mesh: Optional[MeshContext] = None,
                resident_key: Optional[str] = None
                ) -> Tuple[ALSModel, FoldInStats]:
    """Fold fresh data into a trained model: re-solve only the touched
    user/item rows against ``coo`` (the CURRENT deduped dataset, whose
    touched rows/columns must be complete — the solve is least-squares
    over whatever it is given, so partial histories produce rows biased
    to the fresh slice).

    ``coo.n_users``/``coo.n_items`` may exceed the model's (grown
    vocabularies): new rows are appended zero-initialized and solved when
    touched, so existing dense indices — and the deployed factor rows
    behind them — never move.

    ``resident_key`` names a device-residency slot: when the passed
    model's host tables are the ones the previous tick published under
    the same key, the grown tables (and implicit Grams) are reused
    in-place on device and the tick uploads only its solve plans.
    """
    t0 = time.perf_counter()
    from predictionio_tpu.parallel.sharded_table import is_sharded, \
        layout_of
    sharded = is_sharded(als.user_factors)
    if sharded != is_sharded(als.item_factors):
        raise ValueError(
            "fold_in_coo needs both factor tables in the same layout; "
            f"got user={type(als.user_factors).__name__} "
            f"item={type(als.item_factors).__name__}")
    if mesh is None and sharded:
        # serve/fold threads must resolve the SAME mesh for a given
        # shard count (current_mesh is thread-local)
        from predictionio_tpu.parallel.mesh import model_mesh
        mesh = model_mesh(als.user_factors.n_shards)
    mesh = mesh or current_mesh()
    layout_token = layout_of(als.user_factors)
    rank = als.rank
    n_users = max(coo.n_users, als.n_users)
    n_items = max(coo.n_items, als.n_items)
    tu = np.unique(np.asarray(touched_users, dtype=np.int64))
    ti = np.unique(np.asarray(touched_items, dtype=np.int64))
    stats = FoldInStats(
        n_new_users=n_users - als.n_users,
        n_new_items=n_items - als.n_items)
    implicit = cfg.implicit_prefs

    # chaos opt-in (ISSUE 5): a `fold.ratings:corrupt=P` PIO_FAULTS
    # clause poisons this tick's data — the sentinel below must catch it
    from predictionio_tpu.resilience.faults import maybe_corrupt_array
    vals, vals_corrupted = maybe_corrupt_array("fold.ratings", coo.rating)
    if vals_corrupted:
        coo = RatingsCOO(coo.user_idx, coo.item_idx, vals,
                         coo.n_users, coo.n_items)

    # -- per-tick constants, hoisted out of the sweep loop ------------------
    solver = resolve_solver(cfg.solver, mesh.n_devices)
    als_cfg = _als_config(cfg, rank, solver)
    degenerate = (
        (tu.size == 0 and ti.size == 0)
        or coo.rating.size == 0
        # all-zero ratings: every solve would return x = 0 and ZERO the
        # deployed rows (explicit: zero targets; implicit: preference 0)
        or not np.any(coo.rating))
    prep_u = prep_i = None
    if not degenerate:
        prep_u = _prep_side(coo.user_idx, coo.item_idx, coo.rating, tu,
                            cfg, mesh, rank)
        prep_i = _prep_side(coo.item_idx, coo.user_idx, coo.rating, ti,
                            cfg, mesh, rank)
        degenerate = prep_u is None and prep_i is None
    if degenerate:
        # no-op tick (ISSUE 5 satellite): nothing solvable — return the
        # deployed model unchanged WITHOUT uploading tables or building
        # an empty solve plan, and make it countable
        _degenerate_counter().inc()
        stats.degenerate = True
        stats.wall_s = time.perf_counter() - t0
        return als, stats

    # -- tables onto the device (once per tick, or not at all) --------------
    # vocab shape-buckets (ISSUE 9): device tables live on the rungs of
    # the resident-table ladder (the ones the serve dims use), so
    # vocabulary growth INSIDE a rung re-uses every traced program (and,
    # with residency, the device arrays themselves); promotion to the
    # next rung is one predictable re-pad + compile
    from predictionio_tpu.compile.buckets import (
        bucket_table_rows, bucket_table_rows_sharded)
    U_tab = V_tab = None
    if sharded:
        stats.sharded = True
        mp = mesh.model_parallelism
        U_tab, V_tab = als.user_factors, als.item_factors
        n_users_b = max(bucket_table_rows_sharded(n_users, mp),
                        U_tab.padded_rows)
        n_items_b = max(bucket_table_rows_sharded(n_items, mp),
                        V_tab.padded_rows)
        # bucket promotion: the one O(table) host reshuffle + upload,
        # paid per rung crossed (steady-state ticks never enter these
        # branches)
        if n_users_b > U_tab.padded_rows:
            U_tab = U_tab.grown(als.n_users, n_users_b)
        if n_items_b > V_tab.padded_rows:
            V_tab = V_tab.grown(als.n_items, n_items_b)
    else:
        n_users_b = bucket_table_rows(n_users)
        n_items_b = bucket_table_rows(n_items)
    payload = device_cache.get_resident(
        resident_key, (als.user_factors, als.item_factors),
        sharding=layout_token) if resident_key else None
    if payload is not None and payload.get("mesh") is mesh \
            and payload.get("implicit") == implicit \
            and (not sharded
                 or (payload["U"].shape[0] == n_users_b
                     and payload["V"].shape[0] == n_items_b)):
        U_dev = payload["U"] if sharded \
            else _grown_dev(payload["U"], n_users_b)
        V_dev = payload["V"] if sharded \
            else _grown_dev(payload["V"], n_items_b)
        # appended zero rows contribute nothing to a Gram: carry it
        gram_u, gram_v = payload.get("GU"), payload.get("GV")
        incr = int(payload.get("incr", 0))
        stats.resident_hit = True
    elif sharded:
        # residency miss: the tables' own attached device handles are
        # the second-chance fast path (a just-trained or just-swapped
        # ShardedTable arrives with its arrays still resident); only a
        # genuinely cold table uploads — per-shard slices, budget-
        # checked at 1/N of the table
        U_dev = U_tab.device(mesh)
        V_dev = V_tab.device(mesh)
        gram_u = gram_v = None
        incr = 0
    else:
        U_host = _grown_table(als.user_factors, n_users_b)
        V_host = _grown_table(als.item_factors, n_items_b)
        # the enforced per-device budget (ISSUE 12): a replicated fold
        # costs each device the FULL table — refuse loudly instead of
        # silently overcommitting HBM (factor_sharding='model' is the
        # supported path past the budget)
        device_cache.check_table_budget(U_host.nbytes,
                                        table="fold user table")
        device_cache.check_table_budget(V_host.nbytes,
                                        table="fold item table")
        U_dev = mesh.put_replicated(U_host)
        V_dev = mesh.put_replicated(V_host)
        _record_h2d(U_host.nbytes + V_host.nbytes)
        gram_u = gram_v = None
        incr = 0
    if implicit and (gram_u is None or gram_v is None
                     or incr >= _GRAM_REFRESH_EVERY):
        gram_u = _gram(U_dev)
        gram_v = _gram(V_dev)
        incr = 0

    # -- sentinel (ISSUE 5): touched rows checked after each side -----------
    sentinel = None
    if cfg.sentinel and guard_enabled():
        g0 = time.perf_counter()
        # O(model) baseline scan only on the FIRST tick of a model
        # lineage: every published fold carries its norm forward (the
        # untouched rows' norms are covered by the previous baseline,
        # the touched rows by the checks that passed), so steady-state
        # ticks stay O(touched)
        baseline = getattr(als, "_pio_guard_norm", None)
        if baseline is None:
            if sharded:
                baseline = max(als.user_factors.max_row_norm(),
                               als.item_factors.max_row_norm())
            else:
                baseline = host_max_norm(als.user_factors,
                                         als.item_factors)
        sentinel = SweepSentinel(
            "fold_in", baseline,
            norm_ratio=cfg.sentinel_norm_ratio,
            norm_floor=cfg.sentinel_norm_floor)
        stats.guard_wall_s += time.perf_counter() - g0

    def _timed_check(table, idx, what):
        g0 = time.perf_counter()
        try:
            return sentinel.check_rows(table, idx, what)
        finally:
            stats.guard_wall_s += time.perf_counter() - g0

    sweeps = max(1, int(cfg.sweeps))
    ckpt = None        # device state after the last CLEAN sweep
    fault = None
    for _ in range(sweeps):
        if prep_u is not None:
            U_dev, gram_u = _solve_side(
                prep_u, V_dev, gram_v if implicit else None, U_dev,
                gram_u if implicit else None, als_cfg, cfg, mesh, rank,
                sharded=sharded)
            stats.n_user_rows += len(prep_u.dst_real)
            stats.nnz_user_side += prep_u.nnz
            if sentinel is not None:
                fault = _timed_check(U_dev, prep_u.dst_real,
                                     "user-side solve")
                if fault is not None:
                    break
        if prep_i is not None:
            V_dev, gram_v = _solve_side(
                prep_i, U_dev, gram_u if implicit else None, V_dev,
                gram_v if implicit else None, als_cfg, cfg, mesh, rank,
                sharded=sharded)
            stats.n_item_rows += len(prep_i.dst_real)
            stats.nnz_item_side += prep_i.nnz
            if sentinel is not None:
                fault = _timed_check(V_dev, prep_i.dst_real,
                                     "item-side solve")
                if fault is not None:
                    break
        stats.sweeps += 1
        # the scatter jits mint NEW arrays each sweep and nothing here
        # is donated, so a checkpoint is just references — the last-good
        # rollback costs no copy and no host round trip
        ckpt = (U_dev, V_dev, gram_u, gram_v)
    if fault is not None:
        if ckpt is None:
            # no clean sweep to fall back to: abort the tick; the
            # scheduler restores the popped deltas (PR 1) and the
            # supervision loop owns the retry/escalation policy
            raise fault
        U_dev, V_dev, gram_u, gram_v = ckpt
        stats.sentinel_rollback = True

    if sharded:
        # sharded publish (ISSUE 12): ONLY the touched rows cross the
        # device->host link; they are patched copy-on-write into the
        # per-shard host mirrors, and the tick's final device arrays
        # ride along as the resident fast path — the table as a whole
        # never moves, which is exactly what the over-budget scenario
        # asserts via pio_fold_upload_bytes_total
        idx_u = prep_u.dst_real if prep_u is not None \
            else np.zeros(0, dtype=np.int32)
        idx_v = prep_i.dst_real if prep_i is not None \
            else np.zeros(0, dtype=np.int32)
        rows_u = _fetch_rows(U_dev, idx_u, mesh)
        rows_v = _fetch_rows(V_dev, idx_v, mesh)
        # chaos opt-in: `fold.factors:corrupt=P` — poisons the patched
        # rows, so the host mirrors the gates probe see the corruption
        rows_u, cu = maybe_corrupt_array("fold.factors", rows_u)
        rows_v, cv = maybe_corrupt_array("fold.factors", rows_v)
        U_out = U_tab.with_rows(idx_u, rows_u, n_rows=n_users)
        V_out = V_tab.with_rows(idx_v, rows_v, n_rows=n_items)
        if not (cu or cv):
            U_out.attach_device(U_dev)
            V_out.attach_device(V_dev)
            if resident_key:
                device_cache.put_resident(
                    resident_key, (U_out, V_out),
                    {"U": U_dev, "V": V_dev, "GU": gram_u,
                     "GV": gram_v, "mesh": mesh, "implicit": implicit,
                     "incr": incr + 1},
                    sharding=layout_token)
        stats.wall_s = time.perf_counter() - t0
        out = ALSModel(user_factors=U_out, item_factors=V_out,
                       rank=rank)
        if sentinel is not None and not (cu or cv):
            out._pio_guard_norm = sentinel.observed_max
        return out, stats

    # slice the vocab-bucket padding back off: published models carry
    # exact-sized host tables (the padding is a device-residency shape
    # contract, not part of the model)
    U_host = np.asarray(host_fetch(U_dev)[:n_users], dtype=np.float32)
    V_host = np.asarray(host_fetch(V_dev)[:n_items], dtype=np.float32)
    # chaos opt-in: `fold.factors:corrupt=P` simulates a blow-up that
    # slipped past the sweep sentinel — the pre-swap gates' job
    U_host, cu = maybe_corrupt_array("fold.factors", U_host)
    V_host, cv = maybe_corrupt_array("fold.factors", V_host)
    if resident_key and not (cu or cv):
        # (a corrupted tick must not key the clean device tables under
        # the poisoned host arrays — skip residency so the next tick
        # re-uploads from whatever model is actually deployed)
        device_cache.put_resident(
            resident_key, (U_host, V_host),
            {"U": U_dev, "V": V_dev, "GU": gram_u, "GV": gram_v,
             "mesh": mesh, "implicit": implicit, "incr": incr + 1},
            sharding=layout_token)
    stats.wall_s = time.perf_counter() - t0
    out = ALSModel(user_factors=U_host, item_factors=V_host, rank=rank)
    if sentinel is not None and not (cu or cv):
        out._pio_guard_norm = sentinel.observed_max
    return out, stats

"""Alternating least squares on the TPU mesh — the MLlib ALS replacement.

Replaces `org.apache.spark.mllib.recommendation.ALS.train/trainImplicit` as
called by the reference templates (reference:
examples/scala-parallel-recommendation/custom-prepartor/src/main/scala/
ALSAlgorithm.scala:55 explicit; examples/scala-parallel-similarproduct/multi/
src/main/scala/ALSAlgorithm.scala:130 implicit).

Design (ALX-style, PAPERS.md "ALX: Large Scale Matrix Factorization on
TPUs"): instead of MLlib's factor-block shuffles, both factor tables live in
HBM; each half-iteration sweeps bucketed [B, K] batches of entities
(ops/ratings.build_solve_plan), gathering counterpart factors, forming the
normal equations with batched einsums on the MXU, and solving them (ops/
solve: the Pallas CG on a TPU, Cholesky on the CPU). How a half-sweep is
divided follows from the mesh and `factor_sharding` (`_rows_sharded`):

  one device, or tables replicated over a mesh (`factor_sharding`
  "replicated"): `_solve_sweep`. The batch dim B is sharded over the mesh
  `data` axis and GSPMD partitions the program.

  tables row-sharded over the mesh `model` axis (`factor_sharding` "model",
  for tables larger than one device's HBM): `_solve_sweep_per_chip`, one
  `shard_map` over the half-sweep. Every chip holds a contiguous quarter of
  each table's rows and a quarter of every batch of the plan
  (`plan_axes`), and solves its quarter of the systems with the one-chip
  body. Which chip owns the counterpart row of which slot is a fact of the
  plan, so `_upload_plan` routes it once on the host (`_route_group`): a
  chip holds, beside its quarter of the plan, the local numbers of the
  rows the others need of it (`send`), and for each of its own slots where
  the row will stand in what it receives (`place`, in `idx`'s seat: the
  indices themselves never reach a device). Three exchanges cross the
  chips in a scan step, and nothing else: the rated rows, each shard's
  gathered from its own rows in the compute dtype and handed to the chips
  that rate them by one all-to-all (real slots only; a received row is a
  copy of its owner's), the solved float32 rows (all-gather, each shard
  keeping its own), and their row ids with them. No chip ever holds a
  whole table or another chip's systems. What the compiled programs
  exchange is read from their HLO when telemetry is asked for
  (`sweep_exchange`, telemetry `exchange_bytes`, gauge
  `pio_als_exchange_bytes{side, op}`), and what the routing cost and how
  full the exchanged blocks run is beside it (`route_s`, `route_fill`).

Math parity with MLlib 1.3:
  explicit  — ALS-WR: minimize sum (r - x.v)^2 + lambda * (n_u |x|^2 + ...)
              i.e. per-entity regularizer lambda * n ratings (`lambda_scaling
              ='nratings'`, MLlib's default behavior in 1.3).
  implicit  — Hu-Koren confidence c = 1 + alpha * |r|, preference p = 1(r>0),
              solve (G + V_u^T (C_u - I) V_u + lambda*n*I) x = V_u^T C_u p
              with G = V^T V computed once per half-sweep. Negative ratings
              (e.g. "dislike" events mapped to r = -1) contribute confidence
              with preference 0, exactly MLlib 1.3's c1 = alpha*|r| /
              b += (c1+1)*x when r > 0.
"""

from __future__ import annotations

import collections
import functools
import logging
import os
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from predictionio_tpu.obs import TRACER
from predictionio_tpu.ops.ratings import (RatingsCOO, SolvePlan,
                                          plan_for_items, plan_for_users)
from predictionio_tpu.parallel.mesh import MeshContext, current_mesh

logger = logging.getLogger(__name__)


@dataclass(frozen=True)
class ALSConfig:
    rank: int = 10
    iterations: int = 10
    lam: float = 0.01                  # MLlib's lambda_
    implicit_prefs: bool = False
    alpha: float = 1.0                 # implicit confidence scale
    lambda_scaling: str = "nratings"   # 'nratings' (ALS-WR) | 'constant'
    seed: int = 0
    work_budget: int = 1 << 20         # B*K per solve batch
    compute_dtype: str = "float32"     # einsum dtype ('bfloat16' on TPU ok)
    factor_dtype: str = "float32"      # HBM storage dtype of factor tables
    # 'bfloat16' halves the tables in HBM and changes their stated
    # precision: the carried factors are rounded to bf16 every half-sweep
    # (the solves still build and solve f32 normal equations from the
    # gathered rows). It does NOT speed a half-sweep's gather: at
    # compute_dtype bfloat16 the compiled program gathers from a bf16 copy
    # of the f32 table already (made once a program), and on a v5e the
    # gather costs the same per row whatever a row's bytes or tiling
    # (PERF.md section 6, PR 30: `_gather_pad_rows` is what moves it).
    solver: str = "auto"  # the names ops/solve.resolve_solver takes
    # auto = VMEM-resident CG Pallas kernel on one TPU and on each chip of
    # a TPU mesh over row-sharded tables (`sweep_solver`), jnp CG on a TPU
    # mesh whose sweep GSPMD partitions, LAPACK cholesky on CPU.
    solver_iters: Optional[int] = None  # cap on the primal CG iterations
    # None = the solver default (48). The Pallas kernel stops a tile of
    # systems once they have converged (ops/solve._cg_kernel) and runs to
    # this cap otherwise. The primal rank-dim CG can stall in
    # ill-conditioned implicit configs (large alpha * |r| confidences);
    # K<rank buckets are unaffected (the dual route solves a better-
    # conditioned K-dim system exactly), but large-count entities ride
    # the primal solver — raise this (or set solver='cholesky') there.
    dual_iters_cap: Optional[int] = None  # a lower cap on the dual CG
    # None = the cap is K+8 per bucket (finite-termination bound +
    # roundoff margin). Either way it is a cap: the Pallas kernel stops
    # a tile of these well-conditioned K-dim systems once they have
    # converged, a fifth to a third of the way there (PERF.md, PR 28),
    # so a lower cap only cuts short the systems that had not: it trades
    # a bounded residual on the hard ones for their wall-clock.
    dual_solve: str = "auto"  # 'auto' | 'never'
    # Woodbury/dual formulation for ALS buckets whose padded segment
    # length K < rank — exact algebra replacing the rank-dim solve with a
    # K-dim one. Explicit: solve (M M^T + reg I_K) z = y, x = M^T z.
    # Implicit: A = (G + reg I) + V_S^T D V_S with D = diag(alpha*|r|);
    # eigendecompose the base B = G + reg I ONCE per half-sweep (G is
    # shared by every entity) and apply Sherman-Morrison-Woodbury
    # through the eigenbasis: A^-1 b = B^-1 b - B^-1 V^T D^1/2
    # (I_K + D^1/2 V B^-1 V^T D^1/2)^-1 D^1/2 V B^-1 b — the D^1/2 form
    # stays exact when D has zeros (padding, zero-confidence rows).
    # Under a power-law count distribution most entities live in small-K
    # buckets, so this removes most of the solve work on both paths.
    factor_sharding: str = "replicated"  # 'replicated' | 'model'
    # 'model' shards factor-table rows over the mesh model axis (tables too
    # large for one device's HBM) and divides every batch of the plan over
    # the same chips: each solves its own share of the systems; the rated
    # counterpart rows reach it from their owners as the plan routed them
    # and the solved rows go back to theirs (`_solve_batch_per_chip`) — the
    # analog of MLlib's factor-block shuffles, over ICI.
    keep_sharded: bool = False
    # With factor_sharding='model': return the trained tables as
    # ShardedTable handles (per-shard host slices via
    # host_fetch_sharded + the resident device arrays attached) instead
    # of gathering one monolithic host table — the entry point of the
    # sharded online plane, where the full table never crosses the
    # host link again (fold ticks patch the mirrors, serving ranks
    # per shard). False keeps the legacy gather-to-host behavior.
    sweep_chunk: int = 0
    # Merge this many same-shape solve batches into one scan step (one
    # solver call over chunk*B systems). Each solver call has a fixed
    # cost, so fewer, larger calls amortize it; batches within a
    # half-sweep are independent (they read only the counterpart
    # table), so merging changes no math. Bounded by the normal-matrix
    # memory per step (chunk * B * S^2 * 4B). 0 = auto: 4 on single-device
    # TPU, 1 elsewhere.
    bucket_ratio: float = 1.125
    # Geometric step of the segment-length ladder (ops/ratings.py
    # bucket_lengths). At ML-20M scale nearly every ladder K is its own
    # uniquely-shaped batch, so the ladder size IS the solver-call count
    # per sweep (~125/iteration at 1.125); a coarser ratio trades padding
    # (more gather bytes + Gram flops) for fewer calls.
    sentinel: bool = True
    # Numerical sentinel (ISSUE 5, guard/sentinels.py): after every
    # iteration the factor tables are checked on-device for finiteness
    # and norm explosion (one tiny reduction + scalar fetch per table),
    # and the last clean iteration is checkpointed as an HBM copy. A
    # breach returns the last-good model instead of NaN factors (or
    # raises NumericalFault when no iteration completed cleanly, and
    # always under factor_sharding='model' over several chips, where the
    # tables are checked and no copy is kept: there is no room for one).
    # PIO_GUARD=off disables at runtime; set False to shave the
    # per-iteration copy + sync off latency-critical benches.
    sentinel_norm_cap: float = 1e4
    # Absolute max-row-norm bound for the train sentinel (there is no
    # incumbent model to scale from; init rows are O(1), converged rows
    # O(sqrt(max rating)) — 1e4 only trips on genuine blow-ups).

    def __post_init__(self):
        if self.dual_iters_cap is not None and self.dual_iters_cap < 1:
            # reject at construction: a 0 cap would otherwise surface
            # only when (and if) some bucket takes the dual route, mid-
            # training from inside a jitted trace — or never, falling
            # into spd_solve's `iters or 48` unset-default
            raise ValueError("dual_iters_cap must be >= 1, got "
                             f"{self.dual_iters_cap}")
        if not self.bucket_ratio > 1.0:
            # ratio <= 1 degrades the geometric walk to the linear,
            # maximally fine ladder (bucket_lengths always advances by
            # at least one alignment step) — never what a caller wants,
            # so reject it rather than silently maximize program count
            raise ValueError("bucket_ratio must be > 1.0, got "
                             f"{self.bucket_ratio}")


def default_compute_dtype() -> str:
    """bf16 Gram einsums on TPU (MXU-native, f32 accumulation), f32 on
    CPU where bf16 is emulated."""
    import jax
    return "bfloat16" if jax.default_backend() == "tpu" else "float32"


@dataclass
class ALSModel:
    """Trained factorization. Arrays are host numpy after training; serving
    re-uploads them with the sharding the query path wants."""
    user_factors: np.ndarray   # [n_users, rank] float32
    item_factors: np.ndarray   # [n_items, rank] float32
    rank: int

    @property
    def n_users(self) -> int:
        return self.user_factors.shape[0]

    @property
    def n_items(self) -> int:
        return self.item_factors.shape[0]


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------

def _dual_system_solve(M, y, K: int, solver: str,
                       iters_cap: Optional[int] = None):
    """Solve the K-dim dual/Woodbury system: the shared policy for both
    explicit and implicit dual branches. The cap is K+8 iterations (CG's
    exact-arithmetic finite termination is <= K; the margin absorbs f32
    roundoff: a cap below K would under-solve a bucket's hard systems
    unless the caller opts in via `iters_cap`, whose accuracy cost is
    ALSConfig.dual_iters_cap's to document); under it the Pallas kernel
    stops each tile when its systems have converged. Tiny systems skip
    that kernel, whose per-tile overhead dominates below 32, for the jnp
    CG, which runs the whole K+8. Returns the solution and `spd_solve`'s
    count of CG iterations (run, allowed)."""
    import jax

    from predictionio_tpu.ops.solve import spd_solve
    method = "cg" if (K < 32 and solver == "cg_pallas") else solver
    if iters_cap is not None and iters_cap < 1:
        # 0 would fall into spd_solve's `iters or 48` unset-default and
        # run MORE iterations than uncapped — reject it loudly
        raise ValueError(f"dual_iters_cap must be >= 1, got {iters_cap}")
    iters = K + 8 if iters_cap is None else min(K + 8, iters_cap)
    with jax.named_scope("pio.sweep.solve.jnp_cg" if method == "cg"
                         else "pio.sweep.solve.dual"):
        return spd_solve(M, y, method=method, iters=iters, system="dual")


def _scatter_rows(factors_out, rows, x):
    """Scatter solved rows; padding rows (-1) land on the dummy tail."""
    import jax.numpy as jnp
    import jax
    with jax.named_scope("pio.sweep.scatter"):
        safe = jnp.where(rows < 0, factors_out.shape[0] - 1, rows)
        return factors_out.at[safe].set(x.astype(factors_out.dtype),
                                        mode="drop")


def _solve_batch(factors_out, counter_factors, gram, rows, idx, val, mask,
                 lam, alpha, **statics):
    """Solve one [B, K] batch of normal equations and scatter results into
    factors_out. Traced inside `_solve_sweep`'s scan body — gather ->
    einsum -> solve -> scatter fuse into one XLA program. Returns the
    table and the batch's CG iterations (run, allowed), float32 [2]:
    `spd_solve`'s count. `statics` are `_solve_gathered`'s."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("pio.sweep.gather"):
        Vg = counter_factors[idx]                   # [B, K, R] gather
        Vc = Vg.astype(jnp.dtype(statics["compute_dtype"]))
    x, cg = _solve_gathered(Vc, gram, val, mask, lam, alpha, **statics)
    return _scatter_rows(factors_out, rows, x), cg


def _solve_gathered(Vc, gram, val, mask, lam, alpha, *, nratings_reg: bool,
                    implicit: bool, rank: int, compute_dtype: str,
                    solver: str, dual_solve: str = "auto",
                    solver_iters: Optional[int] = None,
                    dual_iters_cap: Optional[int] = None):
    """The rows x [B, R] that solve one [B, K] batch of normal equations
    built from the gathered counterpart rows `Vc` [B, K, R] (in the
    compute dtype), and the batch's CG iterations. What is local to a chip
    whoever gathered the rows: one device's `_solve_batch` and each chip of
    a row-sharded mesh (`_solve_batch_per_chip`) run this same body.
    Explicit batches with K < rank take the dual (Woodbury) K x K route; K
    is static per batch group, so the choice costs nothing at runtime."""
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops.solve import spd_solve

    cd = jnp.dtype(compute_dtype)
    K = Vc.shape[1]
    with jax.named_scope("pio.sweep.gram"):
        eye = jnp.eye(rank, dtype=jnp.float32)
        n = mask.sum(axis=-1)                        # ratings per entity
        reg = (lam * jnp.maximum(n, 1.0) if nratings_reg
               else jnp.full_like(n, lam))

    if dual_solve == "auto" and not implicit and K < rank:
        # dual/Woodbury: with M = mask-weighted factor rows [K, R],
        # (M^T M + reg I_R)^-1 M^T y == M^T (M M^T + reg I_K)^-1 y.
        # Gram is K^2*R instead of K*R^2, solve is K-dimensional.
        with jax.named_scope("pio.sweep.gram"):
            Vm = Vc * mask[..., None].astype(cd)
            Ad = jnp.einsum("bkr,blr->bkl", Vm, Vm,
                            preferred_element_type=jnp.float32)
            Ad = Ad + reg[:, None, None] * jnp.eye(K, dtype=jnp.float32)
            y = (val * mask)
        z, cg = _dual_system_solve(Ad, y, K, solver,
                                   iters_cap=dual_iters_cap)
        with jax.named_scope("pio.sweep.gram"):
            x = jnp.einsum("bkr,bk->br", Vm, z.astype(cd),
                           preferred_element_type=jnp.float32)
        return x, cg

    if implicit:
        with jax.named_scope("pio.sweep.gram"):
            G, gram_w, gram_q = gram if isinstance(gram, tuple) \
                else (gram, None, None)
            absval = jnp.abs(val)
            conf_minus_1 = (alpha * absval) * mask   # c - 1, 0 on padding
            # preference p = 1(r>0): negative signals add confidence to
            # A only
            pos = (val > 0).astype(val.dtype) * mask
            b = jnp.einsum("bk,bkr->br",
                           ((1.0 + alpha * absval) * pos).astype(cd), Vc,
                           preferred_element_type=jnp.float32)
        if gram_w is not None and dual_solve == "auto" and K < rank:
            # implicit dual: B = G + reg I = Q (w + reg) Q^T (eig shared
            # across the whole half-sweep); Woodbury for the K-rank
            # confidence update, all R-dim work as eigenbasis einsums.
            # Two precisions. b's own projections (Q^T b in, Q (.) out:
            # [B, R] x [R, R], 1/K of the stage's products) run in f32 at
            # `highest`: G's top eigenvalue stands ~R times over the rest
            # (every factor positive at init), b lies mostly along its
            # vector, and a bf16 Q leaks 2^-9 of that part into the
            # directions that 1/denom then weights ~R times more: a row
            # error of ~1e-3 sqrt(K). The K-rank correction (V Q, W, t, s)
            # keeps compute_dtype operands: what it adds to x is small
            # beside B^-1 b and carries the Gram operands' own rounding.
            hi = jax.lax.Precision.HIGHEST
            with jax.named_scope("pio.sweep.smw.project"):
                # G is PSD, so clamp eigh's roundoff-negative tail: a
                # small reg (constant lambda_scaling grid points) must
                # never meet a negative w and flip the sign of 1/denom.
                denom = (jnp.maximum(gram_w, 0.0)[None, :]
                         + reg[:, None])                      # [B, R]
                Vq = jnp.einsum("bkr,rs->bks", Vc,            # V~ Q
                                gram_q.astype(cd),
                                preferred_element_type=jnp.float32)
                bq_d = jnp.einsum("br,rs->bs", b, gram_q,     # Q^T b
                                  precision=hi) / denom
            with jax.named_scope("pio.sweep.smw.assemble"):
                W = jnp.einsum("bks,bs,bls->bkl", Vq.astype(cd),
                               (1.0 / denom).astype(cd), Vq.astype(cd),
                               preferred_element_type=jnp.float32)
                dhalf = jnp.sqrt(conf_minus_1)                # [B, K]
                M = (jnp.eye(K, dtype=jnp.float32)
                     + dhalf[:, :, None] * W * dhalf[:, None, :])
                t = jnp.einsum("bks,bs->bk", Vq.astype(cd),   # V B^-1 b
                               bq_d.astype(cd),
                               preferred_element_type=jnp.float32)
            z, cg = _dual_system_solve(M, dhalf * t, K, solver,
                                       iters_cap=dual_iters_cap)
            with jax.named_scope("pio.sweep.smw.back"):
                s = jnp.einsum("bks,bk->bs", Vq.astype(cd),
                               (dhalf * z).astype(cd),
                               preferred_element_type=jnp.float32)
                # x = B^-1 b - B^-1 V^T D^1/2 z = Q ((Q^T b - s) / denom)
                x = jnp.einsum("bs,rs->br", bq_d - s / denom, gram_q,
                               precision=hi)
            return x, cg
        with jax.named_scope("pio.sweep.gram"):
            A = G + jnp.einsum("bk,bkr,bks->brs", conf_minus_1.astype(cd),
                               Vc, Vc,
                               preferred_element_type=jnp.float32)
    else:
        with jax.named_scope("pio.sweep.gram"):
            A = jnp.einsum("bk,bkr,bks->brs", mask.astype(cd), Vc, Vc,
                           preferred_element_type=jnp.float32)
            b = jnp.einsum("bk,bkr->br", (val * mask).astype(cd), Vc,
                           preferred_element_type=jnp.float32)
    with jax.named_scope("pio.sweep.gram"):
        A = A + reg[:, None, None] * eye
    with jax.named_scope("pio.sweep.solve.jnp_cg" if solver == "cg"
                         else "pio.sweep.solve.primal"):
        x, cg = spd_solve(A, b, method=solver, iters=solver_iters)
    return x, cg


def _solve_sweep_impl(factors_out, counter_factors, gram, groups, lam,
                      alpha, *, nratings_reg: bool, implicit: bool,
                      rank: int, compute_dtype: str, solver: str,
                      dual_solve: str = "auto",
                      solver_iters: Optional[int] = None,
                      dual_iters_cap: Optional[int] = None):
    """Every batch of `groups` solved into `factors_out`. Returns the table
    and the CG iterations the program's Pallas solves ran and were allowed
    (float32 [2], summed over the systems: `spd_solve`)."""
    import jax

    from predictionio_tpu.ops.solve import no_cg_iterations

    def body(carry, batch):
        f, cg = carry
        rows, idx, val, mask = batch
        f, cg_batch = _solve_batch(
            f, counter_factors, gram, rows, idx, val, mask, lam, alpha,
            nratings_reg=nratings_reg, implicit=implicit, rank=rank,
            compute_dtype=compute_dtype, solver=solver,
            dual_solve=dual_solve, solver_iters=solver_iters,
            dual_iters_cap=dual_iters_cap)
        return (f, cg + cg_batch), None

    carry = (factors_out, no_cg_iterations())
    for group in groups:
        carry, _ = jax.lax.scan(body, carry, group)
    return carry


_SWEEP_STATICS = ("nratings_reg", "implicit", "rank", "compute_dtype",
                  "solver", "dual_solve", "solver_iters", "dual_iters_cap")

#: One half-iteration in ONE dispatch: `groups` is a tuple of stacked
#: same-shape batch groups (rows [N,B], idx/val/mask [N,B,K]); each group
#: is consumed by a `lax.scan` over its leading dim, carrying the donated
#: factor table through every scatter (no HBM copy per sweep). Collapses
#: ~45 dispatches per half-sweep (each with fresh host scalars) to a
#: single device program, and the per-bucket compile count to one
#: program per plan signature. Returns (table, CG iterations [2]).
_solve_sweep = __import__("jax").jit(
    _solve_sweep_impl, static_argnames=_SWEEP_STATICS, donate_argnums=(0,))


# -- the half-sweep over row-sharded tables: one program a chip ------------

def _axis_size(mesh, axes) -> int:
    """How many ways the mesh axes `axes` (a name, a tuple of names or
    None) divide an array dimension."""
    if axes is None:
        return 1
    names = (axes,) if isinstance(axes, str) else tuple(axes)
    n = 1
    for name in names:
        n *= mesh.shape[name]
    return n


def sweep_shards(table, device_groups):
    """(table shards, batch shards): how many ways a half-sweep's table
    rows and its batch dimension ARE divided over the chips, read off the
    arrays (telemetry's `table_shards` / `batch_shards`; nothing is
    decided from it). 1 for anything that is not a NamedSharding on a mesh
    (one device, a host array)."""
    import jax

    def ways(x, dim):
        sh = getattr(x, "sharding", None)
        if not isinstance(sh, jax.sharding.NamedSharding):
            return 1
        return _axis_size(sh.mesh, (tuple(sh.spec) + (None,) * 2)[dim])

    return (ways(table, 0),
            ways(device_groups[0][0], 1) if device_groups else 1)


def _solve_batch_per_chip(f_local, counter_local, gram, rows, place, val,
                          mask, send, lam, alpha, *, table_axis: str,
                          batch_axes, **statics):
    """One scan step of a half-sweep as ONE chip of a row-sharded mesh runs
    it (the ALX arrangement, PAPERS.md arXiv:2112.02194): this chip holds
    `f_local` / `counter_local`, its contiguous quarter of each table's
    rows, and `rows` / `place` / `val` / `mask`, its quarter [B/n, ...] of
    the step's systems. Which chip owns which slot's counterpart row is a
    fact of the plan, so the host has routed it (`_route_group`, once a
    train): `send` [n, L] holds, for each chip of the table axis, the local
    numbers of the rows of THIS shard that that chip's systems rate, and
    `place` [B/n, K], where `idx` sat, where each slot's row stands in what
    this chip receives. Three exchanges cross the chips, and nothing else:

      rows     each shard gathers the rows the others (and itself) need of
               it, `counter_local[send]` [n, L, R] in the compute dtype: a
               plain gather of owned rows, real slots only; one all-to-all
               over the table axis hands every chip the [n, L, R] rows of
               its own systems, each a copy of its owner's row, which
               `place` spreads to [B/n, K, R] (a padding slot reads
               position 0 and is masked in every route of
               `_solve_gathered`, as a one-chip plan's padding `idx` 0 is);
      solved   the solved [B/n, R] float32 rows all-gathered, each shard
               keeping the rows it owns;
      indices  the systems' row ids all-gathered with them: which solved
               row goes where.

    Between `rows` and `solved` the chip runs `_solve_gathered` on local
    operands: the Gram, the solver routes and their Pallas kernels are the
    one-chip ones, on B/n systems."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    shard = lax.axis_index(table_axis)
    with jax.named_scope("pio.sweep.exchange.indices"):
        rows_all = lax.all_gather(rows, batch_axes, axis=0, tiled=True)
    Vc = _routed_rows(counter_local, send[0], place, table_axis)
    x, cg = _solve_gathered(Vc, gram, val, mask, lam, alpha, **statics)
    with jax.named_scope("pio.sweep.exchange.solved"):
        x_all = lax.all_gather(x.astype(f_local.dtype), batch_axes, axis=0,
                               tiled=True)          # [B, R]
    with jax.named_scope("pio.sweep.scatter"):
        n_out = f_local.shape[0]
        at = rows_all - shard * n_out
        # padding systems (row -1) and other shards' rows: out of range,
        # so dropped
        at = jnp.where((rows_all >= 0) & (at >= 0) & (at < n_out), at, n_out)
        return f_local.at[at].set(x_all, mode="drop"), cg


def _routed_rows(counter_local, send, place, table_axis: str):
    """The counterpart rows [B/n, K, R] of this chip's systems, fetched
    from their owners as the plan routed them (`_route_group`): `send`
    [n, L] and `place` [B/n, K] are this chip's of one scan step."""
    import jax
    from jax import lax
    with jax.named_scope("pio.sweep.gather"):
        owned = counter_local[send]                     # [n, L, R]
    with jax.named_scope("pio.sweep.exchange.rows"):
        got = lax.all_to_all(owned, table_axis, 0, 0)   # [n, L, R]
    with jax.named_scope("pio.sweep.gather.place"):
        return got.reshape(-1, got.shape[-1])[place]


def _solve_sweep_per_chip_impl(factors_out, counter_factors, gram, groups,
                               lam, alpha, *, mesh, table_axis: str,
                               batch_axes, **statics):
    """`_solve_sweep_impl` written per chip: one `shard_map` over the whole
    half-sweep, its scans inside, each step `_solve_batch_per_chip`. The
    tables go in and out sharded on their rows, the batch groups (rows,
    place, val, mask, send: `_upload_plan` where `_rows_sharded`) sharded
    on the batch dimension, the shared Gram and the scalars whole. The CG
    iterations returned are the chips' summed."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from predictionio_tpu.ops.solve import no_cg_iterations

    def per_chip(f_local, counter_local, gram, groups, lam, alpha):
        # the gathers read a copy of the counterpart shard in the compute
        # dtype, as one chip's do; made here once for every scan (left to
        # the compiler, each scan group hoists a copy of its own: five of
        # 2.7 GB in the item half-sweep of rec-amazon14-all-r200)
        with jax.named_scope("pio.sweep.gather"):
            counter_local = counter_local.astype(
                statics["compute_dtype"])

        def body(carry, batch):
            f, cg = carry
            f, cg_batch = _solve_batch_per_chip(
                f, counter_local, gram, *batch, lam, alpha,
                table_axis=table_axis, batch_axes=batch_axes, **statics)
            return (f, cg + cg_batch), None

        carry = (f_local, no_cg_iterations())
        for group in groups:
            # one group after the other, as the table they carry says: a
            # group of one step is no loop but straight-line code, and the
            # compiler's scheduler starts the gathers and exchanges of
            # dozens of them at once (the 81 groups of an item side: 19 GB)
            carry, group = lax.optimization_barrier((carry, group))
            carry, _ = lax.scan(body, carry, group)
        f_local, cg = carry
        return f_local, lax.psum(cg, batch_axes)

    table = P(table_axis, None)
    # rows, place, val, mask divided on the batch dimension; send on its
    # source chip
    batch = tuple((P(None, batch_axes), P(None, batch_axes, None),
                   P(None, batch_axes, None), P(None, batch_axes, None),
                   P(None, batch_axes, None, None))
                  for _ in groups)
    whole = jax.tree_util.tree_map(lambda _: P(), gram)
    return jax.shard_map(
        per_chip, mesh=mesh,
        in_specs=(table, table, whole, batch, P(), P()),
        out_specs=(table, P()), check_vma=False)(
            factors_out, counter_factors, gram, groups, lam, alpha)


#: `_solve_sweep` for tables row-sharded over a mesh, the batches divided
#: over the same chips: statics beside `_SWEEP_STATICS` are the jax mesh
#: and the axis names (`_sweep_statics`).
_solve_sweep_per_chip = __import__("jax").jit(
    _solve_sweep_per_chip_impl,
    static_argnames=_SWEEP_STATICS + ("mesh", "table_axis", "batch_axes"),
    donate_argnums=(0,))


def _live_gram(factors, n_live: Optional[int]):
    """Y^T Y over the table's first `n_live` rows (None: all of them). A
    training table ends in the scatter's dummy row, which is no entity:
    the slice is taken here, inside the program, where it is an operand
    of the product and no copy of the table (sliced by the caller it was
    a second table in HBM for as long as the Gram ran)."""
    import jax.numpy as jnp
    live = factors if n_live is None else factors[:n_live]
    return jnp.einsum("ir,is->rs", live, live,
                      preferred_element_type=jnp.float32)


def _gram_impl(factors, n_live: Optional[int] = None):
    import jax
    with jax.named_scope("pio.sweep.gram_full.gram"):
        return _live_gram(factors, n_live)


def _gram_eig_impl(factors, n_live: Optional[int] = None):
    """Gram + its eigendecomposition — computed ONCE per implicit
    half-sweep and shared by every entity's Woodbury solve (the base
    B = G + reg*I diagonalizes as Q diag(w + reg) Q^T for any reg)."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("pio.sweep.gram_full.gram"):
        G = _live_gram(factors, n_live)
    with jax.named_scope("pio.sweep.gram_full.eigh"):
        w, q = jnp.linalg.eigh(G)
    return G, w, q


_gram = __import__("jax").jit(_gram_impl, static_argnames=("n_live",))
_gram_eig = __import__("jax").jit(_gram_eig_impl,
                                  static_argnames=("n_live",))


# ---------------------------------------------------------------------------
# Training driver
# ---------------------------------------------------------------------------

def table_rows(n: int, row_multiple: int = 1) -> int:
    """Rows of a training table of `n` entities: at least one trailing
    dummy row (the scatter target for padding), the total rounded up so
    that a model-axis sharding over `row_multiple` shards divides it."""
    return ((n + 1 + row_multiple - 1) // row_multiple) * row_multiple


#: Rows of one random stream of `_init_factors`: a table of up to this many
#: rows is one stream (what it always was); a larger one is a stream a
#: block, drawn by as many threads as the host has cores (one stream fills
#: 30M x 200 in minutes: rec-amazon14-all-r200).
_INIT_BLOCK_ROWS = 1 << 20


def _init_factors(n: int, rank: int, seed: int, salt: int,
                  row_multiple: int = 1) -> np.ndarray:
    # MLlib seeds factors with abs(normal)/sqrt(rank) per block; we use a
    # deterministic numpy RNG — scale keeps initial predictions O(1).
    # float32 [rows, rank]; the same whatever the host's cores.
    rows = table_rows(n, row_multiple)
    stream = seed * 2654435761 % (2 ** 31) + salt
    f = np.empty((rows, rank), dtype=np.float32)

    def fill(block: int) -> None:
        part = f[block * _INIT_BLOCK_ROWS:(block + 1) * _INIT_BLOCK_ROWS]
        rng = np.random.default_rng(stream if block == 0
                                    else [stream, block])
        rng.standard_normal(dtype=np.float32, out=part)
        np.abs(part, out=part)
        np.divide(part, np.sqrt(rank), out=part)    # in float64, as ever

    blocks = range(-(-rows // _INIT_BLOCK_ROWS))
    if len(blocks) == 1:
        fill(0)
    else:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(min(len(blocks), os.cpu_count() or 1)) as ex:
            list(ex.map(fill, blocks))
    return f


def sweep_solver(method: str, mesh: MeshContext,
                 factor_sharding: str = "replicated") -> str:
    """`ops/solve.resolve_solver` for a half-sweep on `mesh`: where the
    sweep is written per chip (row-sharded tables, `plan_axes`) the solver
    sees one chip's local operands, so "auto" is the one-chip answer
    (`cg_pallas` on a TPU); a replicated mesh's sweep is GSPMD's to
    partition, which `pallas_call` cannot take."""
    from predictionio_tpu.ops.solve import resolve_solver
    return resolve_solver(method, 1 if _rows_sharded(mesh, factor_sharding)
                          else mesh.n_devices)


def resolve_sweep_chunk(chunk: int, n_devices: int = 1) -> int:
    """0 (auto) -> 4 on a single TPU device, 1 elsewhere. The chunked
    layout is shape-identical math; the default only changes where the
    per-solver-call fixed cost is measured to matter."""
    if chunk:
        return chunk
    import jax
    return 4 if (jax.default_backend() == "tpu" and n_devices == 1) else 1


#: The TPU compiler tiles a gather's index vector by 1,024 and picks the
#: gather's step from what the last tile holds: 256 rows a step where the
#: row count leaves it between these bounds, 128 where it leaves it nearly
#: full, empty or nearly empty (counts of 10^4 to 8*10^6 into tables of
#: 0.9M to 4.2M rows compiled for a described v5e: 256 at 32-64 up to
#: 768-896, the edges moving with the count; PERF.md section 6, PR 30).
#: The steps are latency-bound, 1.4-1.5 us each whatever they hold: 11.6 ns
#: a row at 128, 6.1 at 256, whatever a row's bytes or tiling. Seen at rank
#: 64, 200 and 256; a table of 10 or 32 columns gathers 128 rows a step
#: whatever the count, and at rank 10 (the table lies row-index-minor) a
#: batch of an odd size costs the compiler minutes: 177 s for one K 8 rung
#: of 262,160 systems against 2.4 s for 262,144.
_GATHER_TILE, _GATHER_STEP_256, _GATHER_MIN_RANK = 1024, (128, 704), 64


def _gather_pad_rows(b: int, k: int) -> int:
    """How many padding systems (row -1, mask 0: what a plan pads its own
    batches with) to append to the [b, k] batch one chip gathers for, so
    that its gather of (b + extra) * k rows gets the compiler's 256-row
    step (`_GATHER_STEP_256`): the fewest, at most b // 32 (3% more
    systems); 0 where the count lies there already or none that few does
    it (a few of the longest rungs, b < 32 or k a multiple of 1,024)."""
    lo, hi = _GATHER_STEP_256
    for extra in range(b // 32 + 1):
        if lo <= (b + extra) * k % _GATHER_TILE <= hi:
            return extra
    return 0


def _rows_sharded(mesh: MeshContext, factor_sharding: str) -> bool:
    """True where `als_train` shards the tables' rows over more than one
    chip: "model" on a mesh whose model axis is wider than 1. The
    half-sweep is then the per-chip one."""
    return factor_sharding == "model" and mesh.model_parallelism > 1


def plan_axes(mesh: MeshContext, factor_sharding: str = "replicated"):
    """The mesh axes a plan's batch dimension is divided over: the data
    axis; and with row-sharded tables the model axis too, so that every
    chip that holds a quarter of the rows also holds, and solves, a
    quarter of every batch."""
    if _rows_sharded(mesh, factor_sharding):
        return (mesh.DATA_AXIS, mesh.MODEL_AXIS)
    return mesh.DATA_AXIS


def batch_shards(mesh: MeshContext, factor_sharding: str = "replicated"
                 ) -> int:
    """How many ways `plan_axes` divides a batch: what a plan's batch
    sizes have to be a multiple of (`plan_for_*`'s `batch_multiple`)."""
    return _axis_size(mesh.mesh, plan_axes(mesh, factor_sharding))


def _gather_layout(mesh: MeshContext, rank: Optional[int] = None,
                   factor_sharding: str = "replicated") -> str:
    """How the uploaded batches stand for the half-sweeps' gathers, by what
    the program can see: "rows+pad256" where a TPU gathers a step's rows in
    a program of its own, which is a single TPU device and each chip of a
    TPU mesh over row-sharded tables (`_solve_batch_per_chip`);
    `_upload_plan` then pads each chip's [B/n, K] slots of a batch group
    by `_gather_pad_rows` systems (and `_route_rows` rounds what the
    owners of row-sharded tables gather). "rows" anywhere else:
    another backend's gather has no such step, GSPMD partitions a
    replicated mesh's gather as it likes, and tables of a known `rank`
    under `_GATHER_MIN_RANK` gain nothing."""
    tpu = mesh.mesh.devices.flat[0].platform == "tpu"
    own_gather = mesh.n_devices == 1 or _rows_sharded(mesh, factor_sharding)
    wide = rank is None or rank >= _GATHER_MIN_RANK
    return "rows+pad256" if tpu and own_gather and wide else "rows"


def _upload_plan(mesh: MeshContext, plan: SolvePlan, chunk: int = 1,
                 rank: Optional[int] = None,
                 factor_sharding: str = "replicated"):
    """Stack same-shape batches into [N, B(, K)] groups and upload each
    group once, sharded on the batch dim (dim 1) over the mesh data axis
    (and the model axis under `factor_sharding` "model": `plan_axes`; the
    plan's batches are then multiples of `batch_shards`).
    The index/rating/mask tensors are constant across iterations, so they
    stay resident in HBM for the whole train (re-uploading per sweep would
    put ~NNZ*12B on the host<->device link every iteration). Stacking is
    what lets `_solve_sweep` consume a whole side in one dispatch via
    scan.

    `chunk` > 1 merges that many batches into each scan step ([N, B] ->
    [N/chunk, chunk*B]): batches within a half-sweep are independent, so
    this only amortizes the solver's per-call fixed cost over more
    systems (ALSConfig.sweep_chunk); a remainder that doesn't fill a
    chunk becomes its own group. On a single TPU device each group is
    then padded by a few systems that solve nothing, so that its gather
    runs at the compiler's faster step (`_gather_pad_rows`;
    `_gather_layout` says when, from the mesh, the tables' `rank` where
    the caller gives it, and their sharding).

    Over row-sharded tables (`_rows_sharded`) a group is (rows, place,
    val, mask, send): the plan's indices are routed here, on the host,
    once (`_route_group`), and stay here."""
    with TRACER.region("train.upload"):
        return _upload_plan_now(mesh, plan, chunk, rank, factor_sharding)


def _upload_plan_now(mesh: MeshContext, plan: SolvePlan, chunk: int,
                     rank: Optional[int], factor_sharding: str):
    pad = _gather_layout(mesh, rank, factor_sharding) != "rows"
    axes = plan_axes(mesh, factor_sharding)
    table_shards = (mesh.model_parallelism
                    if _rows_sharded(mesh, factor_sharding) else 1)
    groups = tuple(
        tuple(mesh.put_stacked(x, axes) for x in tensors)
        for tensors in _host_groups(
            plan, chunk, pad, batch_shards(mesh, factor_sharding),
            table_shards))
    # host->device transfer accounting (obs.jaxmon): the plan upload is
    # the largest per-train / per-fold-in host->device transfer
    from predictionio_tpu.obs import jaxmon
    jaxmon.record_h2d(jaxmon.nbytes_of(
        t for group in groups for t in group))
    return groups


def _host_groups(plan: SolvePlan, chunk: int, pad: bool, shards: int,
                 table_shards: int = 1):
    """The host arrays of each batch group `_upload_plan` uploads, in its
    order: (rows, idx, val, mask), same-shape batches stacked, merged
    `chunk` at a time and, where `pad`, padded for the gather; for tables
    row-sharded `table_shards` > 1 ways (the batches divided over `shards`
    chips) each chip gathers its own [B/shards, K] slots, so that count is
    what is padded for, and the group is (rows, place, val, mask, send):
    `_route_group`'s `place` in `idx`'s seat, `send` appended, and what
    was routed goes to `_route_log`."""
    groups = _padded_groups(plan, chunk, pad, shards)
    if table_shards == 1:
        yield from groups
        return
    import time
    from concurrent.futures import ThreadPoolExecutor
    if plan.n_counter is None:
        raise ValueError(
            "a plan uploaded for row-sharded tables has to say how many "
            "rows its indices point into (SolvePlan.n_counter: "
            "plan_for_users / plan_for_items do)")
    t0 = time.perf_counter()
    rows_a_shard = table_rows(plan.n_counter, table_shards) // table_shards
    degrees = _rung_degrees(plan)
    seconds, real, room = time.perf_counter() - t0, 0, 0
    with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
        for rows, idx, val, mask in groups:
            t0 = time.perf_counter()
            with TRACER.region("train.route"):
                place, send, needed, made = _route_group(
                    rows, idx, mask, degrees[idx.shape[2]], rows_a_shard,
                    table_shards, shards, pad, pool)
            seconds += time.perf_counter() - t0
            real, room = real + needed, room + made
            yield rows, place, val, mask, send
    _route_log.append((seconds, real, room))


def _padded_groups(plan: SolvePlan, chunk: int, pad: bool, shards: int):
    """`_host_groups`' (rows, idx, val, mask); where `pad`, each of the
    `shards` chips' [B/shards, K] slots padded for its gather."""
    by_shape = {}
    for b in plan.batches:
        by_shape.setdefault(b.shape, []).append(b)
    for shape in sorted(by_shape):
        bs = by_shape[shape]
        rows = np.stack([b.rows for b in bs])    # [N, B]
        idx = np.stack([b.idx for b in bs])      # [N, B, K]
        val = np.stack([b.val for b in bs])
        mask = np.stack([b.mask for b in bs])
        chunks = [(rows, idx, val, mask)]
        if chunk > 1 and len(bs) > 1:
            m = min(chunk, len(bs))
            n_full = (len(bs) // m) * m
            chunks = []
            if n_full:
                chunks.append(tuple(
                    x[:n_full].reshape(n_full // m, m * x.shape[1],
                                       *x.shape[2:])
                    for x in (rows, idx, val, mask)))
            if len(bs) > n_full:
                chunks.append(tuple(x[n_full:]
                                    for x in (rows, idx, val, mask)))
        for tensors in chunks:
            b, k = tensors[1].shape[1:]
            extra = shards * _gather_pad_rows(b // shards, k) if pad else 0
            if extra:
                tensors = tuple(
                    np.pad(x, [(0, 0), (0, extra)] + [(0, 0)] * (x.ndim - 2),
                           constant_values=fill)
                    for x, fill in zip(tensors, (-1, 0, 0, 0)))
            yield tensors


# -- the routing of a plan over row-sharded tables, on the host ------------

#: Slots one thread routes at a time (`_route_group`): a few passes of
#: whole-array numpy over 4 MB of indices each, or one scan step's.
_ROUTE_SLOTS = 1 << 20

#: What each `_upload_plan` over row-sharded tables routed, (seconds, real
#: rows, rows of room in `send`): `als_train` clears it before its two
#: uploads and reads `route_s` / `route_fill` after them (`_routed`).
_route_log: list = []


def _routed() -> dict:
    """Telemetry of the uploads in `_route_log`: `route_s`, the host's
    seconds routing them (a part of `upload_s`), and `route_fill`, the real
    rows over the rows of room the chips exchange for them (padding in `L`:
    an uneven spread of owners, and the room that keeps `L` off the seed).
    Empty where nothing was routed."""
    if not _route_log:
        return {}
    seconds, real, room = (sum(x) for x in zip(*_route_log))
    return {"route_s": seconds, "route_fill": real / max(room, 1)}


def _rung_degrees(plan: SolvePlan) -> dict:
    """{K: ratings a system of rung K has}, over every batch of the plan:
    from the plan's degrees alone, whatever ids carry them."""
    systems, slots = collections.Counter(), collections.Counter()
    for b in plan.batches:
        systems[b.shape[1]] += int(np.count_nonzero(b.rows >= 0))
        slots[b.shape[1]] += int(np.count_nonzero(b.mask))
    return {k: slots[k] / max(systems[k], 1) for k in systems}


def _route_rows(expected: float, observed: int, table_shards: int,
                pad: bool) -> int:
    """`L`, the rows a chip sends each chip in a scan step of one batch
    group: room for `expected`, the rows a pair of chips exchanges where
    owners are spread evenly (`_route_group`: from the plan's degrees
    alone, so the same for every pairing of the same degree sequences, and
    with it the compiled program and its place in the compile cache), a
    sixteenth and eight standard deviations of a fair draw over it; over
    `observed`, the most any pair of the group really exchanges, by steps
    of an eighth (a catalogue whose popular rows crowd one shard pays in
    `L` and in a program of its own, and stays exact). A multiple of 16 (a
    bfloat16 tile's sublanes), and where `pad` such that the owners' gather
    of `table_shards` * L rows gets the compiler's 256-row step
    (`_GATHER_STEP_256`)."""
    want = int(np.ceil(expected * 17 / 16 + 8 * np.sqrt(expected))) + 16
    while want < observed:
        want = -(-want * 9 // 8)
    lo, hi = _GATHER_STEP_256
    L = -(-want // 16) * 16
    while pad and not lo <= table_shards * L % _GATHER_TILE <= hi:
        L += 16
    return L


def _route_steps(idx, mask, owner, counts, rows_a_shard: int):
    """First pass over some scan steps of a group, `idx` / `mask` /
    `owner` [n, blocks, S] (a chip's block of a step in a row) and `counts`
    [n, blocks, table_shards]: into `owner` (int8) goes the shard whose
    range a real slot's row lies in, -1 for padding; into `counts` how many
    slots of the block each shard owns."""
    q = idx // rows_a_shard
    q += 1
    q *= mask != 0              # in place: `np.where` costs a third more
    q -= 1
    np.copyto(owner, q, casting="unsafe")
    for s in range(counts.shape[-1]):
        counts[:, :, s] = np.count_nonzero(owner == s, axis=-1)


def _place_steps(idx, owner, counts, place, send, rows_a_shard: int):
    """Second pass, `L` known (`send` [n, data, table_shards, table_shards,
    L], zeros: by sending chip (data, shard) and receiving index on the
    table axis; `place` zeros): `send[t, d, s, m]` becomes the local numbers
    of the rows shard s sends the chip of block (d, m) in step t, in slot
    order (what is left zero, row 0, is sent and never placed), and
    `place` of a real slot s * L + its place in that run. Sorts nothing
    and holds the interpreter for no whole-array pass (numpy's running sum
    does: the threads of `_route_group` would take turns)."""
    L = send.shape[-1]
    idx, owner, place = idx.ravel(), owner.ravel(), place.ravel()
    real = np.flatnonzero(owner >= 0)
    owner = owner[real]
    for s in range(counts.shape[-1]):
        at = real[owner == s]           # in (step, block, slot) order
        count = counts[:, :, s]
        first = np.cumsum(count) - count.ravel()
        among = np.arange(at.size) - np.repeat(first, count.ravel())
        place[at] = among + s * L
        send[:, :, s][np.arange(L) < count.reshape(
            send.shape[:2] + (-1, 1))] = idx[at] - s * rows_a_shard


def _route_group(rows, idx, mask, degree: float, rows_a_shard: int,
                 table_shards: int, blocks: int, pad: bool, pool=None):
    """The static routing of one padded batch group over row-sharded
    tables, on the host: whole-array passes, no loop over systems, no sort;
    `pool`'s threads take some scan steps each (numpy releases the
    interpreter). `rows` [N, B], `idx` / `mask` [N, B, K], the B systems of
    a step dealt to `blocks` chips in order, chip (d, m) of the data and
    table axes holding block d * table_shards + m; `degree` the rung's
    `_rung_degrees`. Returns

      place  [N, B, K] int32, `idx`'s seat on the device: where the slot's
             row stands among the [table_shards * L] rows its chip
             receives in that step (owner * L + its place among the
             block's slots of that owner; 0 for a padding slot);
      send   [N, blocks, table_shards, L] int32: `send[t, c, m]` the local
             row numbers chip c gathers from its shard in step t for the
             chip m of its table axis, zeros (row 0: sent, never placed)
             beyond what m needs;
      real, room  the rows really needed, and the N * blocks *
             table_shards * L of room (`route_fill`)."""
    n, b, k = idx.shape
    steps = max(_ROUTE_SLOTS // (b * k), 1)
    cuts = [slice(lo, lo + steps) for lo in range(0, n, steps)]
    each = pool.map if pool is not None else map
    idx, mask = (x.reshape(n, blocks, -1) for x in (idx, mask))
    owner = np.empty(idx.shape, np.int8)
    counts = np.empty((n, blocks, table_shards), np.int64)
    list(each(lambda c: _route_steps(idx[c], mask[c], owner[c], counts[c],
                                     rows_a_shard), cuts))
    systems = int((rows.reshape(n, blocks, -1) >= 0).sum(axis=-1).max())
    L = _route_rows(systems * degree / table_shards, int(counts.max()),
                    table_shards, pad)
    place = np.zeros(idx.shape, np.int32)
    send = np.zeros((n, blocks // table_shards, table_shards, table_shards,
                     L), np.int32)
    list(each(lambda c: _place_steps(idx[c], owner[c], counts[c], place[c],
                                     send[c], rows_a_shard), cuts))
    return (place.reshape(n, b, k),
            send.reshape(n, blocks, table_shards, L), int(counts.sum()),
            n * blocks * table_shards * L)


#: An implicit half-sweep of more batch groups than this is dispatched as
#: several programs, the groups dealt round-robin (batches of a half-sweep
#: are independent, so neither the split nor the order changes a row). The
#: TPU compiler's own memory for one program grows faster than the number
#: of eig-SMW scan groups in it: 25 GB of host memory for the 76 groups of
#: a 4.16M-item side at rank 200 (6 GB for the same plan's explicit
#: program), which a 40 GiB host does not survive beside the process; a
#: third of the groups takes a fifth of it. Each program beyond the first
#: re-pays the donated table's two layout copies (PERF.md section 7,
#: row 6), so explicit sweeps, whose programs compile in little, stay one.
_IMPLICIT_GROUPS_PER_PROGRAM = 32


def _sweep_programs(device_groups, implicit: bool):
    """`device_groups` as the tuples of groups each dispatched program of
    one half-sweep consumes."""
    n = -(-len(device_groups) // _IMPLICIT_GROUPS_PER_PROGRAM) \
        if implicit else 1
    if n <= 1:
        return (device_groups,)
    return tuple(tuple(device_groups[p::n]) for p in range(n))


#: The CG iterations (run, allowed: `_solve_sweep`'s second result, still on
#: the device) of each program of the last two half-sweeps, which is the
#: last whole iteration of a train. Nothing on the hot path waits for them;
#: `last_cg_iterations` fetches.
_cg_iters_log: collections.deque = collections.deque(maxlen=2)


def last_cg_iterations() -> Optional[Tuple[float, float]]:
    """(run, allowed) CG iterations of the Pallas solves of the last two
    half-sweeps this process dispatched, each summed over the systems;
    None before any half-sweep. Fetches a few scalars: for after the
    timed path."""
    import jax
    programs = [cg for half_sweep in tuple(_cg_iters_log)
                for cg in half_sweep]
    if not programs:
        return None
    run, allowed = np.sum(jax.device_get(programs), axis=0)
    return float(run), float(allowed)


def _sweep_statics(cfg: ALSConfig, mesh: Optional[MeshContext]) -> dict:
    """The static arguments of `cfg`'s half-sweep program: `_SWEEP_STATICS`,
    and with them the jax mesh and the axis names where the sweep is the
    per-chip one (`_rows_sharded`, the one rule; `mesh` None: never)."""
    statics = dict(
        nratings_reg=(cfg.lambda_scaling == "nratings"),
        implicit=cfg.implicit_prefs, rank=cfg.rank,
        compute_dtype=cfg.compute_dtype, solver=cfg.solver,
        dual_solve=cfg.dual_solve, solver_iters=cfg.solver_iters,
        dual_iters_cap=cfg.dual_iters_cap)
    if mesh is not None and _rows_sharded(mesh, cfg.factor_sharding):
        statics.update(mesh=mesh.mesh, table_axis=mesh.MODEL_AXIS,
                       batch_axes=plan_axes(mesh, cfg.factor_sharding))
    return statics


def _run_side(device_groups, factors, counter_factors, cfg: ALSConfig,
              gram, lam=None, alpha=None, side: Optional[str] = None,
              mesh: Optional[MeshContext] = None):
    """One half-iteration: solve every batch of one side, in one dispatch
    (explicit) or a few (`_sweep_programs`). `lam`/`alpha` should be
    device-resident scalars (uploaded once per train); numpy fallbacks
    keep ad-hoc callers working. `side` ("user"/"item") only labels the
    `pio.train.half_sweep` span. Returns the table; the programs' counts
    of CG iterations stay on the device for `last_cg_iterations`.

    `mesh` is the mesh the caller placed the tables and `_upload_plan`ed
    the batches on: where that divided both over the model axis
    (`_rows_sharded`) the program is `_solve_sweep_per_chip`. Without it
    (one device, the online fold) it is `_solve_sweep`, GSPMD's to
    partition over whatever shardings the operands carry."""
    if lam is None:
        lam = np.float32(cfg.lam)
    if alpha is None:
        alpha = np.float32(cfg.alpha)
    # compile attribution (obs/costmon): sweeps dispatched from a fold
    # tick keep the fold's label; bare train sweeps book as als_sweep
    from predictionio_tpu.obs import costmon
    attrs = {"side": side} if side else {}
    statics = _sweep_statics(cfg, mesh)
    with TRACER.region("train.half_sweep", **attrs), \
            costmon.executable(costmon.ALS_SWEEP, defer_to_outer=True):
        counts = []
        for groups in _sweep_programs(device_groups, cfg.implicit_prefs):
            args = (factors, counter_factors, gram, groups, lam, alpha)
            if "mesh" in statics:
                factors, cg = _solve_sweep_per_chip(*args, **statics)
            else:
                factors, cg = _solve_sweep(*args, **statics)
            counts.append(cg)
        _cg_iters_log.append(counts)
        return factors


def sweep_exchange(mesh: MeshContext, device_groups, factors,
                   counter_factors, cfg: ALSConfig, gram=None) -> dict:
    """{collective: bytes} of what one half-sweep of these operands
    exchanges between the chips: the output bytes of every collective its
    compiled programs run, each counted as often as its scan runs it
    (`parallel/collective_stats.executed_collective_stats`), and under
    "sent" what one chip sends for them by the ring model (`sent_bytes`).
    Empty where the sweep is not the per-chip one. For telemetry, after
    the timed path: each program is lowered and compiled once more from
    its operands' shapes (nothing is read or donated), which the
    persistent compile cache serves where it is on."""
    import jax

    from predictionio_tpu.parallel.collective_stats import (
        executed_collective_stats, merged_stats, sent_bytes)
    statics = _sweep_statics(cfg, mesh)
    if "mesh" not in statics:
        return {}

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), tree)

    scalar = jax.ShapeDtypeStruct((), np.float32,
                                  sharding=mesh.replicated())
    stats = merged_stats(
        executed_collective_stats(_solve_sweep_per_chip.lower(
            *shaped((factors, counter_factors, gram, groups)), scalar,
            scalar, **statics).compile())
        for groups in _sweep_programs(device_groups, cfg.implicit_prefs))
    out = {op: ent["bytes"] for op, ent in stats.items() if op != "total"}
    out["sent"] = sent_bytes(stats, batch_shards(mesh, cfg.factor_sharding))
    return out


def _side_gram(cfg: ALSConfig, table, n_live: int, side: str):
    """What an implicit half-sweep shares across its rows: the Gram of the
    counterpart table's `n_live` entity rows (`side` names that table),
    with its eigendecomposition where the eig-SMW dual route will read
    it. None for explicit training."""
    if not cfg.implicit_prefs:
        return None
    gram_of = _gram_eig if cfg.dual_solve == "auto" else _gram
    with TRACER.region("train.gram", side=side):
        return gram_of(table, n_live=n_live)


def als_train(ratings: RatingsCOO, cfg: ALSConfig,
              mesh: Optional[MeshContext] = None,
              telemetry: Optional[dict] = None) -> ALSModel:
    """Train explicit/implicit ALS. Factor tables carry one extra dummy row
    (index n) used as the scatter target for padding; it is dropped in the
    returned model.

    `telemetry`, when a dict, receives what `auto` resolved to (solver,
    compute_dtype, sweep_chunk, n_devices, and gather_layout: whether the
    uploaded batches are padded for the gather, `_gather_layout`) and
    per-phase wall times
    (plan_s, upload_s, iters_s, s_per_iter, fetch_s; with two or more
    iterations also iters_s = compile_s + sweeps_s, where compile_s is
    what the first iteration took beyond a steady one: trace, lowering
    and compilation or persistent-cache load). Each timing is closed by
    a hard one-element host fetch (a dispatch-queue timer would lie on
    asynchronous backends), which costs one extra tiny transfer — only
    paid when telemetry is requested. It also receives cg_iters_run and
    cg_iters_budget: the CG iterations the last iteration's Pallas solves
    ran and were allowed, summed over their systems (0 and 0 under a
    solver that is not cg_pallas)."""
    import dataclasses
    import time as _time

    import jax

    mesh = mesh or current_mesh()
    t0 = _time.perf_counter()
    cfg = dataclasses.replace(
        cfg, solver=sweep_solver(cfg.solver, mesh, cfg.factor_sharding))
    dp = batch_shards(mesh, cfg.factor_sharding)
    user_plan = plan_for_users(ratings, work_budget=cfg.work_budget,
                               batch_multiple=dp,
                               bucket_ratio=cfg.bucket_ratio)
    item_plan = plan_for_items(ratings, work_budget=cfg.work_budget,
                               batch_multiple=dp,
                               bucket_ratio=cfg.bucket_ratio)
    logger.info(
        "ALS: %d users, %d items, %d ratings; %d user batches %s "
        "(pad %.2fx), %d item batches %s (pad %.2fx)",
        ratings.n_users, ratings.n_items, ratings.nnz,
        len(user_plan.batches), user_plan.kernel_shapes,
        user_plan.padding_overhead,
        len(item_plan.batches), item_plan.kernel_shapes,
        item_plan.padding_overhead)
    if telemetry is not None:
        telemetry["plan_s"] = _time.perf_counter() - t0
        t0 = _time.perf_counter()

    if cfg.factor_sharding == "model":
        put_factors = mesh.put_model_sharded
        row_multiple = mesh.model_parallelism
    else:
        put_factors = mesh.put_replicated
        row_multiple = 1
    fdt = np.dtype(cfg.factor_dtype) if cfg.factor_dtype != "bfloat16" \
        else __import__("jax").numpy.bfloat16
    U = put_factors(_init_factors(ratings.n_users, cfg.rank, cfg.seed, 1,
                                  row_multiple).astype(fdt, copy=False))
    V = put_factors(_init_factors(ratings.n_items, cfg.rank, cfg.seed, 2,
                                  row_multiple).astype(fdt, copy=False))
    chunk = resolve_sweep_chunk(cfg.sweep_chunk, mesh.n_devices)
    _route_log.clear()
    user_batches = _upload_plan(mesh, user_plan, chunk, cfg.rank,
                                cfg.factor_sharding)
    item_batches = _upload_plan(mesh, item_plan, chunk, cfg.rank,
                                cfg.factor_sharding)
    if telemetry is not None:
        n_table, n_batch = sweep_shards(U, user_batches)
        telemetry.update(_routed())
        telemetry.update(solver=cfg.solver,
                         compute_dtype=cfg.compute_dtype,
                         sweep_chunk=chunk, n_devices=mesh.n_devices,
                         table_shards=n_table, batch_shards=n_batch,
                         gather_layout=_gather_layout(
                             mesh, cfg.rank, cfg.factor_sharding))
    # hyperparameters ride along as device-resident scalars: no per-call
    # host uploads, and sweeping lam/alpha (evaluation tuning) does not
    # recompile the sweep program
    lam_dev = mesh.put_replicated(np.float32(cfg.lam))
    alpha_dev = mesh.put_replicated(np.float32(cfg.alpha))
    if telemetry is not None:
        # hard sync: uploads must have landed before iteration timing
        # (one element of the factor table AND of the last-enqueued batch
        # group — per-device transfers complete in order, so the latter
        # fences the bulk of the plan upload)
        float(np.asarray(jax.device_get(V[:1, :1]))[0, 0])
        if item_batches:
            float(np.asarray(jax.device_get(
                item_batches[-1][2][:1, :1, :1])).ravel()[0])
        telemetry["upload_s"] = _time.perf_counter() - t0
        t0 = _time.perf_counter()
    # train-sweep sentinel (ISSUE 5): per-iteration finite/norm check +
    # a checkpointed last-good iteration (HBM copies, never host fetch).
    # Row-sharded tables are checked and not copied: they are sharded
    # because no chip holds them, and a second pair has no room beside a
    # half-sweep's temporaries (rec-amazon14-all-r200: 6.1 GB of tables a
    # chip, 6.6 of temporaries, 15.75 in all). A breach then raises at
    # whatever iteration: nothing is published, and nothing is rolled back.
    sentinel = None
    last_good = None
    keep_last_good = not _rows_sharded(mesh, cfg.factor_sharding)
    if cfg.sentinel:
        from predictionio_tpu.guard.sentinels import (SweepSentinel,
                                                      device_copy,
                                                      guard_enabled)
        if guard_enabled():
            sentinel = SweepSentinel("train", 0.0,
                                     norm_floor=cfg.sentinel_norm_cap)

    def _checked(it: int) -> bool:
        """True to continue; False when a breach rolled back (training
        stops at the last clean iteration). Raises on iteration 0."""
        nonlocal U, V, last_good
        if sentinel is None:
            return True
        with TRACER.region("train.sentinel"):
            fault = (sentinel.check_table(U, f"iteration {it} user table")
                     or sentinel.check_table(V,
                                             f"iteration {it} item table"))
            if fault is None:
                if keep_last_good:
                    # copies survive the next iteration's donated sweep;
                    # the older pair leaves first: never three pairs
                    last_good = None
                    last_good = (device_copy(U), device_copy(V))
                return True
        if last_good is None:
            raise fault
        logger.error("ALS %s — rolling back to iteration %d and "
                     "stopping early", fault, it - 1)
        U, V = last_good
        return False

    def _first_iteration_done(it: int):
        if telemetry is not None and it == 0:
            float(np.asarray(jax.device_get(V[:1, :1]))[0, 0])
            telemetry["first_iter_s"] = _time.perf_counter() - t0

    _cg_iters_log.clear()          # an earlier train's, or a fold tick's
    for it in range(cfg.iterations):
        gram_v = _side_gram(cfg, V, ratings.n_items, "item")
        U = _run_side(user_batches, U, V, cfg, gram_v, lam_dev,
                      alpha_dev, side="user", mesh=mesh)
        gram_u = _side_gram(cfg, U, ratings.n_users, "user")
        V = _run_side(item_batches, V, U, cfg, gram_u, lam_dev,
                      alpha_dev, side="item", mesh=mesh)
        if not _checked(it):
            break
        _first_iteration_done(it)
    if telemetry is not None:
        # hard sync again: the loop above only enqueues device work
        float(np.asarray(jax.device_get(V[:1, :1]))[0, 0])
        telemetry["iters_s"] = _time.perf_counter() - t0
        first = telemetry.pop("first_iter_s", None)
        if first is not None and cfg.iterations > 1:
            steady = (telemetry["iters_s"] - first) / (cfg.iterations - 1)
            telemetry["compile_s"] = max(first - steady, 0.0)
            telemetry["sweeps_s"] = (telemetry["iters_s"]
                                     - telemetry["compile_s"])
        telemetry["s_per_iter"] = (telemetry["iters_s"]
                                   / max(cfg.iterations, 1))
        # what a half-sweep of each side exchanges between the chips
        # (none where no sweep ran per chip); outside every phase's time
        exchanged = {}
        if cfg.iterations > 0 and _rows_sharded(mesh, cfg.factor_sharding):
            from predictionio_tpu.obs import costmon
            exchanged = {
                "user": sweep_exchange(mesh, user_batches, U, V, cfg,
                                       gram_v),
                "item": sweep_exchange(mesh, item_batches, V, U, cfg,
                                       gram_u)}
            for side, by_op in exchanged.items():
                costmon.record_exchange_bytes(side, by_op)
        telemetry["exchange_bytes"] = exchanged
        t0 = _time.perf_counter()
    with TRACER.region("train.fetch"):
        _fetch_cg_iterations(telemetry)
        return _fetch_model(U, V, ratings, cfg, mesh, telemetry, t0)


def _fetch_cg_iterations(telemetry: Optional[dict]) -> None:
    """The last iteration's count of CG iterations leaves the device with
    the tables: into `telemetry` and the registry's gauge."""
    counted = last_cg_iterations()
    if counted is None:
        return
    from predictionio_tpu.obs import costmon
    costmon.record_cg_iterations(*counted)
    if telemetry is not None:
        telemetry["cg_iters_run"], telemetry["cg_iters_budget"] = counted


def _fetch_model(U, V, ratings: RatingsCOO, cfg: ALSConfig,
                 mesh: MeshContext, telemetry: Optional[dict],
                 t0: float) -> ALSModel:
    """The trained tables leave the device: `als_train`'s last step."""
    import time as _time

    from predictionio_tpu.parallel.mesh import host_fetch
    if cfg.factor_sharding == "model" and cfg.keep_sharded:
        # sharded online plane: the tables leave training as
        # ShardedTable handles — per-shard host mirrors (each process
        # fetches only its addressable slices) plus the trained device
        # arrays attached as the resident fast path for the first fold
        # tick / serve call. No replicating gather ever runs.
        from predictionio_tpu.parallel.mesh import host_fetch_sharded
        from predictionio_tpu.parallel.sharded_table import ShardedTable

        def _as_sharded(dev, n_rows):
            offsets, slices = host_fetch_sharded(dev)
            t = ShardedTable(slices, offsets, n_rows,
                             int(dev.shape[0]), mesh.model_parallelism)
            return t.attach_device(dev)

        U_t = _as_sharded(U, ratings.n_users)
        V_t = _as_sharded(V, ratings.n_items)
        if telemetry is not None:
            telemetry["fetch_s"] = _time.perf_counter() - t0
        return ALSModel(user_factors=U_t, item_factors=V_t,
                        rank=cfg.rank)
    if cfg.factor_sharding == "model":
        # gather the model-sharded tables through a replicating jit (a
        # direct np.asarray on a cross-process sharded array is illegal)
        import jax.numpy as jnp
        gather = __import__("jax").jit(lambda a: jnp.asarray(a),
                                       out_shardings=mesh.replicated())
        U, V = gather(U), gather(V)
    U_host = host_fetch(U)[:ratings.n_users].astype(np.float32, copy=False)
    V_host = host_fetch(V)[:ratings.n_items].astype(np.float32, copy=False)
    if telemetry is not None:
        telemetry["fetch_s"] = _time.perf_counter() - t0
    return ALSModel(user_factors=U_host, item_factors=V_host, rank=cfg.rank)


# ---------------------------------------------------------------------------
# Scoring / prediction
# ---------------------------------------------------------------------------

@functools.partial(__import__("jax").jit, static_argnames=("k",))
def _user_topk(user_factors, item_factors, user_ix, exclude_ix, k: int):
    """Single-dispatch serve path: inputs are one scalar index + a small
    padded exclude-index array (pad = -1), so only a few hundred bytes move
    host->device per query — the factor tables are device-resident."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("pio.serve.user_rows"):
        u = user_factors[user_ix]                              # [R]
    with jax.named_scope("pio.serve.score"):
        scores = jnp.einsum("ir,r->i", item_factors, u,
                            preferred_element_type=jnp.float32)
        safe = jnp.where(exclude_ix < 0, scores.shape[0], exclude_ix)
        scores = scores.at[safe].set(-jnp.inf, mode="drop")
    with jax.named_scope("pio.serve.topk"):
        return jax.lax.top_k(scores, k)


def _pad_exclude(exclude, multiple: int = 64) -> np.ndarray:
    ex = np.asarray(exclude, dtype=np.int32).ravel()
    n = max(multiple, ((ex.size + multiple - 1) // multiple) * multiple)
    out = np.full(n, -1, dtype=np.int32)
    out[:ex.size] = ex
    return out


@functools.partial(__import__("jax").jit, static_argnames=("k",))
def _users_topk(user_factors, item_factors, user_ixs, k: int):
    """Batched top-k over EXACT-size tables — kept as the reference
    implementation the compile plane's bucketed kernel
    (``_users_topk_b`` via ``users_topk_serve``) is parity-tested
    against, the same role ``solve_rows`` plays for ``fold_in_coo``.
    Serving dispatches the bucketed path."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("pio.serve.user_rows"):
        u = user_factors[user_ixs]                            # [B, R]
    with jax.named_scope("pio.serve.score"):
        scores = jnp.einsum("br,ir->bi", u, item_factors,
                            preferred_element_type=jnp.float32)
    with jax.named_scope("pio.serve.topk"):
        return jax.lax.top_k(scores, k)


# Table rows that one pass over the whole table handles in the time of
# one row read: ~1.5 us a read against ~1.9 ns a table row of the gather's
# copy, on a v5e at rank 200 (PERF.md, PR 26: ~750, rounded up to a pow2).
_ROWS_PER_READ = 1024


def _batch_rows(table, ixs):
    """``table[ixs]`` for a static batch of row indices, with the same
    treatment of a bad index (a negative one wraps once, then clamps).
    Why it is not written ``table[ixs]``: see :func:`_users_topk_impl`."""
    import jax
    import jax.numpy as jnp
    n_rows, rank = table.shape
    if ixs.shape[0] * _ROWS_PER_READ > n_rows:
        return table[ixs]
    return jnp.concatenate(
        [jax.lax.dynamic_slice(table, (ixs[j], 0), (1, rank))
         for j in range(ixs.shape[0])])


def _users_topk_impl(user_factors, item_factors, user_ixs, n_items,
                     k: int):
    """Traced body shared by the packed and unpacked serve executables
    (unjitted — always composed under one of the two jit wrappers
    below, so both variants rank identically).

    The batch's user rows are read by :func:`_batch_rows`, one
    ``dynamic_slice`` per (static) batch slot, and NOT as
    ``user_factors[user_ixs]``. At a rank that is no multiple of 128
    (200 is the default) the TPU holds an ``[n, rank]`` f32 table with
    the row index minor, ``{0,1:T(8,128)}``, so a row is not contiguous;
    XLA's gather wants it row-major and makes it so with a copy of the
    WHOLE table — transposed, rounded to bf16 and padded to 256 lanes —
    inside every dispatch: 6.7 GB read and 4.3 GB written to fetch 16
    rows of an 8.4M-user table, two thirds of the serve executable's
    device time (PERF.md, PR 26). Scalar-offset slices fuse into one
    small read of the table as it lies, rounded after the pick exactly
    as the copy rounded before it, so ids and scores do not change. Do
    not "simplify" this back to a gather, nor wrap the slices in
    ``lax.map`` / ``vmap`` (both become the gather again).

    The unrolled read grows with the batch bucket, which has no cap
    (``users_topk_serve`` is public), while the gather's copy grows with
    the table: a shape test picks at trace time. Past one row read per
    ``_ROWS_PER_READ`` table rows the pass over the table is the
    cheaper program again (and the smaller one: 1,024 unrolled slices
    take 8-11 s to compile), and a table that small is copied in
    microseconds; every batch bucket a server sends (<= micro_batch 16)
    against a table of 16,384+ rows reads row by row. At a rank that is
    a multiple of 128 the table already lies row-major, the gather
    carries no copy, and the two forms cost the same."""
    import jax
    import jax.numpy as jnp
    with jax.named_scope("pio.serve.user_rows"):
        u = _batch_rows(user_factors, user_ixs)               # [B, R]
    with jax.named_scope("pio.serve.score"):
        scores = jnp.einsum("br,ir->bi", u, item_factors,
                            preferred_element_type=jnp.float32)
        valid = jnp.arange(item_factors.shape[0]) < n_items
        scores = jnp.where(valid[None, :], scores, -jnp.inf)
    with jax.named_scope("pio.serve.topk"):
        return jax.lax.top_k(scores, k)


@functools.partial(__import__("jax").jit, static_argnames=("k",))
def _users_topk_b(user_factors, item_factors, user_ixs, n_items, k: int):
    """Bucket-stable serve kernel (ISSUE 9 compile plane): the factor
    tables arrive padded to their vocab shape-buckets, so vocabulary
    growth inside a bucket changes NO traced shape — ``n_items`` rides
    along as a device scalar masking the padding rows (-inf, sorted
    last, filtered by the caller). k is a pow2 bucket, so client-chosen
    ``num`` never mints a program either."""
    return _users_topk_impl(user_factors, item_factors, user_ixs,
                            n_items, k=k)


@functools.partial(__import__("jax").jit, static_argnames=("k", "p"))
def _users_topk_b_packed(user_factors, item_factors, user_ixs, n_items,
                         k: int, p: int):
    """:func:`_users_topk_b` with the readback-plane pack fused on
    (ISSUE 19): same ranking, but the executable's ONE output is the
    contiguous ids+quantized-scores payload — k x batch x 6 bytes
    instead of two full-width arrays, so each serve window pays one
    small d2h wall. ``p`` (the pack mode) is a static bucket dim."""
    from predictionio_tpu.ops import readback
    scores, idx = _users_topk_impl(user_factors, item_factors,
                                   user_ixs, n_items, k=k)
    return readback.pack_device(scores, idx, p)


def _aot_batch_predict_builder(u: int = 0, i: int = 0, b: int = 0,
                               k: int = 0, r: int = 0, s: int = 0,
                               p: int = 0):
    """(jit_fn, example avals, statics) for one batch_predict bucket —
    what the AOT registry lowers+compiles at deploy/swap time.

    ``s`` > 0 selects the model-sharded layout (sharded online plane):
    the item table's aval carries a NamedSharding over the ``s``-wide
    model axis and the program is the two-phase per-shard top-k +
    cross-shard merge (ops/topk) — so the bucket ladder and swap-time
    warmup cover both layouts through one label.

    ``p`` > 0 selects the packed-readback variant (ISSUE 19): the pack
    is fused into the SAME executable, so the bucket's output aval IS
    the contiguous payload and steady-state packing compiles nothing —
    each (layout, pack-mode) pair owns its own warmed programs."""
    import jax
    sds = jax.ShapeDtypeStruct
    if s:
        from predictionio_tpu.compile.aot import sharded_aval
        from predictionio_tpu.ops.topk import (make_batched_sharded_topk,
                                               sharded_k_split)
        from predictionio_tpu.parallel.mesh import model_mesh
        mesh = model_mesh(s)
        k_local, k_final = sharded_k_split(k, i, s)
        fn = make_batched_sharded_topk(mesh, k_local, k_final,
                                       has_mask=False,
                                       filter_positive=False,
                                       pack=p)
        return (fn,
                (sharded_aval((b, r), np.float32, mesh=mesh),
                 sharded_aval((i, r), np.float32, "model", None,
                              mesh=mesh),
                 sds((), np.int32)),
            {})
    avals = (sds((u, r), np.float32), sds((i, r), np.float32),
             sds((b,), np.int32), sds((), np.int32))
    if p:
        return (_users_topk_b_packed, avals, {"k": k, "p": p})
    return (_users_topk_b, avals, {"k": k})


_aot_specs_registered = False


def register_aot_specs():
    """Idempotently register this module's executable specs with the
    compile plane (deferred off import so `import ops.als` stays
    side-effect-light)."""
    global _aot_specs_registered
    if _aot_specs_registered:
        return
    from predictionio_tpu.obs import costmon
    from predictionio_tpu.compile.aot import get_aot
    get_aot().register(costmon.BATCH_PREDICT, _aot_batch_predict_builder)
    _aot_specs_registered = True


def batch_predict_dims(model: "ALSModel", batch: int, k: int) -> dict:
    """The shape-bucket dims covering one batched top-k over ``model``
    — shared by the serve dispatch and the deploy/swap warm path.
    Model-sharded tables get the sharded-layout dims (``s`` = shard
    count, item bucket = the table's resident sharded bucket, no user
    dim — query vectors come from the host shard mirrors), so the same
    warm path covers both layouts."""
    from predictionio_tpu.compile import buckets as B
    from predictionio_tpu.ops import readback
    from predictionio_tpu.parallel.sharded_table import is_sharded
    p = readback.pack_flag()
    if is_sharded(model.item_factors):
        V = model.item_factors
        i_b = max(V.padded_rows,
                  B.bucket_table_rows_sharded(model.n_items, V.n_shards))
        return {"i": i_b, "b": B.bucket_batch(batch),
                "k": min(B.bucket_batch(k, floor=B.K_FLOOR), i_b),
                "r": model.rank, "s": V.n_shards, "p": p}
    i_b = B.bucket_table_rows(model.n_items)
    return {"u": B.bucket_table_rows(model.n_users), "i": i_b,
            "b": B.bucket_batch(batch),
            "k": min(B.bucket_batch(k, floor=B.K_FLOOR), i_b),
            "r": model.rank, "p": p}


def users_topk_serve(model: "ALSModel", user_ixs, k: int
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Batched serve top-k through the compile plane: tables uploaded
    at vocab-bucket shapes (cached), batch and k padded to their
    buckets, dispatched via the AOT registry (a warmed bucket runs
    zero trace / zero compile; a cold one falls back to the jit and
    adopts in the background). Returns ([n, k_b], [n, k_b]) host
    arrays — rows may carry -inf/padding entries past ``model.n_items``
    valid items, which callers drop via their finite-filter."""
    return users_topk_serve_begin(model, user_ixs, k)()


def users_topk_serve_begin(model: "ALSModel", user_ixs, k: int):
    """Two-phase serve top-k for the pipelined executor (ISSUE 14):
    enqueue the device program NOW (JAX async dispatch — the call
    returns as soon as the work is queued) and defer the device->host
    readback to the returned ``finish() -> (scores, idx)`` callable,
    so batch formation / supplement / serialization of neighboring
    windows overlap this window's device compute. The d2h copy is
    initiated here too (ops/readback ``copy_to_host_async`` — packed
    to ids + quantized scores under ``PIO_SERVE_PACK``), so ``finish``
    only waits on an already-in-flight transfer. ``finish`` is safe
    to call from another thread; calling it is the only sync."""
    from predictionio_tpu.compile.aot import (get_aot,
                                              precompile_next_rung)
    from predictionio_tpu.obs import costmon
    from predictionio_tpu.ops import readback
    from predictionio_tpu.parallel.sharded_table import is_sharded
    from predictionio_tpu.utils.device_cache import cached_put_rows
    register_aot_specs()
    user_ixs = np.asarray(user_ixs, dtype=np.int32)
    n = user_ixs.shape[0]
    dims = batch_predict_dims(model, n, k)
    if is_sharded(model.item_factors):
        return _users_topk_serve_sharded_begin(model, user_ixs, dims)
    ixs = np.zeros(dims["b"], dtype=np.int32)
    ixs[:n] = user_ixs
    U = cached_put_rows(model.user_factors, dims["u"], table="user")
    V = cached_put_rows(model.item_factors, dims["i"], table="item")
    k_b, p = dims["k"], dims["p"]
    if p:
        packed = get_aot().dispatch(
            costmon.BATCH_PREDICT, dims,
            lambda *a: _users_topk_b_packed(*a, k=k_b, p=p),
            U, V, ixs, np.int32(model.n_items))
        fetch = readback.begin_fetch_packed(packed, p)
    else:
        scores, idx = get_aot().dispatch(
            costmon.BATCH_PREDICT, dims,
            lambda *a: _users_topk_b(*a, k=k_b),
            U, V, ixs, np.int32(model.n_items))
        # packing off still pays ONE d2h wall: both copies go in
        # flight now, the finish() below only waits
        fetch = readback.begin_fetch(scores, idx)
    precompile_next_rung(costmon.BATCH_PREDICT, dims, "i", model.n_items)
    precompile_next_rung(costmon.BATCH_PREDICT, dims, "u", model.n_users)

    def finish() -> Tuple[np.ndarray, np.ndarray]:
        scores_h, idx_h = fetch()
        return scores_h[:n], idx_h[:n]
    return finish


def _users_topk_serve_sharded_begin(model: "ALSModel",
                                    user_ixs: np.ndarray, dims: dict):
    """The sharded serve route of :func:`users_topk_serve`: query
    vectors gathered from the USER table's host shard mirrors (the
    user table needs no serving HBM at all), the item table resident
    model-sharded, ranking via per-shard top-k + cross-shard merge
    (ops/topk.batched_sharded_top_k) dispatched through the AOT
    registry under the same ``batch_predict`` label — warmed sharded
    buckets run zero trace / zero compile, exactly like replicated
    ones. Returns a ``finish() -> (scores, idx)`` readback callable
    (the two-phase pipelined contract of users_topk_serve_begin)."""
    from predictionio_tpu.compile.aot import precompile_next_rung
    from predictionio_tpu.obs import costmon
    from predictionio_tpu.ops.topk import batched_sharded_top_k_begin
    from predictionio_tpu.parallel.mesh import model_mesh
    from predictionio_tpu.parallel.sharded_table import table_rows
    from predictionio_tpu.utils.device_cache import note_table_rows
    V = model.item_factors
    mesh = model_mesh(V.n_shards)
    note_table_rows("item", model.n_items, dims["i"])
    n = user_ixs.shape[0]
    q = np.zeros((dims["b"], model.rank), dtype=np.float32)
    q[:n] = table_rows(model.user_factors, user_ixs)
    # a table padded below its covering sharded bucket (e.g. fresh
    # from training) uploads AT the bucket (zero-filled tail) and the
    # handle stays resident — the published model object is never
    # mutated from the serve path (real promotions are the fold
    # tick's job, where the host mirrors must follow)
    fetch = batched_sharded_top_k_begin(
        V.device(mesh, target_rows=dims["i"]), q, model.n_items,
        dims["k"], mesh, label=costmon.BATCH_PREDICT, dims=dims)
    precompile_next_rung(costmon.BATCH_PREDICT, dims, "i", model.n_items)

    def finish() -> Tuple[np.ndarray, np.ndarray]:
        scores, idx = fetch()
        return scores[:n], idx[:n]
    return finish


@functools.partial(__import__("jax").jit, static_argnames=("k",))
def _topk_scores(user_vecs, item_factors, seen_mask, k: int):
    """scores = u . V^T with seen items masked out; returns (scores, idx)."""
    import jax.numpy as jnp
    scores = jnp.einsum("br,ir->bi", user_vecs, item_factors,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(seen_mask, -jnp.inf, scores)
    import jax
    return jax.lax.top_k(scores, k)


def recommend_products(model: ALSModel, user_ix: int, k: int,
                       exclude: Optional[np.ndarray] = None
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k items for one user (MatrixFactorizationModel.recommendProducts
    analog). Returns (scores, item_indices). The item-factor table is
    device-cached — only the query row and mask move per call."""
    from predictionio_tpu.utils.device_cache import cached_put
    k_eff = min(k, model.n_items)
    scores, idx = _user_topk(
        cached_put(model.user_factors), cached_put(model.item_factors),
        np.int32(user_ix),
        _pad_exclude(exclude if exclude is not None else ()), k_eff)
    return np.asarray(scores), np.asarray(idx)


def recommend_products_sharded(model: ALSModel, user_ix: int, k: int,
                               mesh: Optional[MeshContext] = None,
                               exclude: Optional[np.ndarray] = None,
                               allowed_mask: Optional[np.ndarray] = None
                               ) -> Tuple[np.ndarray, np.ndarray]:
    """Serve-time top-k with BOTH factor tables kept model-sharded on the
    mesh — the P-model serve path for tables larger than one device's HBM
    (reference: controller/PAlgorithm.scala:44-125's distributed-model
    query; MLlib-side analog examples/scala-parallel-similarproduct/multi/
    src/main/scala/ALSAlgorithm.scala:146-190). The user row is gathered
    across shards by GSPMD; scoring + ranking run as a two-phase sharded
    top-k over ICI (ops/topk.sharded_top_k). Nothing is ever replicated."""
    import jax
    from predictionio_tpu.ops.topk import sharded_top_k
    from predictionio_tpu.parallel.sharded_table import is_sharded
    from predictionio_tpu.utils.device_cache import cached_put_padded

    from predictionio_tpu.utils.device_cache import cached_put

    if mesh is None and is_sharded(model.item_factors):
        from predictionio_tpu.parallel.mesh import model_mesh
        mesh = model_mesh(model.item_factors.n_shards)
    mesh = mesh or current_mesh()
    mp = mesh.model_parallelism
    sh = mesh.model_sharded(2)
    mask_sh = mesh.sharding(mesh.MODEL_AXIS)

    def _dev(table):
        # a ShardedTable already owns a resident sharded device copy
        return table.device(mesh) if is_sharded(table) \
            else cached_put_padded(table, sh, mp)

    U = _dev(model.user_factors)
    V = _dev(model.item_factors)
    has_filter = (allowed_mask is not None or
                  (exclude is not None and len(np.atleast_1d(exclude))))
    if not has_filter:
        # the padding-only mask is a pure function of (table, mp): keep it
        # alive on the model so cached_put keeps it device-resident — no
        # per-query H2D on the latency-sensitive serve path
        base = getattr(model, "_serve_mask", None)
        if base is None or base.shape[0] != V.shape[0]:
            base = np.ones(V.shape[0], dtype=bool)
            base[model.n_items:] = False
            model._serve_mask = base
        mask_dev = cached_put(base, mask_sh)
    else:
        mask = np.zeros(V.shape[0], dtype=bool)
        mask[:model.n_items] = (True if allowed_mask is None
                                else allowed_mask[:model.n_items])
        if exclude is not None and len(np.atleast_1d(exclude)):
            mask[np.asarray(exclude, dtype=np.int64)] = False
        mask_dev = jax.device_put(mask, mask_sh)
    u = _row_of(U, np.int32(user_ix))     # cross-shard gather -> replicated
    k_eff = min(k, model.n_items)
    scores, idx = sharded_top_k(V, u, k_eff, mesh,
                                allowed_mask_sharded=mask_dev)
    return scores[:k_eff], idx[:k_eff]


@functools.partial(__import__("jax").jit)
def _row_of(table, ix):
    return table[ix]


def predict_ratings(model: ALSModel, user_ix: np.ndarray,
                    item_ix: np.ndarray, chunk: int = 1 << 20) -> np.ndarray:
    """Pointwise r_hat = u . v for parallel (user, item) index arrays."""
    import jax.numpy as jnp
    import jax

    from predictionio_tpu.parallel.sharded_table import (is_sharded,
                                                         table_rows)
    if is_sharded(model.user_factors) or is_sharded(model.item_factors):
        # sharded tables: row gathers run against the host shard
        # mirrors (O(pairs * rank) host flops — the fold-tick loss
        # probe's pairs are the touched histories, not the corpus) so
        # the loss never forces a device gather of a replicated table
        out = np.empty(len(user_ix), dtype=np.float32)
        for lo in range(0, len(user_ix), chunk):
            sl = slice(lo, lo + chunk)
            out[sl] = np.sum(
                table_rows(model.user_factors, user_ix[sl])
                * table_rows(model.item_factors, item_ix[sl]), axis=-1)
        return out

    @jax.jit
    def _dot(U, V, ui, ii):
        return jnp.sum(U[ui] * V[ii], axis=-1)

    from predictionio_tpu.utils.device_cache import cached_put
    U = cached_put(model.user_factors)
    V = cached_put(model.item_factors)
    out = np.empty(len(user_ix), dtype=np.float32)
    for lo in range(0, len(user_ix), chunk):
        sl = slice(lo, lo + chunk)
        out[sl] = np.asarray(_dot(U, V, np.asarray(user_ix[sl]),
                                  np.asarray(item_ix[sl])))
    return out


def als_rmse(model: ALSModel, ratings: RatingsCOO) -> float:
    pred = predict_ratings(model, ratings.user_idx, ratings.item_idx)
    return float(np.sqrt(np.mean((pred - ratings.rating) ** 2)))

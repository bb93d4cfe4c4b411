"""Batched SPD solves for the normal-equation sweeps.

The ALS normal matrix A = Gram + lam*n*I arrives pre-regularized — its
condition number is bounded by ~rank*E[v^2]/lam — so conjugate gradient,
whose only primitive is multiply-accumulate, converges to f32 working
precision in a bounded number of iterations. One path per platform
(`resolve_solver`):

  one TPU   `cg_solve_pallas`: batched CG in a Pallas kernel, grid over
            16-entity tiles whose [16, R, R] systems stay VMEM-resident
            for every iteration (HBM reads A exactly once); a tile stops
            when its systems have converged, under an iteration cap
            (`_cg_kernel`; PERF.md, PR 28, has the chip's timings).
            Each chip of a mesh over row-sharded tables runs it too: the
            half-sweep is written per chip there (ops/als
            `_solve_sweep_per_chip`) and the solver sees local operands.
  TPU mesh  `cg_solve`: the same iteration in jnp, for a sweep that GSPMD
            partitions (replicated tables, batches over the data axis),
            where pallas_call cannot take sharded operands; also what
            ops/als gives systems under 32 wide on one TPU.
  CPU       `cholesky_solve`: LAPACK-style factorize-and-substitute, and
            the tests' numerical reference.

Replaces the `choleskyDecomposition.solve` step of MLlib ALS
(reference consumer: examples/scala-parallel-recommendation/custom-prepartor/
src/main/scala/ALSAlgorithm.scala:55 `ALS.train` -> mllib
NNLS/CholeskySolver).
"""

from __future__ import annotations

import functools

import numpy as np


def _kernel_name(kind: str, system: str, A) -> str:
    """What a device trace calls one Pallas solve: the solver, whether
    its systems are a sweep's primal (R x R) or dual (K x K) ones, and
    their static size, e.g. ``pio_cg_dual_b11920_n176``."""
    return f"pio_{kind}_{system}_b{A.shape[0]}_n{A.shape[-1]}"


def cholesky_solve(A, b):
    """LAPACK-style direct solve — the CPU path and the numerical
    reference."""
    import jax
    chol = jax.lax.linalg.cholesky(A)
    x = jax.lax.linalg.triangular_solve(chol, b[..., None], left_side=True,
                                        lower=True)
    return jax.lax.linalg.triangular_solve(
        chol, x, left_side=True, lower=True, transpose_a=True)[..., 0]


def cg_solve(A, b, iters: int = 48):
    """Batched Jacobi-preconditioned conjugate gradient on SPD A [B,R,R] —
    pure jnp reference (and the GSPMD-mesh path, where pallas_call can't
    take sharded operands). The ALS normal matrix's per-entity regularizer
    lam*n*I plus its dominant diagonal keep the *preconditioned* condition
    number small, so a fixed iteration count converges to f32 working
    precision; adversarial spectra need iters ~ sqrt(cond)*ln(1/eps)
    (tests/test_solve.py covers both)."""
    import jax
    import jax.numpy as jnp

    dinv = 1.0 / jnp.maximum(
        jnp.diagonal(A, axis1=-2, axis2=-1), 1e-30)        # Jacobi M^-1
    x = jnp.zeros_like(b)
    r = b
    z = dinv * r
    p = z
    rz = jnp.sum(r * z, axis=1)

    def body(_, c):
        x, r, p, rz = c
        Ap = jnp.einsum("brs,bs->br", A, p,
                        preferred_element_type=jnp.float32)
        alpha = rz / jnp.maximum(jnp.sum(p * Ap, axis=1), 1e-30)
        x = x + alpha[:, None] * p
        r = r - alpha[:, None] * Ap
        z = dinv * r
        rz2 = jnp.sum(r * z, axis=1)
        p = z + (rz2 / jnp.maximum(rz, 1e-30))[:, None] * p
        return (x, r, p, rz2)

    x, *_ = jax.lax.fori_loop(0, iters, body, (x, r, p, rz))
    return x


#: Iterations the Pallas CG kernel runs between two looks at its
#: residuals. A look is one reduction of the tile to a scalar and a branch
#: on it; a tile runs on average half a block past the iteration at which
#: its slowest system converged (PERF.md, PR 28, has the chip's timing of
#: 4 against 8).
_CG_BLOCK = 4

#: The Pallas CG kernel leaves when every system of its tile has brought
#: the preconditioned residual r^T M^-1 r under _CG_TOL^2 times what it
#: started from (or when its budget ends). One rule for every caller, set
#: from a study through the interpreter on the kernel itself (PERF.md,
#: PR 28): of a half-decade grid the loosest value at which every probed
#: rung of ALS systems as ops/als builds them (explicit dual K 32-176 and
#: primal K 208-5,120, the implicit eig-SMW dual and primal, before each
#: of three iterations) ended within 1e-5 of a float64 solve of the same
#: float32 system, or where the whole budget does not reach that (the
#: explicit primal rungs under K 320: 3e-5 to 9e-5 at their 48) within
#: what the whole budget reaches. 3e-7 left four rungs up to 1.3 times
#: over; the residual stands that far under the error because the error
#: is the residual through A^-1, up to sqrt(cond) times larger.
_CG_TOL = 1e-7


def _cg_kernel(a_ref, b_ref, x_ref, n_ref, *, iters: int,
               tol: float = _CG_TOL, block: int = _CG_BLOCK):
    """Per-tile Jacobi-PCG: A stays VMEM-resident for every iteration; the
    matvec contracts over the sublane axis (A is symmetric, so A[t,s,:]
    rows serve as columns), which reduces to cheap vreg adds instead of
    cross-lane shuffles.

    `iters` is the cap. Between blocks of `block` iterations the tile
    leaves once none of its systems has r^T M^-1 r above tol^2 times its
    first value: a system that needs the whole budget gets it, and so do
    the tile's others beside it (their extra passes move them by less
    than their rounding, as the whole fixed budget did). A system with
    b = 0 (a batch's padding) starts at 0 and holds nothing back.
    `n_ref` takes the iterations the tile ran."""
    import jax
    import jax.numpy as jnp

    A = a_ref[:]
    bb = b_ref[:]
    rank = A.shape[-1]
    eye = jnp.eye(rank, dtype=jnp.float32)[None]
    dinv = 1.0 / jnp.maximum(jnp.sum(A * eye, axis=1), 1e-30)

    def mv(p):
        return jnp.sum(A * p[:, :, None], axis=1)

    x = jnp.zeros_like(bb)
    r = bb
    z = dinv * r
    p = z
    rz = jnp.sum(r * z, axis=1)
    enough = (tol * tol) * rz

    def unconverged(rz):
        return jnp.max(jnp.where(rz > enough, 1, 0)) > 0

    def step(_, c):
        x, r, p, rz = c
        Ap = mv(p)
        alpha = rz / jnp.maximum(jnp.sum(p * Ap, axis=1), 1e-30)
        x = x + alpha[:, None] * p
        r = r - alpha[:, None] * Ap
        z = dinv * r
        rz2 = jnp.sum(r * z, axis=1)
        p = z + (rz2 / jnp.maximum(rz, 1e-30))[:, None] * p
        return (x, r, p, rz2)

    def go_on(c):
        done, live = c[:2]
        return jnp.logical_and(done < iters, live)

    def run_block(c):
        done, _, *state = c
        n = jnp.minimum(block, iters - done)
        state = jax.lax.fori_loop(0, n, step, tuple(state))
        return (done + n, unconverged(state[-1]), *state)

    done, _, x, *_ = jax.lax.while_loop(
        go_on, run_block, (jnp.int32(0), unconverged(rz), x, r, p, rz))
    x_ref[:] = x
    n_ref[:] = jnp.full(n_ref.shape, done, jnp.int32)


def cg_solve_pallas(A, b, iters: int = 48, tile: int = 16,
                    system: str = "primal", interpret: bool = False):
    """TPU production solver: grid over batch tiles of 16 entities, each
    tile's [16, R, R] systems VMEM-resident across its CG iterations, of
    which `iters` is the cap: a tile stops when its systems have converged
    (`_cg_kernel`). Returns the solutions and float32 [2]: the iterations
    the tiles ran and the iterations `iters` allowed them, each times the
    tile's systems (the batch's padding among them)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, rank = A.shape[0], A.shape[-1]
    # pad the batch UP to a full tile (never shrink the tile: sub-8 batch
    # dims produce vector shapes Mosaic can't reduce over)
    if B % tile != 0:
        pad = tile - B % tile
        A = jnp.concatenate(
            [A, jnp.broadcast_to(jnp.eye(rank, dtype=A.dtype),
                                 (pad, rank, rank))], axis=0)
        b = jnp.concatenate([b, jnp.zeros((pad, rank), b.dtype)], axis=0)
    tiles = A.shape[0] // tile
    kernel = functools.partial(_cg_kernel, iters=iters)
    x, ran = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((A.shape[0], rank), jnp.float32),
                   jax.ShapeDtypeStruct((tiles, 1, 128), jnp.int32)),
        grid=(tiles,),
        in_specs=[
            pl.BlockSpec((tile, rank, rank), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, rank), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(pl.BlockSpec((tile, rank), lambda i: (i, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((1, 1, 128), lambda i: (i, 0, 0),
                                memory_space=pltpu.VMEM)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name=_kernel_name("cg", system, A),
    )(A.astype(jnp.float32), b)
    return x[:B], tile * jnp.stack(
        [ran[:, 0, 0].sum(), iters * tiles]).astype(jnp.float32)


#: Every solver name a config may carry: `resolve_solver` alone reads it.
_SOLVERS = ("auto", "cholesky", "cg", "cg_pallas")


def resolve_solver(method: str, n_devices: int = 1) -> str:
    """The one place that knows the solver names (`_SOLVERS`): any other
    is a ValueError here, before a plan is built or a program traced.
    'auto' -> the platform's method: CG on TPU (Pallas where the solver
    sees one device's operands, `n_devices` 1: a single device, or a chip
    of ops/als's per-chip sweep, `sweep_solver`; the jnp formulation where
    GSPMD partitions the sweep over `n_devices`, since pallas_call can't
    consume sharded operands), cholesky on CPU/GPU (LAPACK/cuSOLVER are
    fine there)."""
    if method not in _SOLVERS:
        raise ValueError(f"unknown solver {method!r}: one of "
                         + " | ".join(_SOLVERS))
    if method != "auto":
        return method
    import jax
    if jax.default_backend() == "tpu":
        return "cg_pallas" if n_devices == 1 else "cg"
    return "cholesky"


def no_cg_iterations():
    """`spd_solve`'s count for systems that no counting solver solved."""
    import jax.numpy as jnp
    return jnp.zeros((2,), jnp.float32)


def spd_solve(A, b, method: str = "auto", iters: int | None = None,
              system: str = "primal"):
    """Batched SPD solve by `resolve_solver(method)`. Returns the
    solutions and float32 [2]: the CG iterations these systems ran and the
    iterations their budget allowed, each summed over the systems. Only
    'cg_pallas' stops early and counts; the others report (0, 0).

    iters:  the CG methods' budget. 'cg' runs all of it; for 'cg_pallas'
            it is the cap (a tile stops once its systems have converged).
    system: 'primal' | 'dual', which of a sweep's systems these are:
            only names the Pallas kernel in a device trace.
    """
    method = resolve_solver(method)
    counted = no_cg_iterations()
    if method == "cholesky":
        x = cholesky_solve(A, b)
    elif method == "cg":
        x = cg_solve(A, b, iters or 48)
    else:
        x, counted = cg_solve_pallas(A, b, iters or 48, system=system)
    return x, counted

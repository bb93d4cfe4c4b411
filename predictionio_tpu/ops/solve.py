"""Batched SPD solves for the normal-equation sweeps — the MXU-native
replacement for factorize-and-substitute.

Why not Cholesky: XLA's TPU cholesky + triangular_solve on batched
[B, rank, rank] systems runs at ~0.05% MXU utilization (measured: ~9.3 s
of a 9.8 s ML-20M ALS iteration; see docs/benchmarks.md). Iterative
methods whose only primitive is multiply-accumulate map to the hardware
instead, and the ALS normal matrix A = Gram + lam*n*I arrives
pre-regularized — its condition number is bounded by
~rank*E[v^2]/lam — so a bounded number of iterations converges to f32
working precision.

Production path (TPU): batched conjugate gradient in a Pallas kernel,
grid over 16-entity tiles whose [16, R, R] systems stay VMEM-resident for
every iteration (HBM reads A exactly once); a tile stops when its systems
have converged, under an iteration cap (`_cg_kernel`; PERF.md, PR 28,
has the chip's timings).

Also provided: the Schulz/Hotelling–Bodewig inverse iteration
X_{k+1} = X_k(2I - A X_k) (pure batched MXU matmuls, bf16-safe because
self-correcting, plus two f32 refinement steps) in jnp and Pallas forms —
slower than CG here (~35 ms) but useful where an explicit inverse or a
matmul-only formulation is wanted — and LAPACK-style `cholesky_solve`,
the CPU path and numerical reference.

`spd_solve` picks per backend: cholesky on CPU, CG-Pallas on TPU, jnp CG
under GSPMD meshes.

Replaces the `choleskyDecomposition.solve` step of MLlib ALS
(reference consumer: examples/scala-parallel-recommendation/custom-prepartor/
src/main/scala/ALSAlgorithm.scala:55 `ALS.train` -> mllib
NNLS/CholeskySolver).
"""

from __future__ import annotations

import functools

import numpy as np


def _schulz_iters_default(rank: int) -> int:
    # quadratic convergence: error after k steps ~ (1 - 1/kappa)^(2^k);
    # 18 doublings resolve kappa ~ 1e4 to f32 eps with margin
    return 18


def schulz_solve(A, b, iters: int | None = None, compute_dtype="bfloat16"):
    """Solve A x = b for batched SPD A [B, R, R], b [B, R] by Schulz
    iteration. Pure jnp — runs on any backend, used as the Pallas
    kernel's correctness reference."""
    import jax
    import jax.numpy as jnp

    rank = A.shape[-1]
    iters = iters or _schulz_iters_default(rank)
    cd = jnp.dtype(compute_dtype)
    alpha = 1.0 / jnp.maximum(
        jnp.max(jnp.sum(jnp.abs(A), axis=-1), axis=-1), 1e-30)   # 1/||A||_inf
    eye = jnp.eye(rank, dtype=jnp.float32)
    X = alpha[:, None, None] * eye

    def body(_, X):
        Y = jnp.einsum("brs,bst->brt", A.astype(cd), X.astype(cd),
                       preferred_element_type=jnp.float32)
        return 2.0 * X - jnp.einsum("brs,bst->brt", X.astype(cd),
                                    Y.astype(cd),
                                    preferred_element_type=jnp.float32)

    X = jax.lax.fori_loop(0, iters, body, X)
    x = jnp.einsum("brs,bs->br", X, b, preferred_element_type=jnp.float32)
    # two f32 iterative-refinement steps: with X ~ A^-1 to epsilon_it, each
    # step multiplies the solution error by epsilon_it — recovers near-f32
    # solutions even when the iterate converged in bf16
    for _ in range(2):
        r = b - jnp.einsum("brs,bs->br", A, x,
                           preferred_element_type=jnp.float32)
        x = x + jnp.einsum("brs,bs->br", X, r,
                           preferred_element_type=jnp.float32)
    return x


def _schulz_kernel(a_ref, b_ref, x_ref, *, iters: int, compute_dtype):
    import jax
    import jax.numpy as jnp

    A = a_ref[:]                                   # [BT, R, R] f32, VMEM
    rank = A.shape[-1]
    cd = jnp.dtype(compute_dtype)
    alpha = 1.0 / jnp.maximum(
        jnp.max(jnp.sum(jnp.abs(A), axis=-1), axis=-1), 1e-30)
    eye = jnp.eye(rank, dtype=jnp.float32)[None]
    X = alpha[:, None, None] * eye
    Abf = A.astype(cd)
    bmm = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    def body(_, X):
        Y = bmm(Abf, X.astype(cd))
        return 2.0 * X - bmm(X.astype(cd), Y.astype(cd))

    X = jax.lax.fori_loop(0, iters, body, X)
    bvec = b_ref[:]
    bmv = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    x = bmv(X, bvec)
    for _ in range(2):   # f32 iterative refinement (see schulz_solve)
        x = x + bmv(X, bvec - bmv(A, x))
    x_ref[:] = x


def _kernel_name(kind: str, system: str, A) -> str:
    """What a device trace calls one Pallas solve: the solver, whether
    its systems are a sweep's primal (R x R) or dual (K x K) ones, and
    their static size, e.g. ``pio_cg_dual_b11920_n176``."""
    return f"pio_{kind}_{system}_b{A.shape[0]}_n{A.shape[-1]}"


def schulz_solve_pallas(A, b, iters: int | None = None,
                        compute_dtype="bfloat16", tile: int = 8,
                        system: str = "primal"):
    """TPU kernel: grid over batch tiles; each tile's inverse iterate lives
    in VMEM for all `iters` Schulz steps, so HBM traffic is one read of A +
    one write of x (vs one read/write of [B,R,R] per step for the XLA
    loop)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, rank = A.shape[0], A.shape[-1]
    iters = iters or _schulz_iters_default(rank)
    if B % tile != 0:
        pad = tile - B % tile
        A = jnp.concatenate(
            [A, jnp.broadcast_to(jnp.eye(rank, dtype=A.dtype),
                                 (pad, rank, rank))], axis=0)
        b = jnp.concatenate([b, jnp.zeros((pad, rank), b.dtype)], axis=0)
    nb = A.shape[0] // tile
    kernel = functools.partial(_schulz_kernel, iters=iters,
                               compute_dtype=compute_dtype)
    x = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((A.shape[0], rank), jnp.float32),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((tile, rank, rank), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, rank), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile, rank), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        name=_kernel_name("schulz", system, A),
    )(A.astype(jnp.float32), b)
    return x[:B]


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


def _blocked_cholesky_solve(A, b, panel: int = 8):
    """Batched blocked (right-looking) Cholesky + blocked substitution,
    written so every slice is static AND scatter-free: Mosaic's TPU
    lowering has no scatter, so instead of writing panels back into a
    full L, the Python panel loop keeps each panel's factors in lists
    (static slices recover any L block during substitution), per-column
    updates are where-masks over a traced broadcasted_iota (an eager
    jnp.arange would be captured as a kernel constant, which pallas_call
    rejects), and the trailing Schur update recurses on the shrinking
    submatrix rather than scattering into A. Flop layout per system:
    ~R^3/3 in trailing matmul updates (MXU) + 2R^2 substitution, vs CG's
    ~96 R^2 of cross-sublane VPU matvecs and Schulz's ~72 R^3 of
    matmuls. Used inside the Pallas tile kernel AND directly
    (interpret/CPU correctness path, GSPMD meshes as 'chol_blocked').

    A: [B, R, R] SPD (R % panel == 0 — wrappers pad), b: [B, R]."""
    import jax
    import jax.numpy as jnp

    B, R = b.shape
    PW = panel
    A = jnp.asarray(A, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    rank_in = R
    if R % PW:
        # pad to a whole panel with an identity block (decoupled rows
        # solve to 0) — without this, trailing rows would silently never
        # be factored. Outside-kernel path only: wrappers pre-pad before
        # pallas_call, so jnp.pad/jnp.eye never trace inside a kernel.
        pad = PW - R % PW
        A = (jnp.pad(A, ((0, 0), (0, pad), (0, pad)))
             + jnp.pad(jnp.eye(pad, dtype=jnp.float32),
                       ((rank_in, 0), (rank_in, 0)))[None])
        b = jnp.pad(b, ((0, 0), (0, pad)))
        R = R + pad
    nP = R // PW
    # [1, PW] traced column ids — where-masks replace .at[] column sets
    cids = jax.lax.broadcasted_iota(jnp.int32, (1, PW), 1)
    L11s, L21s = [], []
    Atr = A                                    # trailing [B, M, M]
    for p in range(nP):
        A11 = Atr[:, :PW, :PW]                 # [B, PW, PW]
        # unblocked factor of the diagonal block (PW static steps)
        L11 = jnp.zeros_like(A11)
        for c in range(PW):
            d = jnp.sqrt(jnp.maximum(A11[:, c, c], 1e-30))
            col = A11[:, :, c] / d[:, None]    # [B, PW]
            col = jnp.where(cids >= c, col, 0.0)   # lower part only
            L11 = jnp.where((cids == c).reshape(1, 1, PW),
                            col[:, :, None], L11)
            A11 = A11 - col[:, :, None] * col[:, None, :]
        L11s.append(L11)
        if Atr.shape[1] > PW:
            A21 = Atr[:, PW:, :PW]             # [B, M, PW]
            # L21 L11^T = A21: forward substitution, PW static steps
            L21 = jnp.zeros_like(A21)
            for c in range(PW):
                acc = A21[:, :, c]
                for k in range(c):
                    acc = acc - L21[:, :, k] * L11[:, c, k][:, None]
                L21 = jnp.where((cids == c).reshape(1, 1, PW),
                                (acc / L11[:, c, c][:, None])[:, :, None],
                                L21)
            L21s.append(L21)
            # trailing syrk — the MXU step: A22 -= L21 @ L21^T
            upd = jnp.einsum("bmk,bnk->bmn", L21, L21,
                             preferred_element_type=jnp.float32)
            Atr = Atr[:, PW:, PW:] - upd
        else:
            L21s.append(None)

    def _l_block(p, q):
        # L[lo_p:hi_p, lo_q:hi_q] for p > q, recovered from panel q's
        # below-diagonal strip (its row 0 is global row hi_q)
        o = (p - q - 1) * PW
        return L21s[q][:, o:o + PW, :]

    # blocked forward substitution: L y = b
    ys = []
    for p in range(nP):
        rhs = b[:, p * PW:(p + 1) * PW]
        for q in range(p):
            rhs = rhs - jnp.einsum("bmk,bk->bm", _l_block(p, q), ys[q],
                                   preferred_element_type=jnp.float32)
        L11 = L11s[p]
        yp = jnp.zeros_like(rhs)
        for c in range(PW):
            acc = rhs[:, c]
            for k in range(c):
                acc = acc - L11[:, c, k] * yp[:, k]
            yp = jnp.where(cids == c, (acc / L11[:, c, c])[:, None], yp)
        ys.append(yp)
    # blocked back substitution: L^T x = y
    xs = [None] * nP
    for p in reversed(range(nP)):
        rhs = ys[p]
        for q in range(p + 1, nP):
            rhs = rhs - jnp.einsum("bkm,bk->bm", _l_block(q, p), xs[q],
                                   preferred_element_type=jnp.float32)
        L11 = L11s[p]
        xp = jnp.zeros_like(rhs)
        for c in reversed(range(PW)):
            acc = rhs[:, c]
            for k in range(c + 1, PW):
                acc = acc - L11[:, k, c] * xp[:, k]
            xp = jnp.where(cids == c, (acc / L11[:, c, c])[:, None], xp)
        xs[p] = xp
    # assemble [B, R] from panels with iota-built selector matmuls
    # (concatenate on a non-lane-aligned minor dim is exactly what
    # Mosaic dislikes; a [PW, R] one-hot embed is a cheap MXU op and
    # fully traced)
    x = jnp.zeros_like(b)
    rows = jax.lax.broadcasted_iota(jnp.int32, (PW, R), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (PW, R), 1)
    for p in range(nP):
        sel = (rows + p * PW == cols).astype(jnp.float32)   # [PW, R]
        x = x + jnp.einsum("bp,pr->br", xs[p], sel,
                           preferred_element_type=jnp.float32)
    return x[:, :rank_in]


def _chol_kernel(a_ref, b_ref, x_ref, *, panel: int):
    x_ref[:] = _blocked_cholesky_solve(a_ref[:], b_ref[:], panel)


def cholesky_solve_pallas(A, b, tile: int = 8, panel: int = 8,
                          interpret: bool = False,
                          system: str = "primal"):
    """MXU-packed panel factorization: grid over batch tiles; each tile's
    [tile, R, R] systems are factorized in VMEM with panel-width trailing
    updates as batched matmuls (the MXU share grows as R^3/3 while the
    sequential column work stays R^2-ish). The candidate replacement for
    CG on the dense (K >= rank) ALS buckets, whose cross-sublane matvecs
    bound the VPU path (docs/benchmarks.md)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, rank = A.shape[0], A.shape[-1]
    if rank % panel:
        pad = panel - rank % panel
        R2 = rank + pad
        Ap = jnp.zeros((B, R2, R2), A.dtype)
        Ap = Ap.at[:, :rank, :rank].set(A)
        Ap = Ap.at[:, rank:, rank:].set(jnp.eye(pad, dtype=A.dtype))
        A = Ap
        b = jnp.concatenate([b, jnp.zeros((B, pad), b.dtype)], axis=1)
    R2 = A.shape[-1]
    if B % tile != 0:
        padb = tile - B % tile
        A = jnp.concatenate(
            [A, jnp.broadcast_to(jnp.eye(R2, dtype=A.dtype),
                                 (padb, R2, R2))], axis=0)
        b = jnp.concatenate([b, jnp.zeros((padb, R2), b.dtype)], axis=0)
    kernel = functools.partial(_chol_kernel, panel=panel)
    x = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((A.shape[0], R2), jnp.float32),
        grid=(A.shape[0] // tile,),
        in_specs=[
            pl.BlockSpec((tile, R2, R2), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, R2), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile, R2), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name=_kernel_name("chol", system, A),
    )(A.astype(jnp.float32), b)
    return x[:B, :rank]


def resolve_solver(method: str, n_devices: int = 1) -> str:
    """'auto' -> concrete method: CG on TPU (Pallas single-device; the jnp
    formulation under GSPMD meshes, where pallas_call can't consume sharded
    operands), cholesky on CPU/GPU (LAPACK/cuSOLVER are fine there)."""
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
              compute_dtype: str = "bfloat16", system: str = "primal"):
    """Batched SPD solve with backend-appropriate method selection.
    Returns the solutions and float32 [2]: the CG iterations these systems
    ran and the iterations their budget allowed, each summed over the
    systems. Only 'cg_pallas' stops early and counts; every other method
    reports (0, 0).

    method: 'auto' | 'cholesky' | 'cg' | 'cg_pallas' | 'schulz' |
            'schulz_pallas'
    iters:  the CG methods' budget. 'cg' runs all of it; for 'cg_pallas'
            it is the cap (a tile stops once its systems have converged).
    system: 'primal' | 'dual', which of a sweep's systems these are:
            only names the Pallas kernels in a device trace.
    """
    if method == "auto":
        method = resolve_solver(method)
    counted = no_cg_iterations()
    if method == "cholesky":
        x = cholesky_solve(A, b)
    elif method == "cg":
        x = cg_solve(A, b, iters or 48)
    elif method == "cg_pallas":
        x, counted = cg_solve_pallas(A, b, iters or 48, system=system)
    elif method == "schulz":
        x = schulz_solve(A, b, iters, compute_dtype)
    elif method == "schulz_pallas":
        x = schulz_solve_pallas(A, b, iters, compute_dtype, system=system)
    elif method == "chol_pallas":
        x = cholesky_solve_pallas(A, b, system=system)
    elif method == "chol_blocked":   # jnp form (any backend / GSPMD meshes)
        x = _blocked_cholesky_solve(A, b)
    else:
        raise ValueError(f"unknown solver {method!r}")
    return x, counted

"""Plain reference of this configuration: implicit-feedback ALS (Hu, Koren &
Volinsky, "Collaborative Filtering for Implicit Feedback Datasets", ICDM
2008, as MLlib 1.3's `ALS.trainImplicit` runs it), each row the exact
minimiser of its own confidence-weighted least-squares problem. It imports
nothing of the program and takes nothing the program has made.

Per entity u with observed counterpart rows y_i, i in S_u, and summed event
counts r_ui:

    c_ui = 1 + alpha * |r_ui|        confidence
    p_ui = 1 if r_ui > 0 else 0      preference
    G    = sum over ALL counterpart rows of y y^T (`gram`)
    A_u  = G + sum_{i in S_u} (c_ui - 1) y_i y_i^T + reg_u I
    b_u  = sum_{i in S_u} c_ui p_ui y_i
    x_u  = A_u^-1 b_u                by a Cholesky of the full R x R A_u

No Woodbury identity, no eigenbasis, no iterative solver: A_u is formed as
written and factored. Two departures from the paper, both MLlib 1.3's and
both the program's: the regulariser is reg_u = lam * n_u with n_u = |S_u|
(`lambda_scaling` "nratings", ALS-WR's weighting; "constant" gives the
paper's lam), and a negative value (a "dislike" mapped to r < 0) adds its
confidence to A_u with preference 0, where the paper has no negative values.

jax.numpy in float32 with every product at `highest` precision (on a TPU a
float32 product is otherwise rounded to bfloat16); it runs wherever JAX
runs, in blocks that the comparison sizes.

The control of this configuration is this reference put in the program's
place at a lower precision: `round_operands` rounds a table through that
type before it is read (the operands of both Grams and of the right-hand
side; accumulation stays float32). It rounds on the host: inside a jitted
function XLA:TPU removes a float32 -> float8 -> float32 pair as excess
precision, and the control would be the reference itself."""

from __future__ import annotations

import numpy as np


def round_operands(table, precision: str) -> np.ndarray:
    """`table` rounded through `precision` (an ml_dtypes name), as float32,
    on the host."""
    import ml_dtypes
    return np.asarray(table, np.float32).astype(
        getattr(ml_dtypes, precision)).astype(np.float32)


def gram(table, rows: int | None = None, block: int = 1 << 16):
    """G = Y^T Y [R, R] over the first `rows` rows of `table` (all of them
    by default), summed block of rows by block of rows. One program for
    every block: the block's first row is an argument, and rows outside
    [lo, rows) (the last block is slid back to end inside the table) are
    taken as zero."""
    import jax
    import jax.numpy as jnp
    rows = int(table.shape[0]) if rows is None else int(rows)
    block = min(block, int(table.shape[0]))

    @jax.jit
    def add(G, table, lo):
        with jax.default_matmul_precision("highest"):
            start = jnp.minimum(lo, table.shape[0] - block)
            part = jax.lax.dynamic_slice_in_dim(table, start, block, 0)
            row = start + jnp.arange(block)
            part = jnp.where(((row >= lo) & (row < rows))[:, None],
                             part.astype(jnp.float32), 0.0)
            return G + jnp.einsum("ir,is->rs", part, part)

    G = jnp.zeros((table.shape[1],) * 2, jnp.float32)
    for lo in range(0, rows, block):
        G = add(G, table, jnp.int32(lo))
    return G


def solve_rows(counter_rows, counts, mask, gram, lam: float, alpha: float,
               lambda_scaling: str):
    """One block of a half-sweep. counter_rows [B, K, R]: the counterpart
    row of each of an entity's observations; counts, mask [B, K] (mask 0 on
    padding); gram [R, R] of the whole counterpart table. Returns x [B, R]
    with A_u x_u = b_u as the module's docstring writes them."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_solve
    with jax.default_matmul_precision("highest"):
        m = jnp.asarray(mask, jnp.float32)
        Y = jnp.asarray(counter_rows, jnp.float32) * m[..., None]
        r = jnp.asarray(counts, jnp.float32) * m
        n = m.sum(axis=-1)
        reg = (lam * jnp.maximum(n, 1.0) if lambda_scaling == "nratings"
               else jnp.full_like(n, lam))
        conf = 1.0 + alpha * jnp.abs(r)
        pref = (r > 0).astype(jnp.float32)
        A = (jnp.asarray(gram, jnp.float32)[None]
             + jnp.einsum("bk,bkr,bks->brs", (conf - 1.0) * m, Y, Y)
             + reg[:, None, None] * jnp.eye(Y.shape[-1], dtype=jnp.float32))
        b = jnp.einsum("bk,bkr->br", conf * pref * m, Y)
        return cho_solve((jnp.linalg.cholesky(A), True), b[..., None])[..., 0]

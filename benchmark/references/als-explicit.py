"""Plain reference of this configuration: explicit ALS-WR (MLlib 1.3 parity,
regulariser lam * n ratings), each row the exact minimiser of its own
least-squares problem by a Cholesky solve, and dot-product top-k ranking.
It imports nothing of the program and takes nothing the program has made.

The solve is jax.numpy in float32 with every product at `highest`
precision (on a TPU a float32 product is otherwise rounded to bfloat16),
and runs wherever JAX runs, in blocks that the comparison sizes. The
ranking is numpy float64 on the host.

The control of this configuration is this reference put in the program's
place at a lower precision: `round_operands` rounds a table through that
type before it is read (the operands of the Gram, the right-hand side and
the scoring product; accumulation stays float32 or float64). It rounds on
the host: inside a jitted function XLA:TPU removes a float32 -> float8 ->
float32 pair as excess precision, and the control would be the reference
itself."""

from __future__ import annotations

import numpy as np


def round_operands(table, precision: str) -> np.ndarray:
    """`table` rounded through `precision` (an ml_dtypes name), as float32,
    on the host."""
    import ml_dtypes
    return np.asarray(table, np.float32).astype(
        getattr(ml_dtypes, precision)).astype(np.float32)


def solve_rows(counter_rows, ratings, mask, lam: float, lambda_scaling: str):
    """One block of a half-sweep. counter_rows [B, K, R]: the counterpart
    row of each of an entity's ratings; ratings, mask [B, K] (mask 0 on
    padding). Returns x [B, R] with (G^T G + reg I) x = G^T r."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import cho_solve
    with jax.default_matmul_precision("highest"):
        m = jnp.asarray(mask, jnp.float32)
        G = jnp.asarray(counter_rows, jnp.float32) * m[..., None]
        r = jnp.asarray(ratings, jnp.float32) * m
        n = m.sum(axis=-1)
        reg = (lam * jnp.maximum(n, 1.0) if lambda_scaling == "nratings"
               else jnp.full_like(n, lam))
        A = jnp.einsum("bkr,bks->brs", G, G)
        A = A + reg[:, None, None] * jnp.eye(G.shape[-1], dtype=jnp.float32)
        b = jnp.einsum("bkr,bk->br", G, r)
        return cho_solve((jnp.linalg.cholesky(A), True), b[..., None])[..., 0]


def _operand(x, precision: str) -> np.ndarray:
    if precision in ("float32", "float64"):
        return np.asarray(x, np.float64)
    return round_operands(x, precision).astype(np.float64)


def top_k(user_rows, item_table, k: int, precision: str = "float32",
          block: int = 1 << 18):
    """(scores [Q, k], ids [Q, k]) of the k best items of each query row,
    best first, scanning the item table in blocks of rows."""
    u = _operand(user_rows, precision)
    q = u.shape[0]
    best_s = np.full((q, 0), -np.inf)
    best_i = np.zeros((q, 0), np.int64)
    for lo in range(0, item_table.shape[0], block):
        s = u @ _operand(item_table[lo:lo + block], precision).T
        kk = min(k, s.shape[1])
        part = np.argpartition(-s, kk - 1, axis=1)[:, :kk]
        best_s = np.concatenate(
            [best_s, np.take_along_axis(s, part, axis=1)], axis=1)
        best_i = np.concatenate([best_i, part + lo], axis=1)
        keep = np.argsort(-best_s, axis=1, kind="stable")[:, :k]
        best_s = np.take_along_axis(best_s, keep, axis=1)
        best_i = np.take_along_axis(best_i, keep, axis=1)
    return best_s, best_i


def scores_of(user_rows, item_table, ids) -> np.ndarray:
    """Exact float64 scores [Q, k] of the given item ids for each query."""
    u = np.asarray(user_rows, np.float64)
    rows = np.asarray(item_table[np.asarray(ids)], np.float64)   # [Q, k, R]
    return np.einsum("qr,qkr->qk", u, rows)

"""Operations and bytes the algorithm needs, from the data's degree
sequences and the rank alone. Nothing here reads the program's plan, so the
counts do not move when a PR changes bucketing, padding, batch sizes or the
solver."""

from __future__ import annotations

import numpy as np


def als_side_flops(degrees: np.ndarray, rank: int) -> float:
    """One half-sweep: Gram 2*n*R^2 and right-hand side 2*n*R per row with
    n ratings, and a direct solve of the smaller of the primal (R) and dual
    (n) systems, min(n, R)^3 / 3."""
    d = degrees[degrees > 0].astype(np.float64)
    nnz = d.sum()
    return float(2.0 * nnz * rank * rank + 2.0 * nnz * rank
                 + (np.minimum(d, rank) ** 3).sum() / 3.0)


def als_side_bytes(degrees: np.ndarray, rank: int,
                   factor_bytes: int = 4) -> float:
    """One half-sweep: one counterpart row read per rating, each rating's
    index and value read once, each solved row written once."""
    d = degrees[degrees > 0]
    nnz = float(d.sum())
    return (nnz * rank * factor_bytes + nnz * 8.0
            + float(d.size) * rank * factor_bytes)


def als_iteration_flops(user_degrees, item_degrees, rank: int) -> float:
    return (als_side_flops(np.asarray(user_degrees), rank)
            + als_side_flops(np.asarray(item_degrees), rank))


def als_iteration_bytes(user_degrees, item_degrees, rank: int,
                        factor_bytes: int = 4) -> float:
    return (als_side_bytes(np.asarray(user_degrees), rank, factor_bytes)
            + als_side_bytes(np.asarray(item_degrees), rank, factor_bytes))


def topk_query_flops(n_items: int, rank: int) -> float:
    """Scoring one query against every item."""
    return 2.0 * n_items * rank


def topk_dispatch_bytes(n_items: int, rank: int, batch: float,
                        factor_bytes: int = 4) -> float:
    """One batched dispatch: the item table scanned once, and one user row
    per query of the batch."""
    return (n_items + batch) * rank * float(factor_bytes)


def roofline_seconds(flops: float, nbytes: float, peaks: dict
                     ) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "hbm")

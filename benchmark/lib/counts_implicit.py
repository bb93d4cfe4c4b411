"""Operations and bytes implicit-feedback ALS needs, from the data's degree
sequences, the table sizes and the rank alone: the explicit half-sweep's
(benchmark/lib/counts.py: per observed pair the rank-one update of A_u and
of b_u, and a direct solve of the smaller system) plus the Gram every row
of the half-sweep shares, Y^T Y over all N counterpart rows, taken once.
The eigendecomposition of that R x R Gram is the program's way to share
it, not the algorithm's need, and is not counted. Nothing here reads the
program's plan."""

from __future__ import annotations

import numpy as np

from benchmark.lib import counts


def ials_side_flops(degrees: np.ndarray, n_counter: int, rank: int) -> float:
    """One half-sweep over entities of these degrees against a counterpart
    table of `n_counter` rows."""
    return counts.als_side_flops(degrees, rank) \
        + 2.0 * float(n_counter) * rank * rank


def ials_side_bytes(degrees: np.ndarray, n_counter: int, rank: int,
                    factor_bytes: int = 4) -> float:
    """The explicit half-sweep's bytes and the counterpart table read once
    for the Gram."""
    return counts.als_side_bytes(degrees, rank, factor_bytes) \
        + float(n_counter) * rank * factor_bytes


def ials_iteration_flops(user_degrees, item_degrees, rank: int) -> float:
    du, di = np.asarray(user_degrees), np.asarray(item_degrees)
    return (ials_side_flops(du, di.size, rank)
            + ials_side_flops(di, du.size, rank))


def ials_iteration_bytes(user_degrees, item_degrees, rank: int,
                         factor_bytes: int = 4) -> float:
    du, di = np.asarray(user_degrees), np.asarray(item_degrees)
    return (ials_side_bytes(du, di.size, rank, factor_bytes)
            + ials_side_bytes(di, du.size, rank, factor_bytes))

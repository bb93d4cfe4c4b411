"""Operations and bytes a filtered top-k dispatch needs, from the
configuration and the batch alone: the live rows of the table the route
scores against read once, the category array and the availability bitmap
read once, one query vector and one filter list per query, against the
scoring operations. Nothing here reads the program: the counts do not move
when a PR changes how the mask is composed, bucketed or padded."""

from __future__ import annotations


def query_flops(n_items: int, rank: int) -> float:
    """Scoring one query against every item (the mask's compares are not
    floating-point operations and are not counted)."""
    return 2.0 * n_items * rank


def dispatch_bytes(n_items: int, rank: int, batch: float,
                   category_slots: int = 1, listed_per_query: float = 0.0,
                   factor_bytes: int = 4) -> float:
    """One batched dispatch: the item table's live rows, an int32 category
    slot and one availability bit per item, each read once; per query its
    vector and its list entries (an int32 index each)."""
    return (n_items * (rank * float(factor_bytes) + 4.0 * category_slots
                       + 1.0 / 8.0)
            + batch * (rank * float(factor_bytes) + 4.0 * listed_per_query))

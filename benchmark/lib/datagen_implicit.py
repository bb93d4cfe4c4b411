"""Inputs of an implicit-feedback configuration from the seed: the distinct
(user, item) pairs of benchmark/lib/datagen.py, unedited, in the
configuration's degree sequences, each carrying the number of view events
the pair summed to: what the e-commerce template hands `ALS.trainImplicit`
once it has reduced its view events by key.

A pair's count is 1 + a geometric number of repeat views whose mean makes
the counts sum to the configuration's `n_events` (the published number of
`pv` rows) over its `n_ratings` pairs, capped at `assumed.count_cap`.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib import datagen


def view_counts(config: dict, seed: int, n_pairs: int) -> np.ndarray:
    """float32 counts >= 1 for `n_pairs` pairs, from the seed."""
    mean = float(config["n_events"]) / float(config["n_ratings"])
    rng = np.random.default_rng([int(seed), 3])
    counts = rng.geometric(1.0 / mean, n_pairs)
    np.minimum(counts, int(config["assumed"]["count_cap"]), out=counts)
    return counts.astype(np.float32)


def view_events(config: dict, seed: int
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(user_idx int32 sorted, item_idx int32, count float32): the pairs as
    datagen.ratings pairs them for this seed, the values replaced by view
    counts."""
    pairs = dict(config, assumed=dict(config["assumed"],
                                      rating_values=[1, 1]))
    user_idx, item_idx, _ones = datagen.ratings(pairs, seed)
    return user_idx, item_idx, view_counts(config, seed, user_idx.size)

"""Inputs from the seed: rating sets in a configuration's published counts,
and served factor tables.

The two degree sequences are a function of the configuration alone, so the
program's solve plan (bucket populations, batch shapes: the compile cache's
keys) is the same for every seed. The seed chooses which entity carries
which degree, the pairing of users with items, and the rating values.
Pairs are distinct: explicit ALS trains on deduplicated ratings, so a
duplicate would change the counts the configuration publishes.
"""

from __future__ import annotations

import threading

import numpy as np


def _scale_to_total(weights: np.ndarray, total: int, cap: int) -> np.ndarray:
    """Integer degrees proportional to `weights`, each in [1, cap], summing
    to `total` exactly."""
    n = weights.size
    if not n <= total <= n * cap:
        raise ValueError(f"{total} ratings cannot be spread over {n} "
                         f"entities with degrees in [1, {cap}]")
    w = weights / weights.sum()

    def degrees(scale):
        return np.clip(np.floor(scale * w), 1, cap)

    lo, hi = 0.0, float(total)
    while degrees(hi).sum() < total:
        hi *= 2.0
    for _ in range(48):
        mid = 0.5 * (lo + hi)
        if degrees(mid).sum() <= total:
            lo = mid
        else:
            hi = mid
    d = degrees(lo).astype(np.int64)
    short = int(total - d.sum())
    if short:
        # hand the remainder to the entities nearest their next integer
        frac = lo * w - np.floor(lo * w)
        frac[d >= cap] = -1.0
        order = np.lexsort((np.arange(n), -frac))   # ties by position
        d[order[:short]] += 1
    assert d.sum() == total and d.min() >= 1 and d.max() <= cap
    return d


def degree_sequences(config: dict) -> tuple[np.ndarray, np.ndarray]:
    """(user degrees, item degrees), descending, from the configuration's
    counts and its `assumed` distributions: lognormal user activity and
    Zipf item popularity with an offset, each capped at the dataset's known
    head."""
    a = config["assumed"]
    nnz = int(config["n_ratings"])
    # a fixed stream, not the run's seed: the sequence belongs to the
    # configuration
    z = np.random.default_rng(20140101).standard_normal(
        int(config["n_users"]))
    du = _scale_to_total(np.exp(a["user_activity_sigma"] * z), nnz,
                         int(a["user_degree_cap"]))
    ranks = np.arange(int(config["n_items"]), dtype=np.float64)
    di = _scale_to_total(
        (ranks + a["item_popularity_offset"])
        ** -a["item_popularity_exponent"], nnz, int(a["item_degree_cap"]))
    return np.sort(du)[::-1].copy(), np.sort(di)[::-1].copy()


def _repair_duplicates(key: np.ndarray, n_items: int,
                       rng: np.random.Generator) -> np.ndarray:
    """`key` = user * n_items + item, sorted. Re-pair the duplicate edges by
    swapping items with random other edges until every pair is distinct;
    both degree sequences are kept exactly."""
    dup = np.flatnonzero(key[1:] == key[:-1]) + 1
    if not dup.size:
        return key
    base = key.copy()            # stays sorted: the pairs as they were
    made = np.empty(0, np.int64)    # sorted: the pairs the repair has made
    for _ in range(4096):
        if not dup.size:
            return key
        partner = np.unique(rng.integers(0, key.size, dup.size))
        partner = partner[~np.isin(partner, dup)]
        m = min(dup.size, partner.size)
        d, p = dup[:m], rng.permutation(partner)[:m]
        ud, id_ = key[d] // n_items, key[d] % n_items
        up, ip = key[p] // n_items, key[p] % n_items
        new_d, new_p = ud * n_items + ip, up * n_items + id_
        both = np.concatenate([new_d, new_p])
        pos = np.searchsorted(base, both)
        taken = base[np.minimum(pos, base.size - 1)] == both
        taken |= np.isin(both, made)
        _, first, cnt = np.unique(both, return_index=True,
                                  return_counts=True)
        clash = np.ones(both.size, bool)
        clash[first[cnt == 1]] = False
        bad = taken | clash
        ok = ~(bad[:m] | bad[m:])
        key[d[ok]], key[p[ok]] = new_d[ok], new_p[ok]
        made = np.union1d(made, np.concatenate([new_d[ok], new_p[ok]]))
        dup = np.concatenate([d[~ok], dup[m:]])
    raise RuntimeError(f"{dup.size} duplicate pairs left after repair")


def ratings(config: dict, seed: int
            ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(user_idx int32, item_idx int32, rating float32): distinct pairs with
    exactly the configuration's degree sequences, paired by the seed."""
    du, di = degree_sequences(config)
    n_users, n_items = du.size, di.size
    rng = np.random.default_rng([int(seed), 1])
    users = np.repeat(rng.permutation(n_users).astype(np.int64), du)
    items = np.repeat(rng.permutation(n_items).astype(np.int32), di)
    rng.shuffle(items)
    key = users
    key *= n_items
    key += items
    del users, items
    key.sort()
    key = _repair_duplicates(key, n_items, rng)
    lo, hi = config["assumed"]["rating_values"]
    value = rng.integers(lo, hi + 1, key.size).astype(np.float32)
    return ((key // n_items).astype(np.int32),
            (key % n_items).astype(np.int32), value)


def init_table(rows: int, rank: int, seed: int, salt: int, sharding=None):
    """One float32 factor table [rows, rank] made on the device in one
    jitted call from the seed: |N(0,1)| / sqrt(rank), the distribution the
    program starts training from (ops/als._init_factors). The same call
    gives the same table to the job and, after the window, to the
    reference. `sharding` places it as the program places its own tables,
    so that the first half-sweep meets the arguments every later one
    does."""
    import jax
    import jax.numpy as jnp
    seed = int(seed)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), seed >> 31), salt)

    def make(key):
        return jnp.abs(jax.random.normal(key, (rows, rank), jnp.float32)) \
            * np.float32(1.0 / np.sqrt(rank))

    return jax.jit(make, out_shardings=sharding)(key)


def served_tables(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Host float32 factor tables for a serve cell, |N(0,1)|/sqrt(rank) as
    the program initialises them: random tables are enough for speed and
    for the comparison. Filled by a few threads (numpy releases the GIL)."""
    rank = int(config["rank"])
    out = []
    jobs = []
    for salt, n in ((1, int(config["n_users"])), (2, int(config["n_items"]))):
        table = np.empty((n, rank), np.float32)
        out.append(table)
        step = -(-n // 8)
        for part, lo in enumerate(range(0, n, step)):
            jobs.append((table[lo:lo + step], [int(seed), salt, part]))

    def fill(block, key):
        np.random.default_rng(key).standard_normal(
            block.shape, dtype=np.float32, out=block)
        np.abs(block, out=block)
        block *= np.float32(1.0 / np.sqrt(rank))

    threads = [threading.Thread(target=fill, args=j) for j in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out[0], out[1]

"""The served e-commerce deployment's inputs from the seed: the item ->
category map, the seen events and recent views the event store is populated
with, the unavailable list with its re-sets, the requests of the
serve-filtered mix and the check's probes after a re-set. numpy alone (the load generator's parent and the
harness's tests read it; nothing here needs JAX).

What belongs to the configuration and not to the seed is fixed for every
seed, so that every seed offers the same amount of work: the category sizes,
the degree of each position of the store's sample of users, the number of
requests of each kind. The seed chooses which item lies in which category,
which items a user has seen, what the lists hold and which request is of
which kind.
"""

from __future__ import annotations

import numpy as np

from benchmark.lib import datagen

KINDS = ("home", "category", "multi_category", "cart", "campaign",
         "new_visitor")


def category_sizes(config: dict) -> np.ndarray:
    """int64 [n_categories], descending, summing to n_items: offset-Zipf,
    the largest capped at `assumed.category_share_cap` of the catalogue."""
    a = config["assumed"]
    n = int(config["n_categories"])
    ranks = np.arange(n, dtype=np.float64)
    weights = (ranks + a["category_size_offset"]) ** -a[
        "category_size_exponent"]
    return datagen._scale_to_total(
        weights, int(config["n_items"]),
        int(a["category_share_cap"] * config["n_items"]))


def item_categories(config: dict, seed: int) -> np.ndarray:
    """int32 [n_items]: the one category each item lies in."""
    sizes = category_sizes(config)
    rng = np.random.default_rng([int(seed), 11])
    cat = np.repeat(np.arange(sizes.size, dtype=np.int32), sizes)
    rng.shuffle(cat)
    return cat


def items_by_category(cat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(item indices sorted by category, start of each category in them)."""
    order = np.argsort(cat, kind="stable").astype(np.int32)
    start = np.searchsorted(cat[order], np.arange(cat.max() + 2))
    return order, start


def _popularity_cdf(config: dict) -> np.ndarray:
    a = config["assumed"]
    ranks = np.arange(int(config["n_items"]), dtype=np.float64)
    w = (ranks + a["item_popularity_offset"]) ** -a[
        "item_popularity_exponent"]
    return np.cumsum(w / w.sum())


class Popularity:
    """Items drawn in proportion to the train configuration's offset-Zipf
    popularity; the seed decides which item holds which rank."""

    def __init__(self, config: dict, seed: int):
        self.cdf = _popularity_cdf(config)
        self.item_of_rank = np.random.default_rng(
            [int(seed), 12]).permutation(self.cdf.size).astype(np.int32)

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        rank = np.searchsorted(self.cdf, rng.random(n))
        return self.item_of_rank[np.minimum(rank, self.cdf.size - 1)]


def store_users(config: dict) -> np.ndarray:
    """The users whose events the store holds: one in `store_user_stride`
    (the configuration's one cut)."""
    return np.arange(0, int(config["n_users"]),
                     int(config["store_user_stride"]), dtype=np.int32)


def seen_pairs(config: dict, seed: int, popular: Popularity
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(user index, item index, bought) of the store's seen events, sorted
    by user: every pair is a `view`, and `bought` marks those that are a
    `buy` as well. A user's degree follows the train configuration's model
    (lognormal, scaled to `seen_mean` a user, capped); repeats of one item
    by one user are dropped, so a heavy user holds a few pairs fewer."""
    a = config["assumed"]
    users = store_users(config)
    z = np.random.default_rng(20171125).standard_normal(users.size)
    degree = datagen._scale_to_total(
        np.exp(a["user_activity_sigma"] * z),
        int(round(a["seen_mean"] * users.size)), int(a["user_degree_cap"]))
    rng = np.random.default_rng([int(seed), 13])
    degree = rng.permutation(degree)
    u = np.repeat(users.astype(np.int64), degree)
    i = popular.draw(rng, u.size)
    key = np.unique(u * int(config["n_items"]) + i)
    u = (key // int(config["n_items"])).astype(np.int32)
    i = (key % int(config["n_items"])).astype(np.int32)
    bought = rng.random(u.size) < (float(config["n_buys"])
                                   / float(config["n_views"]))
    return u, i, bought


def visitor_views(config: dict, mix: dict, seed: int, popular: Popularity
                  ) -> np.ndarray:
    """int32 [visitors, recent_views]: the recent views of each visitor
    the model has no row for (ids "v<j>"), distinct in a row."""
    rng = np.random.default_rng([int(seed), 14])
    n, k = int(mix["visitors"]), int(mix["recent_views"])
    out = np.empty((n, k), np.int32)
    for j in range(n):
        got = np.unique(popular.draw(rng, 2 * k))
        while got.size < k:
            got = np.unique(np.concatenate([got, popular.draw(rng, k)]))
        out[j] = rng.permutation(got)[:k]
    return out


def unavailable_versions(config: dict, seed: int, n_resets: int
                         ) -> list[np.ndarray]:
    """The unavailable list as first set and after each re-set, sorted int32
    arrays, uniform over the catalogue. A re-set replaces
    `unavailable_replaced` of it: the ids that leave are uniform over the
    list, those that enter uniform over the rest of the catalogue."""
    a = config["assumed"]
    n_items = int(config["n_items"])
    rng = np.random.default_rng([int(seed), 15])
    size = int(round(a["unavailable_share"] * n_items))
    swap = int(round(a["unavailable_replaced"] * size))
    versions = [np.sort(rng.choice(n_items, size, replace=False))]
    for _ in range(n_resets):
        now = versions[-1]
        stay = np.delete(now, rng.choice(now.size, swap, replace=False))
        fresh = np.zeros(0, np.int64)
        while fresh.size < swap:
            more = rng.integers(0, n_items, 2 * (swap - fresh.size))
            more = more[~np.isin(more, now) & ~np.isin(more, fresh)]
            fresh = np.concatenate([fresh, np.unique(more)])[:swap]
        versions.append(np.sort(np.concatenate([stay, fresh])))
    return [v.astype(np.int32) for v in versions]


def reset_probes(config: dict, seed: int, version: int, n: int,
                 old: np.ndarray, new: np.ndarray) -> list[dict]:
    """n requests for the check alone, sent once the re-set to list
    `version` is acknowledged: a known user asking with a whiteList of the
    ids that just became unavailable. Every candidate is out of stock, so
    the right answer is empty; a server whose bitmap is one re-set behind
    answers with ten of them. They give `filter_violations` its power
    against that fault: the mix's own requests, drawn as the shop's web
    tier sends them, meet one of a re-set's 832 uniform ids in 0.2% of
    answers."""
    rng = np.random.default_rng([int(seed), 17, int(version)])
    users = store_users(config)
    entered = np.setdiff1d(new, old).tolist()
    return [{"kind": "reset_probe",
             "user": int(users[rng.integers(users.size)]),
             "categories": [], "black": [], "white": entered}
            for _ in range(n)]


def kind_counts(mix: dict, n: int) -> np.ndarray:
    """Requests of each kind among n: the mix's shares, to the nearest
    request, the remainder to the first kind."""
    counts = np.array([int(round(mix["kinds"][k] * n)) for k in KINDS])
    counts[0] += n - counts.sum()
    return counts


def requests(config: dict, mix: dict, seed: int, n: int, salt: int,
             cat: np.ndarray, popular: Popularity) -> list[dict]:
    """n requests of the mix: {"kind", "user" (index into the model, or -1
    with "visitor"), "categories", "black", "white"} with item indices and
    category numbers; `body()` frames one."""
    rng = np.random.default_rng([int(seed), 16, int(salt)])
    kinds = rng.permutation(np.repeat(np.arange(len(KINDS)),
                                      kind_counts(mix, n)))
    users = store_users(config)
    sizes = category_sizes(config).astype(np.float64)
    order, start = items_by_category(cat)
    by_size = np.cumsum(sizes / sizes.sum())
    big = np.flatnonzero(sizes >= int(mix["whitelist_items"]))
    by_size_big = np.cumsum(sizes[big] / sizes[big].sum())
    out = []
    for kind in kinds:
        name = KINDS[kind]
        q = {"kind": name, "user": int(users[rng.integers(users.size)]),
             "categories": [], "black": [], "white": None}
        if name == "category":
            q["categories"] = [int(np.searchsorted(by_size, rng.random()))]
        elif name == "multi_category":
            got: set = set()
            while len(got) < int(mix["multi_categories"]):
                got.add(int(np.searchsorted(by_size, rng.random())))
            q["categories"] = sorted(got)
        elif name == "cart":
            q["black"] = np.unique(popular.draw(
                rng, int(mix["blacklist_items"]))).tolist()
        elif name == "campaign":
            c = int(big[np.searchsorted(by_size_big, rng.random())])
            members = order[start[c]:start[c + 1]]
            q["white"] = np.sort(rng.choice(
                members, int(mix["whitelist_items"]),
                replace=False)).tolist()
        elif name == "new_visitor":
            q["user"] = -1
            q["visitor"] = int(rng.integers(int(mix["visitors"])))
        out.append(q)
    return out


def body(q: dict, num: int) -> dict:
    """The JSON object of one request, ids and category names as the
    deployment spells them: entities "<index>", visitors "v<j>", categories
    "c<number>"."""
    d = {"user": (f"v{q['visitor']}" if q["user"] < 0 else str(q["user"])),
         "num": int(num)}
    if q["categories"]:
        d["categories"] = [f"c{c}" for c in q["categories"]]
    if q["black"]:
        d["blackList"] = [str(i) for i in q["black"]]
    if q["white"] is not None:
        d["whiteList"] = [str(i) for i in q["white"]]
    return d

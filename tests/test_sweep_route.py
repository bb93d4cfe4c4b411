"""The static routing of a solve plan over row-sharded tables (PR 35): which
chip owns which slot's counterpart row is a fact of the plan, so
`ops/als._upload_plan` works it out once on the host (`_route_group`) and a
scan step on the device is a plain gather of owned rows (`send`), one
all-to-all and a placement (`place`). Host-only here: the exchange is played
in numpy. The device's side is tests/test_sharded_train_reference.py."""

import hashlib

import numpy as np
import pytest

from predictionio_tpu.ops import als
from predictionio_tpu.ops.ratings import (RatingsCOO, plan_for_items,
                                          plan_for_users)


def _plan_like(seed, n_counter, n, b, k, empty=0.3, span=None):
    """(rows, idx, mask) of one batch group as a plan holds it: real slots a
    prefix of each system's K, padding `idx` 0; a share `empty` of the
    systems all padding (row -1); indices under `span` where given."""
    rng = np.random.default_rng(seed)
    count = rng.integers(1, k + 1, (n, b))
    count[rng.random((n, b)) < empty] = 0
    mask = (np.arange(k) < count[..., None]).astype(np.float32)
    idx = rng.integers(0, span or n_counter, (n, b, k))
    return (np.where(count > 0, 1, -1).astype(np.int32),
            (idx * mask).astype(np.int32), mask)


def _most(rows, blocks):
    """The most real systems any chip's block of a group holds."""
    n = rows.shape[0]
    return int((rows.reshape(n, blocks, -1) >= 0).sum(axis=-1).max())


def _exchanged(counter, place, send, rows_a_shard, table_shards):
    """What `_routed_rows` hands `_solve_gathered`, played on the host:
    every chip gathers `send` from its shard, the all-to-all over the
    table axis of its data row, `place` spreads what arrived."""
    n, blocks, _m, L = send.shape
    got = np.zeros((n, blocks, table_shards * L) + counter.shape[1:],
                   counter.dtype)
    for c in range(blocks):
        d, s = divmod(c, table_shards)
        owned = counter[s * rows_a_shard:(s + 1) * rows_a_shard][send[:, c]]
        for m in range(table_shards):
            got[:, d * table_shards + m, s * L:(s + 1) * L] = owned[:, m]
    b, k = place.shape[1:]
    at = place.reshape(n, blocks, -1)
    return np.take_along_axis(
        got, at.reshape(at.shape + (1,) * (counter.ndim - 1)),
        axis=2).reshape((n, b, k) + counter.shape[1:])


CASES = {
    # name: (table shards, data rows, counterpart entities, N, B a chip, K,
    #        share of empty systems, indices under)
    "four shards": (4, 1, 1000, 3, 16, 8, 0.3, None),
    "two shards": (2, 1, 1000, 3, 16, 24, 0.3, None),
    "a data axis of two": (2, 2, 1000, 2, 8, 24, 0.0, None),
    "all padding": (4, 1, 1000, 2, 4, 8, 1.0, None),
    "one shard owns everything": (4, 1, 1000, 2, 16, 8, 0.2, 200),
    "two shards own nothing": (4, 1, 1000, 2, 16, 40, 0.2, 450),
    "fewer rows than slots": (4, 1, 37, 2, 16, 8, 0.2, None),
    "one system a chip": (4, 1, 5000, 5, 1, 64, 0.0, None),
}


@pytest.mark.parametrize("case", CASES)
def test_send_exchange_place_is_the_gather_bit_for_bit(case):
    table_shards, data, n_counter, n, b_chip, k, empty, span = CASES[case]
    blocks = table_shards * data
    rows, idx, mask = _plan_like(len(case), n_counter, n, b_chip * blocks,
                                 k, empty, span)
    rows_a_shard = als.table_rows(n_counter, table_shards) // table_shards
    real = int(mask.sum())
    degree = real / max(int((rows >= 0).sum()), 1)
    place, send, said_real, room = als._route_group(
        _most(rows, blocks), idx, mask, degree, rows_a_shard, table_shards,
        blocks, False)
    L = send.shape[-1]
    assert place.shape == idx.shape and place.dtype == np.int32
    assert send.shape == (n, blocks, table_shards, L)
    assert send.dtype == np.int32 and L % 16 == 0
    assert 0 <= send.min() and send.max() < rows_a_shard
    assert 0 <= place.min() and place.max() < table_shards * L
    # `route_fill` is what was counted: real slots over the room made
    assert said_real == real
    assert room == n * blocks * table_shards * L
    # padding slots are never fetched or sent, and point at position 0
    live = mask.astype(bool)
    assert not place[~live].any()
    owner = np.where(live, idx // rows_a_shard, -1).reshape(n, blocks, -1)
    pairs = np.stack([(owner == s).sum(-1) for s in range(table_shards)], -1)
    assert pairs.max() <= L
    sent = send.reshape(n, data, table_shards, table_shards, L)
    for s in range(table_shards):
        for m in range(table_shards):
            count = pairs.reshape(n, data, table_shards, -1)[:, :, m, s]
            beyond = np.arange(L) >= count[..., None]
            assert not sent[:, :, s, m][beyond].any()
    # bit for bit, in the dtype that crosses the chips
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    counter = np.asarray(jnp.asarray(rng.standard_normal(
        (rows_a_shard * table_shards, 3)), jnp.bfloat16))
    got = _exchanged(counter, place, send, rows_a_shard, table_shards)
    assert got.dtype == counter.dtype
    assert (got[live] == counter[idx][live]).all()


def test_a_pool_of_threads_routes_what_one_thread_does(monkeypatch):
    from concurrent.futures import ThreadPoolExecutor
    rows, idx, mask = _plan_like(7, 3000, 9, 64, 16)
    monkeypatch.setattr(als, "_ROUTE_SLOTS", 2 * 64 * 16)   # five cuts
    most = _most(rows, 4)
    one = als._route_group(most, idx, mask, 8.0, 751, 4, 4, False)
    with ThreadPoolExecutor(3) as pool:
        many = als._route_group(most, idx, mask, 8.0, 751, 4, 4, False,
                                pool)
    for a, b in zip(one, many):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("table_shards", [2, 4, 8])
def test_the_room_is_the_plans_and_not_the_pairings(table_shards):
    """`L` is a shape of the compiled half-sweep, so a key of the compile
    cache: ratings of the same degree sequences paired another way (another
    seed of the benchmark) must route to the same `L`, while a catalogue
    whose rated rows crowd one shard gets more room, and stays exact."""
    n_counter, n, b, k = 40_000, 4, 512 * table_shards, 32
    rows_a_shard = als.table_rows(n_counter, table_shards) // table_shards
    seen = set()
    for seed in range(8):
        rows, idx, mask = _plan_like(100, n_counter, n, b, k)
        idx = (np.random.default_rng(seed).integers(0, n_counter, idx.shape)
               * mask).astype(np.int32)
        degree = mask.sum() / (rows >= 0).sum()
        _place, send, real, room = als._route_group(
            _most(rows, table_shards), idx, mask, degree, rows_a_shard,
            table_shards, table_shards, True)
        seen.add(send.shape[-1])
        assert 0.5 < real / room < 1
    assert len(seen) == 1
    L = seen.pop()
    lo, hi = als._GATHER_STEP_256
    assert lo <= table_shards * L % als._GATHER_TILE <= hi
    crowded = (idx % rows_a_shard).astype(np.int32)
    _place, send, real, room = als._route_group(
        _most(rows, table_shards), crowded, mask, degree, rows_a_shard,
        table_shards, table_shards, True)
    assert send.shape[-1] > 0.6 * table_shards * L
    assert real / room < 1.5 / table_shards


@pytest.mark.parametrize("expected,observed,table_shards", [
    (0, 0, 4), (3.25, 8, 4), (21300.0, 21950, 4), (21300.0, 40000, 4),
    (55000.5, 0, 2), (46000.0, 0, 8), (100.0, 0, 3)])
def test_route_rows_rule(expected, observed, table_shards):
    lo, hi = als._GATHER_STEP_256
    for pad in (False, True):
        L = als._route_rows(expected, observed, table_shards, pad)
        assert L % 16 == 0 and L >= max(observed, 16)
        assert L >= expected * 17 / 16 + 8 * np.sqrt(expected)
        if pad:
            assert lo <= table_shards * L % als._GATHER_TILE <= hi
    # room over an even spread costs a tenth or so at a step's size, and
    # what was observed decides only beyond it, by eighths
    if observed <= expected:
        assert L <= expected * 1.13 + 8 * np.sqrt(expected) + 16 * 17
    assert als._route_rows(expected, 0, table_shards, True) \
        <= als._route_rows(expected, observed, table_shards, True)


def _seeded_ratings():
    rng = np.random.default_rng(34)
    n_u, n_i, nnz = 4000, 300, 40000
    return RatingsCOO(rng.integers(0, n_u, nnz), rng.integers(0, n_i, nnz),
                      rng.uniform(1, 5, nnz).astype(np.float32), n_u, n_i)


def _digest(groups):
    h = hashlib.sha256()
    for group in groups:
        for x in group:
            x = np.asarray(x)
            h.update(str((x.shape, x.dtype.str)).encode())
            h.update(x.tobytes())
    return h.hexdigest()[:16]


# sha256 of what PR 35's parent (80a925c) uploads for `_seeded_ratings` on
# one device: the routing is for row-sharded tables alone
PARENTS = {
    ("user", 1, "rows"): "a50fc318bb35fa66",
    ("user", 1, "rows+pad256"): "df7072e68ac62075",
    ("user", 3, "rows"): "affbaab04fe2038c",
    ("user", 3, "rows+pad256"): "c3a04402c1849032",
    ("item", 1, "rows"): "c18b6d937da31fbb",
    ("item", 1, "rows+pad256"): "c18b6d937da31fbb",
    ("item", 3, "rows"): "983caef316db01a3",
    ("item", 3, "rows+pad256"): "983caef316db01a3",
}


@pytest.mark.parametrize("side,chunk,layout", PARENTS)
def test_one_device_uploads_what_the_parent_did(monkeypatch, side, chunk,
                                                layout):
    import jax
    from predictionio_tpu.parallel.mesh import make_mesh
    planner = plan_for_users if side == "user" else plan_for_items
    plan = planner(_seeded_ratings(), work_budget=4096)
    monkeypatch.setattr(
        als, "_gather_layout",
        lambda mesh, rank=None, factor_sharding="replicated": layout)
    mesh = make_mesh(devices=jax.devices()[:1])
    groups = als._upload_plan(mesh, plan, chunk, 200)
    assert all(len(group) == 4 for group in groups)
    assert _digest(groups) == PARENTS[side, chunk, layout]
    # and nothing was routed for it
    before = list(als._route_log)
    als._upload_plan(mesh, plan, chunk, 200, "model")
    assert als._route_log == before


def test_the_planners_say_what_their_indices_point_into():
    r = _seeded_ratings()
    assert plan_for_users(r).n_counter == r.n_items
    assert plan_for_items(r).n_counter == r.n_users
    from predictionio_tpu.ops.ratings import build_solve_plan
    bare = build_solve_plan(r.user_idx, r.item_idx, r.rating, r.n_users)
    assert bare.n_counter is None
    with pytest.raises(ValueError, match="n_counter"):
        list(als._host_groups(bare, 1, False, 4, 4))


# sha256 of what the program made of the same ratings for a data-parallel
# mesh of four before plans were dealt (8e40b8a): tables that are not
# row-sharded are not dealt
DATA_PARALLEL = {
    ("user", 1, False): "ecdd5ee88e59997b",
    ("user", 1, True): "09c31f9416cc3f46",
    ("user", 3, False): "23d8f18284355696",
    ("user", 3, True): "134afaf9507881d4",
    ("item", 1, True): "1ebfa8364775f03d",
    ("item", 3, False): "1ebfa8364775f03d",
}


@pytest.mark.parametrize("side,chunk,pad", DATA_PARALLEL)
def test_groups_of_unsharded_tables_are_what_the_parent_made(side, chunk,
                                                             pad):
    planner = plan_for_users if side == "user" else plan_for_items
    plan = planner(_seeded_ratings(), work_budget=1 << 14, batch_multiple=4)
    before = list(als._route_log)
    groups = list(als._host_groups(plan, chunk, pad, 4))
    assert _digest(groups) == DATA_PARALLEL[side, chunk, pad]
    assert als._route_log == before


def _crowded_plan():
    """Users of one shard of four alone, and one user of a rung of its
    own: the deal's two edges in one plan."""
    from predictionio_tpu.ops.ratings import build_solve_plan
    rng = np.random.default_rng(38)
    n_users, n_items = 4000, 300
    users = rng.integers(0, 900, 6000)
    items = rng.integers(0, n_items, 6000)
    heavy = np.full(120, 3100)                   # shard 3; K 120 alone
    key = np.unique(np.concatenate([users, heavy]) * n_items
                    + np.concatenate([items, np.arange(120)]))
    return build_solve_plan(key // n_items, key % n_items,
                            np.ones(key.size, np.float32), n_users,
                            work_budget=1024, batch_multiple=4,
                            n_counter=n_items)


def _systems(groups):
    """{row: (idx, val, mask) of its system} over groups' real systems,
    each row once."""
    out = {}
    for rows, idx, val, mask, *_ in groups:
        live = rows >= 0
        for row, *x in zip(rows[live], idx[live], val[live], mask[live]):
            assert int(row) not in out
            out[int(row)] = x
    return out


PLANS = {
    # name: (plan, chunk, chips, table shards)
    "users": (lambda: plan_for_users(_seeded_ratings(), work_budget=4096,
                                     batch_multiple=4), 1, 4, 4),
    "users, two merged": (lambda: plan_for_users(
        _seeded_ratings(), work_budget=4096, batch_multiple=4), 2, 4, 4),
    "items": (lambda: plan_for_items(_seeded_ratings(), work_budget=4096,
                                     batch_multiple=4), 1, 4, 4),
    "a data axis of two": (lambda: plan_for_users(
        _seeded_ratings(), work_budget=4096, batch_multiple=4), 1, 4, 2),
    "one shard owns the rows, one rung of one system": (
        _crowded_plan, 1, 4, 4),
}


@pytest.mark.parametrize("case", PLANS)
def test_row_sharded_groups_are_the_plain_ones_dealt_and_routed(case):
    """`_host_groups` for row-sharded tables: the systems of the plain
    groups, each real row once with its own `val` / `mask` row; a chip's
    block of a step holds rows of the shard it holds or padding, and is
    padded at its own tail for its gather; `place` / `send` fetch each
    live slot's counterpart; and the log says what was dealt and routed."""
    make, chunk, chips, table_shards = PLANS[case]
    plan = make()
    n_out = als.table_rows(plan.n_entities, table_shards) // table_shards
    n_shard = als.table_rows(plan.n_counter, table_shards) // table_shards
    plain = list(als._host_groups(plan, chunk, False, chips))
    bare = [group for _block, group
            in als._dealt_groups(plan, chunk, False, chips, table_shards)]
    blocks_and_groups = list(als._dealt_groups(plan, chunk, True, chips,
                                               table_shards))
    dealt = [group for _block, group in blocks_and_groups]
    als._route_log.clear()
    routed = list(als._host_groups(plan, chunk, True, chips, table_shards,
                                   "user_plan"))
    assert len(bare) == len(dealt) == len(routed)
    want, got = _systems(plain), _systems(dealt)
    assert want.keys() == got.keys()
    for row, x in want.items():
        for a, b in zip(x, got[row]):
            assert (a == b).all()
    counter = np.arange(table_shards * n_shard, dtype=np.int32)
    lo, hi = als._GATHER_STEP_256
    real = room = systems = slots = 0
    for (brows, *_), (most, group), (rows, place, val, mask, send) in zip(
            bare, blocks_and_groups, routed):
        assert all((x == y).all() for x, y in zip(group, (rows, None, val,
                                                          mask))
                   if y is not None)
        n, b_all, k = group[1].shape
        # chip (d, m) solves rows of shard m, or padding
        blocks = rows.reshape(n, chips, -1)
        for c in range(chips):
            at = blocks[:, c]
            assert ((at == -1) | (at // n_out == c % table_shards)).all()
        # each block padded at its own tail: what it held unpadded, then
        # systems that solve nothing, as many as its gather asks for
        b = brows.shape[1] // chips
        assert blocks.shape[2] == b + als._gather_pad_rows(b, k)
        # `most`, what the routing sizes its room by, bounds every block
        assert b == most >= (blocks >= 0).sum(axis=-1).max()
        assert (blocks[:, :, :b] == brows.reshape(n, chips, b)).all()
        assert (blocks[:, :, b:] == -1).all()
        assert not mask.reshape(n, chips, -1, k)[:, :, b:].any()
        # the rows each live slot receives are its counterpart's
        idx = group[1]
        fetched = _exchanged(counter, place, send, n_shard, table_shards)
        live = mask.astype(bool)
        assert (fetched[live] == idx[live]).all()
        real += int(mask.sum())
        room += send.size
        systems += int((rows >= 0).sum())
        slots += rows.size
    said = als._routed()
    assert said["route_fill"] == pytest.approx(real / room)
    assert said["deal_fill"] == pytest.approx(systems / slots)
    assert 0 < said["deal_fill"] <= 1 and said["route_s"] > 0
    assert als._deal_fills() == {"user_plan": said["deal_fill"]}
    als._route_log.clear()
    assert als._routed() == {} and als._deal_fills() == {}


def test_a_crowded_shard_and_a_lone_system_are_dealt_room_for():
    """Where every real row of a rung lies in one shard, the others are
    dealt padding as wide; a rung of one system takes one system a
    chip, as the plan gave it."""
    plan = _crowded_plan()
    n_out = als.table_rows(plan.n_entities, 4) // 4
    assert all((b.rows < n_out).all() for b in plan.batches
               if b.shape[1] != 120)
    lone = [b for b in plan.batches if b.shape[1] == 120]
    assert len(lone) == 1 and (lone[0].rows >= 0).sum() == 1
    for _most, (rows, idx, _val, _mask) in als._dealt_groups(plan, 1, False,
                                                             4, 4):
        blocks = rows.reshape(rows.shape[0], 4, -1)
        if idx.shape[2] == 120:
            assert rows.shape == (1, 4)
            assert blocks[0, 3, 0] == 3100 and (blocks[:, :3] == -1).all()
            continue
        assert (blocks[:, 1:] == -1).all()
        owned = int((blocks[:, 0] >= 0).sum())
        # shard 0 packed: its steps hold less than a system a step more
        # than it needs
        assert blocks[:, 0].size - owned < rows.shape[0]


@pytest.mark.parametrize("systems,shards,observed", [
    (1, 4, 1), (2, 4, 2), (7, 4, 7), (1000, 4, 262), (18_737_250, 4, 0),
    (18_737_250, 4, 4_700_000), (500, 2, 250), (500, 8, 63)])
def test_deal_room_rule(systems, shards, observed):
    room = als._deal_room(systems, shards, observed)
    assert observed <= room <= systems
    even = systems / shards
    if observed <= even + 4 * np.sqrt(even):
        # the plan's counts alone: the same whatever the pairing
        assert room == als._deal_room(systems, shards, 0)
    # the room over an even share is a few of its square roots, at most
    assert room <= max(even + 4 * np.sqrt(even) + 1, observed * 9 / 8 + 1)

"""The e-commerce engine's filtered serve path (ISSUE 31), on the CPU at
small sizes over seeded tables: served answers against the plain reference
(benchmark/references/ecomm-filtered-topk.py) for every query kind of the
serve-filtered cell, the mask composed on the device against
`build_filter_mask`, the availability bitmap following a `$set`, a seen-read
past its deadline, and the warmed buckets."""

import importlib.util
import os

import numpy as np
import pytest

from predictionio_tpu.data import Event
from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
from predictionio_tpu.data.datamap import DataMap
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.data.storage.registry import Storage
from predictionio_tpu.obs import costmon
from predictionio_tpu.obs.metrics import get_registry
from predictionio_tpu.ops import similarity as S

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_USERS, N_ITEMS, N_CATS, RANK = 40, 300, 12, 16


def _reference():
    path = os.path.join(REPO, "benchmark", "references",
                        "ecomm-filtered-topk.py")
    spec = importlib.util.spec_from_file_location("ref_ecomm", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _ids(prefix, n):
    return EntityIdIxMap(BiMap({f"{prefix}{i}": i for i in range(n)}))


class World:
    """Seeded tables, an item -> categories map with up to three categories
    an item, seen events and an unavailable list in the event store, and
    the same filter data as the reference wants it."""

    def __init__(self, app_id, seed=0):
        from predictionio_tpu.models import ecommerce as E
        self.E, self.app_id = E, app_id
        rng = np.random.default_rng(seed)
        self.rng = rng
        self.U = np.abs(rng.standard_normal((N_USERS, RANK))).astype(
            np.float32)
        self.V = np.abs(rng.standard_normal((N_ITEMS, RANK))).astype(
            np.float32)
        n_cats = rng.integers(0, 4, N_ITEMS)         # some items have none
        self.cat = np.full((N_ITEMS, 3), -1, np.int64)
        for i in range(N_ITEMS):
            self.cat[i, :n_cats[i]] = rng.choice(N_CATS, n_cats[i],
                                                 replace=False)
        pairs = [(i, f"c{c}") for i in range(N_ITEMS)
                 for c in self.cat[i] if c >= 0]
        cats = S.ItemCategories.from_pairs(
            N_ITEMS, [i for i, _ in pairs], [c for _, c in pairs])
        # the reference's codes are the names' numbers; the program's are
        # its vocabulary's
        self.model = E.ECommerceModel(
            rank=RANK, user_factors=self.U, item_factors=self.V,
            item_factors_normalized=S.normalize_rows(self.V),
            user_ix=_ids("u", N_USERS), item_ix=_ids("i", N_ITEMS),
            items={}, item_categories=cats)
        self.algo = E.ECommAlgorithm(E.ECommAlgorithmParams(
            app_name="shop", unseen_only=True,
            seen_events=("buy", "view")))
        self.seen = {u: rng.choice(N_ITEMS, rng.integers(0, 25),
                                   replace=False)
                     for u in range(N_USERS)}
        self.recent = {f"v{j}": rng.choice(N_ITEMS, 10, replace=False)
                       for j in range(4)}
        ev = Storage.get_events()
        sec = 0
        for u, items in self.seen.items():
            for n, i in enumerate(items):
                ev.insert(self._event("buy" if n % 7 == 0 else "view",
                                      f"u{u}", f"i{i}", sec), app_id)
                sec += 1
        for visitor, items in self.recent.items():
            for i in items:
                ev.insert(self._event("view", visitor, f"i{i}", sec),
                          app_id)
                sec += 1
        self.sec = sec
        self.unavailable = [np.sort(rng.choice(N_ITEMS, 30, replace=False))]
        self.set_unavailable(self.unavailable[0])

    @staticmethod
    def _event(name, user, item, sec):
        import datetime as dt
        return Event(event=name, entity_type="user", entity_id=user,
                     target_entity_type="item", target_entity_id=item,
                     event_time=dt.datetime(2017, 11, 25,
                                            tzinfo=dt.timezone.utc)
                     + dt.timedelta(seconds=sec))

    def set_unavailable(self, items):
        import datetime as dt
        self.sec += 1
        Storage.get_events().insert(Event(
            event="$set", entity_type="constraint",
            entity_id="unavailableItems",
            properties=DataMap({"items": [f"i{i}" for i in items]}),
            event_time=dt.datetime(2017, 11, 26, tzinfo=dt.timezone.utc)
            + dt.timedelta(seconds=self.sec)), self.app_id)

    def filter_data(self):
        return {"item_category": self.cat, "unavailable": self.unavailable}

    def query(self, kind, n=0):
        """(the program's Query, the reference's query dict, its route)."""
        rng = np.random.default_rng([7, n])
        user = int(rng.integers(N_USERS))
        q = {"user": f"u{user}", "num": 10}
        ref = {"categories": [], "black": [], "white": None,
               "seen": self.seen[user], "vector": self.U[user],
               "versions": [len(self.unavailable) - 1]}
        route = "dot"
        if kind == "category":
            c = int(rng.integers(N_CATS))
            q["categories"], ref["categories"] = [f"c{c}"], [c]
        elif kind == "multi-category":
            cs = rng.choice(N_CATS, 3, replace=False)
            q["categories"] = [f"c{c}" for c in cs]
            ref["categories"] = cs.tolist()
        elif kind == "cart":
            black = rng.choice(N_ITEMS, 20, replace=False)
            q["blackList"] = [f"i{i}" for i in black]
            ref["black"] = black
        elif kind == "campaign":
            c = int(rng.integers(N_CATS))
            white = np.flatnonzero((self.cat == c).any(axis=1))
            q["whiteList"] = [f"i{i}" for i in white]
            ref["white"] = white
        elif kind == "new-visitor":
            visitor = f"v{n % 4}"
            q["user"] = visitor
            ref.update(seen=self.recent[visitor],
                       recent=self.recent[visitor])
            route = "cos"
        elif kind == "unknown-category":
            q["categories"], ref["categories"] = ["never-seen"], [-3]
        elif kind == "empty-whitelist":
            q["whiteList"], ref["white"] = [], []
        return self.E.Query.from_dict(q), ref, route


@pytest.fixture()
def world(tmp_env, monkeypatch):
    # the bit-exact packed readback: the comparison below is on scores
    monkeypatch.setenv("PIO_SERVE_PACK", "exact")
    app_id = Storage.get_meta_data_apps().insert(App(0, "shop"))
    Storage.get_events().init(app_id)
    return World(app_id)


KINDS = ["home", "category", "multi-category", "cart", "campaign",
         "new-visitor", "unknown-category", "empty-whitelist"]


def _assert_same(result, ref_scores, ref_ids):
    n = int(np.isfinite(ref_scores).sum())
    got = [(int(s.item[1:]), s.score) for s in result.item_scores]
    assert len(got) == n
    np.testing.assert_allclose([g[1] for g in got], ref_scores[:n],
                               rtol=2e-5)
    # the same items, up to the order of scores a rounding apart
    assert {g[0] for g in got[:max(n - 1, 0)]} <= set(ref_ids[:n].tolist())


@pytest.mark.parametrize("kind", KINDS)
def test_served_answers_match_the_reference(world, kind):
    ref = _reference()
    batch = [world.query(kind, n) for n in range(5)]
    served = dict(world.algo.batch_predict(
        world.model, [(j, q) for j, (q, _, _) in enumerate(batch)]))
    for route in ("dot", "cos"):
        rows = [j for j, (_, _, r) in enumerate(batch) if r == route]
        if not rows:
            continue
        scores, ids = ref.rank([batch[j][1] for j in rows], world.V,
                               world.filter_data(), route, 10)
        for at, j in enumerate(rows):
            _assert_same(served[j], scores[at], ids[at])
            allowed = ref.allowed_of(
                batch[j][1], world.filter_data(),
                [int(s.item[1:]) for s in served[j].item_scores])
            assert allowed.all()
    if kind in ("unknown-category", "empty-whitelist"):
        assert all(not served[j].item_scores for j in served)
    else:
        assert any(served[j].item_scores for j in served)


def test_one_batch_of_every_kind_and_the_single_path(world):
    ref = _reference()
    batch = [world.query(kind, 11) for kind in KINDS]
    served = dict(world.algo.batch_predict(
        world.model, [(j, q) for j, (q, _, _) in enumerate(batch)]))
    for j, (q, rq, route) in enumerate(batch):
        scores, ids = ref.rank([rq], world.V, world.filter_data(), route, 10)
        _assert_same(served[j], scores[0], ids[0])
        single = world.algo.predict(world.model, q)
        assert [s.item for s in single.item_scores] == \
            [s.item for s in served[j].item_scores]


@pytest.mark.parametrize("seed", range(6))
def test_device_mask_equals_build_filter_mask(seed):
    """Every allowed item, and no other, survives the composed mask: with
    an all-positive table and k the whole bucket, the finite slots of the
    answer ARE the mask."""
    rng = np.random.default_rng(seed)
    n_items, i_b, b = 100, 128, 4
    sets = [set(f"c{c}" for c in rng.choice(6, rng.integers(0, 4),
                                            replace=False)) or None
            for _ in range(n_items)]
    cats = S.ItemCategories.from_sets(sets)
    filters = S.ItemFilterData(cats)
    unavailable = rng.choice(n_items, 9, replace=False)
    filters.set_unavailable(unavailable)
    table = np.abs(rng.standard_normal((n_items, 8))).astype(np.float32) + 1
    q_cats, listed, has_white, want = [], [], [], []
    for j in range(b):
        names = ([f"c{c}" for c in rng.choice(7, rng.integers(1, 3),
                                              replace=False)]
                 if rng.random() < 0.6 else [])
        gone = rng.choice(n_items, rng.integers(0, 30), replace=False)
        white = (rng.choice(n_items, rng.integers(0, 40), replace=False)
                 if rng.random() < 0.5 else None)
        mask = S.build_filter_mask(
            n_items, exclude=np.concatenate([gone, unavailable]),
            white_list=white, item_categories=sets,
            categories=set(names) if names else None)
        want.append(mask)
        q_cats.append(cats.codes_of(names))
        w = np.zeros(0, np.int64) if white is None else white
        listed.append((np.concatenate([gone, w]), np.concatenate(
            [np.full(gone.size, S.LISTED_OUT),
             np.full(w.size, S.LISTED_WHITE)])))
        has_white.append(white is not None)
    scores, idx = S.composed_top_k_batch_begin(
        table, np.ones((b, 8), np.float32), filters, q_cats, listed,
        has_white, i_b)()
    for j in range(b):
        got = np.zeros(n_items, bool)
        got[idx[j][np.isfinite(scores[j])]] = True
        assert (got == want[j]).all()


def test_item_categories_holds_the_sets_as_padded_codes():
    sets = [{"a"}, None, {"a", "b", "c"}, {"c"}, None]
    cats = S.ItemCategories.from_sets(sets)
    assert cats.ids.shape == (5, 3) and len(cats) == 5
    assert cats.vocab == {"a": 0, "b": 1, "c": 2}
    assert cats.ids.tolist() == [[0, -1, -1], [-1, -1, -1], [0, 1, 2],
                                 [2, -1, -1], [-1, -1, -1]]
    assert S.ItemCategories.from_sets(cats) is cats
    assert cats.matches({"b", "zz"}).tolist() == [False, False, True,
                                                  False, False]
    assert S.ItemCategories.from_sets([None, None]).ids.shape == (2, 1)


def test_the_next_dispatch_after_a_set_excludes_its_items(world):
    q, _, _ = world.query("home", 3)
    before = [s.item for s in
              world.algo.predict(world.model, q).item_scores]
    reloads = get_registry().counter(
        "pio_filter_constraint_reloads_total", "")
    n0 = reloads.value
    # nothing new in the store: the list is not parsed again
    world.algo.predict(world.model, q)
    assert reloads.value == n0
    gone = [int(i[1:]) for i in before[:3]]
    world.set_unavailable(np.concatenate([world.unavailable[0], gone]))
    after = [s.item for s in
             world.algo.predict(world.model, q).item_scores]
    assert reloads.value == n0 + 1
    assert not set(before[:3]) & set(after)
    assert after[:len(before) - 3] == before[3:]
    # the latest `$set` is the whole list: items it no longer names return
    world.set_unavailable(world.unavailable[0])
    assert [s.item for s in
            world.algo.predict(world.model, q).item_scores] == before


def test_a_seen_read_past_its_deadline_is_counted_and_fails_open(
        world, monkeypatch):
    from predictionio_tpu.models import ecommerce as E
    q, rq, _ = world.query("home", 5)
    timeouts = get_registry().counter("pio_filter_seen_timeouts_total", "")
    n0 = timeouts.value
    def slow(*args, **kwargs):
        assert kwargs["entity_type"] == "user" and kwargs["timeout_ms"] == 200
        raise TimeoutError("event lookup exceeded 200 ms deadline")

    monkeypatch.setattr(E.LEventStore, "find_columnar", slow)
    served = world.algo.predict(world.model, q)
    assert timeouts.value == n0 + 1
    scores, ids = _reference().rank(
        [dict(rq, seen=[])], world.V, world.filter_data(), "dot", 10)
    _assert_same(served, scores[0], ids[0])


@pytest.mark.parametrize("error", [TimeoutError, OSError])
def test_a_failed_set_probe_is_counted_and_keeps_the_last_list(
        world, monkeypatch, error):
    """The `$set` probe past its deadline (or failing): the dispatch is
    answered under the last list the bitmap was built from, and the
    failure is counted: the cell holds that count at 0."""
    from predictionio_tpu.models import ecommerce as E
    q, rq, _ = world.query("home", 5)
    world.algo.predict(world.model, q)          # the bitmap holds list 0
    failures = get_registry().counter(
        "pio_filter_constraint_failures_total", "")
    reloads = get_registry().counter(
        "pio_filter_constraint_reloads_total", "")
    n0, r0 = failures.value, reloads.value

    def broken(*args, **kwargs):
        assert kwargs["entity_id"] == "unavailableItems"
        assert kwargs["timeout_ms"] == 200
        raise error("event lookup exceeded 200 ms deadline")

    monkeypatch.setattr(E.LEventStore, "latest_event", broken)
    served = world.algo.predict(world.model, q)
    assert failures.value == n0 + 1 and reloads.value == r0
    scores, ids = _reference().rank(
        [rq], world.V, world.filter_data(), "dot", 10)
    _assert_same(served, scores[0], ids[0])


def test_warmed_buckets_compile_nothing_at_serve_time(world, monkeypatch):
    from predictionio_tpu.compile.aot import get_aot, warm_models
    monkeypatch.delenv("PIO_AOT_WARM", raising=False)
    out = warm_models([world.algo], [world.model], batch_hint=4)
    # b 1 (t 1024, 4096), b 2 and b 4 (t 1024, 4096, 16384)
    assert out["specs"] == 8 and not out["failed"]
    for dims in S.composed_topk_warm_dims(N_ITEMS, RANK, 4, 3):
        assert get_aot().lookup(costmon.BATCH_PREDICT_COMPOSED,
                                dims) is not None
    batch = [world.query(kind, 2)[0] for kind in KINDS[:4]]
    before = sum(costmon.compile_seconds_by_executable().values())
    world.algo.batch_predict(world.model, list(enumerate(batch)))
    world.algo.predict(world.model, world.query("new-visitor", 1)[0])
    assert sum(costmon.compile_seconds_by_executable().values()) == before


def test_the_result_cache_is_bypassed_for_live_filters(world):
    from predictionio_tpu.models import recommendation as R
    from predictionio_tpu.serving import EngineServer, ServerConfig
    server = EngineServer(ServerConfig(ip="127.0.0.1", port=0),
                          engine=world.E.ECommerceEngineFactory.apply())
    assert server.result_cache is not None
    server.algorithms = [world.algo]
    assert not server._cache_usable()
    server.algorithms = [R.ALSAlgorithm(R.ALSAlgorithmParams(rank=4))]
    assert server._cache_usable()


def test_the_filter_upload_is_kilobytes(world):
    sent = get_registry().counter("pio_filter_h2d_bytes_total", "")
    batch = [world.query(kind, 4)[0] for kind in KINDS]
    world.algo.batch_predict(world.model, list(enumerate(batch)))
    n0 = sent.value
    world.algo.batch_predict(world.model, list(enumerate(batch)))
    # two dispatches (dot, cos): codes, flat lists and flags, no [b, I] mask
    assert 0 < sent.value - n0 < 64 * 1024


def test_list_buckets_step_by_four():
    from predictionio_tpu.compile import buckets as B
    assert [B.bucket_list(n) for n in (0, 1, 1024, 1025, 4096, 4097)] == \
        [1024, 1024, 1024, 4096, 4096, 16384]
    dims = S.composed_topk_warm_dims(4162024, 200, 16, 1)
    assert len(dims) == 16
    assert {d["t"] for d in dims if d["b"] == 1} == {1024, 4096}
    assert {d["t"] for d in dims if d["b"] == 16} == {1024, 4096, 16384,
                                                      65536}
    assert {d["i"] for d in dims} == {1 << 22} and dims[0]["c"] == 1

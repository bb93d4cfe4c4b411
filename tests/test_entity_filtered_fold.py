"""ISSUE 4 acceptance: O(touched) fold ticks end to end.

Parity — a fold tick through the entity-filtered read path must produce
factors identical (<=1e-5) to the full-scan path. Cost — on a synthetic
corpus with ~1% touched entities, the filtered tick reads <5% of the
rows the full scan reads (asserted via the fold report's readRows, the
number behind ``pio_fold_read_rows_total``/``fold_read_rows``). Plus the
bounded-deadline point-read satellite (``find_by_entity`` timeout path).
"""

import datetime as dt
import threading

import numpy as np
import pytest

from predictionio_tpu.core import EngineParams
from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage import AccessKey, App, Storage
from predictionio_tpu.models import recommendation as R
from predictionio_tpu.online.scheduler import SchedulerConfig, \
    attach_scheduler
from predictionio_tpu.serving import EngineServer, ServerConfig
from predictionio_tpu.workflow import run_train

UTC = dt.timezone.utc


def _engine_params(num_iterations=4):
    return EngineParams(
        data_source_params=("", R.DataSourceParams(app_name="foldapp")),
        preparator_params=("", R.PreparatorParams()),
        algorithm_params_list=[("als", R.ALSAlgorithmParams(
            rank=4, num_iterations=num_iterations, lam=0.1, seed=1))],
        serving_params=("", None))


def _rate(ev, app_id, user, item, rating=4.0, t=None):
    ev.insert(Event(
        event="rate", entity_type="user", entity_id=user,
        target_entity_type="item", target_entity_id=item,
        properties=DataMap({"rating": float(rating)}),
        event_time=t or dt.datetime.now(UTC)), app_id)


def _seed(n_users, n_items, per_user, t0):
    app_id = Storage.get_meta_data_apps().insert(App(0, "foldapp"))
    ev = Storage.get_events()
    ev.init(app_id)
    Storage.get_meta_data_access_keys().insert(
        AccessKey("foldkey", app_id, []))
    rng = np.random.default_rng(3)
    batch = []
    for u in range(n_users):
        for k, i in enumerate(rng.choice(n_items, per_user,
                                         replace=False)):
            batch.append(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{i}",
                properties=DataMap(
                    {"rating": float(1 + (u + int(i)) % 5)}),
                event_time=t0 + dt.timedelta(
                    milliseconds=u * per_user + k)))
    ev.insert_batch(batch, app_id)
    return app_id, ev, len(batch)


def _server(engine, ep):
    s = EngineServer(ServerConfig(
        ip="127.0.0.1", port=0, engine_id="fold", engine_version="1",
        engine_variant="v1"))
    s.load()
    return s


class TestFilteredVsFullScanParity:
    def test_identical_factors_both_read_paths(self, tmp_env, mesh8):
        """Two schedulers over the same trained instance and the same
        fresh events — one reading O(touched), one full-scanning — must
        publish numerically identical factor tables (the touched rows'
        complete histories are what the solves consume either way)."""
        t0 = dt.datetime(2026, 8, 1, tzinfo=UTC)
        app_id, ev, _ = _seed(n_users=24, n_items=12, per_user=6, t0=t0)
        engine = R.RecommendationEngineFactory.apply()
        ep = _engine_params()
        run_train(engine, ep, engine_id="fold", engine_version="1",
                  engine_variant="v1", engine_factory="recommendation")
        # fresh events: a brand-new user plus new ratings on old users
        # (stamped now(): the scheduler cursor starts at train time)
        later = dt.datetime.now(UTC)
        for k, (u, i) in enumerate([("newbie", "i0"), ("newbie", "i3"),
                                    ("u1", "i5"), ("u2", "i7")]):
            _rate(ev, app_id, u, i, rating=5.0,
                  t=later + dt.timedelta(milliseconds=k))

        s_filt = _server(engine, ep)
        s_full = _server(engine, ep)
        sched_filt = attach_scheduler(s_filt, SchedulerConfig(
            app_name="foldapp", max_deltas=1))
        sched_full = attach_scheduler(s_full, SchedulerConfig(
            app_name="foldapp", max_deltas=1, filtered_reads=False))
        r_filt = sched_filt.tick(force=True)
        r_full = sched_full.tick(force=True)
        assert r_filt["readPath"] == "entity_filtered"
        assert r_full["readPath"] == "full_scan"
        assert r_filt["readRows"] < r_full["readRows"]
        m_filt = s_filt.models[0]
        m_full = s_full.models[0]
        # identical vocab growth and identical factor tables
        assert len(m_filt.user_ix) == len(m_full.user_ix)
        assert m_filt.user_ix["newbie"] == m_full.user_ix["newbie"]
        np.testing.assert_allclose(m_filt.als.user_factors,
                                   m_full.als.user_factors,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(m_filt.als.item_factors,
                                   m_full.als.item_factors,
                                   rtol=1e-5, atol=1e-5)

    def test_large_touched_set_falls_back_to_full_scan(self, tmp_env,
                                                       mesh8):
        """The cost-model cutover: a touched set past the threshold must
        full-scan (filtered pushdown loses past a few thousand ids)."""
        t0 = dt.datetime(2026, 8, 1, tzinfo=UTC)
        app_id, ev, _ = _seed(n_users=10, n_items=8, per_user=4, t0=t0)
        engine = R.RecommendationEngineFactory.apply()
        ep = _engine_params(num_iterations=2)
        run_train(engine, ep, engine_id="fold", engine_version="1",
                  engine_variant="v1", engine_factory="recommendation")
        later = dt.datetime.now(UTC)
        for k in range(4):
            _rate(ev, app_id, f"u{k}", "i1",
                  t=later + dt.timedelta(milliseconds=k))
        server = _server(engine, ep)
        sched = attach_scheduler(server, SchedulerConfig(
            app_name="foldapp", max_deltas=1,
            filtered_read_max_entities=2))   # 4 users + 1 item > 2
        report = sched.tick(force=True)
        assert report["readPath"] == "full_scan"


class TestFilteredReadCost:
    def test_one_percent_touched_reads_under_five_percent(self, tmp_env,
                                                          mesh8):
        """The acceptance bar: ~1% touched entities -> the filtered tick
        reads <5% of the rows the full corpus holds."""
        t0 = dt.datetime(2026, 8, 1, tzinfo=UTC)
        n_users, n_items, per_user = 600, 200, 20
        app_id, ev, corpus_rows = _seed(n_users, n_items, per_user, t0)
        engine = R.RecommendationEngineFactory.apply()
        ep = _engine_params(num_iterations=2)
        run_train(engine, ep, engine_id="fold", engine_version="1",
                  engine_variant="v1", engine_factory="recommendation")
        # ~1% of users rate a couple of existing items
        later = dt.datetime.now(UTC)
        k = 0
        for u in range(0, n_users, n_users // 6):
            for i in ("i1", "i2"):
                _rate(ev, app_id, f"u{u}", i,
                      t=later + dt.timedelta(milliseconds=k))
                k += 1
        server = _server(engine, ep)
        sched = attach_scheduler(server, SchedulerConfig(
            app_name="foldapp", max_deltas=1))
        report = sched.tick(force=True)
        assert report["readPath"] == "entity_filtered"
        full_rows = corpus_rows + k
        assert report["readRows"] < 0.05 * full_rows, \
            (report["readRows"], full_rows)
        # the metric records the same number
        from predictionio_tpu.obs import get_registry
        fam = get_registry().get("pio_fold_read_rows_total")
        by_path = {}
        for lbl, v in fam.samples():
            by_path[(lbl or {}).get("path")] = \
                by_path.get((lbl or {}).get("path"), 0) + v
        assert by_path["entity_filtered"] >= report["readRows"]


class _WedgedEvents:
    """An events DAO whose find() blocks until released."""

    def __init__(self):
        self.release = threading.Event()

    def find(self, *a, **kw):
        self.release.wait(30)
        return iter(())


class _OneApp:
    def get_by_name(self, name):
        return App(1, name)


def _release_and_join(wedged):
    """Release the wedged backend and wait for its stranded workers:
    a worker still on its way out returns its permit to whatever
    semaphore the NEXT test has patched in (this flaked under load)."""
    wedged.release.set()
    for t in threading.enumerate():
        if t.name == "pio-point-read":
            t.join(timeout=10)
            assert not t.is_alive()


class TestPointReadDeadline:
    def _store(self, events):
        from predictionio_tpu.data.store.event_store import EventStore
        return EventStore(apps=_OneApp(), channels=None, events=events)

    def test_timeout_raises_and_counts(self, monkeypatch):
        from predictionio_tpu.data.store.event_store import EventStore
        from predictionio_tpu.obs import get_registry
        wedged = _WedgedEvents()
        store = self._store(wedged)
        counter = get_registry().counter(
            "pio_event_point_read_timeout_total", "x")
        before = counter.value
        try:
            with pytest.raises(TimeoutError, match="deadline"):
                store.find_by_entity("app", "user", "u1", timeout_ms=50)
            assert counter.value == before + 1
        finally:
            _release_and_join(wedged)

    def test_wedged_workers_are_bounded(self, monkeypatch):
        """Each timed-out read strands one worker; past the permit cap,
        new deadline reads fail AT THEIR OWN DEADLINE instead of minting
        more threads — and never wait longer than that deadline."""
        from predictionio_tpu.data.store.event_store import EventStore
        monkeypatch.setattr(EventStore, "_point_read_sem",
                            threading.BoundedSemaphore(2))
        monkeypatch.setattr(EventStore, "POINT_READ_MAX_INFLIGHT", 2)
        wedged = _WedgedEvents()
        store = self._store(wedged)
        n_before = threading.active_count()
        try:
            for _ in range(2):
                with pytest.raises(TimeoutError, match="deadline"):
                    store.find_by_entity("app", "user", "u1",
                                         timeout_ms=30)
            # both permits stranded: the next read times out waiting for
            # a permit, bounded by ITS deadline, without a new worker
            t0 = dt.datetime.now()
            with pytest.raises(TimeoutError, match="busy"):
                store.find_by_entity("app", "user", "u1",
                                     timeout_ms=300)
            waited = (dt.datetime.now() - t0).total_seconds()
            assert 0.25 <= waited < 2.0
            assert threading.active_count() <= n_before + 2
        finally:
            _release_and_join(wedged)

    def test_healthy_burst_past_permits_still_answers(self, monkeypatch):
        """Permit contention from HEALTHY concurrent reads queues within
        the deadline instead of shedding (the permit wait shares the
        deadline; only genuinely wedged permits make reads fail)."""
        from predictionio_tpu.data.store.event_store import EventStore
        monkeypatch.setattr(EventStore, "_point_read_sem",
                            threading.BoundedSemaphore(2))
        monkeypatch.setattr(EventStore, "POINT_READ_MAX_INFLIGHT", 2)

        class _Slowish:
            def find(self, *a, **kw):
                import time as _t
                _t.sleep(0.05)
                return iter(())

        store = self._store(_Slowish())
        errors = []

        def one():
            try:
                assert store.find_by_entity("app", "user", "u1",
                                            timeout_ms=2000) == []
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=one) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert not errors, errors

    def test_late_result_is_discarded_and_permit_returns(self,
                                                         monkeypatch):
        from predictionio_tpu.data.store.event_store import EventStore
        monkeypatch.setattr(EventStore, "_point_read_sem",
                            threading.BoundedSemaphore(1))
        monkeypatch.setattr(EventStore, "POINT_READ_MAX_INFLIGHT", 1)
        wedged = _WedgedEvents()
        store = self._store(wedged)
        with pytest.raises(TimeoutError):
            store.find_by_entity("app", "user", "u1", timeout_ms=30)
        wedged.release.set()   # backend recovers; worker finishes late
        deadline = dt.datetime.now() + dt.timedelta(seconds=5)
        while dt.datetime.now() < deadline:
            try:
                assert store.find_by_entity("app", "user", "u1",
                                            timeout_ms=500) == []
                break
            except TimeoutError:
                continue       # permit not back yet
        else:
            pytest.fail("permit never returned after late completion")

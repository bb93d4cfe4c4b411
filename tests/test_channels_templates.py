"""Channel-scoped template reads: training AND serve-time lookups must hit
the configured channel (code-review finding: channeled deployments)."""

import numpy as np
import pytest

from predictionio_tpu.core import EngineParams
from predictionio_tpu.data import DataMap, Event
from predictionio_tpu.data.storage import App, Channel, Storage


@pytest.fixture
def channeled_app(tmp_env):
    app_id = Storage.get_meta_data_apps().insert(App(0, "chapp"))
    chan_id = Storage.get_meta_data_channels().insert(
        Channel(0, "mobile", app_id))
    ev = Storage.get_events()
    ev.init(app_id)
    ev.init(app_id, chan_id)
    # default channel holds decoy data; "mobile" holds the real data
    rng = np.random.default_rng(0)
    for u in range(6):
        for i in range(6):
            if rng.random() < 0.8:
                ev.insert(Event(
                    event="rate", entity_type="user", entity_id=f"u{u}",
                    target_entity_type="item", target_entity_id=f"i{i}",
                    properties=DataMap({"rating": 5.0})), app_id, chan_id)
    ev.insert(Event(event="rate", entity_type="user", entity_id="decoy",
                    target_entity_type="item", target_entity_id="decoyitem",
                    properties=DataMap({"rating": 5.0})), app_id)
    return app_id, chan_id


class TestChanneledTraining:
    def test_recommendation_reads_channel_only(self, channeled_app, mesh8):
        from predictionio_tpu.models import recommendation as R
        ds = R.RecommendationDataSource(R.DataSourceParams(
            app_name="chapp", channel_name="mobile"))
        td = ds.read_training()
        users = {r.user for r in td.ratings}
        assert "decoy" not in users and len(users) == 6

    def test_unknown_channel_raises(self, channeled_app):
        from predictionio_tpu.models import recommendation as R
        ds = R.RecommendationDataSource(R.DataSourceParams(
            app_name="chapp", channel_name="nope"))
        with pytest.raises(ValueError, match="channel"):
            ds.read_training()


class TestChanneledServeTime:
    def test_ecommerce_seen_items_respect_channel(self, channeled_app,
                                                  mesh8):
        from predictionio_tpu.models import ecommerce as E
        app_id, chan_id = channeled_app
        ev = Storage.get_events()
        # u0 saw i0 on the mobile channel only
        ev.insert(Event(event="view", entity_type="user", entity_id="u0",
                        target_entity_type="item", target_entity_id="i0"),
                  app_id, chan_id)
        algo = E.ECommAlgorithm(E.ECommAlgorithmParams(
            app_name="chapp", channel_name="mobile", unseen_only=True,
            seen_events=("view",)))
        assert algo._seen_items("u0") == ["i0"]
        # default-channel algo must NOT see it
        algo_default = E.ECommAlgorithm(E.ECommAlgorithmParams(
            app_name="chapp", unseen_only=True, seen_events=("view",)))
        assert algo_default._seen_items("u0") == []

    def test_ecommerce_unavailable_items_respect_channel(self,
                                                         channeled_app):
        from predictionio_tpu.models import ecommerce as E
        app_id, chan_id = channeled_app
        Storage.get_events().insert(
            Event(event="$set", entity_type="constraint",
                  entity_id="unavailableItems",
                  properties=DataMap({"items": ["i1"]})), app_id, chan_id)
        import numpy as np
        from predictionio_tpu.data.bimap import EntityIdIxMap
        from predictionio_tpu.ops.similarity import (ItemCategories,
                                                     pack_available)

        def synced_bits(**params):
            """The availability bitmap after one sync from the store."""
            ix = EntityIdIxMap.build(["i0", "i1", "i2"])
            model = E.ECommerceModel(
                rank=2, user_factors=np.zeros((1, 2), np.float32),
                item_factors=np.zeros((3, 2), np.float32),
                item_factors_normalized=np.zeros((3, 2), np.float32),
                user_ix=EntityIdIxMap.build(["u0"]), item_ix=ix, items={},
                item_categories=ItemCategories.from_sets([None] * 3))
            algo = E.ECommAlgorithm(E.ECommAlgorithmParams(
                app_name="chapp", **params))
            filters = model.filter_data()
            algo._sync_unavailable(model, filters)
            return filters, ix

        filters, ix = synced_bits(channel_name="mobile")
        assert filters.unavailable_tag is not None
        np.testing.assert_array_equal(
            filters.available_bits,
            pack_available(filters._rows, [ix["i1"]]))
        filters, _ = synced_bits()      # the default channel holds no $set
        assert filters.unavailable_tag is None
        np.testing.assert_array_equal(
            filters.available_bits, pack_available(filters._rows, ()))

"""P-model serve path: factor tables stay model-sharded at query time
(ops/als.recommend_products_sharded + models/recommendation.MeshALSAlgorithm)
— a table bigger than one device's HBM must be servable without
replication.
"""

import numpy as np
import pytest

from predictionio_tpu.ops.als import (ALSConfig, als_train,
                                      recommend_products,
                                      recommend_products_sharded)
from predictionio_tpu.ops.ratings import RatingsCOO
from predictionio_tpu.parallel.mesh import make_mesh, use_mesh


@pytest.fixture(scope="module")
def trained(mesh8):
    rng = np.random.default_rng(7)
    n_u, n_i, nnz = 48, 32, 800
    ui = rng.integers(0, n_u, nnz).astype(np.int32)
    ii = rng.integers(0, n_i, nnz).astype(np.int32)
    vv = (1 + 4 * rng.random(nnz)).astype(np.float32)
    ratings = RatingsCOO(ui, ii, vv, n_u, n_i)
    model = als_train(ratings, ALSConfig(rank=8, iterations=4, lam=0.1,
                                         seed=1, work_budget=512), mesh8)
    return model


class TestShardedServe:
    def test_matches_replicated_topk(self, trained, mesh8):
        """Sharded two-phase ranking returns the same items/scores as the
        replicated single-device path."""
        mp_mesh = make_mesh(model_parallelism=4)
        for user in (0, 7, 23):
            s_rep, i_rep = recommend_products(trained, user, 5)
            s_sh, i_sh = recommend_products_sharded(trained, user, 5,
                                                    mesh=mp_mesh)
            np.testing.assert_array_equal(i_sh, i_rep)
            np.testing.assert_allclose(s_sh, s_rep, rtol=1e-5, atol=1e-5)

    def test_k_exceeds_shard_rows(self, trained):
        """k larger than a shard's row count must still return min(k,
        n_items) results (review finding: k_eff used to cap at
        shard_rows)."""
        mp_mesh = make_mesh(model_parallelism=8)  # 4 rows/shard after pad
        k = 20
        s_rep, i_rep = recommend_products(trained, 5, k)
        s_sh, i_sh = recommend_products_sharded(trained, 5, k, mesh=mp_mesh)
        assert len(i_sh) == k
        np.testing.assert_array_equal(i_sh, i_rep)

    def test_allowed_mask(self, trained):
        """Category-style candidate masks apply on the sharded path."""
        mp_mesh = make_mesh(model_parallelism=4)
        allowed = np.zeros(trained.n_items, dtype=bool)
        allowed[[1, 5, 9, 13]] = True
        _, idx = recommend_products_sharded(trained, 2, 3, mesh=mp_mesh,
                                            allowed_mask=allowed)
        assert set(idx).issubset({1, 5, 9, 13})

    def test_exclude(self, trained):
        mp_mesh = make_mesh(model_parallelism=4)
        _, i_all = recommend_products_sharded(trained, 3, 5, mesh=mp_mesh)
        excl = i_all[:2]
        _, i_ex = recommend_products_sharded(trained, 3, 5, mesh=mp_mesh,
                                             exclude=excl)
        assert not set(excl).intersection(i_ex)

    def test_tables_actually_sharded(self, trained):
        """The resident device arrays are sharded over the model axis, not
        replicated: each shard holds 1/mp of the rows."""
        from predictionio_tpu.utils.device_cache import cached_put_padded
        mp_mesh = make_mesh(model_parallelism=4)
        V = cached_put_padded(trained.item_factors,
                              mp_mesh.model_sharded(2), 4)
        shard_shapes = {s.data.shape for s in V.addressable_shards}
        assert shard_shapes == {(V.shape[0] // 4, trained.rank)}

    def test_mesh_algorithm_end_to_end(self, trained, mesh8):
        """MeshALSAlgorithm trains model-sharded and serves through the
        sharded path under a model-parallel mesh."""
        from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
        from predictionio_tpu.models import recommendation as R

        mp_mesh = make_mesh(model_parallelism=2)
        with use_mesh(mp_mesh):
            rng = np.random.default_rng(1)
            n_u, n_i, nnz = 24, 16, 300
            coo = RatingsCOO(
                rng.integers(0, n_u, nnz).astype(np.int32),
                rng.integers(0, n_i, nnz).astype(np.int32),
                (1 + 4 * rng.random(nnz)).astype(np.float32), n_u, n_i)
            pd = R.PreparedData(
                coo,
                EntityIdIxMap(BiMap({f"u{i}": i for i in range(n_u)})),
                EntityIdIxMap(BiMap({f"i{i}": i for i in range(n_i)})))
            algo = R.MeshALSAlgorithm(R.ALSAlgorithmParams(
                rank=4, num_iterations=3, lam=0.1, seed=0))
            assert algo.placement == "mesh"
            model = algo.train(pd)
            res = algo.predict(model, R.Query(user="u3", num=3))
            assert len(res.item_scores) == 3
            assert all(s.item.startswith("i") for s in res.item_scores)
            # sharded model persists via the sharded-checkpoint manifest
            from predictionio_tpu.core.persistence import PersistentModel
            assert isinstance(algo.make_persistent_model(model),
                              PersistentModel)

    def test_sharded_checkpoint_round_trip(self, tmp_path, monkeypatch):
        """ShardedALSModelCheckpoint: save -> manifest -> load restores a
        model that predicts identically, without retraining."""
        import numpy as np

        from predictionio_tpu.core.persistence import (
            PersistentModelManifest, load_persistent_model)
        from predictionio_tpu.data.bimap import BiMap, EntityIdIxMap
        from predictionio_tpu.models import recommendation as R
        from predictionio_tpu.ops.als import ALSModel

        monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
        rng = np.random.default_rng(2)
        als = ALSModel(rng.standard_normal((12, 4)).astype(np.float32),
                       rng.standard_normal((9, 4)).astype(np.float32), 4)
        model = R.RecommendationModel(
            als,
            EntityIdIxMap(BiMap({f"u{i}": i for i in range(12)})),
            EntityIdIxMap(BiMap({f"i{i}": i for i in range(9)})))
        ckpt = R.ShardedALSModelCheckpoint(model)
        assert ckpt.save("inst42", None)
        manifest = PersistentModelManifest(type(ckpt).loader_name())
        restored = load_persistent_model(manifest, "inst42", None)
        np.testing.assert_allclose(restored.als.user_factors,
                                   als.user_factors, rtol=1e-6)
        np.testing.assert_allclose(restored.als.item_factors,
                                   als.item_factors, rtol=1e-6)
        assert restored.user_ix["u7"] == 7
        assert restored.item_ix.id_of(3) == "i3"
        algo = R.MeshALSAlgorithm(R.ALSAlgorithmParams(rank=4))
        a = algo.predict(model, R.Query(user="u1", num=3))
        b = algo.predict(restored, R.Query(user="u1", num=3))
        assert [s.item for s in a.item_scores] == \
            [s.item for s in b.item_scores]

"""Compile/cost attribution (ISSUE 6 tentpole piece 3): executable
labels over real jit dispatches, cache hit/miss accounting,
cost_analysis gauges, and the per-resident-table HBM samples."""

import numpy as np
import pytest

from predictionio_tpu.obs import costmon
from predictionio_tpu.obs.metrics import get_registry
from predictionio_tpu.utils import device_cache


@pytest.fixture(autouse=True)
def installed():
    costmon.install()


def _seconds(label):
    return costmon.compile_seconds_by_executable().get(label, 0.0)


def _counts(label):
    c = costmon.cache_counts()
    return (c["hits"].get(label, 0), c["misses"].get(label, 0))


class TestAttribution:
    def test_real_compile_attributed_to_label(self):
        import jax
        import jax.numpy as jnp

        # a shape unique to this test so the first call really compiles
        x = jnp.ones((17, 3))
        fn = jax.jit(lambda a: (a * 2.0).sum(axis=0))
        before_s = _seconds("test_exec")
        _, before_miss = _counts("test_exec")
        with costmon.executable("test_exec"):
            fn(x).block_until_ready()
        assert _seconds("test_exec") > before_s
        assert _counts("test_exec")[1] == before_miss + 1
        # warm call: cache hit, no new compile seconds
        mid_s = _seconds("test_exec")
        hits_before, _ = _counts("test_exec")
        with costmon.executable("test_exec"):
            fn(x).block_until_ready()
        assert _seconds("test_exec") == mid_s
        assert _counts("test_exec")[0] == hits_before + 1

    def test_defer_to_outer_keeps_operator_label(self):
        import jax
        import jax.numpy as jnp

        x = jnp.ones((19, 5))
        fn = jax.jit(lambda a: (a + 1.0).mean())
        inner_before = _seconds("inner_exec")
        outer_hm = _counts("outer_exec")
        with costmon.executable("outer_exec"):
            with costmon.executable("inner_exec", defer_to_outer=True):
                fn(x).block_until_ready()
        assert _seconds("outer_exec") > 0
        assert _seconds("inner_exec") == inner_before
        # the deferred inner scope must not double-count: exactly ONE
        # miss lands, on the outer label
        hits, misses = _counts("outer_exec")
        assert (hits, misses) == (outer_hm[0], outer_hm[1] + 1)
        assert _counts("inner_exec") == (0, 0)

    def test_inner_label_wins_without_defer(self):
        import jax
        import jax.numpy as jnp

        x = jnp.ones((23, 2))
        fn = jax.jit(lambda a: a.min())
        with costmon.executable("outer2_exec"):
            with costmon.executable("inner2_exec"):
                fn(x).block_until_ready()
        assert _seconds("inner2_exec") > 0

    def test_listener_ignores_non_compile_events(self):
        before = _seconds("unlabeled")
        stages = costmon.compile_stage_seconds().get("unlabeled", {})
        costmon._on_duration("/jax/core/some_trace_duration", 5.0)
        assert _seconds("unlabeled") == before
        costmon._on_duration("/jax/core/compile/"
                             "backend_compile_duration", 0.25)
        assert _seconds("unlabeled") == pytest.approx(before + 0.25)
        # a backend compile with no cache event is a compile, all of it
        after = costmon.compile_stage_seconds()["unlabeled"]
        assert after["compile"] == pytest.approx(
            stages.get("compile", 0.0) + 0.25)
        assert after["cache_load"] == stages.get("cache_load", 0.0)

    def test_one_family_by_stage_and_one_counter_by_outcome(self):
        import jax
        import jax.numpy as jnp
        x = jnp.ones((13, 7))
        with costmon.executable("stage_exec"):
            jax.jit(lambda a: (a * 3.0).max())(x).block_until_ready()
        fam = get_registry().get("pio_compile_seconds_total")
        stages = {labels["stage"] for labels, _ in fam.samples()
                  if labels and labels["executable"] == "stage_exec"}
        assert stages == {"trace", "lower", "compile", "cache_load"}
        req = get_registry().get("pio_compile_requests_total")
        got = {labels["outcome"]: v for labels, v in req.samples()
               if labels and labels["executable"] == "stage_exec"}
        assert got == {"compiled": 1.0}
        # the duplicates are gone
        for gone in ("pio_compile_executable_seconds_total",
                     "pio_compile_pcache_hits_total",
                     "pio_jax_compiles_total",
                     "pio_jax_compile_seconds_total"):
            assert get_registry().get(gone) is None


class TestCostAnalysis:
    def test_analyze_jit_banks_flops_and_bytes(self):
        import jax.numpy as jnp

        got = costmon.analyze_jit(
            "analysis_exec", lambda a, b: a @ b,
            jnp.ones((8, 4)), jnp.ones((4, 8)))
        assert got is not None and got["flops"] > 0
        flops = get_registry().get("pio_executable_flops")
        sample = {labels["executable"]: v
                  for labels, v in flops.samples()}
        assert sample["analysis_exec"] == got["flops"]


class TestHbmTableGauge:
    def test_resident_sizes_and_samples(self):
        key = np.ones((16, 4), dtype=np.float32)
        payload = {"table": np.zeros((32, 8), dtype=np.float32),
                   "pair": (np.zeros(4, dtype=np.float32), None)}
        device_cache.put_resident("test_slot", (key,), payload)
        try:
            sizes = device_cache.resident_sizes()
            assert sizes["test_slot"] == 32 * 8 * 4 + 4 * 4
            fam = get_registry().get("pio_hbm_table_bytes")
            samples = {labels["table"]: v
                       for labels, v in fam.samples()}
            assert samples["test_slot"] == float(32 * 8 * 4 + 4 * 4)
        finally:
            device_cache.drop_resident("test_slot")

    def test_dropped_slot_leaves_no_sample(self):
        key = np.ones((4, 4), dtype=np.float32)
        device_cache.put_resident("test_slot2", (key,), {"t": key})
        device_cache.drop_resident("test_slot2")
        assert "test_slot2" not in device_cache.resident_sizes()


class TestBenchViews:
    def test_cache_counts_shape(self):
        c = costmon.cache_counts()
        assert set(c) == {"hits", "misses"}
        for d in c.values():
            for k, v in d.items():
                assert isinstance(k, str) and v >= 0

"""Prometheus exposition conformance for the obs metrics registry
(ISSUE 2 satellite): label escaping, histogram _bucket/_sum/_count and
le ordering, and the registry round-trip — every metric a server
registers appears in its rendered /metrics output."""

import math
import re

import pytest

from predictionio_tpu.obs.metrics import (DEFAULT_BUCKETS, Histogram,
                                          MetricsRegistry)


class TestLabelEscaping:
    def _render_one_label(self, value):
        r = MetricsRegistry()
        g = r.gauge("esc_gauge", "h", labelnames=("k",))
        g.labels(k=value).set(1)
        return r.render(include_parent=False)

    def test_backslash(self):
        text = self._render_one_label("a\\b")
        assert 'esc_gauge{k="a\\\\b"} 1' in text

    def test_quote(self):
        text = self._render_one_label('say "hi"')
        assert 'esc_gauge{k="say \\"hi\\""} 1' in text

    def test_newline(self):
        text = self._render_one_label("line1\nline2")
        assert 'esc_gauge{k="line1\\nline2"} 1' in text
        # the sample must stay one exposition line
        for line in text.splitlines():
            if line.startswith("esc_gauge{"):
                assert "\n" not in line

    def test_help_escapes_newline(self):
        r = MetricsRegistry()
        r.counter("c_total", "first\nsecond")
        text = r.render(include_parent=False)
        assert "# HELP c_total first\\nsecond" in text


class TestHistogramExposition:
    def _hist(self):
        r = MetricsRegistry()
        h = r.histogram("lat_seconds", "h")
        for v in (0.0001, 0.002, 0.03, 0.4, 7.0, 99.0):
            h.observe(v)
        return r.render(include_parent=False), h

    def test_components_present(self):
        text, h = self._hist()
        assert "# TYPE lat_seconds histogram" in text
        assert "lat_seconds_sum" in text
        assert "lat_seconds_count 6" in text
        assert 'lat_seconds_bucket{le="+Inf"} 6' in text

    def test_le_ascending_and_cumulative(self):
        text, h = self._hist()
        les, counts = [], []
        for m in re.finditer(
                r'lat_seconds_bucket\{le="([^"]+)"\} (\d+)', text):
            les.append(math.inf if m.group(1) == "+Inf"
                       else float(m.group(1)))
            counts.append(int(m.group(2)))
        assert les == sorted(les), "le bounds must ascend"
        assert les[-1] == math.inf, "+Inf bucket must be last"
        assert counts == sorted(counts), "bucket counts are cumulative"
        assert counts[-1] == 6
        assert les[:-1] == sorted(DEFAULT_BUCKETS)

    def test_sum_matches_observations(self):
        _, h = self._hist()
        assert h.sum == pytest.approx(0.0001 + 0.002 + 0.03 + 0.4
                                      + 7.0 + 99.0)

    def test_percentiles_bracket_observations(self):
        h = Histogram("p", "h")
        for _ in range(100):
            h.observe(0.003)
        # all mass in the (0.0025, 0.005] bucket
        assert 0.0025 <= h.percentile(50) <= 0.005
        assert 0.0025 <= h.percentile(99) <= 0.005
        snap = h.snapshot()
        assert snap["count"] == 100
        assert snap["p50"] <= snap["p95"] <= snap["p99"]

    def test_inf_bucket_clamps_to_last_bound(self):
        h = Histogram("p", "h", buckets=(0.1, 1.0))
        h.observe(50.0)
        assert h.percentile(99) == 1.0

    def test_labeled_histogram_children(self):
        r = MetricsRegistry()
        h = r.histogram("st_seconds", "h", buckets=(1.0, 5.0),
                        labelnames=("stage",))
        h.labels(stage="train").observe(2.0)
        text = r.render(include_parent=False)
        assert 'st_seconds_bucket{stage="train",le="5"} 1' in text
        assert 'st_seconds_count{stage="train"} 1' in text


class TestRegistrySemantics:
    def test_get_or_create_and_type_clash(self):
        r = MetricsRegistry()
        a = r.counter("x_total", "h")
        assert r.counter("x_total", "h") is a
        with pytest.raises(ValueError):
            r.gauge("x_total", "h")

    def test_counter_monotonic(self):
        r = MetricsRegistry()
        c = r.counter("c_total", "h")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_parent_chain_and_shadowing(self):
        parent = MetricsRegistry()
        parent.counter("shared_total", "h").inc(7)
        parent.counter("parent_only_total", "h").inc(1)
        child = MetricsRegistry(parent=parent)
        child.counter("shared_total", "h").inc(2)
        text = child.render()
        assert "shared_total 2" in text          # own shadows parent
        assert "shared_total 7" not in text
        assert "parent_only_total 1" in text     # parent rides along

    def test_func_collector_survives_raising_fn(self):
        r = MetricsRegistry()
        r.gauge_func("boom", "h", lambda: 1 / 0)
        r.counter("ok_total", "h").inc()
        text = r.render(include_parent=False)
        assert "ok_total 1" in text              # scrape not poisoned
        assert "# TYPE boom gauge" in text

    def test_int_values_render_unsuffixed(self):
        r = MetricsRegistry()
        r.counter_func("n_total", "h", lambda: 3)
        assert "n_total 3\n" in r.render(include_parent=False)


class _Req:
    params = {}


class TestServerRoundTrip:
    """Every metric family a server's registry knows appears in its
    rendered /metrics output — the no-hand-built-sample-lists
    guarantee."""

    def _assert_all_families_rendered(self, registry, text):
        names = [fam[0] for fam in registry.collect()]
        assert names, "registry should not be empty"
        for name in names:
            assert f"# TYPE {name} " in text, f"{name} missing"

    def test_engine_server_metrics_roundtrip(self):
        from predictionio_tpu.serving.server import (EngineServer,
                                                     ServerConfig)
        s = EngineServer(ServerConfig(port=0, micro_batch=4))
        try:
            text = s._metrics(_Req).body
            self._assert_all_families_rendered(s.metrics, text)
            # the serving histograms ride the same registry
            assert "# TYPE pio_engine_query_seconds histogram" in text
            assert ("# TYPE pio_engine_batch_wait_seconds histogram"
                    in text)
            # process-wide families ride the parent chain, the compile
            # account's among them (ISSUE 37)
            assert "pio_jax_host_to_device_bytes_total" in text
            assert "# TYPE pio_compile_seconds_total counter" in text
            assert "# TYPE pio_compile_requests_total counter" in text
            assert "pio_jax_compiles_total" not in text
        finally:
            if s.batcher is not None:
                s.batcher.stop()

    def test_event_server_metrics_roundtrip(self, tmp_env):
        from predictionio_tpu.data.api.event_server import (
            EventServer, EventServerConfig)
        s = EventServer(EventServerConfig(port=0, stats=True))
        text = s._metrics(_Req).body
        self._assert_all_families_rendered(s.metrics, text)
        assert "# TYPE pio_event_write_seconds histogram" in text

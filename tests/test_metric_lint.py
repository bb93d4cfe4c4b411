"""Metric-name lint (ISSUE 6 satellite): conventions enforced over a
FULL scrape of both servers, table-driven — every counter ends in
``_total``, every histogram exposes ``_bucket``/``_sum``/``_count``,
and no name is registered as different types across the process
registry and the per-server child registries (the scrape-breaking
duplicate-registration bug)."""

import re

import pytest

from predictionio_tpu.data.api.event_server import (EventServer,
                                                    EventServerConfig)
from predictionio_tpu.obs.metrics import Histogram, get_registry
from predictionio_tpu.serving.server import EngineServer, ServerConfig


@pytest.fixture(scope="module")
def registries():
    """Both servers constructed in-process (never started): every
    family each one mounts, plus the process-wide registry both chain
    to. Module-scoped — construction is the expensive part."""
    engine = EngineServer(ServerConfig(ip="127.0.0.1", port=0))
    events = EventServer(EventServerConfig(ip="127.0.0.1", port=0,
                                           stats=True))
    # exercise lazily-registered process families so the scrape is full
    from predictionio_tpu.obs import costmon
    from predictionio_tpu.obs.flight import FLIGHT
    from predictionio_tpu.obs.slo import lock_probe
    costmon.install()
    lock_probe("lint")
    FLIGHT.record("lint")
    FLIGHT._register_metrics()
    yield {"engine_server": engine.metrics,
           "event_server": events.metrics,
           "process": get_registry()}
    if engine.batcher is not None:
        engine.batcher.stop()


def _families(reg):
    return reg.collect(include_parent=True)


class TestNamingConventions:
    def test_every_counter_ends_in_total(self, registries):
        offenders = [
            (where, name)
            for where, reg in registries.items()
            for name, mtype, _help, _samples in _families(reg)
            if mtype == "counter" and not name.endswith("_total")]
        assert not offenders, f"counters missing _total: {offenders}"

    def test_histograms_expose_bucket_sum_count(self, registries):
        for where, reg in registries.items():
            for name, mtype, _help, samples in _families(reg):
                if mtype != "histogram":
                    continue
                suffixes = {s[0] for s in samples}
                assert {"_bucket", "_sum", "_count"} <= suffixes, (
                    f"{where}:{name} exposes only {suffixes}")
                # every bucket series carries le=, +Inf present
                les = [s[1]["le"] for s in samples
                       if s[0] == "_bucket"]
                assert les and "+Inf" in les, f"{where}:{name}"

    def test_metric_names_are_prometheus_legal(self, registries):
        legal = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
        for where, reg in registries.items():
            for name, _mtype, _help, _samples in _families(reg):
                assert legal.match(name), f"{where}:{name}"


class TestNoDuplicateRegistrations:
    def test_one_type_per_name_across_registries(self, registries):
        seen = {}
        for where, reg in registries.items():
            for name, mtype, _help, _samples in _families(reg):
                prev = seen.setdefault(name, (where, mtype))
                assert prev[1] == mtype, (
                    f"{name} is a {prev[1]} in {prev[0]} but a "
                    f"{mtype} in {where}")

    def test_no_repeated_type_line_in_one_scrape(self, registries):
        for where, reg in registries.items():
            typed = re.findall(r"^# TYPE (\S+) ", reg.render(),
                               flags=re.M)
            dupes = {n for n in typed if typed.count(n) > 1}
            assert not dupes, f"{where} scrape TYPEs twice: {dupes}"

    def test_child_shadowing_preserves_type(self, registries):
        """A name present in both a child and the process registry
        must be the same family type (shadowing is allowed, type
        clashes are not)."""
        proc = {name: mtype for name, mtype, _h, _s
                in registries["process"].collect()}
        for where in ("engine_server", "event_server"):
            for name, mtype, _h, _s in registries[where].collect(
                    include_parent=False):
                if name in proc:
                    assert proc[name] == mtype, (f"{where}:{name} "
                                                 "shadows with a "
                                                 "different type")


class TestExemplarConformance:
    """ISSUE 11 satellite: OpenMetrics exemplars — rendered ONLY on
    ``_bucket`` lines, correctly escaped, and never breaking the
    line-oriented parse of a full scrape of either server."""

    EXEMPLAR_RE = re.compile(
        r'^\S+_bucket\{[^}]*\} \S+ '
        r'# \{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
        r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\} '
        r'[0-9.eE+-]+( [0-9.]+)?$')

    @pytest.fixture(autouse=True)
    def _seed_exemplars(self, registries):
        """Observe inside a trace so both servers' scrapes actually
        carry exemplar suffixes."""
        from predictionio_tpu.obs.trace import TRACER
        with TRACER.trace("lint_exemplar") as t:
            t.discard = True
            for reg in registries.values():
                for name, mtype, _h, _s in reg.collect(
                        include_parent=False):
                    fam = reg.get(name)
                    if isinstance(fam, Histogram) \
                            and not fam.labelnames:
                        fam.observe(0.003)

    def test_exemplars_only_on_bucket_lines(self, registries):
        for where, reg in registries.items():
            for line in reg.render(exemplars=True).splitlines():
                if " # {" not in line:
                    continue
                assert "_bucket{" in line, (
                    f"{where}: exemplar on a non-bucket line: {line}")
                assert self.EXEMPLAR_RE.match(line), (
                    f"{where}: malformed exemplar: {line}")

    def test_exemplars_present_after_traced_observe(self, registries):
        scrape = registries["engine_server"].render(exemplars=True)
        assert " # {" in scrape, "no exemplar landed in the scrape"
        assert 'trace_id="' in scrape
        # OpenMetrics bodies terminate with the EOF marker
        assert scrape.rstrip("\n").endswith("# EOF")

    def test_default_render_is_classic_parser_safe(self, registries):
        """A stock 0.0.4 scraper must never see an exemplar suffix:
        the default render drops them (and the EOF marker) even when
        the histograms carry exemplars."""
        for where, reg in registries.items():
            scrape = reg.render()
            assert " # {" not in scrape, (
                f"{where}: exemplar leaked into the classic render")
            assert "# EOF" not in scrape

    def test_exemplar_escaping(self):
        """A trace id carrying quote/backslash/newline must render
        with the label-value escaping rules (same as sample labels)."""
        from predictionio_tpu.obs.metrics import MetricsRegistry
        from predictionio_tpu.obs.trace import Tracer
        tracer = Tracer()
        reg = MetricsRegistry()
        h = reg.histogram("lint_escape_seconds", "h")
        evil = 'a"b\\c\nd'
        import predictionio_tpu.obs.metrics as m
        old = m._trace_id_fn
        m._trace_id_fn = lambda: evil
        try:
            h.observe(0.003)
        finally:
            m._trace_id_fn = old
        line = next(l for l in reg.render(exemplars=True).splitlines()
                    if " # {" in l)
        assert '\\"' in line and "\\\\" in line and "\\n" in line
        assert "\n" not in line
        _ = tracer  # silence unused

    def test_scrape_still_line_parseable(self, registries):
        """Every non-comment line still splits into
        name{labels} value [exemplar] — the minimal property any
        Prometheus/OpenMetrics scraper relies on."""
        sample_re = re.compile(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.eE+naif-]+"
            r"( # \{.*\} \S+( \S+)?)?$")
        for where, reg in registries.items():
            for exemplars in (False, True):
                for line in reg.render(exemplars=exemplars).splitlines():
                    if not line or line.startswith("#"):
                        continue
                    assert sample_re.match(line), f"{where}: {line!r}"

    def test_stats_histogram_block_carries_exemplars(self, registries):
        """The /stats.json histogram view names the same trace ids the
        scrape exposes."""
        fam = registries["engine_server"].get("pio_engine_query_seconds")
        assert isinstance(fam, Histogram)
        snap = fam.snapshot()
        assert "exemplars" in snap
        for le, ex in snap["exemplars"].items():
            assert set(ex) >= {"traceId", "value"}


class TestFleetFederationLint:
    """ISSUE 13 satellite: the federated ``/fleet/metrics`` merge must
    itself pass the metric lint — {role,pid} relabeling yields no
    duplicate or type-clashing series, HELP/TYPE once per family, and
    the body stays classic-0.0.4-parser safe (exemplar suffixes never
    survive federation: members are scraped through the default
    render)."""

    SAMPLE_RE = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? -?[0-9.eE+naif-]+$")

    @pytest.fixture(scope="class")
    def federated(self, registries):
        import os

        from predictionio_tpu.obs import fleet
        from predictionio_tpu.utils.http import (HttpServer, Response,
                                                 Router)
        from predictionio_tpu.utils.prometheus import CONTENT_TYPE

        # ISSUE 17: seed a tenant-labeled device-time series so the
        # federation exercises the tenant dimension — the {role,pid}
        # relabeling must PRESERVE an existing tenant label
        from predictionio_tpu.obs import costmon
        from predictionio_tpu.obs.tenantctx import register_tenant
        register_tenant("lint-tenant")
        costmon.install()
        st = costmon._device_state("lint_exec", "lint-tenant")
        st.device_s.inc(0.001)
        st.dispatch_s.inc(0.001)
        st.syncs.inc()

        def serve(reg):
            r = Router()
            r.add("GET", "/metrics",
                  lambda req: Response(200, reg.render(),
                                       content_type=CONTENT_TYPE))
            srv = HttpServer(r, "127.0.0.1", 0)
            srv.start()
            return srv

        servers = [serve(registries["engine_server"]),
                   serve(registries["event_server"]),
                   serve(registries["engine_server"])]
        # co-located pair (same pid, distinct roles) + a second
        # engine_server in "another process" (pid 1): the two collision
        # shapes federation must keep apart
        members = [
            {"memberId": f"engine_server-{os.getpid()}",
             "role": "engine_server", "pid": os.getpid(),
             "host": "127.0.0.1", "port": servers[0].port},
            {"memberId": f"event_server-{os.getpid()}",
             "role": "event_server", "pid": os.getpid(),
             "host": "127.0.0.1", "port": servers[1].port},
            {"memberId": "engine_server-1", "role": "engine_server",
             "pid": 1, "host": "127.0.0.1", "port": servers[2].port},
        ]
        text = fleet.federate_metrics(members)
        for s in servers:
            s.stop()
        return text

    def test_no_duplicate_series(self, federated):
        seen = {}
        for line in federated.splitlines():
            if not line or line.startswith("#"):
                continue
            m = self.SAMPLE_RE.match(line)
            assert m, f"unparseable federated line: {line!r}"
            key = (m.group(1), m.group(2))
            assert key not in seen, f"duplicate series: {line!r}"
            seen[key] = line

    def test_one_type_per_name_no_clashes(self, federated):
        typed = re.findall(r"^# TYPE (\S+) (\S+)$", federated,
                           flags=re.M)
        names = [n for n, _t in typed]
        assert len(names) == len(set(names)), "TYPE declared twice"
        # the shared codebase means no member can clash types, so the
        # drop-on-clash path must never have fired
        assert "type clashes" not in federated

    def test_every_member_sample_carries_role_and_pid(self, federated):
        for line in federated.splitlines():
            if (not line or line.startswith("#")
                    or line.startswith("pio_fleet_member_up")):
                continue
            assert re.match(r'^\S+?\{role="[a-z_]+",pid="\d+"', line), \
                f"sample without role/pid relabel: {line!r}"

    def test_classic_parser_safe(self, federated):
        assert " # {" not in federated      # no exemplar suffixes
        assert "# EOF" not in federated
        for line in federated.splitlines():
            if not line:
                continue
            assert line.startswith("#") or self.SAMPLE_RE.match(line), \
                f"{line!r}"

    def test_counter_convention_survives_federation(self, federated):
        for name, mtype in re.findall(r"^# TYPE (\S+) (\S+)$",
                                      federated, flags=re.M):
            if mtype == "counter":
                assert name.endswith("_total"), name

    def test_member_up_gauge_present(self, federated):
        assert "# TYPE pio_fleet_member_up gauge" in federated
        ups = [l for l in federated.splitlines()
               if l.startswith("pio_fleet_member_up{")]
        assert len(ups) == 3
        assert all(l.endswith(" 1") for l in ups)

    def test_tenant_label_survives_relabeling(self, federated):
        # ISSUE 17: federation prepends {role,pid} but must PRESERVE a
        # member's own tenant label — cost attribution has to stay
        # queryable fleet-wide as {role,pid,tenant}.
        rows = [l for l in federated.splitlines()
                if l.startswith("pio_device_time_seconds_total{")
                and 'tenant="lint-tenant"' in l]
        assert rows, "seeded tenant series lost in federation"
        for line in rows:
            assert re.match(r'^\S+?\{role="[a-z_]+",pid="\d+",', line), \
                f"role/pid not first on tenant row: {line!r}"
            assert 'executable="lint_exec"' in line
            assert self.SAMPLE_RE.match(line), f"unparseable: {line!r}"
        # the engine_server member is scraped twice (real pid + fake
        # pid 1): same tenant series, distinct after relabeling
        assert len(set(rows)) == len(rows)


class TestTenantLabelLint:
    """ISSUE 17 satellite: every tenant-labeled family shares the ONE
    label name ``tenant``, and the rendered value set stays bounded by
    the registered tenants (plus "" for untenanted process work)."""

    TENANTISH = re.compile(r"tenant", re.I)

    def test_shared_label_name(self, registries):
        for where, reg in registries.items():
            for name, _mtype, _h, _s in _families(reg):
                fam = reg.get(name)
                for ln in getattr(fam, "labelnames", ()) or ():
                    if self.TENANTISH.search(ln):
                        assert ln == "tenant", (
                            f"{where}:{name} labels tenants as {ln!r}; "
                            f"the shared label name is 'tenant'")

    def test_cardinality_bounded_by_registered_tenants(self, registries):
        from predictionio_tpu.obs.tenantctx import registered_tenants
        allowed = registered_tenants() | {""}
        for where, reg in registries.items():
            for m in re.finditer(r'tenant="((?:[^"\\]|\\.)*)"',
                                 reg.render()):
                assert m.group(1) in allowed, (
                    f"{where}: tenant label value {m.group(1)!r} is not "
                    f"a registered tenant — cardinality leak")


class TestIssue6FamiliesPresent:
    """The diagnostics plane's own families ride both scrapes."""

    @pytest.mark.parametrize("name,where", [
        ("pio_lock_wait_seconds", "process"),
        ("pio_flight_records_total", "process"),
        ("pio_flight_dropped_total", "process"),
        ("pio_compile_seconds_total", "process"),
        ("pio_compile_requests_total", "process"),
        ("pio_compile_cache_hits_total", "process"),
        ("pio_compile_cache_misses_total", "process"),
        ("pio_hbm_table_bytes", "process"),
        ("pio_engine_query_seconds", "engine_server"),
        ("pio_event_write_seconds", "event_server"),
    ])
    def test_family_registered(self, registries, name, where):
        assert registries[where].get(name) is not None

    def test_lock_wait_is_histogram(self, registries):
        fam = registries["process"].get("pio_lock_wait_seconds")
        assert isinstance(fam, Histogram)

"""Test configuration: run JAX on a virtual 8-device CPU mesh.

This is the TPU build's analog of the reference's `local[4]` Spark test mode
(reference: core/src/test/scala/io/prediction/workflow/BaseTest.scala):
distributed behavior is exercised without a cluster by faking 8 devices on
the host CPU.
"""

import os

# Force CPU regardless of the ambient platform: the env var is what
# subprocess-spawning tests hand their children, the jax.config calls
# below are what this process runs under.
os.environ["JAX_PLATFORMS"] = "cpu"
# Hermetic suite: the persistent XLA compile cache (ISSUE 9) is a
# cross-process, cross-RUN disk store — exactly the shared state a
# test run must not depend on (and its background disk writes perturb
# the suite's deadline-bounded storage reads on slow filesystems).
# The compile-plane tests that exercise the cache opt back in
# explicitly against their own tmp dirs. Likewise deploy/swap-time AOT
# warming: dozens of server fixtures would each compile the full
# bucket ladder (~1-2 s apiece); dispatch + background adoption stay
# on, and the canary-warm acceptance tests opt back in.
os.environ.setdefault("PIO_XLA_CACHE", "off")
os.environ.setdefault("PIO_AOT_WARM", "off")
# Likewise the ISSUE 11 runtime-attribution background work: the
# always-on sampling profiler (a 19 Hz stack walker) and the slow-query
# capture (every >250 ms request builds a waterfall — under a saturated
# 2-core CI box MOST requests cross that) add load the suite's
# timing-sensitive tests (hot-swap hammering, scheduler staleness
# windows) must not absorb. Production servers keep both always-on;
# the profiler/slowlog tests opt back in via monkeypatch.
os.environ.setdefault("PIO_PROFILER", "off")
os.environ.setdefault("PIO_SLOW_QUERY_MS", "1e9")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    import jax
    from predictionio_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) == 8, "conftest must run before jax init"
    return make_mesh()


@pytest.fixture()
def tmp_env(tmp_path, monkeypatch):
    """Isolated storage environment rooted at a tmp dir."""
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "pio"))
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_METADATA_NAME", "pio_meta")
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_METADATA_SOURCE", "SQLITE")
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME", "pio_event")
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE", "SQLITE")
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_MODELDATA_NAME", "pio_model")
    monkeypatch.setenv("PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE", "LOCALFS")
    monkeypatch.setenv("PIO_STORAGE_SOURCES_SQLITE_TYPE", "sqlite")
    monkeypatch.setenv("PIO_STORAGE_SOURCES_SQLITE_URL",
                       str(tmp_path / "pio" / "pio.db"))
    monkeypatch.setenv("PIO_STORAGE_SOURCES_LOCALFS_TYPE", "localfs")
    monkeypatch.setenv("PIO_STORAGE_SOURCES_LOCALFS_HOSTS",
                       str(tmp_path / "pio" / "models"))
    from predictionio_tpu.data.storage import registry
    registry.clear_cache()
    yield tmp_path
    registry.clear_cache()


def pytest_configure(config):
    # advisory marker: no pytest-timeout plugin in this environment; the
    # subprocess-based distributed tests enforce their own deadlines via
    # communicate(timeout=...)
    config.addinivalue_line(
        "markers", "timeout(seconds): advisory wall-clock bound")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` lane")
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection suite (scripts/chaos_smoke.sh); "
        "implies slow so the tier-1 lane never runs it")


def pytest_collection_modifyitems(config, items):
    # chaos tests stay out of the tier-1 `-m 'not slow'` lane without
    # every test double-marking: the chaos marker implies slow
    import pytest as _pytest
    for item in items:
        if item.get_closest_marker("chaos") is not None \
                and item.get_closest_marker("slow") is None:
            item.add_marker(_pytest.mark.slow)

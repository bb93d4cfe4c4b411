"""The timed programs compiled at the real shapes for a described v5e chip,
here, where there is none: what the TPU's compiler refuses (memory above
all) is found before chip time is spent. Nothing runs, so nothing here is a
time. One file, so that one test worker loads the TPU's library."""

import json
import os

import pytest

import tinytree

HBM = 15.75e9          # what the compiler itself allows of the chip's 16 GB


def _config(name):
    with open(os.path.join(tinytree.REPO, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def sds():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)


def _memory(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes, m.temp_size_in_bytes,
            m.output_size_in_bytes - m.alias_size_in_bytes)


def test_serve_kernel_at_the_amazonbooks_buckets_fits(sds):
    import jax.numpy as jnp
    from predictionio_tpu.compile import buckets as B
    from predictionio_tpu.ops import als, readback
    c = _config("rec-amazonbooks14-r200")
    u_b, i_b = B.bucket_rows(c["n_users"]), B.bucket_rows(c["n_items"])
    assert (u_b, i_b) == (1 << 23, 1 << 22)
    k_b = B.bucket_batch(c["serve"]["num"], floor=B.K_FLOOR)
    compiled = als._users_topk_b_packed.lower(
        sds((u_b, c["rank"]), jnp.float32), sds((i_b, c["rank"]), jnp.float32),
        sds((c["serve"]["micro_batch"],), jnp.int32), sds((), jnp.int32),
        k=k_b, p=readback.pack_flag() or 1).compile()
    args, temp, out = _memory(compiled)
    assert args >= (u_b + i_b) * c["rank"] * 4
    assert args + temp + out < HBM
    # the next user bucket, which the program compiles in the background
    # at 95.7% occupancy, cannot fit: the promotion can only fail
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        als._users_topk_b_packed.lower(
            sds((2 * u_b, c["rank"]), jnp.float32),
            sds((i_b, c["rank"]), jnp.float32), sds((16,), jnp.int32),
            sds((), jnp.int32), k=k_b, p=1).compile()


def _sweep(sds, n_out, n_counter, rank, shapes, chunk, steps=2):
    """The program's half-sweep over a plan of `steps` batches of each
    (B, K) in `shapes`, merged `chunk` at a time as als._upload_plan
    merges them."""
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    groups = []
    for B, K in shapes:
        n, b = max(1, steps // chunk), B * min(chunk, steps)
        groups.append((sds((n, b), jnp.int32), sds((n, b, K), jnp.int32),
                       sds((n, b, K), jnp.float32),
                       sds((n, b, K), jnp.float32)))
    return als._solve_sweep.lower(
        sds((n_out, rank), jnp.float32), sds((n_counter, rank), jnp.float32),
        None, tuple(groups), sds((), jnp.float32), sds((), jnp.float32),
        nratings_reg=True, implicit=False, rank=rank,
        compute_dtype="bfloat16", solver="cg_pallas", dual_solve="auto",
        solver_iters=None, dual_iters_cap=None).compile()


# (B, K) of the heaviest steps of each route in the program's plan of the
# goodreads ratings at work_budget 2^20 (ops/ratings.plan_for_users /
# plan_for_items over benchmark/lib/datagen.py's ratings; PERF.md section 4)
GOODREADS_SHAPES = [(131072, 8), (43690, 24), (8738, 120), (5041, 208),
                    (655, 1600), (32, 32000)]


def test_goodreads_half_sweeps_fit_at_the_configurations_sweep_chunk(sds):
    c = _config("rec-goodreads-r200")
    plans = 2 * c["n_ratings"] * 1.1 * 12      # both sides' idx, val, mask
    tables = (c["n_users"] + c["n_items"] + 2) * c["rank"] * 4
    for n_out, n_counter in ((c["n_users"] + 1, c["n_items"] + 1),
                             (c["n_items"] + 1, c["n_users"] + 1)):
        compiled = _sweep(sds, n_out, n_counter, c["rank"], GOODREADS_SHAPES,
                          c["sweep_chunk"], steps=2 * c["sweep_chunk"])
        _args, temp, out = _memory(compiled)
        assert tables + plans + temp + out < HBM


def test_amazonbooks_user_half_sweep_does_not_fit(sds):
    """The fault that keeps rec-amazonbooks14-r200.train out of the
    benchmark (PERF.md, Open questions 1): at rank 200 the sweep copies the
    donated 8.03M-row table into a padded layout, 7.65 GB beside the 5.98 GB
    argument. When the program is mended this test fails, and the cell can
    be added."""
    c = _config("rec-amazonbooks14-r200")
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
        _sweep(sds, c["n_users"] + 1, c["n_items"] + 1, c["rank"],
               [(131072, 8), (906, 104), (101, 208)], 1)


def test_rank_200_tables_are_copied_and_rank_256_are_not(sds):
    """Why: the donated table of a scan of scatters is held as
    {0,1:T(8,128)} at rank 200 and copied to a padded {1,0:T(8,128)}; at a
    rank that is a multiple of 128 it is updated in place."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def sweep(f, c, rows, idx):
        def body(f, b):
            r, i = b
            return f.at[r].set(c[i].sum(axis=1)), None
        return jax.lax.scan(body, f, (rows, idx))[0]

    temps = {}
    for rank in (200, 256):
        compiled = sweep.lower(
            sds((1_000_000, rank), jnp.float32),
            sds((500_000, rank), jnp.float32), sds((10, 4096), jnp.int32),
            sds((10, 4096, 8), jnp.int32)).compile()
        temps[rank] = compiled.memory_analysis().temp_size_in_bytes
    assert temps[200] > 1_000_000 * 256 * 4       # a padded copy, and more
    assert temps[256] < 64 << 20

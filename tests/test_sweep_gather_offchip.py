"""The ALS half-sweep compiled at the goodreads cell's shapes for a described
v5e chip, here, where there is none (PR 30): the step of its gathers. The TPU
compiler runs a gather in steps of 128 or 256 rows and picks by what the row
count leaves in the last 1,024-tile of the index vector; on the chip a step
takes 1.4-1.5 us whatever it holds, so the gather costs 11.6 ns a row at 128
and 6.1 at 256, whatever a row's bytes or tiling (PERF.md section 6; nothing
runs here, so nothing here is a time). `ops/als._upload_plan` pads each batch
by `_gather_pad_rows` systems that solve nothing, which puts the count where
the compiler picks 256 (for tables of 64 columns and more: `_GATHER_MIN_RANK`).
When the compiler's rule moves, these tests fail and `_GATHER_STEP_256` has to
follow it. One file, so that one test worker loads the TPU's library for these
compiles."""

import re

import pytest

N_OUT, N_COUNTER, CHUNK = 876_146, 2_360_651, 2     # the user half-sweep

# rank -> one dual and one primal rung (B, K) of the plan at `CHUNK`, and the
# parent's temp_size_in_bytes for that program (off-chip compile, PR 30,
# commit 6d1ba24: the unpadded program here)
CASES = {
    200: ([(87380, 24), (10082, 208)], 7_179_008_000),
    256: ([(87380, 24), (7942, 264)], 5_385_074_688),
    64: ([(262144, 8), (87380, 24)], 4_121_076_224),
}
ROOM = 8 << 20       # a few more rows a batch; the compiler's scratch moves


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
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _half_sweep(sds, rank, padded):
    """`_solve_sweep` over two scan steps of each rung, the batches as
    `_upload_plan` uploads them on one TPU (`padded`) or anywhere else."""
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    groups = []
    for b, k in CASES[rank][0]:
        b += als._gather_pad_rows(b, k) if padded else 0
        groups.append((sds((2, b), jnp.int32), sds((2, b, k), jnp.int32),
                       sds((2, b, k), jnp.float32),
                       sds((2, b, k), jnp.float32)))
    return als._solve_sweep.lower(
        sds((N_OUT, rank), jnp.float32), sds((N_COUNTER, rank), jnp.float32),
        None, tuple(groups), sds((), jnp.float32), sds((), jnp.float32),
        nratings_reg=True, implicit=False, rank=rank,
        compute_dtype="bfloat16", solver="cg_pallas", dual_solve="auto",
        solver_iters=None, dual_iters_cap=None).compile()


def _gather_steps(compiled):
    """(rows, rows a step) of the half-sweep's row gathers, one a rung."""
    found = []
    for line in compiled.as_text().splitlines():
        if ("kind=kCustom" in line and "pio.sweep.gather" in line
                and "/gather" in line):
            rows = re.search(r"= bf16\[(\d+),", line)
            step = re.search(r'"integer_config":\{"integer":"(\d+)"\}', line)
            found.append((int(rows.group(1)), int(step.group(1))))
    return found


def test_unpadded_rungs_gather_in_steps_of_128_at_rank_200(sds):
    """The fault the padding works around, shown on this compiler: both
    rungs' counts leave the index vector's last tile nearly full."""
    compiled = _half_sweep(sds, 200, padded=False)
    assert _gather_steps(compiled) == [(87380 * 24, 128), (10082 * 208, 128)]
    assert compiled.memory_analysis().temp_size_in_bytes \
        == pytest.approx(CASES[200][1], rel=0.01)


@pytest.mark.parametrize("rank", [64, 200, 256])
def test_padded_rungs_gather_in_steps_of_256(sds, rank):
    from predictionio_tpu.ops import als
    compiled = _half_sweep(sds, rank, padded=True)
    want = [((b + als._gather_pad_rows(b, k)) * k, 256)
            for b, k in CASES[rank][0]]
    assert _gather_steps(compiled) == want
    # a handful of rows more a batch: the program holds what the parent's did
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= CASES[rank][1] + ROOM

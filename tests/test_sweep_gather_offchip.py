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


# -- the half-sweep over row-sharded tables, compiled for a v5e:2x2 (PR 33) --
#
# rec-amazon14-all-r200 on the mesh `model_mesh(4)` builds: both tables
# row-sharded four ways, the batches divided over the same chips
# (`ops/als._solve_sweep_per_chip`). The three widest rungs of each side's
# plan and the users' K 8 rung, three quarters of that side's slots
# (systems and slots a step, `batch_multiple` 4; and L, the rows a chip
# sends each chip in a step, as `_route_group` sets it for the plan at the
# published counts, the same on two seeds: a builder's host run, PR 35), two
# scan steps each, beside a quarter of both whole plans (4.18 GB of
# int32/float32 [B, K] arrays and row ids and 0.74 GB of `send`: scratch
# shapes.py of PR 33, PERF.md section 4).
SHARDED_USERS, SHARDED_ITEMS, CHIPS = 20_980_000, 9_350_000, 4
WIDEST = {"user": [(21848, 48, 66080), (26216, 40, 64800),
                   (11916, 88, 65056), (131072, 8, 20512)],
          # and the rung of the heaviest item, the longest gather of a row
          "item": [(1640, 640, 66224), (2852, 368, 67104),
                   (4372, 240, 66944), (12, 65408, 49440)]}
QUARTER_OF_BOTH_PLANS = (4_180_357_216 + 739_518_464) // 4
HBM = 15.75e9


@pytest.fixture(scope="module")
def mesh4(sds):
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(1, CHIPS), ("data", "model"))

    def shaped(shape, dt, *spec):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, P(*spec)))
    return mesh, shaped


def _sharded_half_sweep(mesh4, side):
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    mesh, shaped = mesh4
    n_out, n_counter = ((SHARDED_USERS, SHARDED_ITEMS) if side == "user"
                        else (SHARDED_ITEMS, SHARDED_USERS))
    both = ("data", "model")
    groups = []
    for b, k, L in WIDEST[side]:
        b += CHIPS * als._gather_pad_rows(b // CHIPS, k)
        groups.append((shaped((2, b), jnp.int32, None, both),
                       shaped((2, b, k), jnp.int32, None, both, None),
                       shaped((2, b, k), jnp.float32, None, both, None),
                       shaped((2, b, k), jnp.float32, None, both, None),
                       shaped((2, CHIPS, CHIPS, L), jnp.int32, None, both,
                              None, None)))
    return als._solve_sweep_per_chip.lower(
        shaped((als.table_rows(n_out, CHIPS), 200), jnp.float32, "model",
               None),
        shaped((als.table_rows(n_counter, CHIPS), 200), jnp.float32,
               "model", None),
        None, tuple(groups), shaped((), jnp.float32), shaped((), jnp.float32),
        nratings_reg=True, implicit=False, rank=200,
        compute_dtype="bfloat16", solver="cg_pallas", dual_solve="auto",
        solver_iters=None, dual_iters_cap=None, mesh=mesh,
        table_axis="model", batch_axes=both).compile()


def _routed_gathers(compiled):
    """{scope: [(rows, rows a step)]} of the per-chip half-sweep's two
    gathers a rung: the owners' (`pio.sweep.gather`) and the placement of
    what arrived (`pio.sweep.gather.place`)."""
    found = {"pio.sweep.gather": [], "pio.sweep.gather.place": []}
    for line in compiled.as_text().splitlines():
        scope = re.search(r"/(pio\.sweep\.gather[a-z.]*)/gather", line)
        if "kind=kCustom" in line and scope:
            rows = re.search(r"= bf16\[(\d+),200\]", line)
            step = re.search(r'"integer_config":\{"integer":"(\d+)"\}', line)
            found[scope.group(1)].append((int(rows.group(1)),
                                          int(step.group(1))))
    return found


# argument_size_in_bytes + temp_size_in_bytes of PR 35's parent (80a925c)
# for these rungs of each side: the [B, K, 256] block every chip gathered
# of a whole step's slots is gone (off-chip compiles, PR 35)
PARENT_PEAK = {"user": 14_005_321_216, "item": 12_073_402_368}


@pytest.mark.parametrize("side", ["user", "item"])
def test_sharded_half_sweep_fits_a_chip_and_holds_no_whole_table(mesh4,
                                                                 side):
    from predictionio_tpu.ops import als
    from predictionio_tpu.parallel.collective_stats import \
        executed_collective_stats
    compiled = _sharded_half_sweep(mesh4, side)
    text = compiled.as_text()
    m = compiled.memory_analysis()
    peak = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert peak + QUARTER_OF_BOTH_PLANS <= HBM
    assert peak < PARENT_PEAK[side]
    n_counter = SHARDED_ITEMS if side == "user" else SHARDED_USERS
    whole = {als.table_rows(n, CHIPS) for n in (SHARDED_USERS,
                                                SHARDED_ITEMS)}
    shard = als.table_rows(n_counter, CHIPS) // CHIPS
    rows_held = {int(r) for r in re.findall(r"\[(\d{6,}),200\]", text)}
    assert not rows_held & whole              # never a whole table's rows
    assert shard in rows_held                 # its own quarter
    # the counterpart shard is read through ONE copy, in the compute dtype
    assert not re.search(rf"= f32\[{shard},200\]\S* copy\(", text)
    assert len(re.findall(rf"= bf16\[{shard},200\]\S* (?:copy|convert)\(",
                          text)) <= 2        # the copy, and its relayout
    # the Pallas CG runs on the chip's own quarter of the systems
    assert "tpu_custom_call" in text
    # both gathers of a step at the compiler's 256-row step: the owners'
    # of CHIPS * L rows, and the placement of a chip's own [B/n, K] slots,
    # which where the rows that arrived are under ~80 MB (the users' K 8
    # rung, the heaviest items') gets a form of its own, step "0", that
    # the chip runs faster still (3.7 ns a row over the mix: PERF.md
    # section 6, PR 35); no chip gathers a whole step's B * K slots
    padded = [(b + CHIPS * als._gather_pad_rows(b // CHIPS, k), k, L)
              for b, k, L in WIDEST[side]]
    found = _routed_gathers(compiled)
    assert found["pio.sweep.gather"] == [(CHIPS * L, 256)
                                         for _b, _k, L in padded]
    assert found["pio.sweep.gather.place"] == [
        (b // CHIPS * k, 256 if CHIPS * L * 400 > 80e6 else 0)
        for b, k, L in padded]
    # only the exchanges the algorithm needs: the rated rows all-to-all
    # from their owners, the solved rows all-gathered, the row ids and the
    # CG counts summed; nothing is reduce-scattered, no index crosses
    ran = executed_collective_stats(compiled)
    assert set(ran) == {"all-to-all", "all-gather", "all-reduce", "total"}
    steps = 2
    # every chip receives CHIPS * L bfloat16 rows a step, at their 200
    # columns (the compiler does not pad them to 256 lanes)
    assert ran["all-to-all"]["bytes"] == steps * sum(
        CHIPS * L * 200 * 2 for _b, _k, L in padded)
    assert ran["all-gather"]["bytes"] == steps * sum(
        b * 200 * 4 for b, _k, _L in padded)
    small = sum(b + 32 for b, _k, _L in padded) * 4 * steps + 64
    assert ran["all-reduce"]["bytes"] <= small


def test_the_sentinel_checks_the_sharded_tables_in_place(mesh4):
    """`als_train` with the sentinel on (the default: `pio train`) at
    rec-amazon14-all-r200: the per-iteration check of a row-sharded table
    reads the shard where it lies, no temporary and no copy, so between
    half-sweeps a chip holds its quarter of both tables and plans and
    nothing more; and a last-good pair is not kept, because it could not
    be: beside one, the user half-sweep would need more than the chip has
    (when this last assertion fails there is room again, and
    `ops/als.als_train`'s rule can go)."""
    import jax
    import jax.numpy as jnp
    from predictionio_tpu.guard import sentinels
    from predictionio_tpu.ops import als
    _mesh, shaped = mesh4
    at_rest = QUARTER_OF_BOTH_PLANS
    for n in (SHARDED_USERS, SHARDED_ITEMS):
        table = shaped((als.table_rows(n, CHIPS), 200), jnp.float32,
                       "model", None)
        m = jax.jit(sentinels._table_stats_impl).lower(
            table).compile().memory_analysis()
        assert m.temp_size_in_bytes <= 1 << 20
        assert m.argument_size_in_bytes <= 1.001 * (
            als.table_rows(n, CHIPS) // CHIPS) * 200 * 4
        at_rest += m.argument_size_in_bytes
    assert at_rest <= 0.5 * HBM                  # 7.1 GB a chip
    tables = at_rest - QUARTER_OF_BOTH_PLANS
    m = _sharded_half_sweep(mesh4, "user").memory_analysis()
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + QUARTER_OF_BOTH_PLANS + tables) > HBM

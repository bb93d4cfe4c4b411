"""ALS training over row-sharded factor tables (`factor_sharding="model"`)
on a forced CPU mesh at small sizes, seeded ratings: the half-sweep written
per chip (ops/als._solve_sweep_per_chip: the plan dealt on the host so that
each chip solves rows of its own shard; each shard gathers the rows the
plan's routing says the others need of it, one all-to-all hands every chip
the rows of its own systems, each a copy of its owner's row, placed by the
plan; the one-chip solve on B/n systems; the solved rows scattered into the
chip's own shard, where they lie).

Every case runs explicit and implicit training on mesh shapes 1x4 and 1x2
(data x model); `layout` and `rows` also on 2x2, where a data axis wider
than 1 divides the batches further and the rows cross within a data row:

  reference   one half-sweep's rows against the configuration's plain
              reference (benchmark/references/als-explicit.py and
              als-implicit.py: Cholesky, float32 at `highest`, nothing of
              the program imported) solving the same systems;
  layout      the same ratings and seed give the same rows on one device
              and on the mesh, within float32 rounding;
  rows        the counterpart rows a chip's systems receive are copies of
              their owners' rows: `table[idx]` bit for bit, in bfloat16;
  shards      after one half-sweep rows of every shard are written, and
              only rows that have ratings;
  program     each device's compiled half-sweep holds Gram and solve
              operands of leading dimension B/n, no operand or temporary
              with a whole table's rows or a whole step's slots, an
              all-to-all and no reduce-scatter or all-gather;
  exchange    `telemetry["exchange_bytes"]` is what the compiled programs'
              collectives say (parallel/collective_stats), and so is the
              gauge `pio_als_exchange_bytes`;
  uneven      a catalogue whose rated rows crowd one shard is dealt with
              more padding and trains the same rows;
  deal_fill   `telemetry["deal_fill"]` and the gauge `pio_als_deal_fill`
              on a row-sharded mesh, neither on one device;
  sentinel    with the sentinel on, row-sharded tables are checked every
              iteration and never copied, and a breach raises.
"""

import importlib.util
import os
import re
import weakref

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RANK, N_USERS, N_ITEMS, NNZ = 16, 301, 157, 6000
LAM, ALPHA = 0.1, 1.0
KINDS = {"explicit": False, "implicit": True}
MESHES = {"1x4": (1, 4), "1x2": (1, 2)}
CASES = [(k, m) for k in KINDS for m in MESHES]
WIDE = dict(MESHES, **{"2x2": (2, 2)})


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_"),
        os.path.join(REPO, "benchmark", "references", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def references():
    return {False: _load("als-explicit"), True: _load("als-implicit")}


@pytest.fixture(scope="module")
def ratings():
    from predictionio_tpu.ops.ratings import RatingsCOO
    rng = np.random.default_rng(33)
    key = rng.choice(N_USERS * N_ITEMS, NNZ, replace=False)
    # users 0..9 rate nothing: their rows must stay as they were
    key = key[key // N_ITEMS >= 10]
    return RatingsCOO((key // N_ITEMS).astype(np.int32),
                      (key % N_ITEMS).astype(np.int32),
                      rng.integers(1, 6, key.size).astype(np.float32),
                      N_USERS, N_ITEMS)


def _mesh(name):
    import jax
    from predictionio_tpu.parallel.mesh import make_mesh
    dp, mp = WIDE[name]
    return make_mesh(devices=jax.devices()[:dp * mp], model_parallelism=mp)


def _cfg(implicit, **kw):
    from predictionio_tpu.ops.als import ALSConfig
    kw = dict(dict(iterations=2), **kw)
    return ALSConfig(rank=RANK, lam=LAM, alpha=ALPHA, seed=3,
                     work_budget=512, implicit_prefs=implicit,
                     sentinel=False, **kw)


@pytest.fixture(scope="module")
def trained(ratings):
    """als_train on one device and on each mesh, with its telemetry."""
    import jax
    from predictionio_tpu.ops import als
    from predictionio_tpu.parallel.mesh import make_mesh
    out = {}
    for kind, implicit in KINDS.items():
        out[kind, "one"] = (als.als_train(
            ratings, _cfg(implicit),
            make_mesh(devices=jax.devices()[:1])), None)
        for name in WIDE:
            tel = {}
            model = als.als_train(
                ratings, _cfg(implicit, factor_sharding="model"),
                _mesh(name), telemetry=tel)
            out[kind, name] = (model, tel)
    return out


@pytest.mark.parametrize("kind,mesh_name",
                         [(k, m) for k in KINDS for m in WIDE])
def test_same_rows_on_one_device_and_on_the_mesh(trained, kind, mesh_name):
    one = trained[kind, "one"][0]
    many, tel = trained[kind, mesh_name]
    dp, n = WIDE[mesh_name]
    assert (tel["table_shards"], tel["batch_shards"]) == (n, dp * n)
    assert tel["n_devices"] == dp * n
    np.testing.assert_allclose(many.user_factors, one.user_factors,
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(many.item_factors, one.item_factors,
                               rtol=0, atol=2e-5)
    # the deal and the routing engaged, and say what they cost and how
    # full they ran
    assert tel["route_s"] > 0 and 0 < tel["route_fill"] <= 1
    assert 0 < tel["deal_fill"] <= 1


@pytest.mark.parametrize("mesh_name", WIDE)
def test_received_rows_are_copies_of_their_owners(ratings, mesh_name):
    """The plan as `_upload_plan` routes and places it, and the fetch a scan
    step makes with it (`_routed_rows`: the owners' gather, the all-to-all,
    the placement), on a bfloat16 table: wherever the mask is 1 the row a
    system's slot receives is `table[idx]`, to the last bit."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from predictionio_tpu.ops import als
    from predictionio_tpu.ops.ratings import plan_for_users
    mesh = _mesh(mesh_name)
    mp = mesh.model_parallelism
    axes = als.plan_axes(mesh, "model")
    chips = als.batch_shards(mesh, "model")
    plan = plan_for_users(ratings, work_budget=512, batch_multiple=chips)
    table = np.asarray(jnp.asarray(
        als._init_factors(N_ITEMS, RANK, 9, 2, mp), jnp.bfloat16))
    V = mesh.put_model_sharded(table)

    @jax.jit
    def fetch(V, place, send):
        def per_chip(v, place, send):
            return jax.lax.map(lambda step: als._routed_rows(
                v, step[1][0], step[0], "model"), (place, send))
        return jax.shard_map(
            per_chip, mesh=mesh.mesh,
            in_specs=(P("model", None), P(None, axes, None),
                      P(None, axes, None, None)),
            out_specs=P(None, axes, None, None), check_vma=False)(
                V, place, send)

    dealt = [group for _block, group
             in als._dealt_groups(plan, 1, False, chips, mp)]
    groups = als._upload_plan(mesh, plan, 1, RANK, "model")
    # one group a rung
    assert len(groups) == len(dealt) == len(
        {b.shape[1] for b in plan.batches}) > 2
    for (_rows, idx, _val, mask), group in zip(dealt, groups):
        _r, place, _v, _m, send = group
        got = np.asarray(fetch(V, place, send))
        live = mask.astype(bool)
        assert got.dtype == table.dtype
        assert (got[live] == table[idx][live]).all()


def _half_sweep(ratings, implicit, mesh):
    """One user half-sweep from seeded tables, placed and dispatched by the
    functions als_train calls. Returns (rows before, rows after, plan,
    counterpart table)."""
    from predictionio_tpu.ops import als
    from predictionio_tpu.ops.ratings import plan_for_users
    cfg = _cfg(implicit, factor_sharding="model", solver="cholesky")
    mp = mesh.model_parallelism
    U0 = als._init_factors(N_USERS, RANK, 5, 1, mp)
    V0 = als._init_factors(N_ITEMS, RANK, 5, 2, mp)
    plan = plan_for_users(ratings, work_budget=cfg.work_budget,
                          batch_multiple=als.batch_shards(mesh, "model"),
                          bucket_ratio=cfg.bucket_ratio)
    groups = als._upload_plan(mesh, plan, 1, RANK, "model")
    V = mesh.put_model_sharded(V0)
    gram = als._side_gram(cfg, V, N_ITEMS, "item")
    U = als._run_side(groups, mesh.put_model_sharded(U0), V, cfg, gram,
                      side="user", mesh=mesh)
    return U0, U, plan, V0


@pytest.mark.parametrize("kind,mesh_name", CASES)
def test_half_sweep_rows_match_the_plain_reference(ratings, references,
                                                   kind, mesh_name):
    implicit = KINDS[kind]
    reference = references[implicit]
    mesh = _mesh(mesh_name)
    U0, U, plan, V0 = _half_sweep(ratings, implicit, mesh)
    got = np.asarray(U)
    extra = ((reference.gram(V0, N_ITEMS), LAM, ALPHA) if implicit
             else (LAM,))
    worst = 0.0
    for b in plan.batches:
        x = np.asarray(reference.solve_rows(V0[b.idx], b.val, b.mask,
                                            *extra, "nratings"))
        live = b.rows >= 0
        err = (np.linalg.norm(got[b.rows[live]] - x[live], axis=1)
               / np.linalg.norm(x[live], axis=1))
        worst = max(worst, float(err.max()))
    assert worst < 2e-4


@pytest.mark.parametrize("kind,mesh_name", CASES)
def test_rows_of_every_shard_are_written_after_one_half_sweep(
        ratings, kind, mesh_name):
    mesh = _mesh(mesh_name)
    U0, U, _plan, _V0 = _half_sweep(ratings, KINDS[kind], mesh)
    changed = (np.asarray(U) != U0).any(axis=1)
    rated = np.zeros(U0.shape[0], bool)
    rated[np.unique(ratings.user_idx)] = True
    # every row with ratings was solved, by whichever chip, and landed in
    # its owner's shard; no other row (users 0-9, the dummy tail) moved
    assert (changed == rated).all()
    per_shard = U0.shape[0] // mesh.model_parallelism
    by_shard = np.bincount(np.flatnonzero(changed) // per_shard,
                           minlength=mesh.model_parallelism)
    assert (by_shard > 0).all()
    for shard in U.addressable_shards:
        # and on the device that holds the shard, not only in the view
        lo = shard.index[0].start or 0
        np.testing.assert_array_equal(
            (np.asarray(shard.data) != U0[lo:lo + per_shard]).any(axis=1),
            rated[lo:lo + per_shard])


@pytest.fixture(scope="module")
def uneven():
    """Ratings whose heavy users crowd the first shard of a table dealt
    four ways: nine in ten of the ratings come from its rows, 40 a user,
    and a rating or two from each user of the others."""
    from predictionio_tpu.ops.ratings import RatingsCOO
    rng = np.random.default_rng(35)
    crowd = rng.integers(0, N_USERS // 4, 3000)
    rest = rng.integers(N_USERS // 4, N_USERS, 300)
    key = np.unique(np.concatenate([crowd, rest]) * N_ITEMS
                    + rng.integers(0, N_ITEMS, 3300))
    return RatingsCOO((key // N_ITEMS).astype(np.int32),
                      (key % N_ITEMS).astype(np.int32),
                      rng.integers(1, 6, key.size).astype(np.float32),
                      N_USERS, N_ITEMS)


@pytest.mark.parametrize("mesh_name", WIDE)
def test_uneven_owners_are_dealt_and_train_the_same_rows(uneven,
                                                         mesh_name):
    import jax
    from predictionio_tpu.ops import als
    from predictionio_tpu.ops.ratings import plan_for_users
    from predictionio_tpu.parallel.mesh import make_mesh
    one = als.als_train(uneven, _cfg(False),
                        make_mesh(devices=jax.devices()[:1]))
    tel = {}
    many = als.als_train(uneven, _cfg(False, factor_sharding="model"),
                         _mesh(mesh_name), telemetry=tel)
    np.testing.assert_allclose(many.user_factors, one.user_factors,
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(many.item_factors, one.item_factors,
                               rtol=0, atol=2e-5)
    # a crowded rung is paid for in padding: the chips of every shard are
    # dealt the room the crowded one needs
    mp = WIDE[mesh_name][1]
    heavy = [group for _most, group in als._dealt_groups(
        plan_for_users(uneven, work_budget=512,
                       batch_multiple=als.batch_shards(_mesh(mesh_name),
                                                       "model")),
        1, False, WIDE[mesh_name][0] * mp, mp) if group[1].shape[2] >= 32]
    assert heavy
    for rows, *_ in heavy:
        blocks = rows.reshape(rows.shape[0], -1, mp, rows.shape[1]
                              // WIDE[mesh_name][0] // mp)
        assert (blocks[:, :, 0] >= 0).any()
        assert (blocks[:, :, 1:] == -1).all()
    assert 0 < tel["deal_fill"] < 1


def test_deal_fill_is_told_on_a_row_sharded_mesh_alone(ratings):
    import jax
    from predictionio_tpu.obs.metrics import get_registry
    from predictionio_tpu.ops import als
    from predictionio_tpu.parallel.mesh import make_mesh
    one = {}
    als.als_train(ratings, _cfg(False, iterations=1),
                  make_mesh(devices=jax.devices()[:1]), telemetry=one)
    assert "deal_fill" not in one and "route_fill" not in one
    tel = {}
    als.als_train(ratings, _cfg(False, iterations=1,
                                factor_sharding="model"),
                  _mesh("1x4"), telemetry=tel)
    fills = als._deal_fills()
    assert set(fills) == {"user_plan", "item_plan"}
    assert all(0 < fill <= 1 for fill in fills.values())
    # the train's own is over both sides' slots together
    assert min(fills.values()) <= tel["deal_fill"] <= max(fills.values())
    gauge = dict((labels["side"], value) for labels, value
                 in get_registry().get("pio_als_deal_fill").samples())
    assert gauge == {"user": fills["user_plan"], "item": fills["item_plan"]}


def _compiled(mesh, cfg, table, counter, gram, groups):
    """The per-chip half-sweep of these operands, compiled as `_run_side`
    dispatches it."""
    from predictionio_tpu.ops import als
    return als._solve_sweep_per_chip.lower(
        table, counter, gram, groups, np.float32(cfg.lam),
        np.float32(cfg.alpha), **als._sweep_statics(cfg, mesh)).compile()


@pytest.mark.parametrize("kind,mesh_name", CASES)
def test_each_chip_solves_its_share_and_holds_no_whole_table(
        ratings, kind, mesh_name):
    """From the compiled program's text and its memory analysis: with one
    rung of K >= rank the primal systems are [B/n, R, R] on every chip."""
    import jax
    from predictionio_tpu.ops import als
    implicit, n = KINDS[kind], MESHES[mesh_name][1]
    mesh = _mesh(mesh_name)
    B, K = 32, 24                                  # K >= RANK: primal
    rng = np.random.default_rng(7)
    rows_u = als.table_rows(N_USERS, n)
    rows_v = als.table_rows(N_ITEMS, n)
    rows = rng.permutation(N_USERS)[:B].astype(np.int32)[None]
    idx = rng.integers(0, N_ITEMS, (1, B, K)).astype(np.int32)
    mask = np.ones((1, B, K), np.float32)
    place, send, _real, _room = als._route_group(
        B // n, idx, mask, K, rows_v // n, n, n, False)
    L = send.shape[-1]
    group = (rows, place, rng.integers(1, 6, (1, B, K)).astype(np.float32),
             mask, send)
    groups = (tuple(mesh.put_stacked(x, als.plan_axes(mesh, "model"))
                    for x in group),)
    U = mesh.put_model_sharded(als._init_factors(N_USERS, RANK, 1, 1, n))
    V = mesh.put_model_sharded(als._init_factors(N_ITEMS, RANK, 1, 2, n))
    cfg = _cfg(implicit, factor_sharding="model", solver="cholesky")
    gram = als._side_gram(cfg, V, N_ITEMS, "item")
    compiled = _compiled(mesh, cfg, U, V, gram, groups)
    text = compiled.as_text()
    shapes = set(re.findall(r"f32\[([\d,]+)\]", text))
    # Gram and solve operands: a quarter (half) of the batch a chip
    assert f"{B // n},{RANK},{RANK}" in shapes
    assert f"{B},{RANK},{RANK}" not in shapes
    # the rows a chip's systems rate arrive as [n, L] from their owners
    # and are placed as [B/n, K]; no chip holds a whole step's slots
    assert f"{B // n},{K},{RANK}" in shapes
    assert f"{n},{L},{RANK}" in shapes
    held = set(re.findall(r"\[([\d,]+)\]", text))
    assert not {d for d in held
                if d.startswith((f"{B},{K}", f"{B * K},", f"1,{B},{K}"))
                or d == f"{B * K}"}
    # one all-to-all of rows; nothing is reduce-scattered or gathered: no
    # index and no solved row crosses the chips
    assert " all-to-all(" in text
    assert "reduce-scatter" not in text
    assert "all-gather" not in text
    # a chip holds its shard of each table and never a whole one
    assert f"{rows_u // n},{RANK}" in shapes
    assert f"{rows_v // n},{RANK}" in shapes
    assert f"{rows_u},{RANK}" not in shapes
    assert f"{rows_v},{RANK}" not in shapes
    m = compiled.memory_analysis()
    whole = (rows_u + rows_v) * RANK * 4
    assert m.argument_size_in_bytes < whole / n + 64 * 1024
    # temporaries are a step's blocks, whatever the tables' rows
    assert m.temp_size_in_bytes < 16 * B * K * RANK * 4


@pytest.mark.parametrize("kind,mesh_name", CASES)
def test_exchange_bytes_are_the_compiled_programs_collectives(
        ratings, trained, kind, mesh_name):
    from predictionio_tpu.obs.metrics import get_registry
    from predictionio_tpu.ops import als
    from predictionio_tpu.ops.ratings import plan_for_items, plan_for_users
    from predictionio_tpu.parallel.collective_stats import (
        executed_collective_stats, merged_stats, sent_bytes)
    implicit, n = KINDS[kind], MESHES[mesh_name][1]
    mesh = _mesh(mesh_name)
    _model, tel = trained[kind, mesh_name]
    assert set(tel["exchange_bytes"]) == {"user", "item"}
    # the programs that train ran, compiled again here from operands
    # placed by the functions als_train calls
    cfg = _cfg(implicit, factor_sharding="model",
               solver=als.sweep_solver("auto", mesh, "model"))
    tables = {side: mesh.put_model_sharded(
        als._init_factors(rows, RANK, cfg.seed, salt, n))
        for side, rows, salt in (("user", N_USERS, 1), ("item", N_ITEMS, 2))}
    kw = dict(work_budget=cfg.work_budget, bucket_ratio=cfg.bucket_ratio,
              batch_multiple=als.batch_shards(mesh, "model"))
    for side, other, n_other, plan in (
            ("user", "item", N_ITEMS, plan_for_users(ratings, **kw)),
            ("item", "user", N_USERS, plan_for_items(ratings, **kw))):
        groups = als._upload_plan(mesh, plan, 1, RANK, "model")
        gram = als._side_gram(cfg, tables[other], n_other, other)
        want = merged_stats(
            executed_collective_stats(_compiled(
                mesh, cfg, tables[side], tables[other], gram, program))
            for program in als._sweep_programs(groups, implicit))
        got = dict(tel["exchange_bytes"][side])
        assert got.pop("sent") == sent_bytes(want, n) > 0
        assert got == {op: ent["bytes"] for op, ent in want.items()
                       if op != "total"}
        # what crosses: the rated rows all-to-all from their owners, and
        # the CG counts summed; no solved row and no row id
        assert got["all-to-all"] > 0
        assert set(got) <= {"all-to-all", "all-reduce"}
    # the gauge holds the last train's: this module's last is implicit 1x2
    gauge = get_registry().get("pio_als_exchange_bytes")
    assert gauge is not None


def test_one_device_and_replicated_meshes_keep_the_gspmd_sweep(ratings):
    """What decides: `factor_sharding` "model" on a mesh whose model axis
    is wider than 1, and a caller that hands `_run_side` that mesh."""
    import jax
    from predictionio_tpu.ops import als
    from predictionio_tpu.parallel.mesh import make_mesh
    one = make_mesh(devices=jax.devices()[:1])
    data4 = make_mesh(devices=jax.devices()[:4])
    model4 = _mesh("1x4")
    assert als.plan_axes(one, "model") == "data"
    assert als.plan_axes(data4, "model") == "data"
    assert als.plan_axes(model4, "replicated") == "data"
    assert als.plan_axes(model4, "model") == ("data", "model")
    assert als.batch_shards(model4, "model") == 4
    assert als.batch_shards(data4, "model") == 4
    assert als.batch_shards(one, "model") == 1
    table = model4.put_model_sharded(np.zeros((8, 4), np.float32))
    stacked = np.zeros((1, 8), np.int32)
    divided = ((model4.put_stacked(stacked, ("data", "model")),),)
    whole = ((model4.put_stacked(stacked),),)
    sharded = als.ALSConfig(factor_sharding="model")
    assert als._sweep_statics(sharded, model4)["batch_axes"] == ("data",
                                                                 "model")
    for cfg, mesh in ((sharded, None), (sharded, one), (sharded, data4),
                      (als.ALSConfig(), model4)):
        assert "mesh" not in als._sweep_statics(cfg, mesh)
    # and what telemetry reads off the arrays
    assert als.sweep_shards(table, divided) == (4, 4)
    assert als.sweep_shards(table, whole) == (4, 1)
    assert als.sweep_shards(np.zeros((8, 4)), ()) == (1, 1)


def test_the_per_chip_sweep_takes_the_one_chip_solver():
    from predictionio_tpu.ops import als
    from predictionio_tpu.ops.solve import resolve_solver
    model4 = _mesh("1x4")
    # "auto" is what one device gets, where the sweep is written per chip
    assert als.sweep_solver("auto", model4, "model") == resolve_solver("auto")
    assert als.sweep_solver("cg_pallas", model4, "replicated") == "cg_pallas"
    with pytest.raises(ValueError):
        als.sweep_solver("lu", model4, "model")


def test_gather_padding_keeps_batches_divisible():
    from predictionio_tpu.ops import als
    for b, k in ((131072, 8), (43692, 24), (5044, 208), (12, 65408)):
        # every chip's [b / 4, k] slots padded alike, for its own gather
        extra = 4 * als._gather_pad_rows(b // 4, k)
        assert (b + extra) % 4 == 0
        lo, hi = als._GATHER_STEP_256
        assert extra == 0 or (lo <= (b + extra) // 4 * k % als._GATHER_TILE
                              <= hi)


TPU_HLO = """
%all-reduce-scatter.clone (input.4: bf16[64,8,16]) -> bf16[16,8,128] {
  %all-reduce.15 = bf16[64,8,128]{2,1,0} all-reduce(%pad.48), channel_id=10, replica_groups={{0,1,2,3}}, to_apply=%region_8
  ROOT %dynamic-slice.12 = bf16[16,8,128]{2,1,0} dynamic-slice(%all-reduce.15, %multiply.43, %c, %c), dynamic_slice_sizes={16,8,128}
}
%body.2 (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %all-gather.45 = s32[1,64,8]{2,0,1} all-gather(%copy.206), channel_id=5, replica_groups={{0,1,2,3}}, dimensions={1}
  %fusion.56 = bf16[16,8,128]{2,1,0} fusion(%select.6), kind=kCustom, calls=%all-reduce-scatter.clone
  %all-gather.46 = f32[4,16,16]{1,2,0} all-gather(%copy.212), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}
}
%cond.3 (p: (s32[], f32[8,16])) -> pred[] {
  %constant.422 = s32[]{:T(128)} constant(7)
  %get-tuple-element.415 = s32[]{:T(128)} get-tuple-element(%p), index=0
  ROOT %lt.94 = pred[]{:T(512)} compare(%get-tuple-element.415, %constant.422), direction=LT
}
ENTRY %main.22_spmd (param.19: f32[8,16]) -> f32[8,16] {
  %while.31 = (s32[], f32[8,16]) while(%tuple.79), condition=%cond.3, body=%body.2
  %psum.7 = f32[2]{0} all-reduce(%fusion.31), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_19
}
"""


def test_executed_stats_count_a_scans_collectives_once_a_step():
    from predictionio_tpu.parallel.collective_stats import (
        collective_stats, executed_collective_stats, sent_bytes)
    static = collective_stats(TPU_HLO)
    assert static["all-gather"]["count"] == 2
    assert static["all-reduce"]["count"] == 2      # the fusion's, the psum
    ran = executed_collective_stats(TPU_HLO)
    # the TPU compiler writes no trip count: the condition's bound, 7
    assert ran["all-gather"] == {
        "count": 14, "bytes": 7 * (64 * 8 * 4 + 4 * 16 * 16 * 4)}
    # its reduce-scatter is a fusion of an all-reduce and the kept slice
    assert ran["reduce-scatter"] == {"count": 7,
                                     "bytes": 7 * 16 * 8 * 128 * 2}
    assert ran["all-reduce"] == {"count": 1, "bytes": 8}
    assert ran["total"]["count"] == 22
    n = 4
    assert sent_bytes(ran, n) == pytest.approx(
        ran["all-gather"]["bytes"] * 3 / 4
        + ran["reduce-scatter"]["bytes"] * 3 + 8 * 2 * 3 / 4)
    assert sent_bytes(ran, 1) == 0.0


def test_executed_stats_read_a_known_trip_count_and_nested_loops():
    from predictionio_tpu.parallel.collective_stats import (
        executed_collective_stats, merged_stats)
    hlo = """
%inner.2 (p: (s32[])) -> (s32[]) {
  %rs = bf16[16,8]{1,0} reduce-scatter(%y), dimensions={0}, to_apply=%add
}
%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ag = f32[64,8]{1,0} all-gather(%x), replica_groups={{0,1}}
  %w2 = (s32[]) while(%t), condition=%cond.3, body=%inner.2, backend_config={"known_trip_count":{"n":"5"}}
}
ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]) while(%t), condition=%cond.4, body=%body.1, backend_config={"known_trip_count":{"n":"3"}}
  %ar = f32[4]{0} all-reduce(%y), to_apply=%add
}
"""
    ran = executed_collective_stats(hlo)
    assert ran["all-gather"] == {"count": 3, "bytes": 3 * 64 * 8 * 4}
    assert ran["reduce-scatter"] == {"count": 15, "bytes": 15 * 16 * 8 * 2}
    assert ran["all-reduce"] == {"count": 1, "bytes": 16}
    twice = merged_stats([ran, ran])
    assert twice["reduce-scatter"] == {"count": 30,
                                       "bytes": 30 * 16 * 8 * 2}
    assert twice["total"]["count"] == 2 * ran["total"]["count"]


def test_a_collective_in_a_loop_of_unknown_length_is_an_error():
    from predictionio_tpu.parallel.collective_stats import \
        executed_collective_stats
    hlo = """
%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ag = f32[64,8]{1,0} all-gather(%x), replica_groups={{0,1}}
}
%quiet.2 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %add.3 = f32[8]{0} add(%x, %x)
}
%cond.5 (p: (s32[], f32[8])) -> pred[] {
  ROOT %gt.1 = pred[] compare(%residual, %tolerance), direction=GT
}
ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]) while(%t), condition=%cond.5, body=BODY
}
"""
    # a data-dependent loop with nothing to exchange in it is no matter
    assert executed_collective_stats(
        hlo.replace("BODY", "%quiet.2"))["total"] == {"count": 0, "bytes": 0}
    with pytest.raises(ValueError, match="trip count"):
        executed_collective_stats(hlo.replace("BODY", "%body.1"))


SENTINEL_MESHES = ["one", "1x4", "1x2"]


@pytest.mark.parametrize("mesh_name", SENTINEL_MESHES)
def test_the_sentinel_checks_sharded_tables_without_copying_them(
        ratings, monkeypatch, mesh_name):
    """als_train with the sentinel on (the default; `pio train`): one
    device keeps a last-good pair in HBM, the older pair gone before the
    newer is made, and rolls back to it; row-sharded tables, which are
    sharded because no chip has room for them twice, are checked every
    iteration, never copied, and a breach raises."""
    import jax
    from predictionio_tpu.guard import sentinels
    from predictionio_tpu.guard.sentinels import NumericalFault
    from predictionio_tpu.ops import als
    from predictionio_tpu.parallel.mesh import make_mesh
    sharded = mesh_name != "one"
    mesh = (_mesh(mesh_name) if sharded
            else make_mesh(devices=jax.devices()[:1]))
    kw = dict(rank=RANK, lam=LAM, seed=3, work_budget=512,
              factor_sharding="model" if sharded else "replicated")
    copies, checks, live = [], [], []
    real_copy, real_stats = sentinels.device_copy, sentinels.table_stats

    def counted_copy(table):
        # copies alive when one more is asked for: the older pair is gone
        # before the newer is made, so at most the newer pair's first half
        live.append(sum(c() is not None for c in copies))
        made = real_copy(table)
        copies.append(weakref.ref(made))
        return made

    def counted_stats(table):
        checks.append(table.shape)
        return real_stats(table)

    monkeypatch.setattr(sentinels, "device_copy", counted_copy)
    monkeypatch.setattr(sentinels, "table_stats", counted_stats)
    clean = als.als_train(ratings, als.ALSConfig(iterations=3, **kw), mesh)
    assert len(checks) == 6                      # both tables, every time
    assert len(copies) == (0 if sharded else 6)
    if not sharded:
        assert max(live) == 1              # never three pairs

    def breach_in_iteration_1(table):
        checks.append(table.shape)
        return (False, np.inf) if len(checks) == 3 else real_stats(table)

    checks.clear()
    monkeypatch.setattr(sentinels, "table_stats", breach_in_iteration_1)
    if sharded:
        with pytest.raises(NumericalFault):
            als.als_train(ratings, als.ALSConfig(iterations=3, **kw), mesh)
        return
    rolled_back = als.als_train(ratings, als.ALSConfig(iterations=3, **kw),
                                mesh)
    first = als.als_train(ratings, als.ALSConfig(iterations=1, **kw), mesh)
    np.testing.assert_array_equal(rolled_back.user_factors,
                                  first.user_factors)
    assert not np.array_equal(clean.user_factors, first.user_factors)


@pytest.mark.parametrize("rows", [700, 2500])
def test_init_factors_are_one_stream_a_block(monkeypatch, rows):
    """A table of up to a block of rows is the one stream it always was; a
    larger one is a stream a block (filled by the host's cores), the first
    block that same stream, and the same whatever the threads."""
    from predictionio_tpu.ops import als
    monkeypatch.setattr(als, "_INIT_BLOCK_ROWS", 1000)
    rng = np.random.default_rng(5 * 2654435761 % (2 ** 31) + 2)
    n_rows = als.table_rows(rows, 4)
    was = (np.abs(rng.standard_normal((n_rows, RANK), dtype=np.float32))
           / np.sqrt(RANK)).astype(np.float32)
    got = als._init_factors(rows, RANK, 5, 2, 4)
    assert got.dtype == np.float32 and got.shape == (n_rows, RANK)
    np.testing.assert_array_equal(got[:1000], was[:1000])
    np.testing.assert_array_equal(got, als._init_factors(rows, RANK, 5, 2, 4))
    assert (got >= 0).all() and np.isfinite(got).all()
    if n_rows > 1000:
        assert not np.array_equal(got[1000:2000], was[1000:2000])
        assert not np.array_equal(got[1000:1500], got[2000:2500])
        assert abs(got[1000:].mean() - was.mean()) < 0.01

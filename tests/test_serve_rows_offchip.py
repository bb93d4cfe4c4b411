"""The packed serve executable compiled at the amazonbooks cell's shapes for
a described v5e chip, here, where there is none (PR 26): the compiled
program may hold no operation over the whole user table. At rank 200 the
TPU keeps an [n, 200] f32 table with the row index minor, and a gather of
16 rows made the compiler copy all 8.39M (transposed, rounded to bf16,
padded to 256 lanes: 4.295 GB of temporaries, 14 of a dispatch's 20 ms).
Nothing runs, so nothing here is a time. One file, so that one test worker
loads the TPU's library: PR 27's compiles of the implicit-feedback
configuration's programs (`ecomm-taobao-ub-r200`: both half-sweeps and the
Gram + eigh program, against the chip's memory) are at the end of it."""

import json
import os
import re

import pytest

U_ROWS, I_ROWS, K = 1 << 23, 1 << 22, 16


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


def _compile(fn, sds, b, rank):
    import jax.numpy as jnp
    return fn.lower(sds((U_ROWS, rank), jnp.float32),
                    sds((I_ROWS, rank), jnp.float32), sds((b,), jnp.int32),
                    sds((), jnp.int32), k=K, p=1).compile()


def _whole_table_ops(compiled, rank):
    """Instructions other than the parameter whose result has the user
    table's shape, either way round, in any dtype."""
    shape = re.compile(r"= \w+\[(%d,%d|%d,%d)\]" % (U_ROWS, rank,
                                                    rank, U_ROWS))
    return [line.strip()[:160] for line in compiled.as_text().splitlines()
            if shape.search(line) and " parameter(" not in line]


@pytest.mark.parametrize("b", [1, 2, 4, 8, 16])
def test_no_operation_over_the_whole_user_table_at_rank_200(sds, b):
    from predictionio_tpu.ops import als
    compiled = _compile(als._users_topk_b_packed, sds, b, 200)
    assert _whole_table_ops(compiled, 200) == []
    # the [b, 4194304] f32 scores and little else; the parent: 4.295 GB
    # at every b >= 2
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_the_amazonbooks_tables_fit_at_their_rungs_and_at_the_users_next(sds):
    """ISSUE 32: the cell's executable at its resident shapes (users 2^23,
    items 2,359,296 on the table ladder, not 2^22), b 16, against the 15.75
    GB the compiler allows; and the users' next rung, 9,437,184 rows, which
    a promotion compiles beside the same item table (the power-of-two
    ladder's 2^24 rows were 13.4 GB of user table alone and could only
    fail). Nothing runs: bytes, not times."""
    import jax.numpy as jnp
    from predictionio_tpu.compile import buckets as B
    from predictionio_tpu.ops import als
    u_b, i_b = B.bucket_table_rows(8026324), B.bucket_table_rows(2330066)
    assert (u_b, i_b) == (U_ROWS, 2359296)
    assert B.next_table_bucket(u_b) == 9437184

    def compiled(u_rows):
        return als._users_topk_b_packed.lower(
            sds((u_rows, 200), jnp.float32), sds((i_b, 200), jnp.float32),
            sds((16,), jnp.int32), sds((), jnp.int32), k=K,
            p=1).compile().memory_analysis()
    m = compiled(u_b)
    assert m.argument_size_in_bytes >= 8.59e9
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 15.75e9
    nxt = compiled(B.next_table_bucket(u_b))
    assert nxt.argument_size_in_bytes >= 9.43e9
    assert nxt.argument_size_in_bytes + nxt.temp_size_in_bytes < 15.75e9


def _gathered(monkeypatch):
    """The serve executable as it was before PR 26, its rows gathered. A
    function object of its own: JAX keeps traces by function, and a second
    jit of the same one would be handed the row-by-row trace."""
    import jax
    from predictionio_tpu.ops import als
    monkeypatch.setattr(als, "_batch_rows", lambda table, ixs: table[ixs])
    return jax.jit(
        lambda U, V, ixs, n, k, p: als._users_topk_b_packed.__wrapped__(
            U, V, ixs, n, k=k, p=p), static_argnames=("k", "p"))


def test_a_gather_still_copies_the_whole_table_at_rank_200(sds, monkeypatch):
    """The fault _batch_rows works around, shown on this compiler. When it
    stops copying, this fails and the rows can be gathered again."""
    compiled = _compile(_gathered(monkeypatch), sds, 16, 200)
    assert _whole_table_ops(compiled, 200)
    assert compiled.memory_analysis().temp_size_in_bytes > 4e9


def test_rank_256_is_no_worse_than_a_gather(sds, monkeypatch):
    """At a multiple of 128 the table already lies row-major and the
    gather carries no copy: the row-by-row read may not cost such a
    program anything."""
    from predictionio_tpu.ops import als
    ours = _compile(als._users_topk_b_packed, sds, 16, 256)
    assert _whole_table_ops(ours, 256) == []
    gathered = _compile(_gathered(monkeypatch), sds, 16, 256)
    # the same [16, 4194304] f32 scores, 268.4 MB; the 16 row buffers
    # are 63 KB beside them
    assert (ours.memory_analysis().temp_size_in_bytes
            <= gathered.memory_analysis().temp_size_in_bytes + (1 << 20))


# -- PR 27: the implicit configuration's programs against the chip's memory

HBM = 15.75 * 2**30    # what the compiler itself allows of the chip's 16 GiB

# (B, K) of the heaviest steps of each route in the program's plan of the
# configuration's view counts at work_budget 2^20 (ops/ratings.plan_for_users
# / plan_for_items over benchmark/lib/datagen_implicit.py's pairs; the whole
# plan, 49 + 76 shapes, takes minutes to compile: PERF.md section 4): jnp CG
# K < 32, eig-SMW at its smallest and largest K, primal at 208 and at the
# longest rows
TAOBAO_SHAPES = {
    "user": [(65536, 16), (32768, 32), (5957, 176), (5041, 208), (500, 960)],
    "item": [(131072, 8), (32768, 32), (5957, 176), (5041, 208),
             (605, 1600), (1, 32000)],
}


def _taobao():
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "ecomm-taobao-ub-r200.json")) as f:
        return json.load(f)


def _gram_shapes(sds, rank):
    import jax.numpy as jnp
    return (sds((rank, rank), jnp.float32), sds((rank,), jnp.float32),
            sds((rank, rank), jnp.float32))


@pytest.mark.parametrize("side", ["user", "item"])
def test_taobao_implicit_half_sweep_fits_at_the_files_sweep_chunk(sds, side):
    """Tables, both plans, and the half-sweep's temporaries at the
    configuration's `sweep_chunk`, one scan step of each shape."""
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    c = _taobao()
    rank, chunk = c["rank"], c["sweep_chunk"]
    n_out, n_counter = ((c["n_users"], c["n_items"]) if side == "user"
                        else (c["n_items"], c["n_users"]))
    groups = tuple(
        (sds((1, chunk * B), jnp.int32), sds((1, chunk * B, K), jnp.int32),
         sds((1, chunk * B, K), jnp.float32),
         sds((1, chunk * B, K), jnp.float32))
        for B, K in TAOBAO_SHAPES[side])
    compiled = als._solve_sweep.lower(
        sds((n_out + 1, rank), jnp.float32),
        sds((n_counter + 1, rank), jnp.float32), _gram_shapes(sds, rank),
        groups, sds((), jnp.float32), sds((), jnp.float32),
        nratings_reg=True, implicit=True, rank=rank,
        compute_dtype="bfloat16", solver="cg_pallas", dual_solve="auto",
        solver_iters=None, dual_iters_cap=None).compile()
    m = compiled.memory_analysis()
    tables = (c["n_users"] + c["n_items"] + 2) * rank * 4
    # idx, val, mask of every pair on both sides, with the plans' padding
    # (1.09 and 1.34: PERF.md section 4), and a row index a system
    plans = c["n_ratings"] * (1.09 + 1.34) * 12 + 8e6
    held = tables + plans + 3 * (rank * rank + rank) * 4
    assert (held + m.temp_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes) < HBM
    # the eig-SMW stages and the Pallas dual solve are in the program
    text = compiled.as_text()
    assert "pio.sweep.smw.project" in text and "pio_cg_dual_b" in text


@pytest.mark.parametrize("table", ["item", "user"])
def test_taobao_gram_program_reads_the_table_where_it_lies(sds, table):
    """The Gram over the live rows inside the program: no operation of the
    entry computation makes another table (sliced by the caller, as before
    PR 27, the item table was a second 3.3 GB array while the Gram ran), and
    the program's temporaries are those of a 200 x 200 eigh."""
    import jax.numpy as jnp
    from predictionio_tpu.ops import als
    c = _taobao()
    n, rank = c["n_" + table + "s"], c["rank"]
    compiled = als._gram_eig.lower(sds((n + 1, rank), jnp.float32),
                                   n_live=n).compile()
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    sized = re.compile(r"= \w+\[(%d|%d),%d\]|= \w+\[%d,(%d|%d)\]"
                       % (n, n + 1, rank, rank, n, n + 1))
    made = [line.strip()[:160] for line in entry.splitlines()
            if sized.search(line) and " parameter(" not in line]
    assert made == []
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 64 << 20
    assert m.argument_size_in_bytes < (n + 1) * rank * 4 * 1.001


# -- PR 31: the composed-mask executable at the served taobao buckets ---------

def _compose(sds, b, t, i_rows=I_ROWS, rank=200):
    import jax.numpy as jnp
    from predictionio_tpu.ops import similarity as S
    i32 = jnp.int32
    return S._composed_masked_topk_packed.lower(
        sds((b, rank), jnp.float32), sds((i_rows, rank), jnp.float32),
        sds((i_rows, 1), i32), sds((i_rows // 32,), jnp.uint32),
        sds((), i32), sds((b, 4), i32), sds((t,), i32), sds((t,), i32),
        sds((t,), i32), sds((b,), bool), k=K, p=1).compile()


@pytest.mark.parametrize("b,t", [(1, 1024), (16, 1024), (16, 16384)])
def test_composed_mask_executable_fits_beside_both_item_tables(sds, b, t):
    """The served e-commerce configuration holds two item tables at the
    2^22 bucket (6.71 GB); one dispatch's arguments and temporaries have to
    fit beside the other table in the 15.75 GB the compiler allows."""
    m = _compose(sds, b, t).memory_analysis()
    table = I_ROWS * 200 * 4
    assert m.argument_size_in_bytes >= table
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes + table
            < 15.75e9)


def test_composed_mask_scatters_into_bitmaps_not_a_batch_by_items_array(sds):
    """The lists land in [2, b, I/32] words. A scatter into a [b, I] array
    cost 10 ms a dispatch at I = 2^22 whatever the list's length (my chip
    run, PR 31): the compiled program may hold no scatter whose operand has
    b * I elements."""
    text = _compose(sds, 16, 4096).as_text()
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert scatters
    big = str(16 * I_ROWS)
    assert not [line[:160] for line in scatters if big in line]

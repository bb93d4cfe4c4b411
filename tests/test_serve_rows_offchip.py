"""The packed serve executable compiled at the amazonbooks cell's shapes for
a described v5e chip, here, where there is none (PR 26): the compiled
program may hold no operation over the whole user table. At rank 200 the
TPU keeps an [n, 200] f32 table with the row index minor, and a gather of
16 rows made the compiler copy all 8.39M (transposed, rounded to bf16,
padded to 256 lanes: 4.295 GB of temporaries, 14 of a dispatch's 20 ms).
Nothing runs, so nothing here is a time. One file, so that one test worker
loads the TPU's library."""

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

"""The v5e compiler, asked without the chip (on-chip-measurement guide,
section 2): the fused programs of the smoke's query shapes and the device
parquet decode programs, at 1,048,576 rows, must compile for one described
v5e chip. Nothing runs; a pass here is not a chip run.

Everything TPU-related happens inside fixtures and tests of THIS file: only
one process may hold libtpu, so no other module describes the topology,
and nothing here touches it at import or collection time.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROWS = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compile_cache_off():
    """A v5e executable written to the persistent cache cannot be read
    back without a chip; keep these compiles out of it and silent."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _placed(tree, sharding):
    """Every array leaf of ``tree`` as a ShapeDtypeStruct on the chip."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


# -- the main path: fused programs at 1M rows ---------------------------------

def _dense_join(t):
    from spark_rapids_tpu.ops import aggregates as A
    from spark_rapids_tpu.ops import predicates as P
    from spark_rapids_tpu.ops.expression import col
    return (t["orders"]
            .join(t["customer"],
                  on=P.EqualTo(col("o_custkey"), col("c_custkey")),
                  how="inner")
            .group_by(col("c_nationkey"))
            .agg(A.AggregateExpression(A.Sum(col("o_totalprice")), "total")))


def _query(name):
    from spark_rapids_tpu.workloads import tpch
    return _dense_join if name == "dense_join" else tpch.QUERIES[name]


@pytest.mark.parametrize("name", ["q6", "q1", "dense_join"])
def test_fused_program_compiles_for_v5e(one_chip, monkeypatch, name):
    """Run the query here at a tiny size to get the engine's own fused
    program and boundary inputs, re-capacity the inputs to 1M rows the way
    the warm-up does, and hand that to the v5e compiler."""
    from spark_rapids_tpu.compile import warmup
    from spark_rapids_tpu.exec import fusion
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.workloads import tpch
    runs = []
    monkeypatch.setattr(
        fusion._warmup, "note_run",
        lambda program, sig, inputs, **kw: runs.append((program, inputs)))
    session = TpuSession({"spark.rapids.sql.enabled": True,
                          "spark.rapids.sql.test.enabled": True,
                          "spark.rapids.sql.variableFloatAgg.enabled": True})
    tables = tpch.load(session, tpch.gen_tables(8192, seed=1), cache=False)
    _query(name)(tables).collect()
    program, inputs = runs[-1]      # the attempt whose answer was kept
    at_1m = warmup._map_vec(warmup.capacity_vector(inputs), lambda c: ROWS)
    abstract = _placed(warmup._rebucket(inputs, at_1m), one_chip)
    compiled = program.fn.lower(abstract).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0


def test_dictionary_then_plain_decode_compiles_for_v5e(one_chip):
    """The decode program of a column chunk that falls back from its
    dictionary to PLAIN pages, at the shapes of a default-written SF1
    l_extendedprice chunk (1,048,576 rows, a 131 k-entry dictionary
    bucketed to 262,144, 287 index runs, 294 KB packed)."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.io import parquet_device as PD
    s = jax.ShapeDtypeStruct
    runs = tuple(s((512,), jnp.int32) for _ in range(5))

    def kern(dt, it, pk, pl, dtab, n, dict_count):
        return PD._decode_chunk_device(dt, it, pk, pl, dtab, n, ROWS, 18,
                                       T.DOUBLE, False, dict_count)
    abstract = _placed((runs, runs, s((1 << 19,), jnp.uint8),
                        s((ROWS,), jnp.float64), s((1 << 18,), jnp.float64),
                        s((), jnp.int32), s((), jnp.int32)), one_chip)
    compiled = jax.jit(kern).lower(*abstract).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0


@pytest.mark.parametrize("kind", ["plain_nn", "dict_nn", "dictplain_nn"])
def test_decode_without_nulls_compiles_for_v5e(one_chip, kind):
    """The decode programs of a chunk in which no page holds a null, at
    the shapes of a default-written SF1 lineitem chunk: the validity of a
    PLAIN chunk; l_shipdate-like (2,098 index runs bucketed to 4,096, a
    small dictionary); l_extendedprice-like (as the case above, without
    its definition-level table)."""
    from spark_rapids_tpu.io import parquet_device as PD
    s = jax.ShapeDtypeStruct
    n = s((), jnp.int32)
    if kind == "plain_nn":
        def kern(n):
            return PD._live_rows(n, ROWS)[1]
        abstract = (n,)
    elif kind == "dict_nn":
        def kern(it, pk, dtab, n):
            return PD._decode_chunk_no_nulls(it, pk, None, dtab, n, ROWS)
        abstract = (tuple(s((4096,), jnp.int32) for _ in range(5)),
                    s((1 << 21,), jnp.uint8), s((4096,), jnp.int32), n)
    else:
        def kern(it, pk, pl, dtab, n, dict_count):
            return PD._decode_chunk_no_nulls(it, pk, pl, dtab, n, ROWS,
                                             dict_count)
        abstract = (tuple(s((512,), jnp.int32) for _ in range(5)),
                    s((1 << 19,), jnp.uint8), s((ROWS,), jnp.float64),
                    s((1 << 18,), jnp.float64), n, n)
    compiled = jax.jit(kern).lower(*_placed(abstract, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0


# -- PLAIN byte arrays at SF1's shapes (ISSUE 34) ------------------------------

def test_text_decode_compiles_for_v5e(one_chip):
    """The programs of a default-written SF1 o_comment chunk (1,048,576
    near-unique texts of 19..78 bytes: a dictionary page and 52 PLAIN pages
    of 20,000 values, 55 MB of page bytes in a 64 MiB bucket): the walk
    over the length prefixes with each row's source, then the copy of the
    text into the Arrow layout."""
    from spark_rapids_tpu.io import parquet_device as PD
    s = jax.ShapeDtypeStruct
    n = s((), jnp.int32)
    runs = tuple(s((128,), jnp.int32) for _ in range(5))
    pages = s((128,), jnp.int32)
    src = s((1 << 26,), jnp.uint8)
    rows = s((ROWS,), jnp.int32)

    def walk(src, starts, counts, steps, n_rows, it, pk, dict_count):
        return PD._decode_text_rows(src, starts, counts, steps, n_rows, None,
                                    it, pk, dict_count, ROWS, 1 << 15,
                                    1 << 15)

    def place(src, row_start, row_len):
        return PD._place_text(src, row_start, row_len, 1 << 26)
    for kern, abstract in (
            (walk, (src, pages, pages, n, n, runs, s((1 << 16,), jnp.uint8),
                    n)),
            (place, (src, rows, rows))):
        compiled = jax.jit(kern).lower(*_placed(abstract, one_chip)).compile()
        assert compiled.memory_analysis().temp_size_in_bytes >= 0


def test_like_over_a_flat_comment_column_compiles_for_v5e(one_chip):
    """Q13's ``'%special%requests%'`` over a flat o_comment column as the
    fused program meets it (64 MiB of text; 1,048,576 rows padded to the
    2,097,152-row tier): window compares and two windowed minima over the
    bytes, in well under a chip's memory."""
    from spark_rapids_tpu.ops.strings import Like, _like_literals
    s = jax.ShapeDtypeStruct
    toks = Like(None, "%special%requests%").tokens()

    def like(payload, offsets):
        return _like_literals(payload, offsets, toks, 80)
    abstract = (s((1 << 26,), jnp.uint8), s((2 * ROWS + 1,), jnp.int32))
    compiled = jax.jit(like).lower(*_placed(abstract, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < (2 << 30)

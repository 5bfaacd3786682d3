"""The main path's kernels, asked of the TPU's own compiler — no chip needed.

``chip_smoke.py`` proves the engine on the attached chip; these tests keep
every later PR from handing that chip a kernel its compiler refuses. The
TPU compiler is installed in the sandbox and compiles for a chip that is
DESCRIBED (``v5e:2x2``), not attached: each test builds a kernel the way its
exec builds it, from the physical plan of the smoke's own SQL, and runs
``.lower(...).compile()`` at the smoke's shapes (1,048,576-row batches,
x64 on: int64/float64 operands). Nothing executes, so nothing here is a
time, a rate or a result of a chip run.

Tier-1 holds the compiles measured at a few seconds each (PR 21: 0.2–2.6 s
on the sandbox CPU). The sort-bearing modules of the join path take
minutes to compile (a variadic multi-key ``lax.sort`` is the cost — PERF.md,
PR 21): they are here too, at the shapes the smoke runs them, but marked
``slow`` so that only a by-hand ``-m slow`` run pays for them.

The topology is described inside a module-scoped fixture and never while a
module is imported: only ONE process at a time may load the TPU's library,
and pytest-xdist workers all import every test file.
"""
import time

import numpy as np
import pytest

#: the smoke's batch shape: rows per Parquet row group == batchSizeRows
BATCH = 1 << 20
#: a dimension table's shape bucket (date_dim 1826 rows, item 2000)
DIM = 8192
#: per-test ceiling for the tier-1 compiles (measured 0.2–2.6 s)
FAST_LIMIT_S = 120.0
#: ceiling for the by-hand (slow) compiles (measured 53–600 s)
SLOW_LIMIT_S = 3600.0


@pytest.fixture(scope="module")
def topo():
    """The described chip. Skips — from here, never at import — where no
    v5e topology can be described; keeps the persistent compile cache off
    around these compiles (an executable compiled for a described chip
    can be written to the cache but not read back without one)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def session():
    """The smoke's views over tiny tables: plans (and so kernels) depend
    on schemas, not on row counts."""
    from benchmarks import tpcds, tpch
    from spark_rapids_tpu.api import TpuSession
    s = TpuSession({"spark.rapids.tpu.sql.optimizer.enabled": False,
                    "spark.rapids.tpu.sql.fusedPipeline.enabled": False})
    for name, t in (("lineitem", tpch.gen_lineitem(2048)),
                    ("store_sales", tpcds.gen_store_sales(2048)),
                    ("date_dim", tpcds.gen_date_dim()),
                    ("item", tpcds.gen_item())):
        s.create_dataframe(t).create_or_replace_temp_view(name)
    return s


def _find(node, cls):
    if isinstance(node, cls):
        return node
    for c in node.children:
        got = _find(c, cls)
        if got is not None:
            return got
    return None


def _abstract(x, sharding):
    """Concrete operand (array, scalar) -> its shape on the described
    chip."""
    import jax
    a = np.asarray(x)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _cols(schema, rows, sharding, as_codes=()):
    """[(data, validity)] kernel operands for ``schema`` at ``rows``:
    fixed-width columns in their device dtype, dictionary-coded strings
    (ordinals in ``as_codes``) as int32 codes."""
    import jax
    out = []
    for i, f in enumerate(schema.fields):
        dt = np.int32 if i in as_codes else f.dtype.np_dtype
        out.append((jax.ShapeDtypeStruct((rows,), dt, sharding=sharding),
                    jax.ShapeDtypeStruct((rows,), np.bool_,
                                         sharding=sharding)))
    return out


def _compile(lowered, limit_s):
    """Compile, hold it to its own time limit, and return the
    executable. (A compile cannot be interrupted from Python; the limit
    fails the test after the fact, conftest's per-test alarm bounds it
    from outside.)"""
    t0 = time.perf_counter()
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    assert dt < limit_s, f"compile took {dt:.1f}s (limit {limit_s:.0f}s)"
    # fits one v5e chip's 16 GB beside its operands
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes)
    assert total < 16 * (1 << 30), ma
    return compiled


def _agg_of(session, sql):
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    agg = _find(session.sql(sql)._physical(), TpuHashAggregateExec)
    assert agg is not None
    return agg


def _update_scalars(agg, sharding):
    from spark_rapids_tpu.exec.aggregate import _param_exprs
    from spark_rapids_tpu.exprs.base import (collect_param_literals,
                                             literal_scalars)
    return tuple(_abstract(s, sharding) for s in literal_scalars(
        collect_param_literals(_param_exprs(
            agg._kernel_groupings, agg.aggs, "update",
            agg.pre_stages or None))))


def test_fused_groupby_kernel_q6(session, one_chip):
    """exec/aggregate.py:_build_groupby_kernel — scan -> filter ->
    global aggregate as ONE module, q6's update kernel per 1M-row batch."""
    import jax
    from benchmarks import queries_sql as Q
    from spark_rapids_tpu.exec.aggregate import _get_kernel
    agg = _agg_of(session, Q.TPCH_Q6)
    assert not agg.groupings and agg.pre_stages
    in_schema = agg.children[0].output_schema()
    kernel = _get_kernel(agg._kernel_groupings, agg.aggs,
                         agg._kernel_schema, "update", in_schema=in_schema,
                         stages=agg.pre_stages, n_codes=0)
    n = jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)
    _compile(kernel.lower(_cols(in_schema, BATCH, one_chip), n, BATCH,
                          _update_scalars(agg, one_chip)), FAST_LIMIT_S)


@pytest.fixture(scope="module")
def decimal_session():
    """``lineitem`` with decimal(15,2) money columns (the benchmark's
    configuration tpch_sf10_decimal), tiny."""
    import json
    import os
    import sys
    from spark_rapids_tpu.api import TpuSession
    perfbench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perfbench")
    if perfbench not in sys.path:
        sys.path.insert(0, perfbench)
    import datagen
    with open(os.path.join(perfbench, "configs",
                           "tpch_sf10_decimal.json")) as f:
        config = json.load(f)
    tables = datagen.scaled_tables(config, 2048)
    gen = datagen.load_module("generators", config["generator"])
    s = TpuSession({"spark.rapids.tpu.sql.optimizer.enabled": False})
    s.create_dataframe(gen.generate("lineitem", tables, 7, 0, 2048)) \
        .create_or_replace_temp_view("lineitem")
    with open(os.path.join(perfbench, "queries",
                           "tpch_q1_decimal.sql")) as f:
        s.q1_text = f.read()
    return s


def _q1_carry(session, one_chip):
    """Q1's aggregate, its carried kernels at G=16 ((3+1)x(2+1) key slots)
    and the carry's shapes on the described chip."""
    import jax
    from benchmarks import queries_sql as Q
    from spark_rapids_tpu.columnar.segmented import bucket_segments
    agg = _agg_of(session, getattr(session, "q1_text", Q.TPCH_Q1))
    assert agg._dict_keys == [0, 1] and agg._direct_keys_ok()
    g = bucket_segments((3 + 1) * (2 + 1))
    fold, tail, _flush = agg._build_carry_kernels(g)
    carry = jax.tree_util.tree_map(lambda a: _abstract(a, one_chip),
                                   fold.empty_carry())
    return agg, g, fold, tail, carry


def test_direct_onehot_update_kernel_q1(session, one_chip):
    """exec/aggregate.py:_build_carry_kernels, ``fold`` — q1's one
    dispatch a batch: two dictionary-coded string keys grouped by direct
    one-hot addressing, eight aggregates, fused filter, and the batch's
    partials merged into the running ones slot onto slot."""
    _compile_q1_fold(session, one_chip)


def test_direct_onehot_update_kernel_q1_decimal(decimal_session, one_chip):
    """The same ``fold`` over decimal(15,2) columns: int64 lanes, checked
    products (leading-zero count + unsigned multiply), three-limb sums,
    and the flagged-row count as one more int64 row of the carry."""
    agg = _compile_q1_fold(decimal_session, one_chip)
    assert agg._decimal_checks >= 2 + 7      # two products, seven finals
    from spark_rapids_tpu.types import DecimalType
    assert sum(isinstance(f.dtype, DecimalType)
               for f in agg.output_schema().fields) == 7


def _compile_q1_fold(session, one_chip):
    import jax
    agg, g, fold, _tail, carry = _q1_carry(session, one_chip)
    in_schema = agg.children[0].output_schema()
    names = in_schema.names()
    coded = {names.index("l_returnflag"), names.index("l_linestatus")}

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pairs = tuple((sds((BATCH,), np.int32), sds((BATCH,), np.bool_))
                  for _ in coded)
    remaps = tuple(sds((g,), np.int32) for _ in coded)
    cards = sds((2,), np.int32)
    _compile(fold.lower(carry, cards, None,
                        _cols(in_schema, BATCH, one_chip, coded),
                        sds((), np.int32), BATCH, cards,
                        _update_scalars(agg, one_chip), pairs, remaps),
             FAST_LIMIT_S)
    return agg


def test_direct_carry_tail_kernel_q1(session, one_chip):
    """exec/aggregate.py:_build_carry_kernels, ``tail`` — q1's one tail
    dispatch a query: occupancy compaction, finalize, pack for the one
    fetch."""
    import jax
    _agg, _g, _fold, tail, carry = _q1_carry(session, one_chip)
    _compile(tail.lower(carry, jax.ShapeDtypeStruct((2,), np.int32,
                                                    sharding=one_chip)),
             FAST_LIMIT_S)


def test_direct_carry_tail_kernel_q1_decimal(decimal_session, one_chip):
    """``tail`` over the decimal carry: the limb totals made int64, the
    averages divided and rounded HALF_UP twice on the device (long
    division over base-10^6 digits), the overflow count packed after the
    group count."""
    import jax
    _agg, _g, _fold, tail, carry = _q1_carry(decimal_session, one_chip)
    _compile(tail.lower(carry, jax.ShapeDtypeStruct((2,), np.int32,
                                                    sharding=one_chip)),
             FAST_LIMIT_S)


def test_ingest_decode_kernel(one_chip):
    """columnar/transfer.py:_decode_kernel — the one decode dispatch per
    ingested batch, with the encodings a real lineitem chunk takes
    (dates as uint16+offset, two-decimal doubles as scaled ints)."""
    from benchmarks import tpch
    from spark_rapids_tpu.columnar.transfer import (_decode_kernel,
                                                    encode_columns)
    chunk = tpch.gen_lineitem(BATCH, seed=(1, 0))
    valid = np.ones(BATCH, np.bool_)
    pairs = []
    for name in ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
                 "l_shipdate"):
        col = chunk.column(name).to_numpy()
        if col.dtype.kind == "M":
            col = col.astype("datetime64[D]").astype(np.int32)
        pairs.append((col, valid))
    flat, specs, params, ratio, _raw = encode_columns(pairs)
    assert ratio < 1.0, "lineitem no longer narrows: nothing to decode"
    kernel = _decode_kernel(specs, BATCH)
    compiled = _compile(
        kernel.lower(tuple(_abstract(a, one_chip) for a in flat),
                     tuple(_abstract(p, one_chip) for p in params)),
        FAST_LIMIT_S)
    assert "f64" in compiled.as_text()      # the emulated-f64 decode


def test_packed_concat_kernel_q3_pair(one_chip):
    """columnar/batch.py:_device_concat_packed — two of q3's 4,096-row
    fact partitions (each padded to 8,192) filled into one 8,192-row
    batch: int64 keys, a float64 price, one dispatch, and no sort in what
    the chip's compiler is handed."""
    import jax
    from spark_rapids_tpu.columnar.batch import _device_concat_packed
    pair = [[(jax.ShapeDtypeStruct((8192,), dt, sharding=one_chip),
              jax.ShapeDtypeStruct((8192,), np.bool_, sharding=one_chip))
             for _ in range(2)] for dt in (np.int64, np.int64, np.float64)]
    counts = jax.ShapeDtypeStruct((2,), np.int32, sharding=one_chip)
    compiled = _compile(
        jax.jit(_device_concat_packed, static_argnums=(2,)).lower(
            counts, pair, 8192), FAST_LIMIT_S)
    assert "sort" not in compiled.as_text()


def _q3_join(session, build_left: bool):
    from benchmarks import queries_sql as Q
    from spark_rapids_tpu.exec.joins import TpuBroadcastHashJoinExec
    top = _find(session.sql(Q.TPCDS_Q3)._physical(),
                TpuBroadcastHashJoinExec)
    assert top is not None
    # top: (date_dim x store_sales) x item; its left child is the first
    # join, whose BUILD side (date_dim) is on the left
    return _find(top.children[0], TpuBroadcastHashJoinExec) \
        if build_left else top


@pytest.mark.slow
@pytest.mark.parametrize("batch", [8192, BATCH],
                         ids=["smoke_cut_batch", "default_batch"])
def test_fused_join_kernel_q3(session, one_chip, batch):
    """exec/joins.py:_build_fused_join_kernel over its sort-bearing count
    kernel: date_dim (8192-row bucket) x one store_sales batch, at the
    bucket the smoke cuts q3's batches to and at the default batch.
    53 s and 395 s on the sandbox CPU (PERF.md, PR 21) — by hand only."""
    import jax
    from spark_rapids_tpu.exec.joins import (_build_count_kernel,
                                             _build_fused_join_kernel)
    join = _q3_join(session, build_left=True)
    ls = join.children[0].output_schema()
    rs = join.children[1].output_schema()
    fused = _build_fused_join_kernel(
        _build_count_kernel(join.left_keys, join.right_keys, ls, rs,
                            "inner"), semi_like=False)
    n = jax.ShapeDtypeStruct((), np.int32, sharding=one_chip)
    cfg = jax.ShapeDtypeStruct((3,), np.int32, sharding=one_chip)
    _compile(fused.lower(_cols(ls, DIM, one_chip),
                         _cols(rs, batch, one_chip), n, n, DIM, batch,
                         batch, cfg), SLOW_LIMIT_S)


@pytest.mark.slow
@pytest.mark.parametrize("n_dev", [1, 4])
def test_q3_fragment(topo, n_dev):
    """parallel/planner.py:_build_program — q3's joins and aggregation as
    ONE shard_map module (the one-device form is what
    sql.fusedPipeline.enabled runs), at the mesh phase's sizes and
    bounds; on four devices the compiled program must exchange rows with
    an all-to-all. Minutes on the sandbox CPU — by hand only."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import chip_smoke as cs
    from benchmarks import queries_sql as Q, tpcds
    from spark_rapids_tpu.api import TpuSession
    from spark_rapids_tpu.parallel import make_mesh
    from spark_rapids_tpu.parallel.planner import (DistributedPipelineExec,
                                                   _Env, _bucket)
    mesh = make_mesh(devices=topo.devices[:n_dev])
    s = TpuSession(dict(cs.PINNED_CONF, **cs.MESH_CONF), mesh=mesh)
    for name, t in (("store_sales", tpcds.gen_store_sales(2048)),
                    ("date_dim", tpcds.gen_date_dim()),
                    ("item", tpcds.gen_item())):
        s.create_dataframe(t).create_or_replace_temp_view(name)
    ex = _find(s.sql(Q.TPCDS_Q3)._physical(), DistributedPipelineExec)
    assert ex is not None and ex.n_dev == n_dev
    layout, inputs, off = {}, [], 0
    for (src, replicated), fields in zip(ex.sources, ex._source_fields()):
        assert replicated != any(f.name.startswith("ss_") for f in fields)
        padded = _bucket(2000 if replicated
                         else cs.MESH_Q3_ROWS_PER_DEVICE)
        lead = 1 if replicated else n_dev
        sh = NamedSharding(mesh, P() if replicated else P(ex.axis))
        inputs.append(jax.ShapeDtypeStruct((lead,), np.int32, sharding=sh))
        for f in fields:
            inputs.append(jax.ShapeDtypeStruct(
                (lead * padded,), f.phys.np_dtype, sharding=sh))
            inputs.append(jax.ShapeDtypeStruct((lead * padded,), np.bool_,
                                               sharding=sh))
        layout[len(layout)] = (padded, len(fields), off)
        off += 1 + 2 * len(fields)
    env = _Env(mesh, ex.axis, ex.conf, layout, ex._bounds, ex.sig)
    compiled = _compile(ex._build_program(env).lower(*inputs),
                        SLOW_LIMIT_S)
    assert ("all-to-all" in compiled.as_text()) == (n_dev > 1)

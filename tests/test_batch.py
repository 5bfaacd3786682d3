"""Device concat of batches (columnar/batch.py): where every batch's row
count is a host int and every column a plain device column, a byte
rectangle (which rides as 1-D lanes) or dictionary codes over ONE
dictionary, the live rows of each batch go to a known offset in ONE
jitted call with no sort."""
import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import ColumnarBatch
from spark_rapids_tpu.columnar import batch as batch_mod
from spark_rapids_tpu.columnar.batch import (_device_concat_packed,
                                             concat_batches_device)
from spark_rapids_tpu.columnar.strrect import ByteRectColumn


def _batch(n: int, seed: int) -> ColumnarBatch:
    rng = np.random.RandomState(seed)
    return ColumnarBatch.from_arrow(pa.table({
        "i": pa.array(rng.randint(-50, 50, n), mask=rng.rand(n) < 0.2),
        "f": pa.array(rng.rand(n), mask=rng.rand(n) < 0.2),
        "b": pa.array(rng.rand(n) < 0.5, mask=rng.rand(n) < 0.2),
        "d": pa.array(rng.randint(0, 20000, n).astype("int32"),
                      pa.date32())}))


def _strings(n: int, seed: int, long: bool = False) -> ColumnarBatch:
    rng = np.random.RandomState(seed)
    tail = "-a-longer-tail-that-widens-the-rectangle" if long else ""
    vals = [f"row-{seed}-{i}-{rng.randint(1 << 30)}{tail}" for i in range(n)]
    return ColumnarBatch.from_arrow(pa.table({
        "s": pa.array(vals, pa.string(), mask=rng.rand(n) < 0.1),
        "v": pa.array(rng.rand(n))}))


def _lanes(batches):
    return [[(b.columns[i].data, b.columns[i].validity) for b in batches]
            for i in range(len(batches[0].columns))]


@pytest.fixture
def concat_kernel_ran():
    """Reads whether the jitted concat was resolved since the fixture
    cleared its memo: the only kernel the device concat has."""
    batch_mod._clear_device_concat()
    return lambda: batch_mod._DEVICE_CONCAT_JIT is not None


# row counts of the batches: under-filled pairs (the coalesce's case), a
# zero-row batch first / in the middle / last, a full batch in the middle,
# more rows than one bucket, one row
@pytest.mark.parametrize("counts", [
    (4092, 4092), (4096, 3728), (0, 700), (700, 0, 5), (5, 0),
    (100, 1024, 3), (1000, 1000, 1000, 1000, 1000, 1000, 1000, 1000, 1000),
    (1, 1)])
def test_packed_concat_equals_the_arrow_concat(counts, concat_kernel_ran):
    batches = [_batch(n, seed) for seed, n in enumerate(counts)]
    got = concat_batches_device(batches)
    assert concat_kernel_ran()
    total = sum(counts)
    assert got.num_rows == total and isinstance(got.num_rows_raw, int)
    assert got.padded_len == batch_mod.bucket_for(total)
    want = pa.concat_tables([b.to_arrow() for b in batches])
    assert got.to_arrow().equals(want)
    # beyond the live prefix padding stays padding: no validity, zero data
    for col in got.columns:
        assert not np.asarray(col.validity)[total:].any()
        assert not np.asarray(col.data)[total:].any()


# byte-rectangle strings ride the same kernel as packed word + length
# lanes: under-filled, a zero-row batch, rectangles of different widths
@pytest.mark.parametrize("shapes", [
    ((300, False), (200, False)), ((0, False), (50, False), (7, False)),
    ((300, False), (200, True), (1100, False))])
def test_packed_concat_takes_byte_rectangles(shapes, concat_kernel_ran):
    rect = [_strings(n, seed, long)
            for seed, (n, long) in enumerate(shapes)]
    assert all(isinstance(b.columns[0], ByteRectColumn) for b in rect)
    out = concat_batches_device(rect)
    assert concat_kernel_ran()
    assert isinstance(out.columns[0], ByteRectColumn)
    total = sum(n for n, _ in shapes)
    assert out.num_rows_raw == total
    assert out.padded_len == batch_mod.bucket_for(total)
    assert out.to_arrow().equals(
        pa.concat_tables([b.to_arrow() for b in rect]))


def test_packed_concat_has_no_sort_and_fetches_nothing(monkeypatch,
                                                       concat_kernel_ran):
    batches = [_batch(4092, 0), _batch(4092, 1)]
    hlo = jax.jit(_device_concat_packed, static_argnums=(2,)).lower(
        jnp.zeros(2, jnp.int32), _lanes(batches), 8192).as_text()
    assert "sort" not in hlo and "gather" not in hlo
    assert "dynamic_update_slice" in hlo or "dynamic-update-slice" in hlo
    # what the text looks for, were it there
    assert "sort" in jax.jit(jnp.argsort).lower(
        jnp.zeros(8, jnp.int32)).as_text()

    def no_fetch(*a, **k):
        raise AssertionError("the concat fetched")
    monkeypatch.setattr(jax, "device_get", no_fetch)
    out = concat_batches_device(batches)
    assert concat_kernel_ran()
    assert out.padded_len == 8192 and out.num_rows_raw == 8184


def test_full_batches_and_foreign_columns_keep_their_paths(
        concat_kernel_ran):
    # every batch but the last full: plain concatenation, no kernel of ours
    full = [_batch(1024, 0), _batch(1024, 1), _batch(10, 2)]
    out = concat_batches_device(full)
    assert not concat_kernel_ran()
    assert out.to_arrow().equals(
        pa.concat_tables([b.to_arrow() for b in full]))
    # a lazy count is not this function's to merge
    lazy = _batch(10, 3)
    lazy._num_rows = jnp.int32(10)
    assert concat_batches_device([_batch(10, 4), lazy]) is None


def _dict_batches(counts, dictionary=None):
    """Batches of (codes over one dictionary object, a float), as one
    broadcast join's outputs carry its build side's strings."""
    from spark_rapids_tpu.columnar.column import DictColumn
    from spark_rapids_tpu.types import STRING
    if dictionary is None:
        dictionary = np.array([f"brand #{i}" for i in range(9)], object)
    out = []
    for seed, n in enumerate(counts):
        b = _batch(n, seed)
        rng = np.random.RandomState(100 + seed)
        p = b.padded_len
        codes = np.zeros(p, np.int32)
        valid = np.zeros(p, bool)
        codes[:n] = rng.randint(0, len(dictionary), n)
        valid[:n] = rng.rand(n) > 0.2
        col = DictColumn(jnp.asarray(codes), jnp.asarray(valid), STRING,
                         dictionary)
        out.append(ColumnarBatch(
            [col, b.columns[1]], n,
            type(b.schema)([type(b.schema.fields[0])("s", STRING, True),
                            b.schema.fields[1]])))
    return out


@pytest.mark.parametrize("counts", [(300, 200), (0, 50, 7), (1, 1, 1, 1024)])
def test_one_dictionarys_codes_concat_on_the_device(counts, monkeypatch,
                                                    concat_kernel_ran):
    """DictColumns that share ONE dictionary object concatenate as their
    code lanes, nothing leaves the device, the result is a DictColumn over
    that dictionary and equals the host-staged concat."""
    from spark_rapids_tpu.columnar.column import DictColumn
    batches = _dict_batches(counts)
    want = pa.concat_tables([b.to_arrow() for b in batches])

    def no_arrow(self):
        raise AssertionError("the concat went through Arrow")
    with monkeypatch.context() as m:
        m.setattr(ColumnarBatch, "to_arrow", no_arrow)
        m.setattr(jax, "device_get", no_arrow)
        got = batch_mod.concat_batches(batches)
    assert concat_kernel_ran()
    assert type(got.columns[0]) is DictColumn
    assert got.columns[0].dictionary is batches[0].columns[0].dictionary
    assert got.num_rows_raw == sum(counts)
    assert got.to_arrow().equals(want)


def test_differing_dictionaries_take_the_host_path(concat_kernel_ran):
    """Codes of two dictionaries (equal or not) mean nothing side by side:
    the device concat declines and the host-staged path decodes both."""
    a = _dict_batches((30,))[0]
    b = _dict_batches((20,), np.array([f"other #{i}" for i in range(9)],
                                      object))[0]
    twin = _dict_batches((20,))[0]      # an equal dictionary, another object
    for pair in ([a, b], [a, twin]):
        assert concat_batches_device(pair) is None
        got = batch_mod.concat_batches(pair)
        assert got.to_arrow().equals(
            pa.concat_tables([x.to_arrow() for x in pair]))
    assert not concat_kernel_ran()
    # a dictionary column beside a plain one of another batch: declined too
    assert concat_batches_device([a, _batch(5, 0)]) is None


@pytest.mark.parametrize("rows,bucket", [
    (300_000, 524_288), (524_289, 1_048_576), (1_460_000, 1_572_864),
    (1_572_865, 2_097_152), (2_097_153, 3_145_728), (4_194_305, 8_388_608)])
def test_build_ladder_is_finer_above_a_quarter_million_rows(rows, bucket):
    """A join's build side and coalesced output: x2 steps above 262,144
    rows, x1.5 / x1.33 from 1,048,576 on, DEFAULT_BUCKETS' own below, and
    a bounded number of shapes (multiples of the top beyond it)."""
    from spark_rapids_tpu.columnar.bucketing import (BUILD_BUCKETS,
                                                     DEFAULT_BUCKETS,
                                                     bucket_for)
    assert bucket_for(rows, BUILD_BUCKETS) == bucket
    assert BUILD_BUCKETS[:4] == DEFAULT_BUCKETS[:4]
    assert set(DEFAULT_BUCKETS) <= set(BUILD_BUCKETS) and len(BUILD_BUCKETS) == 10


# rows of the bucket, columns: the validity bits ride in the key's bits below
# the row index (3 columns), beside it in 32-lane words (40 and 90 columns:
# 18 fit the key at 8,192 rows), a 1,048,576-row batch (11 spare bits), the
# smallest shapes
@pytest.mark.parametrize("rows,ncol", [
    (1024, 3), (8192, 40), (8192, 90), (1 << 20, 12), (16, 1), (1, 2)])
def test_compaction_keeps_rows_in_order_with_their_validity(rows, ncol):
    """``compact_rows`` (one unstable sort by a unique key): kept rows to the
    front in their order with their validity, the dropped ones behind them
    in theirs, validity cleared past the count; and the sort it traces has
    ONE key and no bool operand."""
    from spark_rapids_tpu.columnar.segmented import compact_rows
    rng = np.random.default_rng(rows + ncol)
    keep = rng.random(rows) < 0.4
    cols = [(rng.integers(0, 1 << 40, rows) if i % 3 == 0 else
             rng.random(rows) if i % 3 == 1 else rng.random(rows) < 0.5,
             rng.random(rows) < 0.8) for i in range(ncol)]
    args = ([(jnp.asarray(d), jnp.asarray(v)) for d, v in cols],
            jnp.asarray(keep), rows)
    outs, count = jax.jit(compact_rows, static_argnums=2)(*args)
    n = int(count)
    assert n == keep.sum()
    for (d, v), (od, ov) in zip(cols, outs):
        assert (np.asarray(od)[:n] == d[keep]).all()
        assert (np.asarray(od)[n:] == d[~keep]).all()
        assert (np.asarray(ov)[:n] == v[keep]).all()
        assert not np.asarray(ov)[n:].any()
    sorts = [e for e in jax.make_jaxpr(compact_rows, static_argnums=2)(
        *args).jaxpr.eqns if e.primitive.name == "sort"]
    assert len(sorts) == 1
    assert sorts[0].params["num_keys"] == 1 and not sorts[0].params["is_stable"]
    assert all(v.aval.dtype != np.bool_ for v in sorts[0].invars)

"""Sort exec (ref GpuSortExec.scala:86; out-of-core iterator :281).

Device sort = encode each SortOrder into (null_rank u8, key u64) operands
(exec/encoding.py) and run ONE stable ``lax.sort`` carrying every output
column as payload.

Global sort has two regimes (selected by spark.rapids.tpu.sql.batchSizeBytes,
the reference's targetSizeBytes role):
  * small input — concatenate + one device sort (single-batch goal);
  * out-of-core — the reference's GpuOutOfCoreSortIterator re-designed
    TPU-first as a SAMPLE SORT: sort each input batch into a spillable run,
    sample each run's encoded sort keys to pick K-1 range splitters, bucket
    every run by splitter rank on device (one fused lexicographic-compare
    kernel + the contiguous-split sorter), then per bucket concat the slices
    from all runs and device-sort once more. Buckets are range-disjoint and
    emitted in order, so the stream of output batches is globally sorted
    while only ~|total|/K rows are ever resident. Sample sort replaces the
    reference's priority-queue merge because a K-way streaming merge is
    scalar-sequential (hostile to the MXU/vector units), while bucketing and
    re-sorting are single fused XLA ops over static shapes.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import ColumnarBatch, DeviceColumn, concat_batches
from ..columnar.transfer import traced_device_get
from ..exprs.base import DVal, EvalContext
from ..mem import SpillableBatch, with_retry_no_split, wrap_spillables
from ..plan.logical import SortOrder
from ..types import Schema
from .base import ExecContext, TpuExec
from .encoding import order_key_operands

__all__ = ["TpuSortExec", "CpuSortExec", "sort_batch_device"]


def _np_total_order_key(v, valid=None):
    """uint64 whose unsigned order == Spark ascending order (host-side twin
    of exec/encoding.py; numpy has no 64-bit bitcast restriction). Strings
    and other non-numeric comparables are dense-ranked (UTF-8 byte order ==
    codepoint order, which np sorting follows); ``valid`` masks rows whose
    value may be None and must not poison the ranking."""
    import numpy as np
    v = np.asarray(v)
    if np.issubdtype(v.dtype, np.floating):
        d = v.astype(np.float64)
        d = np.where(d == 0.0, 0.0, d)
        d = np.where(np.isnan(d), np.nan, d)
        b = d.view(np.uint64)
        return np.where(b >> np.uint64(63) != 0, ~b,
                        b | np.uint64(1 << 63))
    if v.dtype == np.bool_:
        return v.astype(np.uint64)
    if v.dtype.kind in ("U", "S", "O"):
        vv = v
        if valid is not None and not valid.all():
            if not valid.any():
                return np.zeros(len(v), np.uint64)
            vv = v.copy()
            # placeholder comparable with the column's own values (could
            # be str OR Decimal); null rank decides actual order
            vv[~valid] = vv[valid][0]
        _, inv = np.unique(vv, return_inverse=True)
        return inv.astype(np.uint64)
    return v.astype(np.int64).view(np.uint64) ^ np.uint64(1 << 63)

_SORT_KERNEL_CACHE: Dict[Tuple, object] = {}


def _kernel_cache_key(orders: List[SortOrder], schema: Schema):
    return (tuple(f"{o.expr.key()}|{o.ascending}|{o.nulls_first}"
                  for o in orders),
            tuple((f.name, f.dtype.name) for f in schema.fields))


def _build_sort_kernel(orders: List[SortOrder], schema: Schema,
                       with_keys: bool = False):
    dtypes = [f.dtype for f in schema.fields]

    @functools.partial(jax.jit, static_argnums=(2,))
    def kernel(cols, num_rows, padded_len):
        dvals = [None if c is None else DVal(c[0], c[1], dt)
                 for c, dt in zip(cols, dtypes)]
        ctx = EvalContext(schema, dvals, num_rows, padded_len)
        row_mask = ctx.row_mask()
        pad_flag = jnp.where(row_mask, jnp.uint8(0), jnp.uint8(1))
        operands = [pad_flag]
        for o in orders:
            v = o.expr.eval_device(ctx)
            operands.extend(order_key_operands(v, o.ascending, o.nulls_first))
        # sort (keys, row-index) then gather columns — payload-free sort;
        # the row index is the last KEY: unique keys, so the unstable sort
        # gives the stable order (and compiles in less time)
        perm0 = jnp.arange(padded_len, dtype=jnp.int32)
        n_ops = len(operands)
        out = jax.lax.sort(tuple(operands + [perm0]), num_keys=n_ops + 1,
                           is_stable=False)
        perm = out[n_ops]
        sorted_cols = [(jnp.take(dv.data, perm), jnp.take(dv.validity, perm))
                       for dv in dvals]
        if with_keys:
            # permuted encoded keys ride along so the out-of-core sampler
            # needn't re-evaluate the sort expressions over the run
            return sorted_cols, tuple(out[1:n_ops])
        return sorted_cols

    return kernel


def sort_batch_device(orders: List[SortOrder], batch: ColumnarBatch,
                      with_keys: bool = False):
    key = _kernel_cache_key(orders, batch.schema) + (with_keys,)
    kernel = _SORT_KERNEL_CACHE.get(key)
    if kernel is None:
        kernel = _build_sort_kernel(orders, batch.schema, with_keys)
        _SORT_KERNEL_CACHE[key] = kernel
    cols = [(c.data, c.validity) for c in batch.columns]
    outs = kernel(cols, jnp.int32(batch.num_rows), batch.padded_len)
    ops = None
    if with_keys:
        outs, ops = outs
    new_cols = [c.with_arrays(d, v)
                for (d, v), c in zip(outs, batch.columns)]
    out = ColumnarBatch(new_cols, batch.num_rows, batch.schema)
    return (out, ops) if with_keys else out


#: the largest LIMIT a sort above which takes the selection kernel (TPC-H's
#: and TPC-DS's own are 10 to 100): its loop runs once a kept row
TOP_N_MAX = 128


def _build_topn_kernel(orders: List[SortOrder], schema: Schema, n: int):
    """The first ``n`` rows of the sorted batch WITHOUT the sort: ``n``
    rounds of a lexicographic minimum over the encoded key operands (a
    masked min-reduction an operand, the candidates narrowed to the rows
    that hold it), the lowest row index among equals, which is the stable
    sort's order. A full sort of the batch is what a LIMIT above it pays
    for and throws away; and its module is the one a float64 key makes
    slowest to compile (262,144 rows, ``revenue DESC, o_orderdate``: 231 s
    on the chip's host, PERF.md, PR 32)."""
    from ..columnar.bucketing import bucket_for
    from ..columnar.segmented import _neutral_max
    dtypes = [f.dtype for f in schema.fields]
    out_p = bucket_for(n)

    def topn(cols, num_rows, padded_len):
        dvals = [None if c is None else DVal(c[0], c[1], dt)
                 for c, dt in zip(cols, dtypes)]
        ctx = EvalContext(schema, dvals, num_rows, padded_len)
        operands = []
        for o in orders:
            v = o.expr.eval_device(ctx)
            for op in order_key_operands(v, o.ascending, o.nulls_first):
                if jnp.issubdtype(op.dtype, jnp.floating):
                    # the sort's total order has NaN above +inf (below
                    # -inf negated, for DESC); a min-reduction has not
                    nan = jnp.isnan(op)
                    low = jnp.uint8(1 if o.ascending else 0)
                    operands.append(jnp.where(nan, low, jnp.uint8(1) - low))
                    op = jnp.where(nan, jnp.zeros_like(op), op)
                operands.append(op)

        def pick(t, state):
            alive, picks = state
            cand = alive
            for op in operands:
                least = jnp.min(jnp.where(cand, op, _neutral_max(op.dtype)))
                cand = jnp.logical_and(cand, op == least)
            row = jnp.argmax(cand).astype(jnp.int32)
            return alive.at[row].set(False), picks.at[t].set(row)

        _, picks = jax.lax.fori_loop(
            0, n, pick, (ctx.row_mask(), jnp.zeros(out_p, jnp.int32)))
        count = jnp.minimum(jnp.sum(ctx.row_mask()), n).astype(jnp.int32)
        live = jnp.arange(out_p, dtype=jnp.int32) < count
        return [None if dv is None else
                (jnp.take(dv.data, picks, axis=0),
                 jnp.logical_and(jnp.take(dv.validity, picks), live))
                for dv in dvals], count, picks

    # ONE callable a process (the executable cache's, so its compiles are
    # counted), as the joins' probe kernels
    from ..plan import exec_cache
    return exec_cache.get_or_build_jit(
        f"sort.topn:{_kernel_cache_key(orders, schema)}:{n}", topn,
        static_argnums=(2,))


def topn_batch_device(orders: List[SortOrder], batch: ColumnarBatch,
                      n: int) -> ColumnarBatch:
    """``sort_batch_device`` cut to its first ``n`` rows, in their order.
    The rows are picked by the keys; every other column is gathered by the
    picked rows in the form it has: a device lane (dictionary codes
    included) inside the kernel, a string rectangle as its word lanes by
    the same rows, a column on the host by the fetched rows (``n`` of
    them)."""
    from ..exprs.compiler import gather_batch_device
    kernel = _build_topn_kernel(orders, batch.schema, n)
    plain = [isinstance(c, DeviceColumn) and not hasattr(c, "kernel_lanes")
             for c in batch.columns]
    cols = [(c.data, c.validity) if ok else None
            for c, ok in zip(batch.columns, plain)]
    outs, count, picks = kernel(cols, jnp.int32(batch.num_rows_raw),
                                batch.padded_len)
    rows = batch.num_rows_raw
    rows = min(rows, n) if isinstance(rows, int) else count
    new_cols = [c.with_arrays(*o) if ok else None
                for o, c, ok in zip(outs, batch.columns, plain)]
    rest = [i for i, ok in enumerate(plain) if not ok]
    if rest:
        sub = ColumnarBatch([batch.columns[i] for i in rest],
                            batch.num_rows_raw,
                            Schema([batch.schema.fields[i] for i in rest]))
        live = jnp.arange(picks.shape[0], dtype=jnp.int32) < count
        got = gather_batch_device(sub, jnp.where(live, picks, -1), rows,
                                  int(picks.shape[0]))
        for i, c in zip(rest, got.columns):
            new_cols[i] = c
    return ColumnarBatch(new_cols, rows, batch.schema)


_KEYENC_CACHE: Dict[Tuple, object] = {}


def _build_keyenc_kernel(orders: List[SortOrder], schema: Schema):
    """Encoded sort-key operand arrays for a batch (same encoding the sort
    kernel orders by, so host-side splitter maths agrees with device order)."""
    dtypes = [f.dtype for f in schema.fields]

    @functools.partial(jax.jit, static_argnums=(2,))
    def kernel(cols, num_rows, padded_len):
        dvals = [None if c is None else DVal(c[0], c[1], dt)
                 for c, dt in zip(cols, dtypes)]
        ctx = EvalContext(schema, dvals, num_rows, padded_len)
        operands = []
        for o in orders:
            v = o.expr.eval_device(ctx)
            operands.extend(order_key_operands(v, o.ascending, o.nulls_first))
        return tuple(operands)

    return kernel


def _encode_keys(orders: List[SortOrder], batch: ColumnarBatch):
    key = _kernel_cache_key(orders, batch.schema)
    kern = _KEYENC_CACHE.get(key)
    if kern is None:
        kern = _build_keyenc_kernel(orders, batch.schema)
        _KEYENC_CACHE[key] = kern
    cols = [(c.data, c.validity) for c in batch.columns]
    return kern(cols, jnp.int32(batch.num_rows), batch.padded_len)


@functools.partial(jax.jit, static_argnums=(3,))
def _bucket_id_kernel(operands, splitters, num_rows, padded_len):
    """bucket(row) = #{splitters lexicographically <= row_key}; padding rows
    go to the virtual last bucket. Accumulates over splitters with a
    fori_loop so peak memory is O(P), not O(P x K) — this path runs exactly
    when HBM is tight."""
    P = padded_len
    S = splitters[0].shape[0]

    def body(i, bucket):
        gt = jnp.zeros(P, dtype=jnp.bool_)
        eq = jnp.ones(P, dtype=jnp.bool_)
        for op, sv in zip(operands, splitters):
            s = jax.lax.dynamic_index_in_dim(sv, i, keepdims=False)
            gt = jnp.logical_or(gt, jnp.logical_and(eq, op > s))
            eq = jnp.logical_and(eq, op == s)
        return bucket + jnp.logical_or(gt, eq).astype(jnp.int32)

    bucket = jax.lax.fori_loop(0, S, body, jnp.zeros(P, dtype=jnp.int32))
    live = jnp.arange(P, dtype=jnp.int32) < num_rows
    return jnp.where(live, bucket, jnp.int32(S + 1))


class TpuSortExec(TpuExec):
    #: splitter-sample rows taken per sorted run per target bucket
    OVERSAMPLE = 8

    def __init__(self, orders: List[SortOrder], child: TpuExec,
                 global_sort: bool = True, limit: int = None):
        super().__init__([child])
        self.orders = orders
        self.global_sort = global_sort
        #: rows a LIMIT above keeps (plan/rewrites.py); None: all
        self.limit = limit

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def _top_n(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        """Each input batch cut to its own first ``limit`` rows as it
        arrives, then those cut once more: nothing is held but
        ``limit`` rows a batch, and no batch is sorted."""
        def top(batch):
            with ctx.semaphore.held():
                return topn_batch_device(self.orders, batch, self.limit)
        tops = [with_retry_no_split(
                    lambda b=b: top(b.ensure_device().with_lists_on_host(
                        strings=False)), ctx=ctx, op=self._exec_id)
                for b in self.children[0].execute(ctx)]
        if len(tops) > 1:
            tops = [with_retry_no_split(lambda: top(concat_batches(tops)),
                                        ctx=ctx, op=self._exec_id)]
        yield from tops

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        if self.limit is not None:
            yield from self._top_n(ctx)
            return
        if not self.global_sort:
            for batch in self.children[0].execute(ctx):
                with ctx.semaphore.held():
                    yield sort_batch_device(
                        self.orders,
                        batch.ensure_device().with_lists_on_host())
            return
        spillables = wrap_spillables(
            (b.ensure_device().with_lists_on_host()
             for b in self.children[0].execute(ctx)), ctx.memory)
        if not spillables:
            return
        total = sum(s.device_bytes() for s in spillables)
        target = ctx.conf.batch_size_bytes
        if total > target:
            yield from self._out_of_core(ctx, spillables, total, target)
            return

        def do_sort():
            with ctx.semaphore.held():
                big = concat_batches([sb.get() for sb in spillables])
                return sort_batch_device(self.orders, big)

        try:
            out = with_retry_no_split(do_sort, ctx=ctx, op=self._exec_id)
        finally:
            for sb in spillables:
                sb.close()
        yield out

    # ------------------------------------------------------------------
    def _out_of_core(self, ctx: ExecContext, spillables, total, target
                     ) -> Iterator[ColumnarBatch]:
        from ..shuffle.partitioning import (PartitionedBatches, _split_kernel,
                                            scatter_spillables)
        n_buckets = min(int(-(-total // max(target, 1))), 256)
        splits_m = ctx.metric(self._exec_id, "sortBuckets")
        splits_m.set(n_buckets)

        # pass 1: sort every batch into a run + sample its encoded keys;
        # sample counts are proportional to run size so a small run cannot
        # skew the pooled quantiles (and so bucket loads stay balanced)
        total_rows = max(sum(sb.num_rows for sb in spillables), 1)
        budget = n_buckets * self.OVERSAMPLE * len(spillables)
        runs = []
        samples = []
        try:
            for sb in spillables:
                def sort_one(sb=sb):
                    with ctx.semaphore.held():
                        run, ops = sort_batch_device(self.orders, sb.get(),
                                                     with_keys=True)
                        n = run.num_rows
                        if n == 0:
                            return SpillableBatch(run, ctx.memory), None
                        k = max(min(n, -(-budget * n // total_rows)), 1)
                        idx = jnp.asarray(
                            np.linspace(0, n - 1, num=k, dtype=np.int64))
                        samp = traced_device_get(
                            [jnp.take(op, idx) for op in ops],
                            "d2h.sort_sample")
                        return SpillableBatch(run, ctx.memory), samp
                run_sb, samp = with_retry_no_split(sort_one, ctx=ctx,
                                                   op=self._exec_id)
                sb.close()
                runs.append(run_sb)
                if samp is not None:
                    samples.append(samp)
        except Exception:
            # close() is idempotent: already-consumed inputs are no-ops
            for x in runs + spillables:
                x.close()
            raise
        if not samples:
            for r in runs:
                r.close()
            return

        # pick K-1 splitters from the pooled samples (host; encoded keys
        # order identically to the device sort)
        pooled = [np.concatenate([s[j] for s in samples])
                  for j in range(len(samples[0]))]
        order = np.lexsort(tuple(reversed(pooled)))
        m = len(order)
        cut = [order[int(m * (b + 1) / n_buckets) - 1]
               for b in range(n_buckets - 1)]
        splitters = tuple(jnp.asarray(p[cut]) for p in pooled)

        # pass 2: bucket every run by splitter rank (device)
        def bucket_run(run: ColumnarBatch) -> PartitionedBatches:
            ops = _encode_keys(self.orders, run)
            pid = _bucket_id_kernel(ops, splitters, jnp.int32(run.num_rows),
                                    run.padded_len)
            arrays = [(c.data, c.validity) for c in run.columns]
            cols, counts = _split_kernel(arrays, pid, run.padded_len,
                                         n_buckets + 2)
            counts = traced_device_get(counts, "d2h.sort_counts")
            return PartitionedBatches(cols, counts[:n_buckets], run.schema)

        bucket_slices = scatter_spillables(ctx, runs, bucket_run, n_buckets)

        # pass 3: per bucket, concat + device sort; buckets are range-
        # disjoint and ordered, so the output stream is globally sorted
        try:
            for b in range(n_buckets):
                parts = bucket_slices[b]
                if not parts:
                    continue

                def merge_bucket(parts=parts):
                    with ctx.semaphore.held():
                        big = concat_batches([p.get() for p in parts])
                        return sort_batch_device(self.orders, big)
                try:
                    out = with_retry_no_split(merge_bucket, ctx=ctx,
                                              op=self._exec_id)
                finally:
                    for p in parts:
                        p.close()
                yield out
        except BaseException:
            # fatal merge or abandoned consumer: LATER buckets' slices
            # still pin pool budget (close() is idempotent, so the
            # current bucket's already-closed parts are no-ops)
            for slot in bucket_slices:
                for p in slot:
                    p.close()
            raise

    def describe(self):
        top = "" if self.limit is None else f"; first {self.limit}"
        return "Sort[" + ", ".join(map(repr, self.orders)) + top + "]"


class CpuSortExec(TpuExec):
    is_tpu = False

    def __init__(self, orders: List[SortOrder], child: TpuExec,
                 global_sort: bool = True):
        super().__init__([child])
        self.orders = orders
        self.global_sort = global_sort

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        import numpy as np
        import pyarrow as pa
        from ..exprs.arithmetic import arrow_to_masked_numpy
        tables = [b.to_arrow() for b in self.children[0].execute(ctx)]
        if not tables:
            return
        t = pa.concat_tables(tables)
        # host columns only: from_arrow would put columns back on device,
        # and each eval_host key fetch would then pay two host syncs
        batch = ColumnarBatch.from_arrow_host(t)
        # stable lexsort with per-key order/null-placement (Spark semantics:
        # NaN greatest, -0.0 == 0.0, null rank independent per key)
        lex_keys = []
        for o in reversed(self.orders):  # np.lexsort: last key is primary
            v, ok = arrow_to_masked_numpy(o.expr.eval_host(batch))
            enc = _np_total_order_key(v, ok)
            if not o.ascending:
                enc = ~enc
            enc = np.where(ok, enc, np.uint64(0))
            rank = np.where(ok, 1, 0) if o.nulls_first else np.where(ok, 0, 1)
            lex_keys.extend([enc, rank.astype(np.uint8)])
        idx = np.lexsort(tuple(lex_keys))
        # host-only output: the sorted result is usually terminal (feeds
        # collect) — round-tripping it through HBM costs two host syncs
        yield ColumnarBatch.from_arrow_host(t.take(pa.array(idx)))

    def describe(self):
        return "CpuSort[" + ", ".join(map(repr, self.orders)) + "]"

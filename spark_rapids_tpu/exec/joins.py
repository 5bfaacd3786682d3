"""Equi-join execs (ref GpuHashJoin.scala:1033, JoinGatherer.scala,
GpuShuffledHashJoinExec, GpuBroadcastNestedLoopJoinExecBase).

TPU-first design: cudf's hash join has no XLA analog, so the join is a
SORT-based group-match, all static shapes:

  phase A (count kernel): concatenate both sides' encoded keys, one
    lax.sort, segment boundaries -> per-group counts/starts for each side,
    per-group output pair counts, total output size.
  host sync: total -> output shape bucket (the reference similarly sizes
    gather maps before gathering).
  phase B (gather kernel, static output): for each output slot, locate its
    group via searchsorted over the pair-count prefix sums, derive
    (left_row, right_row) indices arithmetically, gather columns; -1 index
    = null-extended row (outer joins).

Join semantics: null keys never match (each null-key row forms a singleton
group); NaN keys match NaN (canonicalized — ref NormalizeFloatingNumbers);
left/right/full use countX' = max(countX, 1) so null-extension falls out of
the same index maths. Residual (non-equi) conditions are applied as a
post-filter for inner/cross and tagged fallback otherwise.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.segmented import last_valid_scan, prefix_sum
import numpy as np

from ..columnar import ColumnarBatch, DeviceColumn, concat_batches
from ..columnar.bucketing import BUILD_BUCKETS, bucket_for
from ..columnar.transfer import traced_device_get
from ..exprs.base import DVal, EvalContext, Expression
from ..exprs.compiler import (_compact_kernel, _lane_pairs, _lane_rebuild,
                              eval_predicate_device, filter_batch_device,
                              gather_batch_device)
from ..mem import (SpillableBatch, with_retry_no_split,
                   wrap_spillable_sides)
from ..trace import core as trace_core
from ..types import BOOL, DecimalType, Schema, StructField
from .base import ESSENTIAL, ExecContext, TpuExec
from .encoding import grouping_operands, operands_equal

__all__ = ["TpuHashJoinExec", "TpuNestedLoopJoinExec",
           "TpuBroadcastHashJoinExec", "CpuJoinExec"]

_COUNT_CACHE: Dict[Tuple, object] = {}
_FUSED_CACHE: Dict[Tuple, object] = {}
_GATHER_CACHE: Dict[Tuple, object] = {}
_PROBE_CACHE: Dict[Tuple, object] = {}
#: last observed output total per join shape (feeds speculative sizing)
_TOTAL_STATS: Dict[Tuple, int] = {}


def _note_total(ctx: Optional[ExecContext], ck, n: int) -> None:
    """``n`` rows left one execution of join shape ``ck`` BEFORE any
    residual condition: the statistic the next run sizes its outputs from
    is the LARGEST such total of the query (``ctx.join_totals``). A
    streaming join has one a stream batch and its last batch is a partial
    one: sized from that, the next run's full batches overflow."""
    if ctx is not None:
        n = ctx.join_totals[ck] = max(n, ctx.join_totals.get(ck, 0))
    _TOTAL_STATS[ck] = n


class _OutBound:
    """What ONE execution of a streaming broadcast join knows about its
    output size. ``mult`` is the largest multiplicity of a build-side key,
    None until the first stream batch's count kernel has been read (one
    small fetch a join a query). For inner joins and the outer join that
    null-extends the build side, ``total <= n_stream * max(mult, 1)``: at
    ``mult <= 1`` the stream batch's own bucket is a HARD bound, so the
    output keeps it, nothing is speculated and nothing validated at the
    sink (as for semi/anti joins). Above 1 the speculation on the last
    observed total stays."""

    __slots__ = ("stream_left", "mult", "probe", "swap", "paths",
                 "general", "pack", "bits")

    def __init__(self, stream_left: bool):
        self.stream_left = stream_left
        #: stream batches the dense probe and the sort probe joined, and
        #: those the general kernel joined
        self.paths = [0, 0]
        self.general = 0
        #: two or more keys: how the build side packed them into one lane
        #: (``_pack_of``; on the device) and the packed width, 0 until read
        self.pack = None
        self.bits = 0
        self.mult: Optional[int] = None
        #: the build side's keys sorted (_SortedBuild), where the join has
        #: integer keys that the build side holds once a value (for a
        #: semi join: however often): stream batches are then probed
        #: against it by the sort-and-scan kernel, not the general one
        self.probe: Optional[_SortedBuild] = None
        #: an inner join whose BUILD side holds a key twice: the probe
        #: needs one side with unique keys, and each stream batch is
        #: tried as that side (``_join_swapped``)
        self.swap = False

    @property
    def probing(self) -> bool:
        """Whether stream batches go through the sort-and-scan probe."""
        return self.probe is not None or self.swap

    @property
    def hard(self) -> bool:
        return self.mult is not None and self.mult <= 1


def _max_count(cnt):
    """Largest per-group row count of one side, from the count kernel's
    output as it is: a separate small program, so the sort-bearing
    kernels' compiled bodies (and their cache entries) stay as they are."""
    from ..plan import exec_cache
    return exec_cache.get_or_build_jit(
        "joins.max_count", lambda c: jnp.max(c).astype(jnp.int32))(cnt)


def _count_out_bound(hard: bool) -> None:
    """Tracer counter ``join.out_bound``, once per streaming broadcast
    join execution: whether its output buckets were bound hard by the
    build side's unique keys or sized by speculation."""
    tr = trace_core.TRACER
    if tr is not None:
        tr.counter("join.out_bound", {"hard": int(hard),
                                      "speculative": int(not hard)},
                   cat="exec")


def _count_probe_path(exec_id: str, bound: Optional["_OutBound"]) -> None:
    """Tracer counter ``join.probe_path``, once per streaming or broadcast
    equi-join execution: the stream batches the dense probe and the sort
    probe (the exchanged sides' included) joined."""
    tr = trace_core.TRACER
    if tr is not None:
        dense, srt = bound.paths if bound is not None else (0, 0)
        tr.counter("join.probe_path",
                   {"dense": dense, "sorted": srt,
                    "op": int(exec_id.rsplit("@", 1)[-1])}, cat="exec")


def _count_join_keys(exec_id: str, keys: int, bound: Optional["_OutBound"],
                     general: int = 0) -> None:
    """Tracer counter ``join.keys``, once per execution of an equi-join on
    two or more keys: the keys, the width they were packed into (0 where
    no build side was packed), the stream batches probed with the packed
    lane and those the general kernel joined (``general`` more beside what
    ``bound`` counted)."""
    tr = trace_core.TRACER
    if tr is None or keys < 2:
        return
    packed = bits = 0
    if bound is not None:
        packed, bits = sum(bound.paths), bound.bits
        general += bound.general
    tr.counter("join.keys",
               {"keys": keys, "bits": bits, "packed": packed,
                "general": general, "op": int(exec_id.rsplit("@", 1)[-1])},
               cat="exec")


def _count_join_rows(exec_id: str, build, stream, out, parts: int) -> None:
    """Tracer counter ``join.rows``, once per equi-join execution: the rows
    of the build side, of the stream side, of the output, the stream
    batches (or sub-partitions) the join ran as, and ``op``, the number in
    the operator's id (its ``join.build`` / ``join.probe`` spans carry the
    id as ``exec``). Counts still on the device (a filter's survivors, a
    speculated total) are fetched here in ONE packed transfer, and only
    while a tracer is installed."""
    tr = trace_core.TRACER
    if tr is None or not tr.recording:
        return
    from ..columnar.packing import sum_counts
    b, st, o = sum_counts((build, stream, out))
    tr.counter("join.rows", {"build": b, "stream": st, "out": o,
                             "parts": int(parts),
                             "op": int(exec_id.rsplit("@", 1)[-1])},
               cat="exec")


def _count_join_gather(join: "TpuHashJoinExec") -> None:
    """Tracer counter ``join.gather``, once per equi-join execution beside
    ``join.rows``: the columns the join hands on, and the columns of its
    inputs it no longer gathers because nothing above it reads them.
    Plan-time numbers: no device read."""
    tr = trace_core.TRACER
    if tr is not None:
        cols = len(join.output_schema())
        tr.counter("join.gather",
                   {"cols": cols, "dropped": len(join._full_schema) - cols,
                    "op": int(join._exec_id.rsplit("@", 1)[-1])},
                   cat="exec")


def _intake(batch: ColumnarBatch) -> ColumnarBatch:
    """A join input as it is taken in: on the device, list rectangles on
    the host (the gathers move 1-D lanes), string rectangles kept: the
    unique-key probe gathers them as their word lanes, and ``_join``
    demotes them for every other kernel."""
    return batch.ensure_device().with_lists_on_host(strings=False)


def _resolve_counts(spillables) -> None:
    """Install every row count that is still on the device (a filter's
    survivors) among ``spillables``, all in ONE packed transfer.
    SpillableBatch mirrors the lazy count: read WITHOUT get(), which
    would unspill whole batches just for a row count."""
    from ..columnar.packing import fetch_packed
    lazy = [s for s in spillables
            if not isinstance(s._num_rows, (int, np.integer))]
    if lazy:
        for s, v in zip(lazy, fetch_packed([s._num_rows for s in lazy])):
            s._num_rows = int(v)


def _counted(s: SpillableBatch) -> ColumnarBatch:
    """The batch of a spillable whose count ``_resolve_counts`` installed,
    knowing it too: a device concat needs host counts."""
    b = s.get()
    if not isinstance(b.num_rows_raw, int):
        b._resolve_count(s.num_rows)
    return b


# ---------------------------------------------------------------------------
# the unique-key probe: an inner join on integer keys whose build side
# holds each key once (a primary key: the usual build side); two or more
# keys are packed into one int64 lane by the build side's spans. The build
# side is sorted by the key once a join a query; a stream batch then costs
# two single-key sorts and two carry-forward scans over build + stream
# rows and NO row-sized scatter or gather: the general kernel's four
# segment reductions and its group-table lookups are what a batch costs
# there (PERF.md, PR 32: 2.8 ns a row for such a sort on the chip, 10-16 ns
# for a gathered one).
# ---------------------------------------------------------------------------

#: sort key of a row that cannot match: padding, a NULL key, a row the
#: count leaves out. A LIVE build key of this value keeps the general path.
_DEAD_KEY = np.iinfo(np.int64).max


class _SortedBuild:
    """A build side ready for ``_probe_kernel``: ``keys`` its keys as
    int64 in key order, the ``rows`` that can match first, ``_DEAD_KEY``
    past them; ``order`` the row of ``batch`` each of them came from.
    ``batch`` itself stays as it was: only the rows a probe matches are
    ever gathered from it (through ``order``), not the whole side once a
    query (1.5M rows of ``customer`` with their names, where a handful
    match)."""

    __slots__ = ("keys", "order", "batch", "rows", "cols", "lanes",
                 "spans", "span", "narrow", "pack")

    def __init__(self, keys, order, batch: ColumnarBatch, rows, cols,
                 span=None, narrow=False, pack=None):
        self.keys, self.order, self.batch, self.rows = (keys, order, batch,
                                                        rows)
        #: the columns of ``batch`` the join hands on, and those as the
        #: gather takes them, made once (a string rectangle rides as its
        #: word lanes)
        self.cols = cols
        self.lanes, self.spans = _lane_pairs(
            [(i, batch.columns[i]) for i in cols])
        #: the first and last live key (int64[2], on the device), and
        #: whether they lie less than 2^31 apart: for the dense probe
        self.span, self.narrow = span, narrow
        #: how two or more keys were packed into ``keys`` (``_pack_of``),
        #: which a stream batch's keys are packed by too; None for one key
        self.pack = pack


def _key_span(keys, rows):
    """The first and last live key of a sorted build side."""
    return jnp.stack([keys[0], keys[jnp.maximum(rows - 1, 0)]])


#: seconds a (stream row, build key) pair costs the dense probe's compare,
#: and a row one of the sort probe's two sorts (TPU v5 lite; PERF.md
#: section 6, PR 36, call c36a: 2.2 ps a pair at int64 and 1.3-1.8 on
#: 32-bit lanes; 2.4 ns a row at 1,049,600 and at 2,621,440 rows)
_C_PAIR = 2.2e-12
_C_SORT = 2.4e-9


def _dense_probe_wins(p_s: int, p_b: int, cut: int, out_p: int) -> bool:
    """Whether the dense probe (``p_s`` stream rows x ``cut`` build keys)
    costs less than the sort probe (two sorts of ``p_b + p_s`` rows). Its
    pack gathers ``out_p`` blocks of about the square root of ``p_s``
    counts: left out of the cost where ``out_p <= cut`` (a thousandth of
    the compare at Q18's shape), and above that the sort probe runs (the
    first batch of a shape reads its total in the stream's own bucket)."""
    return out_p <= cut and p_s * cut * _C_PAIR < 2 * (p_b + p_s) * _C_SORT


#: the widest packed key: its values stay below 2^62, so none is
#: ``_DEAD_KEY``; a wider pair or triple keeps the general kernel
_PACK_BITS = 62


def _key_lane(keys, schema, dtypes, cols, n, p, pack=None):
    """(int64 sort key, live) of one side's rows inside a kernel: its one
    key, or two or more integer keys packed into one lane by ``pack``
    (``_pack_of``: each key less its build-side low, shifted above the
    keys after it). A row with a NULL key, or with a key outside the build
    side's span, is not live."""
    if len(keys) == 1:
        ctx = _eval_ctx(schema, dtypes, cols, n, p)
        k = keys[0].eval_device(ctx)
        live = jnp.logical_and(ctx.row_mask(), k.validity)
        return jnp.where(live, k.data.astype(jnp.int64), _DEAD_KEY), live
    return _packed(*_key_lanes(keys, schema, dtypes, cols, n, p), pack)


def _eval_ctx(schema, dtypes, cols, n, p) -> EvalContext:
    return EvalContext(schema, [None if c is None else DVal(c[0], c[1], dt)
                                for c, dt in zip(cols, dtypes)], n, p)


def _key_lanes(keys, schema, dtypes, cols, n, p):
    """Each key's int64 lane, and the rows whose keys are all there."""
    ctx = _eval_ctx(schema, dtypes, cols, n, p)
    lanes, live = [], ctx.row_mask()
    for e in keys:
        k = e.eval_device(ctx)
        live = jnp.logical_and(live, k.validity)
        lanes.append(k.data.astype(jnp.int64))
    return lanes, live


def _pack_of(lanes, live):
    """int64[3, k]: each key's low and high over the live rows, and its
    shift, the bits of the keys after it (a key spanning ``hi - lo`` takes
    the bit length of that, 0 where it is one value); and the packed
    width. All traced values: no compiled shape depends on the spans."""
    big = np.iinfo(np.int64)
    any_live = jnp.any(live)
    lo = jnp.stack([jnp.where(any_live, jnp.min(jnp.where(live, x, big.max)),
                              0) for x in lanes])
    hi = jnp.stack([jnp.where(any_live, jnp.max(jnp.where(live, x, big.min)),
                              0) for x in lanes])
    # a span of 2^63 or more wraps negative: 64 bits, over the limit
    width = 64 - jax.lax.clz(hi - lo)
    shift = jnp.cumsum(width[::-1])[::-1] - width
    return jnp.stack([lo, hi, shift]), jnp.sum(width)


def _packed(lanes, live, pack):
    """The keys packed into one lane by ``pack``: ``(k0 - lo0) << s0 |
    (k1 - lo1) << s1 | ...``; ``_DEAD_KEY`` where a key is NULL or outside
    its build-side span."""
    lo, hi, shift = pack[0], pack[1], pack[2]
    key = jnp.zeros(live.shape, jnp.int64)
    for i, x in enumerate(lanes):
        live = jnp.logical_and(live, (x >= lo[i]) & (x <= hi[i]))
        key = key | ((x - lo[i]) << shift[i])
    return jnp.where(live, key, _DEAD_KEY), live


def _build_sort_kernel(keys, schema):
    """One side -> (sorted keys, the permutation, rows that can match,
    whether a key is there twice, whether a live key equals ``_DEAD_KEY``
    and so cannot be told from padding). Two or more keys are packed into
    one: by ``pack`` where it is given (a stream batch sorted in the build
    side's place, ``_join_swapped``), else by this side's own spans, and
    then ``pack`` is returned too and a width past ``_PACK_BITS`` reads as
    ``_DEAD_KEY``'s case."""
    dtypes = [f.dtype for f in schema.fields]

    def join_build(cols, n, p, pack=None):
        wide = None
        if len(keys) == 1:
            key, live = _key_lane(keys, schema, dtypes, cols, n, p)
        else:
            lanes, live = _key_lanes(keys, schema, dtypes, cols, n, p)
            if pack is None:
                pack, bits = _pack_of(lanes, live)
                wide = bits > _PACK_BITS
            key, live = _packed(lanes, live, pack)
        # the row index as a second key: unique keys, so the unstable
        # sort (half the stable one's compile time) gives the stable order
        skey, order = jax.lax.sort(
            (key, jnp.arange(p, dtype=jnp.int32)), num_keys=2,
            is_stable=False)
        rows = jnp.sum(live).astype(jnp.int32)
        again = jnp.logical_and(
            skey == jnp.roll(skey, 1),
            jnp.logical_and(jnp.arange(p, dtype=jnp.int32) < rows,
                            jnp.arange(p) > 0))
        twice = jnp.any(again)
        dead = jnp.any(jnp.logical_and(live, key == _DEAD_KEY))
        if wide is not None:
            dead = jnp.logical_or(dead, wide)
        out = (skey, order, rows, twice, dead)
        return out if len(keys) == 1 else out + (pack,)

    return join_build


def _build_probe_kernel(key_exprs, schema):
    """Stream batch x sorted build keys -> the matching (stream row, build
    row) pairs packed to the front, and their count. The output's size is
    no part of this module: the sorts are what takes minutes to compile,
    and they compile once a pair of input shapes (``_pairs_gather`` cuts
    the pairs to the output bucket and gathers the columns).

    Build keys first, then the stream's: a sort by (key, position) keeps
    the build rows in their own (sorted) order, so the j-th build row met
    is row j of the sorted build side and needs no rank. Each row then
    learns the last live build row at or before it (two carry-forward
    scans: its key and its row); a stream row matches where that key is
    its own. A second single-key sort (matches first, then the position:
    unique, so unstable) packs the matches."""
    dtypes = [f.dtype for f in schema.fields]

    def join_probe(bkeys, b_rows, scols, n_s, p_s, pack=None):
        p_b = bkeys.shape[0]
        skey, _ = _key_lane(key_exprs, schema, dtypes, scols, n_s, p_s,
                            pack)
        keys, pos = jax.lax.sort(
            (jnp.concatenate([bkeys, skey]),
             jnp.arange(p_b + p_s, dtype=jnp.int32)),
            num_keys=2, is_stable=False)
        live_b = pos < b_rows          # build rows come first: pos = row
        near_key, met = last_valid_scan(keys, live_b)
        near_row, _ = last_valid_scan(pos, live_b)
        match = jnp.logical_and(
            jnp.logical_and(pos >= p_b, keys != _DEAD_KEY),
            jnp.logical_and(met, near_key == keys))
        total = jnp.sum(match).astype(jnp.int32)
        _, s_row, b_row = jax.lax.sort(
            (jnp.where(match, jnp.uint32(0), jnp.uint32(1 << 31))
             | jnp.arange(p_b + p_s, dtype=jnp.uint32), pos - p_b,
             near_row), num_keys=1, is_stable=False)
        return total, s_row, b_row

    return join_probe


def _build_dense_probe_kernel(keys, schema):
    """Stream batch x the first ``cut`` sorted build keys, with NO sort:
    for a build side of a few hundred keys, where the sort probe sorts
    the whole stream batch twice to find a handful of matches.

    Each stream row is compared with every live build key in one
    broadcast compare reduced along the build axis (``narrow``: on 32-bit
    lanes, as ``key - lo``, where the live keys span less than 2^31; a
    key outside ``[lo, hi]`` cannot match). The matches are packed by
    counting: the j-th output slot is the (j+1)-th match, the row below
    which j rows or fewer match, found from the running count in blocks
    of about the square root of ``p_s`` rows (which block, then where in
    it: each about that many compares a slot). A slot's
    build row is the place in key order of its stream row's key, from
    the same compare over ``out_p`` keys. The pairs are the sort probe's,
    packed by stream row (its come in key order), ``out_p`` long."""
    dtypes = [f.dtype for f in schema.fields]

    def join_probe(bkeys, span, scols, n_s, p_s, cut, out_p, narrow,
                   pack=None):
        skey, _ = _key_lane(keys, schema, dtypes, scols, n_s, p_s, pack)
        bkeys = bkeys[:cut]
        # a row that cannot match holds _DEAD_KEY (no live build key does)
        live = skey != _DEAD_KEY
        if narrow:
            lo, hi = span[0], span[1]
            live = live & (skey >= lo) & (skey <= hi)
            skey = jnp.where(live, (skey - lo).astype(jnp.int32), -1)
            bkeys = jnp.where(bkeys != _DEAD_KEY,
                              (bkeys - lo).astype(jnp.int32), -2)
        match = jnp.logical_and(
            live, jnp.any(bkeys[:, None] == skey[None, :], axis=0))
        cnt = jnp.cumsum(match.astype(jnp.int32))
        total = cnt[-1]
        c = 1 << max(7, p_s.bit_length() // 2)
        cnt = jnp.pad(cnt, (0, -p_s % c), mode="edge").reshape(-1, c)
        slot = jnp.arange(out_p, dtype=jnp.int32)
        blk = jnp.sum(cnt[:, -1][None, :] <= slot[:, None], axis=1,
                      dtype=jnp.int32)
        within = jnp.take(cnt, jnp.minimum(blk, cnt.shape[0] - 1), axis=0)
        s_row = blk * c + jnp.sum(within <= slot[:, None], axis=1,
                                  dtype=jnp.int32)
        key_at = jnp.take(skey, s_row, mode="clip")
        b_row = jnp.max(jnp.where(
            bkeys[:, None] == key_at[None, :],
            jnp.arange(cut, dtype=jnp.int32)[:, None], -1), axis=0)
        return total, s_row, b_row

    return join_probe


def _pairs_gather(total, s_row, b_row, order, scols, bcols, out_p):
    """The first ``out_p`` (stream row, build row) pairs of a probe, -1
    past ``total``, and both sides' columns gathered by them. A build row
    is a place in key order: ``order`` says which row of the build side
    stands there."""
    short = max(0, out_p - s_row.shape[0])
    live = jnp.arange(out_p, dtype=jnp.int32) < total
    s_row = jnp.where(live, jnp.pad(s_row, (0, short))[:out_p], -1)
    b_row = jnp.where(live, jnp.take(
        order, jnp.pad(b_row, (0, short))[:out_p], mode="clip"), -1)
    return (_packed_gather(scols, s_row, out_p),
            _packed_gather(bcols, b_row, out_p))


def _probe_kernel(kind: str, keys: tuple, schema):
    """``join_build`` / ``join_probe`` (``kind`` "probe": the sort probe,
    "dense": the dense one) for one side's keys (one, or several packed
    into one lane) over one schema, each ONE callable a process (the
    executable cache's, so its compiles are counted), behind a lock-free
    memo for the per-batch path."""
    pk = (kind, keys[0].key() if len(keys) == 1
          else tuple(k.key() for k in keys),
          tuple((f.name, f.dtype.name) for f in schema.fields))
    fn = _PROBE_CACHE.get(pk)
    if fn is None:
        from ..plan import exec_cache
        exec_cache.register_clear_hook(_PROBE_CACHE.clear)
        make, static = {"build": (_build_sort_kernel, (2,)),
                        "probe": (_build_probe_kernel, (4,)),
                        "dense": (_build_dense_probe_kernel, (4, 5, 6, 7)),
                        }[kind]
        fn = _PROBE_CACHE[pk] = exec_cache.get_or_build_jit(
            f"joins.{kind}:{pk[1:]}", make(keys, schema),
            static_argnums=static)
    return fn


def _build_count_kernel(lkey_exprs, rkey_exprs, lschema, rschema, join_type):
    """The general count kernel for one join, ONE callable a process (the
    executable cache's, keyed as ``_COUNT_CACHE`` is)."""
    from ..plan import exec_cache
    ldtypes = [f.dtype for f in lschema.fields]
    rdtypes = [f.dtype for f in rschema.fields]

    def kernel(lcols, rcols, n_l, n_r, p_l, p_r):
        ldv = [None if c is None else DVal(c[0], c[1], dt)
               for c, dt in zip(lcols, ldtypes)]
        rdv = [None if c is None else DVal(c[0], c[1], dt)
               for c, dt in zip(rcols, rdtypes)]
        lctx = EvalContext(lschema, ldv, n_l, p_l)
        rctx = EvalContext(rschema, rdv, n_r, p_r)
        lkeys = [e.eval_device(lctx) for e in lkey_exprs]
        rkeys = [e.eval_device(rctx) for e in rkey_exprs]
        P = p_l + p_r
        lmask = lctx.row_mask()
        rmask = rctx.row_mask()
        real = jnp.concatenate([lmask, rmask])
        pad = jnp.where(real, jnp.uint8(0), jnp.uint8(1))
        operands = [pad]
        null_key = jnp.zeros(P, dtype=jnp.bool_)
        for lk, rk in zip(lkeys, rkeys):
            # promote both sides to a common dtype before encoding
            wide = jnp.promote_types(lk.data.dtype, rk.data.dtype)
            both = DVal(jnp.concatenate([lk.data.astype(wide),
                                         rk.data.astype(wide)]),
                        jnp.concatenate([lk.validity, rk.validity]),
                        lk.dtype)
            operands.extend(grouping_operands(both))
            null_key = jnp.logical_or(null_key,
                                      jnp.logical_not(both.validity))
        null_key = jnp.logical_and(null_key, real)
        side = jnp.concatenate([jnp.zeros(p_l, jnp.uint8),
                                jnp.ones(p_r, jnp.uint8)])
        orig = jnp.concatenate([jnp.arange(p_l, dtype=jnp.int32),
                                jnp.arange(p_r, dtype=jnp.int32)])
        n_ops = len(operands) + 1  # + side (L rows first within a group)
        sorted_all = jax.lax.sort(
            tuple(operands + [side] + [orig, null_key.astype(jnp.uint8)]),
            num_keys=n_ops, is_stable=True)
        s_ops = sorted_all[:len(operands)]
        s_side = sorted_all[len(operands)]
        s_orig = sorted_all[n_ops]
        s_nullk = sorted_all[n_ops + 1].astype(jnp.bool_)
        idx = jnp.arange(P)
        n_total = n_l + n_r
        s_real = idx < n_total
        differs = jnp.zeros(P, dtype=jnp.bool_)
        for op in s_ops[1:]:
            prev = jnp.roll(op, 1)
            differs = jnp.logical_or(
                differs, jnp.logical_not(operands_equal(op, prev)))
        # null-key rows are singleton groups: boundary at them and after them
        flags = jnp.logical_or(idx == 0, differs)
        flags = jnp.logical_or(flags, s_nullk)
        flags = jnp.logical_or(flags, jnp.roll(s_nullk, 1) & (idx != 0))
        flags = jnp.logical_and(flags, s_real)
        gid = jnp.where(s_real, prefix_sum(flags, jnp.int32) - 1, P)
        num_groups = jnp.sum(flags).astype(jnp.int32)
        is_l = jnp.logical_and(s_side == 0, s_real)
        is_r = jnp.logical_and(s_side == 1, s_real)
        # i32 segment sums: emulated-i64 scatter combiners serialize ~4x
        # slower on the TPU scalar core (72 ms vs 18 ms per 1M rows)
        cnt_l = jax.ops.segment_sum(is_l.astype(jnp.int32), gid,
                                    num_segments=P).astype(jnp.int64)
        cnt_r = jax.ops.segment_sum(is_r.astype(jnp.int32), gid,
                                    num_segments=P).astype(jnp.int64)
        big = jnp.array(np.iinfo(np.int32).max, jnp.int32)
        start_l = jax.ops.segment_min(jnp.where(is_l, idx.astype(jnp.int32),
                                                big), gid, num_segments=P)
        start_r = jax.ops.segment_min(jnp.where(is_r, idx.astype(jnp.int32),
                                                big), gid, num_segments=P)
        # per-group output pair counts by join type
        cl1 = jnp.maximum(cnt_l, 1)
        cr1 = jnp.maximum(cnt_r, 1)
        if join_type == "inner":
            pairs = cnt_l * cnt_r
        elif join_type == "left":
            pairs = cnt_l * cr1
        elif join_type == "right":
            pairs = cl1 * cnt_r
        elif join_type == "full":
            pairs = cl1 * cr1
            # group with neither side is impossible
        elif join_type == "leftsemi":
            pairs = jnp.where(cnt_r > 0, cnt_l, 0)
        elif join_type == "leftanti":
            pairs = jnp.where(cnt_r == 0, cnt_l, 0)
        else:
            raise ValueError(join_type)
        glive = jnp.arange(P, dtype=jnp.int32) < num_groups
        pairs = jnp.where(glive, pairs, 0)
        offsets = prefix_sum(pairs)  # inclusive
        total = offsets[-1]
        return (s_orig, cnt_l, cnt_r, start_l, start_r, pairs, offsets,
                total, num_groups)

    ck = (tuple(e.key() for e in lkey_exprs),
          tuple(e.key() for e in rkey_exprs),
          tuple((f.name, f.dtype.name) for f in lschema.fields),
          tuple((f.name, f.dtype.name) for f in rschema.fields), join_type)
    return exec_cache.get_or_build_jit(f"joins.count:{ck}", kernel,
                                       static_argnums=(4, 5))


def _gather_index(s_orig, cnt_l, cnt_r, start_l, start_r, offsets,
                  join_cfg, out_p):
    """:func:`_gather_index_kernel`, ONE callable a process (the
    executable cache's)."""
    from ..plan import exec_cache
    return exec_cache.get_or_build_jit(
        "joins.gather_index", _gather_index_kernel, static_argnums=(7,))(
            s_orig, cnt_l, cnt_r, start_l, start_r, offsets, join_cfg,
            out_p)


def _gather_index_kernel(s_orig, cnt_l, cnt_r, start_l, start_r, offsets,
                         join_cfg, out_p):
    """out slot k -> (left row index or -1, right row index or -1).
    join_cfg: (left_nullable, right_nullable, semi_like) as traced bools are
    static via closure — passed as int32 flags array instead."""
    left_nullable, right_nullable, semi_like = (join_cfg[0], join_cfg[1],
                                                join_cfg[2])
    P = offsets.shape[0]
    # group id per output slot WITHOUT searchsorted (a 1M-element binary
    # search costs ~20 serialized gather passes on TPU): scatter +1 at each
    # live group's output start position, then g = prefix_sum - 1. Empty
    # groups stack their +1 on the next start, which reproduces
    # searchsorted's "count of offsets <= k" exactly.
    pairs_g = jnp.diff(offsets, prepend=offsets[:1] * 0)
    excl = (offsets - pairs_g).astype(jnp.int32)
    # dead/empty groups scatter onto position `total`, polluting only the
    # dead output region beyond n_out (masked by the caller), exactly like
    # searchsorted's clipped result did
    starts = jnp.zeros(out_p, jnp.int32).at[excl].add(1, mode="drop")
    g = prefix_sum(starts) - 1
    gc = jnp.clip(g, 0, P - 1)
    # group-table lookups (i32 tables: 64-bit gathers pay double)
    base = jnp.take(excl, gc, mode="clip")
    k = jnp.arange(out_p, dtype=jnp.int32)
    r = k - base  # position within the group's pair block
    cl = jnp.take(cnt_l.astype(jnp.int32), gc, mode="clip")
    cr = jnp.take(cnt_r.astype(jnp.int32), gc, mode="clip")
    cr1 = jnp.maximum(cr, 1)
    # semi/anti emit each left row once regardless of right multiplicity
    cr1 = jnp.where(semi_like != 0, jnp.ones_like(cr1), cr1)
    li = r // cr1
    ri = r % cr1
    sl = jnp.take(start_l, gc, mode="clip")
    sr = jnp.take(start_r, gc, mode="clip")
    lpos = jnp.where(jnp.logical_and(left_nullable != 0, cl == 0),
                     -1, sl + li.astype(jnp.int32))
    rpos = jnp.where(jnp.logical_and(right_nullable != 0, cr == 0),
                     -1, sr + ri.astype(jnp.int32))
    l_row = jnp.where(lpos >= 0, jnp.take(s_orig, jnp.maximum(lpos, 0),
                                          mode="clip"), -1)
    r_row = jnp.where(rpos >= 0, jnp.take(s_orig, jnp.maximum(rpos, 0),
                                          mode="clip"), -1)
    return l_row.astype(jnp.int32), r_row.astype(jnp.int32)


def _lanes(batch: ColumnarBatch) -> list:
    """A batch's device columns as the kernels take them."""
    return [(c.data, c.validity) if isinstance(c, DeviceColumn) else None
            for c in batch.columns]


def _packed_gather(cols, idx_rows, out_p):
    """Materialize columns by row index with ONE validity gather per 32
    columns: validities pack into int32 bit lanes before the take, so an
    n-column table pays n data gathers + ceil(n/32) validity gathers
    instead of 2n (gathers serialize per element on the TPU scalar core —
    docs/performance.md)."""
    idx = jnp.clip(idx_rows, 0, None)
    null_row = idx_rows < 0
    present = [(i, c) for i, c in enumerate(cols) if c is not None]
    outs = [None] * len(cols)
    for base in range(0, len(present), 32):
        chunk = present[base:base + 32]
        vmask = None
        for bit, (_, (d, v)) in enumerate(chunk):
            lane = v.astype(jnp.uint32) << bit
            vmask = lane if vmask is None else (vmask | lane)
        gmask = jnp.take(vmask, idx, mode="clip")
        for bit, (i, (d, v)) in enumerate(chunk):
            od = jnp.take(d, idx, mode="clip")
            ov = jnp.logical_and(((gmask >> bit) & 1).astype(jnp.bool_),
                                 jnp.logical_not(null_row))
            outs[i] = (od, ov)
    return outs


def _build_fused_join_kernel(count_kern, semi_like: bool, ck: Tuple):
    """count + gather-map + materialization in ONE dispatch (speculative
    sizing makes out_p static without reading the device total, so the
    whole join is a single kernel launch — three dispatches, each with a
    host sync between, become one). ``ck``: the count kernel's key, the
    fused kernel's in the executable cache."""
    from ..plan import exec_cache

    def fused(lcols, rcols, n_l, n_r, p_l, p_r, out_p, cfg):
        (s_orig, cnt_l, cnt_r, start_l, start_r, _pairs, offsets, total,
         _ng) = count_kern(lcols, rcols, n_l, n_r, p_l, p_r)
        l_row, r_row = _gather_index(
            s_orig, cnt_l, cnt_r, start_l, start_r, offsets, cfg, out_p)
        live = jnp.arange(out_p, dtype=jnp.int64) < total
        l_row = jnp.where(live, l_row, -1)
        r_row = jnp.where(live, r_row, -1)
        louts = _packed_gather(lcols, l_row, out_p)
        routs = ([] if semi_like
                 else _packed_gather(rcols, r_row, out_p))
        return total, louts, routs

    return exec_cache.get_or_build_jit(f"joins.fused:{ck}", fused,
                                       static_argnums=(4, 5, 6))


def _join_schema(ls: Schema, rs: Schema, join_type: str,
                 exists_name: str = "exists") -> Schema:
    if join_type in ("leftsemi", "leftanti"):
        return Schema(list(ls.fields))
    if join_type == "existence":
        return Schema(list(ls.fields) + [StructField(exists_name, BOOL,
                                                     nullable=False)])
    return Schema(list(ls.fields) + list(rs.fields))


def _matched_counts_kernel(l_row, r_row, match, p_l, p_r):
    """Per-source-row surviving-pair counts (segment sums over the pair set).
    Pairs with row index -1 (padding) fall into the overflow segment."""
    m = match.astype(jnp.int32)
    ml = jax.ops.segment_sum(m, jnp.where(l_row >= 0, l_row, p_l),
                             num_segments=p_l + 1)[:p_l]
    mr = jax.ops.segment_sum(m, jnp.where(r_row >= 0, r_row, p_r),
                             num_segments=p_r + 1)[:p_r]
    return ml, mr


def _assemble_index_kernel(l_row, r_row, match, ul, ur, out_p):
    """Build the combined output gather maps: surviving pairs first, then
    unmatched-left rows (null-extended right), then unmatched-right rows
    (null-extended left). -1 = null row."""
    buf_l = jnp.full(out_p, -1, jnp.int32)
    buf_r = jnp.full(out_p, -1, jnp.int32)
    mi = match.astype(jnp.int32)
    pos = jnp.where(match, prefix_sum(mi) - 1, out_p)
    buf_l = buf_l.at[pos].set(l_row, mode="drop")
    buf_r = buf_r.at[pos].set(r_row, mode="drop")
    nm = jnp.sum(mi)
    uli = ul.astype(jnp.int32)
    posl = jnp.where(ul, nm + prefix_sum(uli) - 1, out_p)
    buf_l = buf_l.at[posl].set(
        jnp.arange(ul.shape[0], dtype=jnp.int32), mode="drop")
    nu = nm + jnp.sum(uli)
    uri = ur.astype(jnp.int32)
    posr = jnp.where(ur, nu + prefix_sum(uri) - 1, out_p)
    buf_r = buf_r.at[posr].set(
        jnp.arange(ur.shape[0], dtype=jnp.int32), mode="drop")
    return buf_l, buf_r


def _finish_pair_join(join_type: str, lb: ColumnarBatch, rb: ColumnarBatch,
                      l_row, r_row, live, condition: Optional[Expression],
                      out_schema: Schema) -> ColumnarBatch:
    """Finish any join from a candidate pair set: evaluate the residual
    condition on the gathered pairs, then emit per join type (ref
    GpuBroadcastNestedLoopJoinExecBase / conditional JoinGatherer paths).

    ``l_row``/``r_row``: int32 candidate pair gather maps; ``live`` gates
    padding slots. Works for both key-derived candidates (conditional equi-
    joins) and the full cross product (nested loop)."""
    pair_schema = Schema(list(lb.schema.fields) + list(rb.schema.fields))
    if condition is not None:
        n_pairs = int(traced_device_get(jnp.sum(live), "d2h.join_count"))
        lo = gather_batch_device(lb, l_row, n_pairs, int(l_row.shape[0]))
        ro = gather_batch_device(rb, r_row, n_pairs, int(r_row.shape[0]))
        pairs = ColumnarBatch(lo.columns + ro.columns, n_pairs, pair_schema)
        cond = eval_predicate_device(condition, pairs)
        match = jnp.logical_and(cond, live)
    else:
        match = live
    p_l, p_r = lb.padded_len, rb.padded_len
    from ..plan import exec_cache
    ml, mr = exec_cache.get_or_build_jit(
        "joins.matched_counts", _matched_counts_kernel,
        static_argnums=(3, 4))(l_row, r_row, match, p_l, p_r)
    lmask = jnp.arange(p_l, dtype=jnp.int32) < lb.num_rows
    rmask = jnp.arange(p_r, dtype=jnp.int32) < rb.num_rows

    if join_type in ("leftsemi", "leftanti"):
        from ..exprs.compiler import filter_batch_by_mask
        keep = jnp.logical_and(ml > 0 if join_type == "leftsemi" else ml == 0,
                               lmask)
        return filter_batch_by_mask(lb, keep, schema=out_schema)
    if join_type == "existence":
        exists = DeviceColumn(ml > 0, lmask, BOOL)
        return ColumnarBatch(list(lb.columns) + [exists], lb.num_rows,
                             out_schema)

    zl = jnp.zeros_like(lmask)
    ul = jnp.logical_and(ml == 0, lmask) if join_type in ("left", "full") \
        else zl
    ur = jnp.logical_and(mr == 0, rmask) if join_type in ("right", "full") \
        else jnp.zeros_like(rmask)
    n_match, n_ul, n_ur = (int(n) for n in traced_device_get(
        (jnp.sum(match), jnp.sum(ul), jnp.sum(ur)), "d2h.join_count"))
    if join_type == "inner":
        n_ul = n_ur = 0
        ul, ur = zl, jnp.zeros_like(rmask)
    n_out = n_match + n_ul + n_ur
    out_p = bucket_for(max(n_out, 1))
    gl, gr = exec_cache.get_or_build_jit(
        "joins.assemble_index", _assemble_index_kernel,
        static_argnums=(5,))(l_row, r_row, match, ul, ur, out_p)
    lo = gather_batch_device(lb, gl, n_out, out_p)
    ro = gather_batch_device(rb, gr, n_out, out_p)
    return ColumnarBatch(lo.columns + ro.columns, n_out, out_schema)


def _record_sides(sides) -> None:
    """Record each join side's LOGICAL size into the adaptive stats;
    ``sides`` = [(sig, spillables, schema)]. Logical bytes = the batch's
    ACTUAL device footprint scaled by its live-row fraction (the padded
    layout carries the true per-row width, including strings' code+dict
    representation); lazy device row counts from BOTH sides fetch in
    ONE packed transfer (only the big-sides shuffled join pays this
    round trip — the broadcast path's counts are already host ints)."""
    from ..plan.cost import record_runtime_size
    _resolve_counts([s for _sig, spillables, _schema in sides
                     for s in spillables])
    for sig, spillables, schema in sides:
        total = 0.0
        for s in spillables:
            rows = int(s._num_rows)
            cap = s._cap or max(rows, 1)
            total += s.device_bytes() * (rows / max(cap, 1))
        record_runtime_size(sig, int(total))


class TpuHashJoinExec(TpuExec):
    def __init__(self, left: TpuExec, right: TpuExec, join_type: str,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 condition: Optional[Expression] = None):
        super().__init__([left, right])
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition
        ls, rs = left.output_schema(), right.output_schema()
        self._full_schema = _join_schema(ls, rs, join_type)
        self._set_keep(None)

    def output_schema(self) -> Schema:
        return self._schema

    def narrow(self, names) -> None:
        """Hand on only the output columns named in ``names`` (the plan
        reads nothing else above the join), and those the residual
        condition reads, which is evaluated on the join's output: the
        others are never gathered. Nothing changes where the output's
        names repeat (a name would no longer say which column), or where
        nothing or everything would be kept."""
        full = self._full_schema.names()
        want = set(names)
        if self.condition is not None:
            want.update(self.condition.references())
        keep = tuple(i for i, n in enumerate(full) if n in want)
        if len(set(full)) == len(full) and 0 < len(keep) < len(full):
            self._set_keep(keep)

    def _set_keep(self, keep: Optional[Tuple[int, ...]]) -> None:
        """``keep``: the places in the full output of the columns handed
        on, in output order (None: all). Per side, the columns of that
        input they are (``_kept``), so that a gather takes only those."""
        #: places in the full output handed on; None: every column
        self.keep = keep
        full = self._full_schema
        self._schema = full if keep is None else \
            Schema([full.fields[i] for i in keep])
        n_left = len(self.children[0].output_schema())
        places = range(len(full)) if keep is None else keep
        right = self.join_type not in ("leftsemi", "leftanti", "existence")
        self._kept = ([i for i in places if i < n_left],
                      [i - n_left for i in places if i >= n_left and right])

    def _handed_on(self, out: ColumnarBatch) -> ColumnarBatch:
        """A full output as the join hands it on: its kept columns."""
        if self.keep is None:
            return out
        return out.with_columns([out.columns[i] for i in self.keep],
                                self._schema)

    #: join types whose result is the union, over the batches of the
    #: OTHER side, of that batch joined with the whole build side (no
    #: null-extension or per-row mark of the build side across batches),
    #: per build side
    STREAMABLE = {
        "right": ("inner", "left", "leftsemi", "leftanti", "existence",
                  "cross"),
        "left": ("inner", "right", "cross"),
    }

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        rows_m = ctx.metric(self._exec_id, "numOutputRows", ESSENTIAL)
        # both sides materialize (spillable) before anything is joined:
        # which of them is the build side is decided by what they hold.
        # list payloads materialize host-side: the join gather kernels move
        # 1D lanes only (columnar/nested.py with_lists_on_host)
        right_batches, left_batches = wrap_spillable_sides(
            ctx.memory,
            (_intake(b) for b in self.children[1].execute(ctx)),
            (_intake(b) for b in self.children[0].execute(ctx)))
        ls, rs = (self.children[0].output_schema(),
                  self.children[1].output_schema())
        sides = {"left": left_batches, "right": right_batches}
        try:
            # ONE fetch a join: the row counts a filter below left on the
            # device (sizes the build side, lets it concatenate on the
            # device, and is what _record_sides reads afterwards)
            _resolve_counts(left_batches + right_batches)
            rows = {k: sum(s.num_rows for s in v) for k, v in sides.items()}
        except BaseException:
            for s in right_batches + left_batches:
                s.close()
            raise
        build = self._build_side(rows)
        threshold = ctx.conf.join_subpartition_size_bytes
        # what every stream batch would be joined against, whole
        against = (sum(s.device_bytes() for s in sides[build]) if build
                   else sum(s.device_bytes()
                            for s in left_batches + right_batches))
        if (threshold > 0 and against > threshold and self.left_keys
                and self.join_type != "cross" and self.condition is None
                and self._subpartitionable(ls, rs)):
            yield from self._subpartitioned(ctx, left_batches, right_batches,
                                            ls, rs, rows_m, against, rows)
            return
        if build is not None:
            yield from self._streamed(ctx, build, sides, rows, rows_m)
            return

        def run():
            with ctx.semaphore.held():
                lb = concat_batches([_counted(s) for s in left_batches]) \
                    if left_batches else _empty_batch(ls)
                rb = concat_batches([_counted(s) for s in right_batches]) \
                    if right_batches else _empty_batch(rs)
                lb = self._maybe_bloom_filter(ctx, lb, rb)
                return self._join(lb, rb, ctx)

        try:
            out = with_retry_no_split(run, ctx=ctx, op=self._exec_id)
            self._record(left_batches, right_batches, ls, rs)
        finally:
            for s in right_batches + left_batches:
                s.close()
        _count_join_keys(self._exec_id, len(self.left_keys), None, 1)
        _count_join_rows(self._exec_id, rows["right"], rows["left"],
                         out.num_rows_raw, 1)
        _count_join_gather(self)
        rows_m.add(out.num_rows_raw)
        yield out

    def _record(self, left_batches, right_batches, ls, rs) -> None:
        sigs = getattr(self, "side_sigs", None)
        if sigs is not None:
            # AQE stage stats (ref GpuCustomShuffleReaderExec): record
            # LOGICAL side sizes for the next planning of this shape
            _record_sides([(sigs[0], left_batches, ls),
                           (sigs[1], right_batches, rs)])

    def _build_side(self, rows: Dict[str, int]) -> Optional[str]:
        """The side to make ready once and join every batch of the other
        side against: the one with fewer rows among those the join type
        lets stream (None: neither, both sides are joined whole)."""
        if not self.left_keys:
            return None
        ok = [side for side in ("right", "left")
              if self.join_type in self.STREAMABLE[side]]
        return min(ok, key=lambda side: rows[side]) if ok else None

    # -- a build side made ready once, probed by the stream side's batches
    # (ref GpuShuffledHashJoinExec: build side coalesced to one batch, the
    # stream side joined batch by batch) -----------------------------------
    def _streamed(self, ctx, build: str, sides, rows, rows_m
                  ) -> Iterator[ColumnarBatch]:
        """The stream side never leaves its batches: each is sorted with
        the build side alone, and its output is sized from the last
        observed output of this join shape, not from its own bucket (a
        selective join of a 1,048,576-row batch leaves a few thousand
        rows). The outputs' totals are read in ONE fetch at the end,
        checked against what was guessed, and the outputs leave as one
        batch."""
        bi = 1 if build == "right" else 0
        build_batches, stream_batches = sides[build], \
            sides["left" if bi else "right"]
        lsch, rsch = (self.children[0].output_schema(),
                      self.children[1].output_schema())
        outs: List[ColumnarBatch] = []
        try:
            def make_build():
                with ctx.semaphore.held():
                    if not build_batches:
                        return _empty_batch(rsch if bi else lsch)
                    return concat_batches(
                        [_counted(s) for s in build_batches], BUILD_BUCKETS)
            bound = self._out_bound(ctx, bi)
            with self.child_span("join.build", rows=rows[build],
                                 cols=(rsch if bi else lsch).names(),
                                 keys=len(self.left_keys)):
                bb = with_retry_no_split(make_build, ctx=ctx,
                                         op=self._exec_id)
                self._prepare_probe(ctx, bb, bound)
            if bound is None or not bound.probing:
                # the general kernel moves 1-D lanes: a string rectangle
                # of the build side leaves the device once, not a batch
                bb = bb.with_lists_on_host()
                if self.join_type == "leftsemi":
                    bound = None       # its output keeps the hard bound

            def build_bloom_run():
                with ctx.semaphore.held():
                    return self._build_bloom(ctx, lsch, bb)
            bloom = with_retry_no_split(build_bloom_run, ctx=ctx,
                                        op=self._exec_id) if bi else None
            self._record(sides["left"], sides["right"], lsch, rsch)
            for s in build_batches:
                s.close()
            n_spec = len(ctx.speculations)
            for s in stream_batches or [None]:
                def run(s=s):
                    with ctx.semaphore.held():
                        sb = _counted(s) if s is not None else \
                            _empty_batch(lsch if bi else rsch)
                        if bloom is not None and sb.num_rows > 0:
                            sb = self._apply_bloom(ctx, bloom, sb)
                        return (self._join(sb, bb, ctx, bound) if bi
                                else self._join(bb, sb, ctx, bound))
                with self.child_span(
                        "join.probe", cols=(lsch if bi else rsch).names(),
                        keys=len(self.left_keys)):
                    outs.append(with_retry_no_split(run, ctx=ctx,
                                                    op=self._exec_id))
                if s is not None:
                    s.close()
            _count_out_bound(bound is not None and bound.hard)
            _count_probe_path(self._exec_id, bound)
            _count_join_keys(self._exec_id, len(self.left_keys), bound,
                             0 if bound else len(outs))
            # the totals this join registered for the sink are read and
            # checked here, with the outputs' counts
            mine = ctx.speculations[n_spec:]
            del ctx.speculations[n_spec:]
            out = self._coalesced(ctx, outs, mine)
        finally:
            for s in build_batches + stream_batches:
                s.close()
        _count_join_rows(self._exec_id, rows[build],
                         rows["left" if bi else "right"], out.num_rows_raw,
                         len(outs))
        _count_join_gather(self)
        rows_m.add(out.num_rows_raw)
        yield out

    def _out_bound(self, ctx, bi: int) -> Optional[_OutBound]:
        """A stream row meets at most the build side's largest key
        multiplicity of rows where the join emits it once per match or
        once null-extended: there the output can be bound by the input."""
        if ctx.speculate and self.join_type in (
                ("inner", "left", "leftsemi") if bi == 1
                else ("inner", "right")) \
                and (self.condition is None or self.join_type == "inner"):
            return _OutBound(stream_left=(bi == 1))
        return None

    def _coalesced(self, ctx, outs: List[ColumnarBatch],
                   speculated) -> ColumnarBatch:
        """The per-batch outputs as ONE batch. Their counts and the
        totals the probes registered (``speculated``, the context's
        records: each the pairs a batch matched BEFORE any residual
        condition, beside the bucket that was guessed for them) are read
        together, one transfer. A total over its bucket is the
        speculation's overflow: pairs were cut, whatever the condition
        kept of the rest, and the plan re-runs with exact sizing. The
        largest total is what the next run of this join shape sizes its
        outputs from, and the sum of the counts what the cost model learns
        as the join's output."""
        from ..columnar.batch import SpeculativeOverflow, resolve_counts
        totals = resolve_counts(outs, [t for t, _, _, _ in speculated])
        over = None
        for n, (_, cap, ck, _) in zip(totals, speculated):
            _note_total(ctx, ck, n)
            if n > cap and over is None:
                over = (n, cap)
        if over:
            raise SpeculativeOverflow(*over)
        plan_sig = getattr(self, "plan_sig", None)
        if plan_sig is not None:
            from ..plan.cost import record_runtime_rows
            record_runtime_rows(plan_sig, sum(b.num_rows for b in outs))
        if len(outs) == 1:
            return outs[0]

        def run():
            with ctx.semaphore.held():
                return concat_batches(outs, BUILD_BUCKETS)
        return with_retry_no_split(run, ctx=ctx, op=self._exec_id)

    # -- runtime bloom filter (ref InjectRuntimeFilter + jni BloomFilter):
    # inner/semi equi-joins may drop stream rows whose keys cannot be in
    # the build side before paying for the join kernel ------------------
    def _maybe_bloom_filter(self, ctx, lb: ColumnarBatch,
                            rb: ColumnarBatch) -> ColumnarBatch:
        bloom = self._build_bloom(ctx, lb.schema, rb)
        if bloom is None or lb.num_rows == 0:
            return lb
        return self._apply_bloom(ctx, bloom, lb)

    def _build_bloom(self, ctx, ls: Schema, rb: ColumnarBatch):
        """Build a bloom filter over the build side's keys, or None when
        the runtime filter does not apply (conf off, non-inner/semi join,
        join condition present, or non-device-hashable keys)."""
        from ..config import JOIN_BLOOM_FILTER
        if (not ctx.conf.get(JOIN_BLOOM_FILTER)
                or self.join_type not in ("inner", "leftsemi")
                or not self.left_keys or self.condition is not None
                or rb.num_rows == 0):
            return None
        from ..exprs.hash_fns import device_hashable
        from ..types import from_numpy_dtype
        rs = rb.schema
        key_dtypes = []
        for lk, rk in zip(self.left_keys, self.right_keys):
            ldt, rdt = lk.data_type(ls), rk.data_type(rs)
            if (device_hashable.reason_not_supported(ldt)
                    or device_hashable.reason_not_supported(rdt)):
                return None
            # mixed-width keys hash differently per width; promote both
            # sides to the common numpy dtype so probes match the build
            if ldt.np_dtype != rdt.np_dtype:
                try:
                    cdt = from_numpy_dtype(
                        np.promote_types(ldt.np_dtype, rdt.np_dtype))
                except Exception:
                    return None
                if device_hashable.reason_not_supported(cdt):
                    return None
                key_dtypes.append(cdt)
            else:
                key_dtypes.append(ldt)
        from ..exprs.bloom_filter import build_bloom
        from ..exprs.compiler import compile_projection
        rvals = [self._cast_key(DVal(c.data, c.validity, c.dtype), dt)
                 for c, dt in zip(compile_projection(
                     self.right_keys, rs).run(rb), key_dtypes)]
        bloom = build_bloom(rvals, rb.num_rows)
        #: the dtype each key pair was promoted to: probes cast to it
        bloom.key_dtypes = key_dtypes
        return bloom

    @staticmethod
    def _cast_key(v: DVal, dt) -> DVal:
        if v.dtype.np_dtype == dt.np_dtype:
            return v
        return DVal(v.data.astype(dt.np_dtype), v.validity, dt)

    def _apply_bloom(self, ctx, bloom, lb: ColumnarBatch) -> ColumnarBatch:
        from ..exprs.compiler import (compile_projection,
                                      filter_batch_by_mask)
        ls = lb.schema
        lvals = [self._cast_key(DVal(c.data, c.validity, c.dtype), dt)
                 for c, dt in zip(compile_projection(
                     self.left_keys, ls).run(lb), bloom.key_dtypes)]
        live = jnp.arange(lb.padded_len, dtype=jnp.int32) < lb.num_rows
        keep = jnp.logical_and(bloom.might_contain_mask(lvals), live)
        out = filter_batch_by_mask(lb, keep)
        ctx.metric(self._exec_id, "bloomFilterRowsFiltered").add(
            lb.num_rows - out.num_rows)
        return out

    # -- sub-partitioned big join (ref GpuSubPartitionHashJoin.scala,
    # JoinPartitioner at GpuShuffledSizedHashJoinExec.scala:1255-1340) ------
    def _subpartitionable(self, ls: Schema, rs: Schema) -> bool:
        from ..exprs.hash_fns import device_hashable
        for lk, rk in zip(self.left_keys, self.right_keys):
            ldt, rdt = lk.data_type(ls), rk.data_type(rs)
            if (device_hashable.reason_not_supported(ldt)
                    or device_hashable.reason_not_supported(rdt)):
                return False
            # both sides must hash identically: the join kernel promotes
            # mixed-width keys before matching, but the partitioner hashes
            # raw values — int32 5 and int64 5 hash to different words and
            # would land in different sub-partitions (silent row loss)
            if ldt.np_dtype != rdt.np_dtype:
                return False
        return True

    #: sub-partition hash seed — deliberately NOT the shuffle seed (42):
    #: after a repartition on the join keys every row of a task satisfies
    #: murmur3_42(key) % P == const, so re-hashing with the same seed would
    #: collapse all rows into one sub-partition (ref GpuSubPartitionHashJoin
    #: uses a distinct seed for the same reason)
    SUBPARTITION_SEED = 1610612741

    def _subpartitioned(self, ctx, left_batches, right_batches, ls, rs,
                        rows_m, total_bytes, rows) -> Iterator[ColumnarBatch]:
        """Hash both sides into N sub-partitions on the same key hash and run
        N independent joins — matching keys (and null keys, which never match
        anyway) co-locate, so every equi-join type distributes over the
        partitioning. All device work (and the semaphore) is scoped inside
        the retry closure; outputs are parked spillable and yielded after
        the permit is released."""
        from ..shuffle.partitioning import partition_batch
        n_parts = 1 << max(1, (int(total_bytes) //
                                ctx.conf.join_subpartition_size_bytes
                                ).bit_length())
        n_parts = min(n_parts, 64)

        def run():
            outs = []
            try:
                with ctx.semaphore.held():
                    lb = concat_batches([_counted(s) for s in left_batches]) \
                        if left_batches else _empty_batch(ls)
                    rb = concat_batches([_counted(s) for s in right_batches]) \
                        if right_batches else _empty_batch(rs)
                    lp = partition_batch(lb, self.left_keys, n_parts,
                                         seed=self.SUBPARTITION_SEED)
                    rp = partition_batch(rb, self.right_keys, n_parts,
                                         seed=self.SUBPARTITION_SEED)
                    for p in range(n_parts):
                        lbp = lp.partition_device(p)
                        rbp = rp.partition_device(p)
                        if lbp.num_rows == 0 and rbp.num_rows == 0:
                            continue
                        out = self._join(lbp, rbp)
                        if out.num_rows:
                            outs.append(SpillableBatch(out, ctx.memory))
            except Exception:
                for s in outs:
                    s.close()
                raise
            return outs

        try:
            outs = with_retry_no_split(run, ctx=ctx, op=self._exec_id)
        finally:
            for s in left_batches + right_batches:
                s.close()
        _count_join_keys(self._exec_id, len(self.left_keys), None, n_parts)
        _count_join_rows(self._exec_id, rows["right"], rows["left"],
                         [s._num_rows for s in outs], n_parts)
        _count_join_gather(self)
        try:
            for s in outs:
                b = s.get()
                s.close()
                rows_m.add(b.num_rows)
                yield b
        except BaseException:
            # a failed unspill or an abandoned consumer would leak the
            # partitions still parked (close() is idempotent)
            for s in outs:
                s.close()
            raise

    # ------------------------------------------------------------------
    def _join(self, lb: ColumnarBatch, rb: ColumnarBatch,
              ctx: Optional[ExecContext] = None,
              bound: Optional[_OutBound] = None) -> ColumnarBatch:
        """Two batches joined, the output as the join hands it on: the
        probe gathers the kept columns alone, every other path selects
        them from the full output it builds."""
        probing = bound is not None and bound.probing \
            and ctx.speculate and lb.all_device and rb.all_device
        if probing and bound.swap:
            sb, bb = (lb, rb) if bound.stream_left else (rb, lb)
            out = self._join_swapped(ctx, sb, bb, bound)
            if out is not None:
                return out
        elif probing:
            return self._join_probe(ctx, lb if bound.stream_left else rb,
                                    bound, self._shape_key(lb, rb))
        if bound is not None:
            bound.general += 1
        # every kernel but the probe's gather moves 1-D lanes
        lb, rb = lb.with_lists_on_host(), rb.with_lists_on_host()
        return self._handed_on(self._join_general(lb, rb, ctx, bound))

    def _shape_key(self, lb: ColumnarBatch, rb: ColumnarBatch) -> Tuple:
        """The join shape its output statistic is kept under: the keys,
        the inputs' schemas, the join type."""
        return (tuple(e.key() for e in self.left_keys),
                tuple(e.key() for e in self.right_keys),
                tuple((f.name, f.dtype.name) for f in lb.schema.fields),
                tuple((f.name, f.dtype.name) for f in rb.schema.fields),
                self.join_type)

    def _join_general(self, lb: ColumnarBatch, rb: ColumnarBatch,
                      ctx: Optional[ExecContext],
                      bound: Optional[_OutBound]) -> ColumnarBatch:
        """Two batches joined by the general kernels: the full output."""
        if self.join_type == "cross" or not self.left_keys:
            return self._cross(lb, rb)
        if (self.condition is not None and
                self.join_type != "inner") or self.join_type == "existence":
            # conditional non-inner equi-join / existence: enumerate inner
            # candidate pairs on the keys, then finish through the shared
            # pair machinery (ref JoinGatherer conditional gathers)
            l_row, r_row, live = self._candidate_pairs(lb, rb)
            return _finish_pair_join(self.join_type, lb, rb, l_row, r_row,
                                     live, self.condition, self._full_schema)
        ls, rs = lb.schema, rb.schema
        ck = self._shape_key(lb, rb)
        kern = _COUNT_CACHE.get(ck)
        if kern is None:
            kern = _build_count_kernel(self.left_keys, self.right_keys,
                                       ls, rs, self.join_type)
            _COUNT_CACHE[ck] = kern
        lcols = _lanes(lb)
        rcols = _lanes(rb)
        semi_like = self.join_type in ("leftsemi", "leftanti")

        # ONE-dispatch fused path: with speculative sizing the output
        # bucket is known without reading the device total, so count +
        # gather maps + packed materialization run as a single kernel
        # (vs three launches with a host sync between each)
        spec0 = (ctx is not None and ctx.speculate)
        stat0 = _TOTAL_STATS.get(ck)
        all_dev = lb.all_device and rb.all_device
        # the streaming broadcast path's bound (None elsewhere): its first
        # batch runs the count kernel on its own, to read the build
        # side's largest key multiplicity beside the total
        measure = bound is not None and bound.mult is None
        stream_p = hard_p = None
        if bound is not None:
            stream_p = bucket_for(max(
                (lb if bound.stream_left else rb).padded_len, 1))
            hard_p = stream_p if bound.hard else None
        if all_dev and spec0 and self.condition is None and not measure \
                and (semi_like or hard_p or stat0 is not None):
            return self._join_fused(ctx, lb, rb, lcols, rcols, ck,
                                    kern, semi_like, stat0, hard_p)

        (s_orig, cnt_l, cnt_r, start_l, start_r, pairs, offsets, total,
         num_groups) = kern(lcols, rcols, jnp.int32(lb.num_rows_raw),
                            jnp.int32(rb.num_rows_raw), lb.padded_len,
                            rb.padded_len)
        # speculative output sizing: guessing the output bucket from the
        # input sizes skips the count->host->gather sync (a full device
        # round trip PER JOIN). semi/anti have the hard bound
        # out <= n_l; inner/left/right/full register the device total with
        # the context, and the sink validates every registered total once
        # (one batched fetch) — on overflow the plan re-runs with exact
        # sizing (ColumnarBatch.num_rows also guards any other force site).
        spec = (ctx is not None and ctx.speculate)
        stat = _TOTAL_STATS.get(ck)
        if semi_like and spec:
            # hard bound: semi/anti emit at most the left input's rows, so
            # lazy sizing needs no validation at all
            n_out = total
            out_p = bucket_for(max(lb.padded_len, 1))
        elif hard_p and spec and (stat is None or bucket_for(
                max(int(stat * 1.5), 1)) >= hard_p):
            n_out, out_p = total, hard_p
        elif measure:
            n_out, mult = (int(x) for x in traced_device_get(
                (total, _max_count(cnt_r if bound.stream_left else cnt_l)),
                "d2h.join_count"))
            bound.mult = mult
            _note_total(ctx, ck, n_out)
            # unique build keys: the stream's bucket from the first batch
            # on, so every batch of the query leaves in one shape
            out_p = stream_p if bound.hard else bucket_for(max(n_out, 1))
        elif spec and stat is not None:
            # adaptive guess from this join shape's last observed total
            # (x1.5 headroom); validated at the sink, exact re-run on
            # overflow — the AQE-statistics analog of sizing gather maps
            n_out = total
            out_p = bucket_for(max(int(stat * 1.5), 1))
            ctx.speculations.append((total, out_p, ck,
                                     getattr(self, 'plan_sig', None)))
        else:
            n_out = int(traced_device_get(total, "d2h.join_count"))
            _note_total(ctx, ck, n_out)
            out_p = bucket_for(max(n_out, 1))
        left_nullable = 1 if self.join_type in ("right", "full") else 0
        right_nullable = 1 if self.join_type in ("left", "full") else 0
        cfg = jnp.array([left_nullable, right_nullable,
                         1 if semi_like else 0], dtype=jnp.int32)
        l_row, r_row = _gather_index(
            s_orig, cnt_l, cnt_r, start_l, start_r, offsets, cfg, out_p)
        live = jnp.arange(out_p, dtype=jnp.int64) < jnp.asarray(n_out)
        l_row = jnp.where(live, l_row, -1)
        r_row = jnp.where(live, r_row, -1)
        lo = gather_batch_device(lb, l_row, n_out, out_p)
        if semi_like:
            return ColumnarBatch(lo.columns, n_out, self._full_schema)
        ro = gather_batch_device(rb, r_row, n_out, out_p)
        out = ColumnarBatch(lo.columns + ro.columns, n_out,
                            self._full_schema)
        if self.condition is not None:
            out = filter_batch_device(self.condition, out)
        return out

    # -- the unique-key probe (kernels above _build_count_kernel) ----------
    def _probe_key(self, build_left: bool, ls: Schema, rs: Schema):
        """(build side's keys, its schema) where the probe applies: an
        inner or left semi join on keys that are each an integer lane on
        both sides (integers, dates, timestamps; a decimal's lane means
        another number at another scale), without a residual condition on
        the semi join (it would have to see the pairs). Two or more keys
        are packed into one lane (``_pack_of``)."""
        if self.join_type not in ("inner", "leftsemi") \
                or not self.left_keys \
                or (self.join_type == "leftsemi"
                    and (build_left or self.condition is not None)):
            return None
        for keys, sch in ((self.left_keys, ls), (self.right_keys, rs)):
            for k in keys:
                dt = k.data_type(sch)
                if isinstance(dt, DecimalType) or dt.np_dtype is None \
                        or dt.np_dtype.kind != "i":
                    return None
        return (tuple(self.left_keys), ls) if build_left \
            else (tuple(self.right_keys), rs)

    def _side_keys(self, left: bool) -> tuple:
        return tuple(self.left_keys if left else self.right_keys)

    def _prepare_probe(self, ctx, bb: ColumnarBatch,
                       bound: Optional[_OutBound]) -> None:
        """Sort the build side's KEYS, once a join a query (its rows stay
        where they are: ``_SortedBuild``), and read (ONE small fetch) how
        many of its rows can match and whether each key is there once:
        then ``bound`` carries it and every stream batch is probed against
        it. A semi join asks whether a key is there, so a key there twice
        changes nothing for it. An inner join whose build side holds a key
        twice notes that (``bound.swap``): the stream side's keys may
        still be unique. Otherwise ``bound`` stays as it was and the first
        stream batch measures the key multiplicity."""
        if bound is None or not bb.all_device:
            return
        build_left = not bound.stream_left
        found = self._probe_key(build_left,
                                self.children[0].output_schema(),
                                self.children[1].output_schema())
        if found is None:
            return
        kern = _probe_kernel("build", *found)
        from ..plan import exec_cache
        span_of = exec_cache.get_or_build_jit("joins.key_span", _key_span)

        def run():
            with ctx.semaphore.held():
                keys, order, rows, twice, dead, *pack = kern(
                    _lanes(bb), jnp.int32(bb.num_rows_raw), bb.padded_len)
                span = span_of(keys, rows)
                # two or more keys: their spans come in the same fetch
                rows, twice, dead, (lo, hi), *spans = traced_device_get(
                    (rows, twice, dead, span, *pack), "d2h.join_count")
                bits = sum((int(h) - int(l)).bit_length()
                           for l, h in zip(*spans[0][:2])) if spans else 0
                return _SortedBuild(keys, order, bb, int(rows),
                                    self._kept[0 if build_left else 1],
                                    span, int(hi) - int(lo) < 2 ** 31,
                                    pack[0] if pack else None), \
                    bool(twice), bool(dead), bits
        build, twice, dead, bound.bits = with_retry_no_split(
            run, ctx=ctx, op=self._exec_id)
        bound.pack = build.pack
        if dead:
            return
        if not twice or self.join_type == "leftsemi":
            bound.probe = build
            # a stream row matches at most once: its output is bound hard
            bound.mult = min(build.rows, 1)
        else:
            bound.swap = True

    def _join_probe(self, ctx, sb: ColumnarBatch, bound: _OutBound,
                    ck) -> ColumnarBatch:
        """One stream batch against the sorted build keys: the probe, then
        the gather of the matched pairs. The output is sized from the last
        observed total of this join shape where that takes a smaller
        bucket than the stream batch's own (the hard bound: each stream
        row matches at most once); the first batch ever of a shape reads
        its total before the gather, which then takes the bucket the next
        batches guess from it."""
        build = bound.probe
        semi = self.join_type == "leftsemi"
        keys = self._side_keys(bound.stream_left)
        pack = () if build.pack is None else (build.pack,)
        out_p = bucket_for(max(sb.padded_len, 1))
        stat = _TOTAL_STATS.get(ck)
        guess = out_p if stat is None \
            else bucket_for(max(int(stat * 1.5), 1))
        speculative = guess < out_p
        out_p = min(out_p, guess)
        p_b = build.keys.shape[0]
        # the dense probe sees the live keys' bucket: _DEAD_KEY past them
        cut = min(bucket_for(max(build.rows, 1)), p_b)
        if _dense_probe_wins(sb.padded_len, p_b, cut, out_p):
            bound.paths[0] += 1
            total, s_row, b_row = _probe_kernel("dense", keys, sb.schema)(
                build.keys, build.span, _lanes(sb),
                jnp.int32(sb.num_rows_raw), sb.padded_len, cut, out_p,
                build.narrow, *pack)
        else:
            bound.paths[1] += 1
            total, s_row, b_row = _probe_kernel("probe", keys, sb.schema)(
                build.keys, jnp.int32(build.rows), _lanes(sb),
                jnp.int32(sb.num_rows_raw), sb.padded_len, *pack)
        if speculative:
            ctx.speculations.append((total, out_p, ck,
                                     getattr(self, 'plan_sig', None)))
        elif stat is None:
            total = int(traced_device_get(total, "d2h.join_count"))
            _note_total(ctx, ck, total)
            # gathered in the bucket later runs guess from this total, not
            # in the stream's own: a cold process's second query then
            # meets its first query's shapes and compiles nothing more
            out_p = min(out_p, bucket_for(max(int(total * 1.5), 1)))
        # a semi join's output is its stream side's rows
        return self._pairs_batch(total, s_row, b_row, sb,
                                 None if semi else build, out_p,
                                 bound.stream_left)

    def _join_swapped(self, ctx, sb: ColumnarBatch, bb: ColumnarBatch,
                      bound: _OutBound) -> Optional[ColumnarBatch]:
        """One stream batch of an inner join whose build side holds a key
        twice, with the sides' parts exchanged: the stream batch's keys
        are sorted (``join_build``: one small fetch says whether THEY are
        unique) and the build side's rows are probed against them, each
        matching at most one row of this batch, so the output keeps the
        build side's bucket. None where the batch's keys repeat too: the
        general kernel's case. Two or more keys are packed by the build
        side's spans on both sides."""
        skey = self._side_keys(bound.stream_left)
        bkey = self._side_keys(not bound.stream_left)
        pack = () if bound.pack is None else (bound.pack,)
        keys, order, rows, twice, dead = _probe_kernel(
            "build", skey, sb.schema)(
                _lanes(sb), jnp.int32(sb.num_rows_raw), sb.padded_len,
                *pack)[:5]
        if any(traced_device_get((twice, dead), "d2h.join_count")):
            return None
        bound.paths[1] += 1
        total, b_row, s_row = _probe_kernel("probe", bkey, bb.schema)(
            keys, rows, _lanes(bb), jnp.int32(bb.num_rows_raw),
            bb.padded_len, *pack)
        return self._pairs_batch(
            total, b_row, s_row, bb,
            _SortedBuild(keys, order, sb, rows,
                         self._kept[0 if bound.stream_left else 1]),
            bucket_for(max(bb.padded_len, 1)), not bound.stream_left)

    def _pairs_batch(self, total, p_row, s_row, probing: ColumnarBatch,
                     build: Optional[_SortedBuild], out_p: int,
                     probing_left: bool) -> ColumnarBatch:
        """The join's output from a probe's matched pairs: the probing
        batch's columns at ``p_row`` and the sorted side's at ``s_row``
        (none of a sorted side that is None) in ONE gather, the probing
        batch's on the left where ``probing_left``, then the residual
        condition. Only the columns the join hands on are gathered: the
        probing batch's kept ones, and the sorted side's it chose once
        (``_SortedBuild.cols``)."""
        from ..plan import exec_cache
        p_cols = self._kept[0 if probing_left else 1]
        p_lanes, p_spans = _lane_pairs(
            [(i, probing.columns[i]) for i in p_cols])
        pouts, bouts = exec_cache.get_or_build_jit(
            "joins.pairs_gather", _pairs_gather, static_argnums=(6,))(
                total, p_row, s_row,
                s_row if build is None else build.order, p_lanes,
                [] if build is None else build.lanes, out_p)
        p_out = [None] * len(probing.columns)
        _lane_rebuild(probing, p_spans, pouts, p_out)
        p_out = [p_out[i] for i in p_cols]
        b_out = []
        if build is not None:
            b_out = [None] * len(build.batch.columns)
            _lane_rebuild(build.batch, build.spans, bouts, b_out)
            b_out = [b_out[i] for i in build.cols]
        out = ColumnarBatch(p_out + b_out if probing_left
                            else b_out + p_out, total, self._schema)
        if self.condition is not None:
            out = filter_batch_device(self.condition, out)
        return out

    def _join_fused(self, ctx, lb: ColumnarBatch, rb: ColumnarBatch,
                    lcols, rcols, ck, count_kern, semi_like: bool,
                    stat, hard_p: Optional[int] = None) -> ColumnarBatch:
        fk = _FUSED_CACHE.get(ck)
        if fk is None:
            fk = _build_fused_join_kernel(count_kern, semi_like, ck)
            _FUSED_CACHE[ck] = fk
        if semi_like:
            out_p = bucket_for(max(lb.padded_len, 1))
        else:
            # the last observed total (x1.5 headroom) where it is known
            # and sizes the output under the hard bound: a selective join
            # leaves a small share of its stream batch's bucket
            out_p = bucket_for(max(int(stat * 1.5), 1)) \
                if stat is not None else hard_p
            if hard_p and out_p >= hard_p:
                out_p = hard_p
            else:
                hard_p = None
        left_nullable = 1 if self.join_type in ("right", "full") else 0
        right_nullable = 1 if self.join_type in ("left", "full") else 0
        cfg = jnp.array([left_nullable, right_nullable,
                         1 if semi_like else 0], dtype=jnp.int32)
        total, louts, routs = fk(lcols, rcols, jnp.int32(lb.num_rows_raw),
                                 jnp.int32(rb.num_rows_raw),
                                 lb.padded_len, rb.padded_len, out_p, cfg)
        if not semi_like and not hard_p:
            ctx.speculations.append((total, out_p, ck,
                                     getattr(self, 'plan_sig', None)))
        new_cols = [c.with_arrays(d, v)
                    for c, (d, v) in zip(lb.columns, louts)]
        if not semi_like:
            new_cols += [c.with_arrays(d, v)
                         for c, (d, v) in zip(rb.columns, routs)]
        return ColumnarBatch(new_cols, total, self._full_schema)

    def _cross(self, lb: ColumnarBatch, rb: ColumnarBatch) -> ColumnarBatch:
        n_out = lb.num_rows * rb.num_rows
        out_p = bucket_for(max(n_out, 1))
        k = jnp.arange(out_p, dtype=jnp.int64)
        li = (k // max(rb.num_rows, 1)).astype(jnp.int32)
        ri = (k % max(rb.num_rows, 1)).astype(jnp.int32)
        live = jnp.asarray(np.arange(out_p) < n_out)
        li = jnp.where(live, li, -1)
        ri = jnp.where(live, ri, -1)
        lo = gather_batch_device(lb, li, n_out, out_p)
        ro = gather_batch_device(rb, ri, n_out, out_p)
        out = ColumnarBatch(lo.columns + ro.columns, n_out,
                            self._full_schema)
        if self.condition is not None:
            out = filter_batch_device(self.condition, out)
        return out

    def _candidate_pairs(self, lb: ColumnarBatch, rb: ColumnarBatch):
        """Inner-join candidate pair index arrays on the equi keys."""
        ls, rs = lb.schema, rb.schema
        ck = (tuple(e.key() for e in self.left_keys),
              tuple(e.key() for e in self.right_keys),
              tuple((f.name, f.dtype.name) for f in ls.fields),
              tuple((f.name, f.dtype.name) for f in rs.fields), "inner")
        kern = _COUNT_CACHE.get(ck)
        if kern is None:
            kern = _build_count_kernel(self.left_keys, self.right_keys,
                                       ls, rs, "inner")
            _COUNT_CACHE[ck] = kern
        lcols, rcols = _lanes(lb), _lanes(rb)
        (s_orig, cnt_l, cnt_r, start_l, start_r, _pairs, offsets, total,
         _ng) = kern(lcols, rcols, jnp.int32(lb.num_rows),
                     jnp.int32(rb.num_rows), lb.padded_len, rb.padded_len)
        n_out = int(traced_device_get(total, "d2h.join_count"))
        out_p = bucket_for(max(n_out, 1))
        cfg = jnp.zeros(3, dtype=jnp.int32)
        l_row, r_row = _gather_index(
            s_orig, cnt_l, cnt_r, start_l, start_r, offsets, cfg, out_p)
        live = jnp.asarray(np.arange(out_p) < n_out)
        return (jnp.where(live, l_row, -1), jnp.where(live, r_row, -1),
                live)

    def describe(self):
        return f"HashJoin[{self._describe_args()}{self._drops()}]"

    def _describe_args(self) -> str:
        k = ", ".join(f"{a.name_hint}={b.name_hint}"
                      for a, b in zip(self.left_keys, self.right_keys))
        c = f", cond={self.condition.name_hint}" if self.condition else ""
        return f"{self.join_type}, keys=({k}){c}"

    def _drops(self) -> str:
        """The output columns the join no longer hands on, by name."""
        if self.keep is None:
            return ""
        kept = set(self.keep)
        dropped = sorted(f.name for i, f in
                         enumerate(self._full_schema.fields)
                         if i not in kept)
        return f", drops=[{', '.join(dropped)}]"


def _common_arrow_type(a, b):
    """Numeric promotion for host join keys (the device kernel promotes via
    jnp.promote_types; arrow joins require identical key types). Returns
    None when no promotion exists — callers keep the original types and
    let arrow raise its type-mismatch error rather than silently casting
    one side."""
    if a.equals(b):
        return a
    import pyarrow as pa
    try:
        return pa.from_numpy_dtype(np.promote_types(a.to_pandas_dtype(),
                                                    b.to_pandas_dtype()))
    except Exception:
        return None


def _empty_batch(schema: Schema) -> ColumnarBatch:
    import pyarrow as pa
    from ..types import to_arrow
    t = pa.table({f.name: pa.array([], type=to_arrow(f.dtype))
                  for f in schema.fields})
    return ColumnarBatch.from_arrow(t)


class TpuNestedLoopJoinExec(TpuExec):
    """Nested-loop join: arbitrary (non-equi) condition, every join type
    (ref GpuBroadcastNestedLoopJoinExecBase, GpuCartesianProductExec).

    TPU-first design: the candidate pair set is the full cross product laid
    out as one static-shaped index range (li = k / n_r, ri = k % n_r); the
    condition is one fused XLA evaluation over the gathered pair batch and
    the per-type finishing (outer null-extension, semi/anti/existence) is
    the same segment-sum machinery as the conditional equi-join."""

    def __init__(self, left: TpuExec, right: TpuExec, join_type: str,
                 condition: Optional[Expression] = None):
        super().__init__([left, right])
        self.join_type = join_type
        self.condition = condition
        self._schema = _join_schema(left.output_schema(),
                                    right.output_schema(), join_type)

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        rows_m = ctx.metric(self._exec_id, "numOutputRows", ESSENTIAL)
        ls, rs = (self.children[0].output_schema(),
                  self.children[1].output_schema())
        # list payloads materialize host-side: the join gather kernels move
        # 1D lanes only (columnar/nested.py with_lists_on_host)
        right_batches, left_batches = wrap_spillable_sides(
            ctx.memory,
            (b.ensure_device().with_lists_on_host()
             for b in self.children[1].execute(ctx)),
            (b.ensure_device().with_lists_on_host()
             for b in self.children[0].execute(ctx)))

        def run():
            with ctx.semaphore.held():
                lb = concat_batches([s.get() for s in left_batches]) \
                    if left_batches else _empty_batch(ls)
                rb = concat_batches([s.get() for s in right_batches]) \
                    if right_batches else _empty_batch(rs)
                n_pairs = lb.num_rows * rb.num_rows
                out_p = bucket_for(max(n_pairs, 1))
                k = jnp.arange(out_p, dtype=jnp.int64)
                nr = max(rb.num_rows, 1)
                li = (k // nr).astype(jnp.int32)
                ri = (k % nr).astype(jnp.int32)
                live = jnp.asarray(np.arange(out_p) < n_pairs)
                li = jnp.where(live, li, -1)
                ri = jnp.where(live, ri, -1)
                if self.join_type == "cross":
                    lo = gather_batch_device(lb, li, n_pairs, out_p)
                    ro = gather_batch_device(rb, ri, n_pairs, out_p)
                    out = ColumnarBatch(lo.columns + ro.columns, n_pairs,
                                        self._schema)
                    if self.condition is not None:
                        out = filter_batch_device(self.condition, out)
                    return out
                return _finish_pair_join(self.join_type, lb, rb, li, ri,
                                         live, self.condition, self._schema)

        try:
            out = with_retry_no_split(run, ctx=ctx, op=self._exec_id)
        finally:
            for s in right_batches + left_batches:
                s.close()
        rows_m.add(out.num_rows_raw)
        yield out

    def describe(self):
        c = f", cond={self.condition.name_hint}" if self.condition else ""
        return f"NestedLoopJoin[{self.join_type}{c}]"


class TpuBroadcastHashJoinExec(TpuHashJoinExec):
    """Equi-join against a broadcast build side (ref
    GpuBroadcastHashJoinExecBase): the build child is a
    BroadcastExchangeExec whose single cached batch is reused across every
    stream batch — each incoming batch joins independently and leaves as
    one batch, its count still on the device. The planner fills the
    stream batches first where a scan hands them over under-filled, and
    this join's outputs where they reach another per-batch operator (a
    selective join hands on buckets of padding:
    ``plan/overrides.py:insert_coalesce`` puts ``CoalesceBatchesExec``
    above it, which reads the counts a window at a time); a build side
    with unique keys keeps each output in its stream batch's bucket
    (:class:`_OutBound`), and every output's strings are codes of the
    build side's ONE dictionary, which is what lets them concatenate on
    the device.
    Only join types needing no null-extension (or per-row marks) of the
    BUILD side across stream batches may stream; the rest take the
    coalesced whole-sides path."""

    def __init__(self, left, right, join_type, left_keys, right_keys,
                 condition=None, build_side: str = "right"):
        super().__init__(left, right, join_type, left_keys, right_keys,
                         condition)
        assert build_side in ("left", "right")
        self.build_side = build_side

    def from_build_side(self) -> List[bool]:
        """Per column handed on, whether it comes from the build side."""
        n_left, n = len(self._kept[0]), len(self._schema)
        on_right = self.build_side == "right"
        return [(i >= n_left) == on_right for i in range(n)]

    @property
    def streams(self) -> bool:
        """Whether the stream side is joined batch by batch against the
        broadcast relation (the planner's coalesce rule asks too)."""
        from ..shuffle.broadcast import BroadcastExchangeExec
        return (self.join_type in self.STREAMABLE[self.build_side]
                and isinstance(
                    self.children[1 if self.build_side == "right" else 0],
                    BroadcastExchangeExec))

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        if not self.streams:
            yield from super().do_execute(ctx)
            return
        bi = 1 if self.build_side == "right" else 0
        build = self.children[bi]
        rows_m = ctx.metric(self._exec_id, "numOutputRows", ESSENTIAL)
        bound = self._out_bound(ctx, bi)
        with self.child_span("join.build",
                             cols=build.output_schema().names(),
                             keys=len(self.left_keys)):
            bb = build.broadcast(ctx)
            if bb is not None:
                bb = _intake(bb)
                self._prepare_probe(ctx, bb, bound)
        if bb is not None and (bound is None or not bound.probing):
            # list payloads demote like every other join intake (the
            # gather path moves 1D lanes only), and without the probe
            # string rectangles too, once and not a stream batch
            bb = bb.with_lists_on_host()
            if self.join_type == "leftsemi":
                bound = None           # its output keeps the hard bound
        sigs = getattr(self, "side_sigs", None)
        if sigs is not None and bb is not None:
            # record the build side's MEASURED logical bytes: an
            # over-eager broadcast flips back to shuffled next planning
            from ..plan.cost import record_runtime_size
            frac = bb.num_rows / max(bb.padded_len or bb.num_rows, 1)
            measured = int(bb.device_size_bytes() * frac)
            record_runtime_size(sigs[bi], measured)
            from .. import aqe as aqe_mod
            log = aqe_mod.LOG
            if log is not None:
                from ..aqe import AQE_BROADCAST_DEMOTE_ENABLED
                from ..config import AUTO_BROADCAST_THRESHOLD
                thr = int(ctx.conf.get(AUTO_BROADCAST_THRESHOLD))
                if (thr >= 0 and measured > thr
                        and ctx.conf.get(AQE_BROADCAST_DEMOTE_ENABLED)):
                    try:  # tpulint: never-raise
                        log.record(aqe_mod.make_decision(
                            aqe_mod.BROADCAST_DEMOTE,
                            detail=f"build side measured {measured}B > "
                                   f"threshold {thr}B; next planning "
                                   "uses shuffled join",
                            parts=1))
                    except Exception:
                        pass
        # runtime bloom filter: built ONCE from the broadcast build side,
        # applied to every stream batch (build side must be right — the
        # filter drops stream=left rows whose keys cannot match). Like
        # every device kernel here, build and probe run under the
        # semaphore with OOM retry.
        if bi == 1:
            def build_bloom_run():
                with ctx.semaphore.held():
                    return self._build_bloom(
                        ctx, self.children[0].output_schema(), bb)
            bloom = with_retry_no_split(build_bloom_run, ctx=ctx,
                                        op=self._exec_id)
        else:
            bloom = None
        produced = False
        n_stream, n_out = [], []   # counts as they are: no read a batch
        try:
            for sb in self.children[1 - bi].execute(ctx):
                sb = _intake(sb)
                def run(sb=sb):
                    with ctx.semaphore.held():
                        if bloom is not None and sb.num_rows > 0:
                            sb2 = self._apply_bloom(ctx, bloom, sb)
                        else:
                            sb2 = sb
                        return (self._join(sb2, bb, ctx, bound) if bi == 1
                                else self._join(bb, sb2, ctx, bound))
                with self.child_span("join.probe", cols=sb.schema.names(),
                                     keys=len(self.left_keys)):
                    out = with_retry_no_split(run, ctx=ctx,
                                              op=self._exec_id)
                rows_m.add(out.num_rows_raw)
                n_stream.append(sb.num_rows_raw)
                n_out.append(out.num_rows_raw)
                produced = True
                yield out
            if not produced:
                empty = _empty_batch(self.children[1 - bi].output_schema())

                def run_empty():
                    with ctx.semaphore.held():
                        return (self._join(empty, bb, ctx, bound) if bi == 1
                                else self._join(bb, empty, ctx, bound))
                yield with_retry_no_split(run_empty, ctx=ctx,
                                          op=self._exec_id)
        finally:
            _count_out_bound(bound is not None and bound.hard)
            _count_probe_path(self._exec_id, bound)
            _count_join_keys(self._exec_id, len(self.left_keys), bound,
                             0 if bound else len(n_out))
        _count_join_rows(self._exec_id,
                         bb.num_rows_raw if bb is not None else 0,
                         n_stream, n_out, len(n_out))
        _count_join_gather(self)

    def describe(self):
        return (f"BroadcastHashJoin[{self._describe_args()}, "
                f"build={self.build_side}{self._drops()}]")


class CpuJoinExec(TpuExec):
    """Host fallback / oracle via Arrow's join (SQL null semantics match)."""
    is_tpu = False

    def __init__(self, left, right, join_type, left_keys, right_keys,
                 condition=None):
        super().__init__([left, right])
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition
        self._schema = _join_schema(left.output_schema(),
                                    right.output_schema(), join_type)

    def output_schema(self) -> Schema:
        return self._schema

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        import pyarrow as pa
        lt = self.children[0].collect(ctx, validate=False)
        rt = self.children[1].collect(ctx, validate=False)
        if (self.join_type == "existence"
                or (self.condition is not None
                    and self.join_type not in ("inner", "cross"))
                or (not self.left_keys
                    and self.join_type not in ("inner", "cross"))):
            # pair-set path: the condition (or its absence, for keyless
            # outer/semi/anti joins) decides matched-ness per row
            yield self._pairwise_host(lt, rt)
            return
        if self.join_type == "cross" or not self.left_keys:
            out = self._cross_host(lt, rt)
        else:
            lb = ColumnarBatch.from_arrow_host(lt)
            rb = ColumnarBatch.from_arrow_host(rt)
            lkn, rkn = [], []
            for i, (lk, rk) in enumerate(zip(self.left_keys,
                                             self.right_keys)):
                la = lk.eval_host(lb)
                ra = rk.eval_host(rb)
                ct = _common_arrow_type(la.type, ra.type)
                lt = lt.append_column(
                    f"__jk{i}", la.cast(ct) if ct is not None else la)
                rt = rt.append_column(
                    f"__jk{i}", ra.cast(ct) if ct is not None else ra)
                lkn.append(f"__jk{i}")
                rkn.append(f"__jk{i}")
            jt = {"inner": "inner", "left": "left outer",
                  "right": "right outer", "full": "full outer",
                  "leftsemi": "left semi", "leftanti": "left anti"}[
                      self.join_type]
            # suffix every right column to avoid collisions (restored after);
            # coalesce_keys=False keeps Spark semantics: unmatched side's
            # key columns stay null
            rt2 = rt.rename_columns([c + "\x00r" for c in rt.column_names])
            out = lt.join(rt2, keys=lkn,
                          right_keys=[c + "\x00r" for c in rkn],
                          join_type=jt, coalesce_keys=False)
            keep = [c for c in out.column_names
                    if not c.startswith("__jk")]
            out = out.select(keep)
            out = out.rename_columns([c[:-2] if c.endswith("\x00r") else c
                                      for c in out.column_names])
        if self.condition is not None:
            b = ColumnarBatch.from_arrow_host(out)
            import pyarrow.compute as pc
            mask = self.condition.eval_host(b)
            out = out.filter(pc.fill_null(mask, False))
        # host-only output (see CpuFilterExec): no device bounce on the
        # CPU-reverted path
        yield ColumnarBatch.from_arrow_host(out)

    def _cross_host(self, lt, rt):
        import pyarrow as pa
        import numpy as np
        n, m = lt.num_rows, rt.num_rows
        li = pa.array(np.repeat(np.arange(n), m))
        ri = pa.array(np.tile(np.arange(m), n))
        lo = lt.take(li)
        ro = rt.take(ri)
        arrays = list(lo.columns) + list(ro.columns)
        return pa.Table.from_arrays(arrays, names=self._schema.names())

    def _pairwise_host(self, lt, rt) -> ColumnarBatch:
        """Generic host join over an explicit candidate pair set — the only
        correct way to apply a residual condition to outer/semi/anti joins
        (the condition decides matched-ness, it does not post-filter)."""
        import pyarrow as pa
        import pyarrow.compute as pc
        n_l, n_r = lt.num_rows, rt.num_rows
        if self.left_keys:
            lb = ColumnarBatch.from_arrow_host(lt)
            rb = ColumnarBatch.from_arrow_host(rt)
            lks = [k.eval_host(lb) for k in self.left_keys]
            rks = [k.eval_host(rb) for k in self.right_keys]
            cts = [_common_arrow_type(a.type, b.type)
                   for a, b in zip(lks, rks)]
            kt_l = pa.table(
                {f"__jk{i}": a.cast(ct) if ct is not None else a
                 for i, (a, ct) in enumerate(zip(lks, cts))} |
                {"__l": pa.array(np.arange(n_l, dtype=np.int64))})
            kt_r = pa.table(
                {f"__jk{i}": a.cast(ct) if ct is not None else a
                 for i, (a, ct) in enumerate(zip(rks, cts))} |
                {"__r": pa.array(np.arange(n_r, dtype=np.int64))})
            keys = [f"__jk{i}" for i in range(len(self.left_keys))]
            pairs = kt_l.join(kt_r, keys=keys, right_keys=keys,
                              join_type="inner", coalesce_keys=True)
            li = pairs.column("__l").to_numpy()
            ri = pairs.column("__r").to_numpy()
        else:
            li = np.repeat(np.arange(n_l), n_r)
            ri = np.tile(np.arange(n_r), n_l)
        if self.condition is not None and len(li):
            pair_schema = Schema(list(self.children[0].output_schema().fields)
                                 + list(self.children[1].output_schema().fields))
            lo = lt.take(pa.array(li))
            ro = rt.take(pa.array(ri))
            pair_t = pa.Table.from_arrays(
                list(lo.columns) + list(ro.columns),
                names=[f.name for f in pair_schema.fields])
            pb = ColumnarBatch.from_arrow_host(pair_t)
            pb.schema = pair_schema
            mask = pc.fill_null(self.condition.eval_host(pb), False)
            m = mask.to_numpy(zero_copy_only=False)
            li, ri = li[m], ri[m]
        ml = np.bincount(li, minlength=n_l) if n_l else np.zeros(0, np.int64)
        names = self._schema.names()
        if self.join_type == "leftsemi":
            return ColumnarBatch.from_arrow(
                lt.take(pa.array(np.nonzero(ml > 0)[0])))
        if self.join_type == "leftanti":
            return ColumnarBatch.from_arrow(
                lt.take(pa.array(np.nonzero(ml == 0)[0])))
        if self.join_type == "existence":
            out = lt.append_column(names[-1], pa.array(ml > 0))
            return ColumnarBatch.from_arrow(out)
        mr = np.bincount(ri, minlength=n_r) if n_r else np.zeros(0, np.int64)
        gl, gr = [li], [ri]
        if self.join_type in ("left", "full"):
            u = np.nonzero(ml == 0)[0]
            gl.append(u)
            gr.append(np.full(len(u), -1, np.int64))
        if self.join_type in ("right", "full"):
            u = np.nonzero(mr == 0)[0]
            gl.append(np.full(len(u), -1, np.int64))
            gr.append(u)
        gl = np.concatenate(gl) if gl else np.zeros(0, np.int64)
        gr = np.concatenate(gr) if gr else np.zeros(0, np.int64)
        lo = lt.take(pa.array(gl, mask=gl < 0))
        ro = rt.take(pa.array(gr, mask=gr < 0))
        out = pa.Table.from_arrays(list(lo.columns) + list(ro.columns),
                                   names=names)
        return ColumnarBatch.from_arrow(out)

    def describe(self):
        return f"CpuJoin[{self.join_type}]"

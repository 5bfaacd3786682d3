"""Broadcast exchange (ref GpuBroadcastExchangeExec.scala:74,354-477).

The reference builds the broadcast relation once on the driver
(relationFuture collects serialized host batches, lazily concatenated by
SerializeConcatHostBuffersDeserializeBatch) and ships it to every executor,
where GpuBroadcastHelper materializes it onto the device once.

TPU-native shape: one process hosts the query, so "broadcast" = build the
child's result exactly once per query, hold it as a single coalesced batch
in the query's ExecContext until that context closes (the end of the query,
success or failure), and hand the same device-resident batch to every
consumer (all stream batches of a broadcast join, multiple joins reusing
the same exchange — the analog of Spark's reuseExchange). In the
multi-chip path the batch is replicated across the mesh by the sharding
layer (see parallel/collective.py), the moral equivalent of the driver
broadcast hop.
"""
from __future__ import annotations

from typing import Iterator

from ..columnar import ColumnarBatch, concat_batches
from ..exec.base import ESSENTIAL, ExecContext, TpuExec
from ..mem import SpillableBatch
from ..types import Schema

__all__ = ["BroadcastExchangeExec"]


class BroadcastExchangeExec(TpuExec):
    """Build-once, consume-many exchange. ``broadcast(ctx)`` returns the
    single coalesced batch, memoized in ``ctx`` (the per-query analog of
    the executor-wide broadcast cache) under this exec's id."""

    def __init__(self, child: TpuExec):
        super().__init__([child])
        self._schema = child.output_schema()

    def output_schema(self) -> Schema:
        return self._schema

    def broadcast(self, ctx: ExecContext) -> ColumnarBatch:
        """The cached relation is held as a SpillableBatch (lowest spill
        priority — broadcast data is cheap to rebuild from host) so its HBM
        footprint stays visible to the memory manager; `get()` migrates it
        back if it was spilled between consumers. It lives as long as
        ``ctx``: ``ctx.close()`` closes it, and ``exec/query.py`` closes a
        query's context in its ``finally`` — a later query plans new exec
        ids and builds its own relation, so nothing outlives its query."""
        from ..mem.spillable import SpillPriorities
        cache = ctx._broadcast_cache
        sb = cache.get(self._exec_id)
        if sb is None:
            size_m = ctx.metric(self._exec_id, "dataSize", ESSENTIAL)
            from ..mem import wrap_spillables
            spill = wrap_spillables(self.children[0].execute(ctx),
                                    ctx.memory)
            try:
                with ctx.semaphore.held():
                    if len(spill) > 1:
                        # counts a filter left on the device, read once:
                        # with them the batches concatenate on the device
                        from ..exec.joins import _counted, _resolve_counts
                        _resolve_counts(spill)
                        out = concat_batches([_counted(s) for s in spill])
                    elif spill:
                        out = spill[0].get()
                    else:
                        from ..exec.joins import _empty_batch
                        out = _empty_batch(self._schema)
            finally:
                for s in spill:
                    s.close()
            size_m.add(out.device_size_bytes())
            sb = SpillableBatch(
                out, ctx.memory,
                spill_priority=SpillPriorities.OUTPUT_FOR_SHUFFLE)
            cache[self._exec_id] = sb
            ctx.add_cleanup(sb.close)
        return sb.get()

    def do_execute(self, ctx: ExecContext) -> Iterator[ColumnarBatch]:
        yield self.broadcast(ctx)

    def describe(self):
        return "BroadcastExchange"

"""SpillableBatch: a columnar batch that can migrate device -> host -> disk
and come back on demand.

Reference analog: SpillableColumnarBatch (SpillableColumnarBatch.scala:29) +
the tiered stores (RapidsDeviceMemoryStore / RapidsHostMemoryStore /
RapidsDiskStore). Device tier holds jax arrays (HBM); host tier holds an
Arrow table; disk tier holds an Arrow IPC file in the spill directory.
"""
from __future__ import annotations

import os
import threading
import uuid
from typing import Optional

from ..columnar import ColumnarBatch
from .manager import MemoryManager

__all__ = ["SpillableBatch", "SpillPriorities"]


class SpillPriorities:
    """Lower spills first (ref SpillPriorities.scala)."""
    OUTPUT_FOR_SHUFFLE = 0
    ACTIVE_BATCHING = 50
    ACTIVE_ON_DECK = 100


class SpillableBatch:
    """Wraps a ColumnarBatch; while registered it may be spilled by the
    MemoryManager at any time, `get()` migrates it back to device."""

    def __init__(self, batch: ColumnarBatch, mm: Optional[MemoryManager] = None,
                 spill_priority: int = SpillPriorities.ACTIVE_BATCHING):
        self._mm = mm or MemoryManager.get()
        self._lock = threading.RLock()
        self._batch: Optional[ColumnarBatch] = batch
        self._host_table = None           # pyarrow.Table when tier=host
        self._disk_path: Optional[str] = None
        self._disk_block: Optional[int] = None   # native store block id
        self._disk_bytes = 0
        self.tier = "device"
        self.spill_priority = spill_priority
        # keep a lazy count: forcing a device-scalar row count here
        # would cost a host sync on every spillable wrap
        self._num_rows = batch.num_rows_raw
        self._cap = next((c.padded_len for c in batch.columns
                          if hasattr(c, "padded_len")), None)
        self.schema = batch.schema
        self._device_bytes = batch.device_size_bytes()
        #: True while the resident device bytes were admitted by an
        #: OOM_PRESSURE_HOST emergency grant instead of the budget —
        #: the matching release must come from the same pool
        self._granted = False        # tpulint: guarded-by _lock
        self._closed = False
        self._reserve_device(self._device_bytes)
        # register LAST: the moment the handle exists, another thread's
        # spill_device() may pick this batch up — every field the spill
        # paths read must already be published (the r14 concurrency
        # battery caught a half-constructed batch being spilled)
        self._handle = self._mm.register_spillable(self)
        #: creation site for the leak auditor (MemoryCleaner analog) —
        #: only captured in debug mode, a traceback walk per wrap is not
        #: free on the hot path
        self.created_at = None
        import os
        if os.environ.get("SRTPU_LEAK_DEBUG"):
            import traceback
            self.created_at = "".join(traceback.format_stack(limit=6)[:-1])

    def _reserve_device(self, nbytes: int) -> None:
        """Admit ``nbytes`` of device residency: through the budget with
        allocation-site RetryOOM absorption (spill-and-retry a bounded
        number of times before the OOM escapes — bare
        ``[SpillableBatch(b, mm) for b in ...]`` comprehensions survive
        transient pressure), or through the unbudgeted pressure pool when
        the creating thread runs under the escalation ladder's host
        degradation rung (mem/retry.py)."""
        self._device_bytes = nbytes
        if self._mm.in_pressure_grant():
            self._granted = True
            self._mm.reserve_granted(nbytes)
        else:
            self._granted = False
            self._mm.reserve_absorbing_retries(nbytes)

    def _release_device(self, nbytes: int) -> None:
        if self._granted:
            self._granted = False
            self._mm.release_granted(nbytes)
        else:
            self._mm.release(nbytes)

    @property
    def memory_manager(self) -> MemoryManager:
        """The manager accounting for this batch (public accessor —
        splitters re-wrap pieces under the SAME manager)."""
        return self._mm

    @property
    def num_rows(self) -> int:
        if not isinstance(self._num_rows, int):
            n = int(self._num_rows)
            if self._cap is not None and n > self._cap:
                from ..columnar.batch import SpeculativeOverflow
                raise SpeculativeOverflow(n, self._cap)
            self._num_rows = n
        return self._num_rows

    def device_bytes(self) -> int:
        """Device footprint when resident (size estimate for spill/split
        decisions, ref SpillableColumnarBatch.sizeInBytes)."""
        # tpulint: disable=lock-discipline — lock-free by design: a
        # single immutable-int read used as a sizing estimate
        return self._device_bytes

    @property
    def padded_len(self) -> int:
        """Shape-bucket length of the wrapped batch (static — known
        without materializing any tier)."""
        return self._cap if self._cap is not None else self.num_rows

    # ------------------------------------------------------------- migration
    def spill_to_host(self) -> int:
        with self._lock:
            if self.tier != "device" or self._closed:
                return 0
            self._host_table = self._batch.to_arrow()
            nbytes = self._device_bytes
            self._batch = None
            self.tier = "host"
            self._release_device(nbytes)
            self._mm.reserve_host(self._host_table.nbytes)
            self._mm.spill_to_host_bytes += nbytes
            return nbytes

    def spill_to_disk(self) -> int:
        import pyarrow as pa
        with self._lock:
            if self.tier != "host" or self._closed:
                return 0
            nbytes = self._host_table.nbytes
            store = self._native_store()
            if store is not None:
                # native slab block store (spill_store.cpp): append into
                # big shared files with CRC-verified read-back
                sink = pa.BufferOutputStream()
                with pa.ipc.new_file(sink, self._host_table.schema) as w:
                    w.write_table(self._host_table)
                data = sink.getvalue().to_pybytes()
                self._disk_block = store.write(data)
                self._mm.disk_used += len(data)
                self._disk_bytes = len(data)
            else:
                os.makedirs(self._mm.spill_dir, exist_ok=True)
                path = os.path.join(self._mm.spill_dir,
                                    f"spill-{uuid.uuid4().hex}.arrow")
                with pa.OSFile(path, "wb") as f:
                    with pa.ipc.new_file(f, self._host_table.schema) as w:
                        w.write_table(self._host_table)
                self._mm.disk_used += os.path.getsize(path)
                self._disk_path = path
            self._mm.release_host(nbytes)
            self._mm.spill_to_disk_bytes += nbytes
            self._host_table = None
            self.tier = "disk"
            return nbytes

    def _native_store(self):
        from .native_spill import get_store
        return get_store(self._mm.spill_dir)

    def _unspill(self) -> ColumnarBatch:
        """Migrate back to device. The device reservation happens BEFORE
        the source tier is dismantled: a failed reserve (real or injected
        RetryOOM) must leave this batch intact in its current tier — the
        pre-r14 order released the host table / freed the disk block
        first, so an OOM mid-unspill lost the only copy of the data."""
        import pyarrow as pa
        if self.tier == "host":
            table = self._host_table
            batch = ColumnarBatch.from_arrow(table)
            self._reserve_device(batch.device_size_bytes())  # may raise
            self._mm.release_host(table.nbytes)
            self._host_table = None
        elif self._disk_block is not None:
            data = self._native_store().read(self._disk_block)
            table = pa.ipc.open_file(pa.BufferReader(data)).read_all()
            batch = ColumnarBatch.from_arrow(table)
            self._reserve_device(batch.device_size_bytes())  # may raise
            self._native_store().free(self._disk_block)
            self._mm.disk_used -= self._disk_bytes
            self._disk_block, self._disk_bytes = None, 0
        else:  # per-file fallback tier
            with pa.memory_map(self._disk_path, "rb") as f:
                table = pa.ipc.open_file(f).read_all()
            batch = ColumnarBatch.from_arrow(table)
            self._reserve_device(batch.device_size_bytes())  # may raise
            try:
                self._mm.disk_used -= os.path.getsize(self._disk_path)
                os.unlink(self._disk_path)
            except OSError:
                pass
            self._disk_path = None
        self.tier = "device"
        return batch

    # ------------------------------------------------------------------- api
    def get(self) -> ColumnarBatch:
        """Materialize on device (migrating back if spilled)."""
        with self._lock:
            if self._closed:
                raise ValueError("closed SpillableBatch")
            if self.tier != "device":
                self._batch = self._unspill()
            return self._batch

    def size_bytes(self) -> int:
        # tpulint: disable=lock-discipline — lock-free by design: a
        # single immutable-int read used as a sizing estimate
        return self._device_bytes

    def close(self):
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._mm.unregister_spillable(self._handle)
            if self.tier == "device":
                self._release_device(self._device_bytes)
            elif self.tier == "host" and self._host_table is not None:
                self._mm.release_host(self._host_table.nbytes)
                self._host_table = None
            elif self.tier == "disk" and self._disk_block is not None:
                self._native_store().free(self._disk_block)
                self._mm.disk_used -= self._disk_bytes
                self._disk_block = None
            elif self.tier == "disk" and self._disk_path:
                try:
                    self._mm.disk_used -= os.path.getsize(self._disk_path)
                    os.unlink(self._disk_path)
                except OSError:
                    pass
            self._batch = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

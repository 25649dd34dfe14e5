"""The pointer-array micro-benchmark store (§5.2).

Layer: stores (DESIGN.md §1) — contract: key i *is* slot i, one pointer READ
of index I/O per op; point ops only (SCAN is rejected, DESIGN.md §9).

"A minimalistic object storage ... to quantify the pure performance
advancement brought about by CIDER": each slot holds a data pointer to an
out-of-place KV pair in the heap.
"""
from __future__ import annotations

import dataclasses
import functools

import jax

from repro.core import engine, runner
from repro.core.credits import CreditState, credit_init
from repro.core.types import (EngineConfig, IOMetrics, OpBatch, OpKind,
                              SyncMode, UnsupportedOpError)

__all__ = ["PointerArray"]


@functools.partial(jax.profiler.annotate_function, name="store.scan_check")
def _reject_scan(kinds) -> None:
    """Point-op stores cannot serve range reads — fail loudly, not with a
    silent 0-row result (DESIGN.md §9)."""
    if bool((kinds == OpKind.SCAN).any()):
        raise UnsupportedOpError(
            "PointerArray is a point-op object store: it has no key order, "
            "so SCAN has no contiguous leaf run to traverse.  Range reads "
            "need the radix index (repro.stores.SmartART), whose leaf "
            "entries sit in key order (DESIGN.md §9).")


@dataclasses.dataclass
class PointerArray:
    cfg: EngineConfig
    state: engine.StoreState
    credits: CreditState

    @staticmethod
    def create(n_keys: int, mode: SyncMode = SyncMode.CIDER,
               heap_slots: int | None = None, credit_table: int = 4096,
               **kw) -> "PointerArray":
        cfg = EngineConfig(n_slots=n_keys, heap_slots=heap_slots or 4 * n_keys,
                           mode=mode, index_read_iops=1, index_read_bytes=8,
                           **kw)
        return PointerArray(cfg=cfg, state=engine.store_init(cfg),
                            credits=credit_init(credit_table))

    def populate(self, keys, values) -> "PointerArray":
        state = engine.populate(self.cfg, self.state, keys, values)
        return dataclasses.replace(self, state=state)

    def apply(self, batch: OpBatch) -> tuple["PointerArray", engine.Results, IOMetrics]:
        _reject_scan(batch.kinds)
        state, credits, res, io = engine.apply_batch(
            self.cfg, self.state, self.credits, batch)
        return dataclasses.replace(self, state=state, credits=credits), res, io

    @functools.partial(jax.profiler.annotate_function, name="store.apply_stream")
    def apply_stream(self, stream: runner.WindowStream, io_per_window: bool = False
                     ) -> tuple["PointerArray", engine.Results, IOMetrics]:
        """Run every window of ``stream`` in one fused scan (``run_windows``).

        Store/credit buffers are donated to the scan — use the returned
        instance, not ``self``, afterwards.
        """
        _reject_scan(stream.batch.kinds)
        state, credits, res, io = runner.run_windows(
            self.cfg, self.state, self.credits, stream,
            io_per_window=io_per_window)
        return dataclasses.replace(self, state=state, credits=credits), res, io

    def view(self):
        return engine.store_view(self.state)

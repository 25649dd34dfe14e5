"""SMART-style adaptive radix tree index (Luo et al., OSDI'23), CIDER-integrated.

Layer: stores (DESIGN.md §1, §9) — contract: resolve string keys to engine
slots, account index-side I/O, and (radix only) resolve key ranges to
contiguous leaf-slot runs for SCAN.

SMART stores data pointers in radix-tree leaves; clients cache internal
nodes, so the common-case I/O is a leaf READ + the pointer swap — exactly
CIDER's integration point.  We model a fixed-span (8-bit), fixed-depth radix
tree over a ``key_bits``-sized key space:

* the leaf entry address is a *bijective* function of the key (the radix
  path IS the key), so the leaf entry is the engine slot — no reservation
  protocol is needed (unlike the hash index) and structural node splits
  never move leaves;
* radix paths sort in key order, so leaf entries sit in key order and the
  key range ``[k, k+c)`` is a *contiguous leaf-slot run* — the range-scan
  property that separates radix indexes from hash indexes (DESIGN.md §9)
  and the reason this store alone serves ``OpKind.SCAN``;
* per-op index I/O: ``path_misses`` uncached internal-node READs (client
  path cache, SMART §3) + the leaf read; defaults model a warm cache.

Simplifications vs SMART (documented): adaptive node sizes (ART Node4/16/48)
and path compression only change *node bytes*, not the leaf-level concurrency
CIDER optimizes; we fix 256-ary nodes and fold cache-miss traffic into
``index_read_iops``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import engine, runner
from repro.core.credits import CreditState, credit_init
from repro.core.types import EngineConfig, IOMetrics, OpBatch, SyncMode

__all__ = ["SmartART"]


def _radix_slot(keys: jax.Array, key_bits: int) -> jax.Array:
    """Leaf-entry address of a key: the radix path IS the key (fixed span,
    fixed depth), so leaf entries are laid out in key order and a key range
    maps to a contiguous slot run — the property SCAN traversal needs.
    Earlier revisions bit-reversed the path to spread adjacent keys across
    leaf nodes; that permutation is exactly what makes hash-style layouts
    range-incapable, and real radix trees do not do it."""
    return (keys.astype(jnp.int32)) & jnp.int32((1 << key_bits) - 1)


@dataclasses.dataclass
class SmartART:
    cfg: EngineConfig
    key_bits: int
    state: engine.StoreState
    credits: CreditState

    @staticmethod
    def create(key_bits: int = 20, mode: SyncMode = SyncMode.CIDER,
               path_misses: int = 0, credit_table: int = 4096,
               scan_max: int = 16, **kw) -> "SmartART":
        n_slots = 1 << key_bits
        cfg = EngineConfig(n_slots=n_slots, heap_slots=4 * n_slots, mode=mode,
                           index_read_iops=1 + path_misses,
                           index_read_bytes=8 + 256 * 8 * path_misses,
                           scan_max=scan_max, **kw)
        return SmartART(cfg=cfg, key_bits=key_bits,
                        state=engine.store_init(cfg),
                        credits=credit_init(credit_table))

    def slots(self, keys) -> jax.Array:
        return _radix_slot(jnp.asarray(keys, jnp.int32), self.key_bits)

    def populate(self, keys, values) -> "SmartART":
        state = engine.populate(self.cfg, self.state, self.slots(keys), values)
        return dataclasses.replace(self, state=state)

    def apply(self, kinds, keys, values, n_cns: int = 1
              ) -> tuple["SmartART", engine.Results, IOMetrics]:
        kinds = jnp.asarray(kinds, jnp.int32)
        values = jnp.asarray(values, jnp.int32)
        batch = OpBatch.make(kinds, self.slots(keys), values, n_cns=n_cns)
        state, credits, res, io = engine.apply_batch(
            self.cfg, self.state, self.credits, batch)
        return dataclasses.replace(self, state=state, credits=credits), res, io

    @functools.partial(jax.profiler.annotate_function, name="store.apply_stream")
    def apply_stream(self, kinds, keys, values, n_cns: int = 1,
                     io_per_window: bool = False
                     ) -> tuple["SmartART", engine.Results, IOMetrics]:
        """Fused multi-window execution of ``(W, B)`` op arrays: keys resolve
        through the radix path, then one ``run_windows`` scan executes every
        window on-device.  Buffers are donated — use the returned instance.
        """
        with jax.profiler.TraceAnnotation("store.slots"):
            kinds = jnp.asarray(kinds, jnp.int32)
            values = jnp.asarray(values, jnp.int32)
            slots = self.slots(keys)
        stream = runner.make_stream(kinds, slots, values, n_cns=n_cns)
        state, credits, res, io = runner.run_windows(
            self.cfg, self.state, self.credits, stream,
            io_per_window=io_per_window)
        return dataclasses.replace(self, state=state, credits=credits), res, io

    def view(self):
        return engine.store_view(self.state)

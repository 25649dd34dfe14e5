"""The sharded CIDER dataplane: ``StoreState`` partitioned over a mesh axis.

FUSEE/DINOMO-style memory-pool partitioning: slot ``k`` (and its heap) is
owned by shard ``k // slots_per_shard`` along the ``data`` mesh axis.  One
synchronization window executes as a single ``shard_map``: every shard sees
the (replicated) op batch, masks the ops whose keys it owns, and runs the
unmodified ``engine.apply_batch`` on its slot/heap partition; the engine's
credit plane runs on the full batch on every shard (see ``apply_batch``'s
docstring), so the replicated credit table stays bit-identical and no
cross-shard traffic exists beyond the final psum that assembles per-op
results and the global I/O bill.

Equivalence contract (tested in ``tests/test_dist_store.py``): for any mesh
size that divides ``n_slots``/``heap_slots``, the logical store view
(exists/value per slot), ``ver``/``epoch``, per-op ``Results``, the credit
table, and every ``IOMetrics`` counter are identical to the single-device
engine, for all four ``SyncMode``s.  Only the physical heap layout differs
(each shard packs its own commits).

DESIGN.md §3.3 (sharded store): slot-partitioned StoreState under shard_map,
bit-equal to the single device — cross-shard SCAN runs included (§9.3).
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import engine
from repro.core.credits import CreditState
from repro.core.engine import Results, StoreState
from repro.core.runner import WindowStream, _prev_alive
from repro.core.types import (NULL_PTR, EngineConfig, IOMetrics, OpBatch,
                              OpKind)

__all__ = ["shard_extents", "sharded_store_init", "sharded_populate",
           "sharded_store_view", "apply_batch_sharded", "run_windows_sharded",
           "run_windows_sharded_traced", "failover_reown", "promote_replica",
           "host_rehome"]

_NONE = jnp.int32(-1)


def host_rehome(x) -> jax.Array:
    """Pull an array through the host so it sheds its committed device
    placement — required when state crosses mesh topologies (a failover's
    survivor mesh rejects buffers still committed to the dead one)."""
    return jnp.asarray(np.asarray(x))


def shard_extents(cfg: EngineConfig, n_shards: int) -> tuple[int, int]:
    """(slots_per_shard, heap_per_shard); raises unless both divide evenly."""
    if cfg.n_slots % n_shards or cfg.heap_slots % n_shards:
        raise ValueError(
            f"n_slots={cfg.n_slots} / heap_slots={cfg.heap_slots} must be "
            f"divisible by n_shards={n_shards}")
    return cfg.n_slots // n_shards, cfg.heap_slots // n_shards


def sharded_store_init(cfg: EngineConfig, n_shards: int) -> StoreState:
    """Like ``store_init`` but with a per-shard heap bump cursor (n_shards,).

    ``ptr`` holds *shard-local* heap indices; arrays keep their global length
    and are block-partitioned by the ``shard_map`` in ``apply_batch_sharded``.
    """
    shard_extents(cfg, n_shards)
    st = engine.store_init(cfg)
    return dataclasses.replace(st, heap_top=jnp.zeros((n_shards,), jnp.int32))


def sharded_populate(cfg: EngineConfig, n_shards: int, state: StoreState,
                     keys, values) -> StoreState:
    """Bulk-load distinct KV pairs, packing each shard's heap separately."""
    per, hper = shard_extents(cfg, n_shards)
    keys = jnp.asarray(keys, jnp.int32)
    values = jnp.asarray(values, jnp.int32)
    n = keys.shape[0]
    owner = keys // per
    pos = jnp.arange(n, dtype=jnp.int32)
    order = jnp.lexsort((pos, owner))
    own_s = owner[order]
    is_first = jnp.concatenate([jnp.ones((1,), bool), own_s[1:] != own_s[:-1]])
    seg = jnp.cumsum(is_first.astype(jnp.int32)) - 1
    seg_start = jax.ops.segment_min(pos, seg, num_segments=n)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(pos - seg_start[seg])
    loc = state.heap_top[owner] + rank                    # shard-local index
    heap = state.heap.at[owner * hper + loc].set(values)
    ptr = state.ptr.at[keys].set(loc)
    counts = jnp.zeros((n_shards,), jnp.int32).at[owner].add(1)
    return dataclasses.replace(state, ptr=ptr, heap=heap,
                               heap_top=state.heap_top + counts)


def sharded_store_view(cfg: EngineConfig, n_shards: int, state: StoreState
                       ) -> tuple[jax.Array, jax.Array]:
    """Logical (exists, value) view of a sharded store (cf. ``store_view``)."""
    per, hper = shard_extents(cfg, n_shards)
    owner = jnp.arange(cfg.n_slots, dtype=jnp.int32) // per
    exists = state.ptr != NULL_PTR
    val = jnp.where(exists,
                    state.heap[owner * hper + jnp.clip(state.ptr, 0)], _NONE)
    return exists, val


def failover_reown(cfg: EngineConfig, n_from: int, state: StoreState,
                   survivors: tuple[int, ...]) -> tuple[StoreState, dict]:
    """Re-own dead shards' slot partitions onto the survivors.

    DINOMO-style elastic failover: when shards die, the surviving shards
    reconstruct the lost partitions from replicas and re-partition the
    store over ``len(survivors)`` shards (which must divide ``n_slots``/
    ``heap_slots``).  The *logical* store — (exists, value) per slot plus
    the slot-indexed ``ver``/``epoch``/``stranded`` planes — carries over
    unchanged; only the physical heap packing is rebuilt, which is exactly
    the freedom the sharded-equivalence contract already grants.  The
    replicated credit table is global, so it survives for free — pass the
    same ``CreditState`` to the post-failover runner.

    Returns ``(new_state, recovery_io)`` where ``new_state`` feeds the
    ``len(survivors)``-way runner and ``recovery_io`` is the control-plane
    recovery bill (replica reads to reconstruct the lost partitions), kept
    OUT of ``IOMetrics`` so the post-failover data-plane bill stays
    bit-equal to a single-device run with the same CN drop mask (asserted
    in ``benchmarks/recovery.py`` and ``tests/test_recovery.py``).
    """
    n_to = len(survivors)
    per_f, _ = shard_extents(cfg, n_from)
    shard_extents(cfg, n_to)
    dead = sorted(set(range(n_from)) - set(survivors))
    if len(set(survivors)) != n_to or any(s not in range(n_from)
                                          for s in survivors):
        raise ValueError(f"survivors {survivors!r} must be distinct shards "
                         f"of the {n_from}-way store")
    exists, val = sharded_store_view(cfg, n_from, state)
    exists, val = np.asarray(exists), np.asarray(val)
    keys = np.flatnonzero(exists)
    new = sharded_populate(cfg, n_to, sharded_store_init(cfg, n_to),
                           keys, val[keys])
    new = dataclasses.replace(new, meta=host_rehome(state.meta),
                              epoch=host_rehome(state.epoch))
    lost_live = int(exists.reshape(n_from, per_f)[dead].sum()) if dead else 0
    recovery_io = {
        "dead_shards": dead,
        "survivors": list(survivors),
        # one replica READ per lost pointer slot + one per live lost value
        "reown_reads": len(dead) * per_f + lost_live,
        "reown_bytes": (len(dead) * per_f * cfg.ptr_bytes
                        + lost_live * cfg.value_bytes),
    }
    return new, recovery_io


def promote_replica(cfg: EngineConfig, state: StoreState,
                    survivors: tuple[int, ...], dead_replicas: tuple[int, ...],
                    ) -> tuple[StoreState, dict]:
    """Promote a surviving replica MN after replica deaths (DESIGN.md §13).

    SNAPSHOT client-centric replication keeps every replica's logical store
    identical — each acked write hit all R replicas before completing, and
    window-granular execution means no write is mid-fan-out at a window
    boundary — so promotion moves **no data**: clients drop the dead
    replicas from their replica lists and re-point reads at the lowest
    surviving replica.  What failover must still do is re-run the §4.6
    orphaned-lock repair against the promoted replica: every lock the CN
    liveness plane has stranded (``StoreState.stranded``) was recorded
    against the old primary's lock words, so the promoted replica's copies
    are re-armed with one break CAS each, and the whole lock plane is swept
    (one lock-entry READ per slot) to certify that no acquisition was
    mid-fan-out when the replica died.

    Control-plane only: the returned state is the input state (the lazy
    in-band repair contract is untouched — the next locker of a stranded
    slot still breaks and bills it), and the sweep's bill is returned as a
    ``recovery_io`` dict kept OUT of ``IOMetrics`` — which is exactly why
    the post-failover data-plane bill is bit-equal to a plain segmented run
    that swaps ``EngineConfig.n_replicas`` at the crash window (asserted in
    ``benchmarks/replication.py`` and ``tests/test_replication.py``).
    """
    dead = sorted(dead_replicas)
    if not survivors:
        raise ValueError("promote_replica: no surviving replica")
    if set(dead) & set(survivors):
        raise ValueError(f"replicas {sorted(set(dead) & set(survivors))} "
                         f"listed both dead and surviving")
    stranded = int(np.asarray(state.stranded).sum())
    recovery_io = {
        "dead_replicas": dead,
        "survivors": sorted(survivors),
        "promoted": min(survivors),
        # one lock-entry READ per slot on the promoted replica (the
        # mid-fan-out certification sweep) ...
        "promote_reads": cfg.n_slots,
        "promote_bytes": cfg.n_slots * cfg.lock_bytes,
        # ... plus one break CAS re-arming each CN-stranded lock on every
        # surviving replica's copy of the word
        "repair_rearm_cas": stranded * len(survivors),
    }
    return state, recovery_io


def _psum_results(res: Results, axis: str) -> Results:
    """Reassemble exact per-op results across shards: non-owning shards emit
    each field's neutral element, so one psum (offset for the non-zero
    defaults) recovers the single-device values.  Elementwise, so it works
    unchanged on window-stacked ``(W, B)`` results."""
    def psum(x):
        return jax.lax.psum(x, axis)
    return Results(
        ok=psum(res.ok.astype(jnp.int32)) > 0,
        value=psum(res.value - _NONE) + _NONE,
        pessimistic=psum(res.pessimistic.astype(jnp.int32)) > 0,
        combined=psum(res.combined.astype(jnp.int32)) > 0,
        wc_batch=psum(res.wc_batch - 1) + 1,
        retries=psum(res.retries),
        rank=psum(res.rank),
        orphan_wait=psum(res.orphan_wait),
        # each shard counts the rows of its own sub-run of a cross-shard
        # SCAN (run split at partition boundaries, DESIGN.md §9)
        rows=psum(res.rows),
    )


def _store_spec(axis: str) -> StoreState:
    return StoreState(ptr=P(axis), meta=P(axis), epoch=P(axis),
                      heap=P(axis), heap_top=P(axis))


@functools.lru_cache(maxsize=None)
def _sharded_fn(cfg: EngineConfig, mesh, axis: str):
    n_shards = int(mesh.shape[axis])
    per, hper = shard_extents(cfg, n_shards)
    lcfg = dataclasses.replace(cfg, n_slots=per, heap_slots=hper)
    st_spec = _store_spec(axis)

    def run(state, credits, batch, valid):
        base = jax.lax.axis_index(axis).astype(jnp.int32) * per
        owned = (batch.keys >= base) & (batch.keys < base + per)
        st = dataclasses.replace(state, heap_top=state.heap_top[0])
        st2, cr2, res, io = engine.apply_batch(
            lcfg, st, credits, batch, valid=valid, owned=owned,
            slot_base=base)
        st2 = dataclasses.replace(st2, heap_top=st2.heap_top[None])
        return (st2, cr2, _psum_results(res, axis),
                jax.tree.map(lambda x: jax.lax.psum(x, axis), io))

    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(st_spec, P(), P(), P()),
                       out_specs=(st_spec, P(), P(), P()),
                       check_vma=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _sharded_stream_fn(cfg: EngineConfig, mesh, axis: str,
                       io_per_window: bool, traced: bool = False,
                       per_shard_io: bool = False):
    n_shards = int(mesh.shape[axis])
    per, hper = shard_extents(cfg, n_shards)
    lcfg = dataclasses.replace(cfg, n_slots=per, heap_slots=hper)
    st_spec = _store_spec(axis)

    def run(state, credits, stream, prev_alive):
        base = jax.lax.axis_index(axis).astype(jnp.int32) * per

        def step(carry, win):
            st, cr, prev = carry
            batch, valid, alive = win
            owned = (batch.keys >= base) & (batch.keys < base + per)
            died = prev & ~alive
            st, cr, res, io = engine.apply_batch(
                lcfg, st, cr, batch, valid=valid, owned=owned,
                slot_base=base, alive=alive, died=died)
            out = (res, io, jnp.sum(cr.credit)) if traced else (res, io)
            return (st, cr, alive), out

        st = dataclasses.replace(state, heap_top=state.heap_top[0])
        (st, cr, _), outs = jax.lax.scan(
            step, (st, credits, prev_alive),
            (stream.batch, stream.valid, stream.alive))
        ress, ios = outs[0], outs[1]
        st = dataclasses.replace(st, heap_top=st.heap_top[None])
        if not io_per_window:
            ios = jax.tree.map(lambda x: jnp.sum(x, axis=0), ios)
        if per_shard_io:
            # keep every field at exactly ONE psum (the collective census in
            # repro.analysis.jaxpr_check forbids all_gather): each shard
            # scatters its local bill into its own onehot slot, the psum
            # assembles the (..., n_shards) plane, and summing that plane
            # recovers the replicated global bill bit-exactly (asserted by
            # tests/test_dist_store.py) — the weak-scaling benchmark needs
            # the per-shard split because mesh throughput is bound by the
            # HOTTEST shard's NIC, not the sum.
            onehot = (jnp.arange(n_shards, dtype=jnp.int32)
                      == jax.lax.axis_index(axis)).astype(jnp.int32)
            ios = jax.tree.map(
                lambda x: jax.lax.psum(x[..., None] * onehot.astype(x.dtype),
                                       axis), ios)
        else:
            ios = jax.tree.map(lambda x: jax.lax.psum(x, axis), ios)
        res_io = (st, cr, _psum_results(ress, axis), ios)
        # credit mass is computed from the replicated credit table, so every
        # shard already holds the identical (W,) trajectory
        return res_io + (outs[2],) if traced else res_io

    out_specs = (st_spec, P(), P(), P()) + ((P(),) if traced else ())
    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(st_spec, P(), P(), P()),
                       out_specs=out_specs,
                       check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1))


def apply_batch_sharded(cfg: EngineConfig, mesh, state: StoreState,
                        credits, batch: OpBatch,
                        valid: jax.Array | None = None, *, axis: str = "data"
                        ) -> tuple[StoreState, CreditState, Results, IOMetrics]:
    """``engine.apply_batch`` under shard_map on ``mesh.shape[axis]`` shards.

    Drop-in equivalent of the single-device engine (same signature modulo
    mesh); ``state`` must come from ``sharded_store_init``/``sharded_populate``.
    """
    if valid is None:
        valid = batch.kinds != OpKind.NOP
    return _sharded_fn(cfg, mesh, axis)(state, credits, batch, valid)


def run_windows_sharded(cfg: EngineConfig, mesh, state: StoreState,
                        credits, stream: WindowStream, *, axis: str = "data",
                        io_per_window: bool = False,
                        per_shard_io: bool = False,
                        prev_alive: jax.Array | None = None
                        ) -> tuple[StoreState, CreditState, Results, IOMetrics]:
    """Sharded ``repro.core.runner.run_windows``: every window of ``stream``
    executes inside one ``lax.scan`` under one ``shard_map``.

    The credit plane is replicated per window exactly as in
    ``apply_batch_sharded`` — each scan step re-derives its ``owned`` mask
    from that window's keys and runs the full-batch credit decision/feedback,
    so per-window ``Results``, per-window I/O (``io_per_window=True``), the
    credit table, and the store view are bit-identical to the single-device
    ``run_windows`` (tested in ``tests/test_runner.py``).  ``state`` and
    ``credits`` are donated.  ``prev_alive`` overrides the liveness row
    assumed before window 0 (see ``runner._prev_alive``) so a run split
    around a shard failover still strands crashes at the boundary.

    ``per_shard_io=True`` appends a trailing ``(n_shards,)`` axis to every
    ``IOMetrics`` field — shard ``s``'s slice is the bill its own partition
    served, and the sum over shards equals the replicated global bill.  The
    weak-scaling benchmark divides by the hottest shard's service time, since
    parallel MN NICs serve their partitions concurrently.
    """
    return _sharded_stream_fn(cfg, mesh, axis, io_per_window,
                              per_shard_io=per_shard_io)(
        state, credits, stream, _prev_alive(stream, prev_alive))


def run_windows_sharded_traced(cfg: EngineConfig, mesh, state: StoreState,
                               credits, stream: WindowStream, *,
                               axis: str = "data",
                               prev_alive: jax.Array | None = None
                               ) -> tuple[StoreState, CreditState, Results, IOMetrics,
                                          jax.Array]:
    """Sharded ``repro.core.runner.run_windows_traced``: returns
    ``(state, credits, results, io_per_window, credit_mass)`` with the
    ``(W,)`` per-window credit-table mass taken from the replicated credit
    plane (identical on every shard), matching the single-device trace
    bit-exactly.  ``state`` and ``credits`` are donated."""
    return _sharded_stream_fn(cfg, mesh, axis, True, traced=True)(
        state, credits, stream, _prev_alive(stream, prev_alive))

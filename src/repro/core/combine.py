"""Global write-combining as a batched primitive (§4.2, TPU adaptation).

The paper's mechanism: the MCS wait queue *is* a registry of concurrent
conflicting UPDATEs, so the whole queue is served by ONE combined write by
the queue-tail client ("executor"), with last-writer-wins resolution.

The batch analogue on an SPMD dataplane: the ops that would have formed a
wait queue are exactly the ops in the current batch that share a key.  A
stable sort by (key, queue-position) materializes every wait queue at once;
the *last* element of each run is the executor; everyone else is combined.

This module is the kernel-dispatch seam (DESIGN.md §10): ``plan_combine``
and ``per_key_stats`` accept ``backend`` ∈ {"auto", "pallas", "jnp"} and
route the sorted-run sweep through either the fused Pallas kernel
(``repro.kernels.wc_combine``, interpret mode off-TPU) or the pure-jnp
path below — bit-identical by contract and by test.

DESIGN.md §2.1 (the combine primitive): one lexsort materializes every wait
queue; reader ranks extend it to SCAN (§9.2); §10 covers backend dispatch
and the shared-sort derived plans (``stats_from_plan``, ``key_run_ids``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["CombinePlan", "plan_combine", "OpStats", "per_key_stats",
           "stats_from_plan", "key_run_ids", "local_executors_by_run",
           "resolve_backend"]

_BIG = 2**31 - 1   # python int, weak-typed to int32 at use sites


def resolve_backend(backend: str) -> tuple[str, bool]:
    """Resolve a ``kernel_backend`` config value to ``(impl, interpret)``.

    ``auto`` picks the Pallas kernel only where it is compiled (TPU) and the
    jnp reference elsewhere — on CPU the interpreted kernel is strictly
    slower, so "auto" never selects it.  ``pallas`` forces the kernel
    (interpret mode off-TPU: CI exercises the exact kernel dataflow).
    ``jnp`` forces the reference.  DESIGN.md §10.
    """
    if backend == "auto":
        if jax.default_backend() == "tpu":
            return "pallas", False
        return "jnp", False
    if backend == "pallas":
        return "pallas", jax.default_backend() != "tpu"
    if backend == "jnp":
        return "jnp", False
    raise ValueError(f"unknown kernel backend {backend!r} "
                     "(expected 'auto', 'pallas' or 'jnp')")


def _first_last_rank(ks: jax.Array, backend: str
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run boundaries + in-run rank of a *sorted* key array, via the
    dispatch seam: the Pallas ``wc_combine`` kernel or the jnp sweep."""
    impl, interpret = resolve_backend(backend)
    if impl == "pallas":
        from repro.kernels.wc_combine.ops import wc_combine_op
        return wc_combine_op(ks, interpret=interpret)
    idx = jnp.arange(ks.shape[0], dtype=jnp.int32)
    neq = ks[1:] != ks[:-1]
    is_first = jnp.concatenate([jnp.ones((1,), bool), neq])
    is_last = jnp.concatenate([neq, jnp.ones((1,), bool)])
    rank = idx - jax.lax.cummax(jnp.where(is_first, idx, 0))
    return is_first, is_last, rank


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CombinePlan:
    """The materialized wait queues of one synchronization window.

    All arrays are in *sorted* order (by key, then queue position); ``perm``
    maps sorted -> original batch positions.
    """
    perm: jax.Array          # (B,) int32: original index of sorted element
    keys_sorted: jax.Array   # (B,) int32
    is_first: jax.Array      # (B,) bool: head of a key run (the "coordinator")
    is_last: jax.Array       # (B,) bool: tail of a key run (the "executor")
    run_length: jax.Array    # (B,) int32: my queue length (WC batch size)
    rank: jax.Array          # (B,) int32: my position within my queue (0-based)
    n_unique: jax.Array      # () int32: number of distinct keys (executed writes)


def plan_combine(keys: jax.Array, pos: jax.Array, valid: jax.Array,
                 *, backend: str = "jnp") -> CombinePlan:
    """Build wait queues for a batch of write ops.

    ``keys``: (B,) slot ids; ``pos``: (B,) serialization priority (queue
    order); ``valid``: (B,) bool — invalid ops sort to the back and form a
    dedicated run that callers must mask out (they are never executors of a
    real key because the sort key is +inf for them).  ``backend`` selects
    the run-sweep implementation (DESIGN.md §10); the sort itself is XLA
    either way and the outputs are bit-identical.
    """
    b = keys.shape[0]
    k = jnp.where(valid, keys, _BIG)
    # Stable composite sort: primary key, secondary queue position.
    order = jnp.lexsort((pos, k))
    ks = k[order]
    is_first, is_last, rank = _first_last_rank(ks, backend)
    idx = jnp.arange(b, dtype=jnp.int32)
    seg_start = idx - rank
    seg_end = jax.lax.cummin(jnp.where(is_last, idx, _BIG), reverse=True)
    run_length = seg_end - seg_start + 1
    valid_sorted = valid[order]
    n_unique = jnp.sum(is_first & valid_sorted).astype(jnp.int32)
    return CombinePlan(
        perm=order.astype(jnp.int32), keys_sorted=ks, is_first=is_first,
        is_last=is_last, run_length=run_length, rank=rank, n_unique=n_unique,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class OpStats:
    """Per-original-op wait-queue statistics over the masked op subset."""
    is_tail: jax.Array    # (B,) bool — queue tail (the executor / last writer)
    mult_of: jax.Array    # (B,) int32 — queue length of my key (0 if unmasked)
    rank_of: jax.Array    # (B,) int32 — 0-based rank in my queue (0 if unmasked)
    retry_sum: jax.Array  # () int32 — sum of ranks = Σ_k m_k(m_k-1)/2


def stats_from_plan(plan: CombinePlan, mask: jax.Array) -> OpStats:
    """Queue statistics for a *subset* of an existing plan's valid ops.

    Precondition: ``mask ⊆`` the validity the plan was built with, so every
    masked lane sits inside its true key run.  Because the lexsort is stable
    and masked lanes keep their relative ``pos`` order, counting masked
    lanes inside each run reproduces ``per_key_stats(keys, pos, mask)``
    bit-for-bit — without paying a second sort (DESIGN.md §10.2).
    """
    b = plan.perm.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    mask_s = mask[plan.perm]
    m_i = mask_s.astype(jnp.int32)
    c = jnp.cumsum(m_i)               # masked lanes through me, inclusive
    cex = c - m_i                     # masked lanes strictly before me
    seg_start = idx - plan.rank
    seg_end = seg_start + plan.run_length - 1
    rank_s = cex - cex[seg_start]     # masked lanes before me, in-run
    mult_s = c[seg_end] - cex[seg_start]
    is_tail_s = mask_s & (c == c[seg_end])
    zeros_i = jnp.zeros((b,), jnp.int32)
    is_tail = jnp.zeros((b,), bool).at[plan.perm].set(is_tail_s)
    mult_of = zeros_i.at[plan.perm].set(jnp.where(mask_s, mult_s, 0))
    rank_of = zeros_i.at[plan.perm].set(jnp.where(mask_s, rank_s, 0))
    retry_sum = jnp.sum(jnp.where(mask_s, rank_s, 0))
    return OpStats(is_tail=is_tail, mult_of=mult_of, rank_of=rank_of,
                   retry_sum=retry_sum)


def per_key_stats(keys: jax.Array, pos: jax.Array, mask: jax.Array,
                  *, backend: str = "jnp") -> OpStats:
    """Queue statistics per masked op, grouped by key, ordered by ``pos``."""
    return stats_from_plan(plan_combine(keys, pos, mask, backend=backend),
                           mask)


def key_run_ids(plan: CombinePlan) -> jax.Array:
    """Per-lane key-run id in batch order: the sorted index of the first
    lane of the lane's ``plan`` run, in ``[0, B)``.  Two lanes of the plan's
    validity share an id iff they share a key; invalid lanes all share the
    +inf tail run's id."""
    b = plan.perm.shape[0]
    first = jnp.arange(b, dtype=jnp.int32) - plan.rank
    return jnp.zeros((b,), jnp.int32).at[plan.perm].set(first)


def local_executors_by_run(run_id: jax.Array, cn: jax.Array, pos: jax.Array,
                           mask: jax.Array, n_cns: int) -> jax.Array:
    """Local write combining (§3.1): the last (by ``pos``) masked op of each
    (key, compute-node) group — the only one that leaves the CN — found by
    one O(B) scatter-max over a static ``(B * n_cns,)`` cell table keyed by
    (key run, cn), with no sort of its own.

    ``run_id`` comes from ``key_run_ids`` of a plan whose validity covers
    ``mask``, so masked lanes share a cell iff they share (key, cn); the
    table's size depends on the window and the CN count, never on the
    store.  Exact under the ``OpBatch`` contract: ``pos`` unique per batch
    (serialization priorities 0..B-1) and ``cn ∈ [0, n_cns)``
    (``OpBatch.make`` takes cn mod n_cns; the liveness plane clips the same
    way), so each cell's max-pos masked lane is unique.  DESIGN.md §10.2.
    """
    gi = run_id * n_cns + jnp.clip(cn, 0, n_cns - 1)
    buf = jnp.full((run_id.shape[0] * n_cns,), -1, jnp.int32)
    buf = buf.at[gi].max(jnp.where(mask, pos, -1))
    return mask & (buf[gi] == pos)

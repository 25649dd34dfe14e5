"""Fused multi-window execution: W synchronization windows in ONE jitted scan.

The per-window Python loop (``for w: state, ... = apply_batch(...)``) pays one
jit dispatch plus a host round-trip per window, so host dispatch — not the
engine — dominates wall-clock at benchmark sizes and inverts the ordering the
paper measures.  ``run_windows`` replaces that loop with a single
``jax.lax.scan`` over a stacked ``WindowStream``: the store/credit carry never
leaves the device and the buffers are donated, so steady-state windows run
back-to-back at device speed.

Two throughput metrics (see DESIGN.md §6):

* **device wall-clock** — what ``time.time()`` around the fused scan measures;
  an artifact of the TPU/CPU adaptation, useful only as a regression signal.
* **MN-IOPS-modeled** — the paper's metric (§2.3, §5): on real disaggregated
  memory the bottleneck is memory-side NIC IOPS, which the engine meters
  *exactly* per window.  ``modeled_throughput`` converts the verb bill into
  ops/s under the testbed cost model (``SimParams``: ``mn_cap`` verbs/us,
  ``mn_bw`` bytes/us), the same accounting FUSEE/Outback evaluate with.

Modeled latency (the paper's second axis, Figs 11-12): ``modeled_latency``
derives a per-op completion time in microseconds from each op's exact verb
bill and wait-queue rank (``Results.rank``) under the same ``SimParams``
cost model the protocol simulator uses — critical-path RTTs per protocol
workflow (Figs 9-10) plus the memory-side NIC queueing delay of the window's
own verb backlog (``simnet.issue_mn``'s ``(backlog + rank) / cap`` rule).
``latency_stats`` reduces that to p50/p99 (``LatencyStats``); see
DESIGN.md §7 for the per-mode chains.

``run_windows_traced`` additionally returns the per-window credit-table mass
so CIDER's AIMD adaptation (§4.3) is observable as a trajectory without
leaving the fused scan.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.credits import CreditState
from repro.core.engine import Results, StoreState
from repro.core.simnet import SimParams
from repro.core.types import (EngineConfig, IOMetrics, LatencyStats, OpBatch,
                              OpKind, SyncMode)

__all__ = ["WindowStream", "make_stream", "run_windows", "run_windows_traced",
           "io_window", "modeled_throughput", "modeled_latency",
           "latency_stats"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class WindowStream:
    """W stacked synchronization windows: every ``OpBatch`` leaf plus the
    validity mask carries a leading window axis ``(W, B)``.

    ``alive`` is the liveness plane (crash recovery, §4.6): row ``w`` masks
    the compute nodes alive through window ``w``.  A CN whose bit drops
    between consecutive windows *died at* the later window — its in-flight
    ops are dropped at the window boundary and its pessimistic writes strand
    orphaned locks (see ``engine.apply_batch``).  All-ones (the
    ``make_stream`` default) reproduces the failure-free behavior bit-exactly.
    """
    batch: OpBatch      # all leaves (W, B)
    valid: jax.Array    # (W, B) bool
    alive: jax.Array    # (W, n_cns) bool — CN liveness per window

    @property
    def shape(self) -> tuple[int, int]:
        return self.batch.kinds.shape


@functools.partial(jax.profiler.annotate_function, name="runner.make_stream")
def make_stream(kinds, keys, values, n_cns: int = 1,
                lanes_per_cn: int | None = None,
                valid: jax.Array | None = None,
                alive: jax.Array | None = None,
                cn: jax.Array | None = None) -> WindowStream:
    """Stack ``(W, B)`` op arrays into a ``WindowStream``.

    Window ``w`` of the result is exactly ``OpBatch.make(kinds[w], keys[w],
    values[w], n_cns, lanes_per_cn)`` — same serialization priorities and CN
    assignment — so the fused scan sees the batches the per-window loop saw.
    ``alive`` (``(W, n_cns)`` bool, default all alive) attaches a liveness
    schedule; build one with ``repro.recovery.liveness``.

    ``cn`` (``(W, B)`` int32) overrides the default round-robin lane→CN map.
    Open-loop streams need it: a dense re-pack moves an op to a new lane, and
    only an explicit CN plane keeps its (key, cn) write-combining group — and
    hence its bill — identical to the padded original (DESIGN.md §12).
    """
    kinds = jnp.asarray(kinds, jnp.int32)
    keys = jnp.asarray(keys, jnp.int32)
    values = jnp.asarray(values, jnp.int32)
    w, b = kinds.shape
    pos = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32), (w, b))
    if lanes_per_cn is None:
        lanes_per_cn = max(b // max(n_cns, 1), 1)
    if cn is None:
        cn = (pos // lanes_per_cn) % max(n_cns, 1)
    else:
        cn = jnp.asarray(cn, jnp.int32)
        if cn.shape != (w, b):
            raise ValueError(f"cn plane is {cn.shape}, expected {(w, b)}")
    if valid is None:
        valid = kinds != OpKind.NOP
    if alive is None:
        alive = jnp.ones((w, max(n_cns, 1)), bool)
    else:
        alive = jnp.asarray(alive, bool)
        if alive.shape != (w, max(n_cns, 1)):
            raise ValueError(
                f"alive is {alive.shape}, expected ({w}, {max(n_cns, 1)}) — "
                f"the liveness schedule must match the stream's windows AND "
                f"its CN count (a mismatch would silently mis-drop ops)")
    batch = OpBatch(kinds=kinds, keys=keys, values=values, pos=pos, cn=cn,
                    n_cns=max(n_cns, 1))
    return WindowStream(batch=batch, valid=jnp.asarray(valid, bool),
                        alive=alive)


@functools.partial(jax.jit, static_argnames=("cfg", "io_per_window", "traced"),
                   donate_argnums=(1, 2))
def _scan_windows(cfg: EngineConfig, state: StoreState, credits: CreditState,
                  stream: WindowStream, prev_alive: jax.Array,
                  io_per_window: bool, traced: bool):
    """The one fused window scan behind ``run_windows``/``run_windows_traced``
    (and mirrored by ``dist.store``'s sharded variant)."""
    def step(carry, win):
        st, cr, prev, = carry
        batch, valid, alive = win
        # CNs alive at window start but not through this window died HERE —
        # their in-flight pessimistic writes strand locks (engine step 5b)
        died = prev & ~alive
        st, cr, res, io = engine.apply_batch(cfg, st, cr, batch, valid=valid,
                                             alive=alive, died=died)
        out = (res, io, jnp.sum(cr.credit)) if traced else (res, io)
        return (st, cr, alive), out

    (state, credits, _), outs = jax.lax.scan(
        step, (state, credits, prev_alive),
        (stream.batch, stream.valid, stream.alive))
    results, ios = outs[0], outs[1]
    if not io_per_window:
        ios = jax.tree.map(lambda x: jnp.sum(x, axis=0), ios)
    if traced:
        return state, credits, results, ios, outs[2]
    return state, credits, results, ios


def _prev_alive(stream: WindowStream, prev_alive) -> jax.Array:
    """Default liveness at stream start: deaths 'at window 0' cannot strand
    anything (nothing was in flight before the stream began), so the initial
    previous-alive row is row 0 itself.  ``repro.recovery`` passes the last
    alive row of the preceding segment when a run is split (e.g. around a
    shard failover), so a crash at a segment boundary still strands."""
    return stream.alive[0] if prev_alive is None else jnp.asarray(prev_alive,
                                                                  bool)


@functools.partial(jax.profiler.annotate_function, name="runner.dispatch")
def run_windows(cfg: EngineConfig, state: StoreState, credits: CreditState,
                stream: WindowStream, io_per_window: bool = False,
                prev_alive: jax.Array | None = None,
                ) -> tuple[StoreState, CreditState, Results, IOMetrics]:
    """Execute every window of ``stream`` in one fused ``lax.scan``.

    Bit-exact per-window semantics: window ``w``'s ``Results`` row, I/O bill,
    and credit-table transition are identical to calling ``apply_batch`` W
    times from Python (asserted in ``tests/test_runner.py``).  ``state`` and
    ``credits`` are donated — callers must use the returned buffers.

    Returns ``(state, credits, results, io)`` with ``results`` stacked over
    the window axis and ``io`` summed across windows (``io_per_window=True``
    keeps the per-window bill, leaves shaped ``(W,)``).
    """
    return _scan_windows(cfg, state, credits, stream,
                         _prev_alive(stream, prev_alive), io_per_window, False)


def run_windows_traced(cfg: EngineConfig, state: StoreState,
                       credits: CreditState, stream: WindowStream,
                       prev_alive: jax.Array | None = None,
                       ) -> tuple[StoreState, CreditState, Results, IOMetrics,
                                  jax.Array]:
    """``run_windows`` with the AIMD trajectory kept: returns
    ``(state, credits, results, io, credit_mass)`` where ``io`` is always
    per-window (leaves ``(W,)``) and ``credit_mass`` is the total credit-table
    mass AFTER each window (``(W,)`` int32) — the §4.3 adaptation signal the
    dynamic-contention scenarios plot.  Same bit-exact per-window semantics
    and donation contract as ``run_windows``.
    """
    return _scan_windows(cfg, state, credits, stream,
                         _prev_alive(stream, prev_alive), True, True)


def io_window(ios: IOMetrics, w: int) -> IOMetrics:
    """Window ``w``'s bill out of a stacked (``io_per_window=True``) bill."""
    return jax.tree.map(lambda x: x[w], ios)


def modeled_throughput(io: IOMetrics, p: SimParams, n_ops: int
                       ) -> dict[str, Any]:
    """MN-IOPS-bound throughput of ``n_ops`` ops with verb bill ``io``.

    The memory-pool NIC serves ``mn_cap`` verbs and ``mn_bw`` bytes per tick
    (1 tick == 1 us, ``repro.core.simnet``); CN<->CN messages ride client
    NICs and are free here — exactly ShiftLock's design point.  The modeled
    service time is the binding constraint, so throughput in Mops/s is
    ``n_ops / ticks`` directly.
    """
    mn_iops = int(np.asarray(io.mn_iops))
    mn_bytes = int(np.asarray(io.mn_bytes))
    iops_ticks = mn_iops / p.mn_cap
    bw_ticks = mn_bytes / p.mn_bw
    ticks = max(iops_ticks, bw_ticks)
    return {
        "modeled_ticks_us": round(ticks, 2),
        "modeled_mops": round(n_ops / ticks, 4) if ticks > 0 else float("inf"),
        "bound": "iops" if iops_ticks >= bw_ticks else "bandwidth",
        "mn_cap_per_us": p.mn_cap,
        "mn_bw_bytes_per_us": p.mn_bw,
    }


def modeled_latency(cfg: EngineConfig, kinds, res: Results, p: SimParams,
                    valid=None, scan_counts=None) -> np.ndarray:
    """Per-op modeled completion time in microseconds (host-side, numpy).

    Two additive components, mirroring ``repro.core.simnet``'s service model
    (DESIGN.md §7 tabulates the per-mode chains):

    * **critical-path RTTs** — the op's sequential verb chain per the
      protocol workflows (Figs 9-10), scaled by ``p.rtt``; queue waits enter
      through ``Results.rank``: a rank-r optimistic writer pays r failed
      CAS rounds, a rank-r SPIN/MCS waiter sits behind r lock holders, while
      a CIDER combined queue completes with its single executor *regardless
      of rank* — exactly why global WC flattens the tail.
    * **MN NIC queueing** — ``simnet.issue_mn``'s ``(backlog + rank) / cap``
      rule applied to the window's own arrivals: each op waits behind the MN
      verbs of the ops preceding it in the window (serialization order ==
      batch position), so retry storms inflate everyone's tail, not just the
      retrying op's.

    * **lease waits** (crash recovery, §4.6) — an op whose wait queue found
      an orphaned (holder-dead) lock waits ``Results.orphan_wait`` lease
      expirations (``p.lease_us`` each) plus the stale-epoch READ + repair
      CAS round trips before its queue can proceed.  MCS queues wait once
      per dead chain node; CIDER/SPIN once per key — the repair asymmetry
      the recovery benchmark measures.

    * **SCAN chains** (DESIGN.md §9) — a scan's leaf-run READs are
      doorbell-batched (one round trip for the run, one for the found
      values), so its chain is short while its *verb* footprint — which
      feeds everyone's MN queueing — is per-leaf (``scan_counts``, the
      per-op scan length; defaults to ``Results.rows`` when not given,
      undercounting absent-row leaves).  Per mode: OSYNC adds the
      validation re-read round; SPIN/MCS readers wait behind
      ``Results.rank`` exclusive holders on the anchor leaf; CIDER's
      cold scans skip the queue entirely and a credit-hot anchor waits
      for at most its queue's ONE combined executor.

    Aggregate ``IOMetrics`` stay the *exact* bill; this per-op split is the
    documented approximation (locally-combined baseline writers are billed
    as rank-0 writers, CN<->CN hops cost ``p.cn_rtt`` uncontended; a scan's
    per-mode sync verbs are charged per anchor, not per leaf).  Works
    on flat ``(B,)`` or window-stacked ``(W, B)`` results; invalid lanes are
    NaN (``latency_stats`` ignores them).  When a liveness schedule dropped
    ops, pass the post-drop validity (``recovery.liveness`` provides it) so
    dead lanes are masked out.
    """
    kinds = np.asarray(kinds)
    ok = np.asarray(res.ok)
    pess = np.asarray(res.pessimistic)
    comb = np.asarray(res.combined)
    rank = np.asarray(res.rank).astype(np.float64)
    polls = np.asarray(res.retries).astype(np.float64)
    m = np.asarray(res.wc_batch).astype(np.float64)
    if valid is None:
        valid = kinds != OpKind.NOP
    else:
        valid = np.asarray(valid) & (kinds != OpKind.NOP)
    search = kinds == OpKind.SEARCH
    insert = kinds == OpKind.INSERT
    update = kinds == OpKind.UPDATE
    delete = kinds == OpKind.DELETE
    scan = kinds == OpKind.SCAN
    rows = np.asarray(res.rows).astype(np.float64)
    if scan_counts is None:
        counts = np.where(scan, rows, 0.0)
    else:
        # clip to the engine's static probe bound: the model must bill the
        # leaves the engine actually traversed, not the requested length
        counts = np.where(scan, np.minimum(
            np.asarray(scan_counts, np.float64), float(cfg.scan_max)), 0.0)
    idx = float(cfg.index_read_iops)
    rtt, cnr = float(p.rtt), float(p.cn_rtt)

    # ---- critical-path chain: sequential MN RTTs + CN-msg hops (us) --------
    chain = np.full(kinds.shape, idx, np.float64)      # index resolve
    extra = np.zeros(kinds.shape, np.float64)          # CN<->CN hops (us)
    chain = np.where(search, idx + ok, chain)          # + value READ if found
    chain = np.where(insert, idx + 2.0, chain)         # heap WRITE + ptr CAS
    # optimistic writers: rank-r loses r CAS rounds (re-read + re-CAS each)
    opt_u = update & ~pess
    chain = np.where(opt_u & ~comb, idx + 2.0 + 2.0 * rank, chain)
    chain = np.where(opt_u & comb, idx + 2.0, chain)   # rides its executor
    if cfg.mode == SyncMode.OSYNC:
        chain = np.where(delete, idx + 1.0 + 2.0 * rank, chain)
    else:
        chain = np.where(delete, idx + 3.0, chain)     # lock CAS+CAS+FAA
    if cfg.mode == SyncMode.SPIN:
        # acquire CAS + r holders (WRITE + ptr CAS + unlock each) + own 3
        chain = np.where(update & pess, idx + 4.0 + 3.0 * rank, chain)
    elif cfg.mode == SyncMode.MCS:
        # enqueue CAS + own WRITE + ptr CAS + FAA; each predecessor serves
        # 3 RTTs then hands off with one CN msg
        chain = np.where(update & pess, idx + 4.0, chain)
        extra = np.where(update & pess, rank * (3.0 * rtt + cnr), extra)
    elif cfg.mode == SyncMode.CIDER:
        # the whole queue completes with its ONE executor: enqueue CAS +
        # coordinator tail READ (multi-writer queues) + combined WRITE +
        # ptr CAS + release FAA — rank does NOT appear (global WC, §4.2)
        chain = np.where(update & pess, idx + 4.0 + (m > 1), chain)
        extra = np.where(update & pess & (m > 1), 2.0 * cnr, extra)

    # SCAN (DESIGN.md §9): leaf-run READ round + a value round when any row
    # was found (doorbell-batched); readers wait behind `rank` exclusive
    # holders on the anchor leaf — except CIDER, whose cold scans skip the
    # queue and whose hot anchor waits for ONE combined executor
    found = (rows > 0).astype(np.float64)
    if cfg.mode == SyncMode.OSYNC:
        chain = np.where(scan, idx + 2.0 + found, chain)       # + re-read round
    elif cfg.mode == SyncMode.SPIN:
        chain = np.where(scan, idx + 2.0 + found + 3.0 * rank, chain)
    elif cfg.mode == SyncMode.MCS:
        chain = np.where(scan, idx + 2.0 + found, chain)
        extra = np.where(scan, rank * (3.0 * rtt + cnr), extra)
    else:
        chain = np.where(scan, idx + 1.0 + found
                         + np.where(rank > 0, 4.0, 0.0), chain)

    # ---- MN NIC queueing: wait behind earlier ops' verbs in the window ----
    verbs = np.full(kinds.shape, idx, np.float64)
    verbs = np.where(search, idx + ok, verbs)
    verbs = np.where(insert, idx + 2.0, verbs)
    verbs = np.where(opt_u & ~comb, idx + 2.0 + 2.0 * rank, verbs)
    if cfg.mode == SyncMode.OSYNC:
        verbs = np.where(delete, idx + 1.0 + 2.0 * rank, verbs)
    else:
        verbs = np.where(delete, idx + 3.0, verbs)
    if cfg.mode == SyncMode.SPIN:
        verbs = np.where(update & pess, idx + 4.0 + polls, verbs)
    elif cfg.mode == SyncMode.MCS:
        verbs = np.where(update & pess, idx + 4.0, verbs)
    elif cfg.mode == SyncMode.CIDER:
        verbs = np.where(update & pess & comb, idx + 2.0, verbs)   # CAS + FAA
        verbs = np.where(update & pess & ~comb, idx + 4.0 + (m > 1), verbs)
    # SCAN verb footprint is per-leaf even though its chain is batched:
    # leaf READs + found-value READs + the per-mode traversal verbs
    scan_base = idx + counts + rows
    if cfg.mode == SyncMode.OSYNC:
        verbs = np.where(scan, scan_base + counts, verbs)      # version re-reads
    elif cfg.mode in (SyncMode.SPIN, SyncMode.MCS):
        verbs = np.where(scan, scan_base + 2.0 * counts, verbs)
    else:  # CIDER: hot-anchor proxy for the credit-hot leaf subset
        verbs = np.where(scan, scan_base + 2.0 * (rank > 0), verbs)
    # SNAPSHOT replication (DESIGN.md §13): write-class verbs fan out to all
    # R replica MNs, so the backlog everyone queues behind scales on the
    # write portion of each op's footprint (reads — index resolves, SEARCH
    # payloads, SCAN probe READs — stay x1); the op itself additionally
    # waits one `replica_rtt` for the slowest replica's ack.  R=1 skips both
    # terms, keeping the pre-replication latencies bit-exact.
    rep = float(p.n_replicas)
    if rep > 1.0:
        ro = np.full(kinds.shape, idx, np.float64)
        ro = np.where(search, idx + ok, ro)
        ro = np.where(scan, scan_base, ro)
        if cfg.mode == SyncMode.OSYNC:
            ro = np.where(scan, scan_base + counts, ro)
        verbs = ro + rep * (verbs - ro)
        extra = np.where(insert | update | delete,
                         extra + float(p.replica_rtt), extra)
    verbs = np.where(valid, verbs, 0.0)
    backlog = np.cumsum(verbs, axis=-1) - verbs
    # orphaned-lock lease waits: each unit is one lease expiry + the
    # stale-epoch READ + repair CAS of the break (2 RTTs)
    orphan = np.asarray(res.orphan_wait).astype(np.float64)
    lat = (rtt * chain + extra + backlog / float(p.mn_cap)
           + orphan * (float(p.lease_us) + 2.0 * rtt))
    return np.where(valid, lat, np.nan)


def latency_stats(lat_us: np.ndarray) -> LatencyStats:
    """Reduce ``modeled_latency`` output to the paper's percentiles."""
    lat = np.asarray(lat_us, np.float64).ravel()
    lat = lat[~np.isnan(lat)]
    if lat.size == 0:
        return LatencyStats(0.0, 0.0, 0.0, 0.0, 0)
    return LatencyStats(
        p50_us=round(float(np.percentile(lat, 50)), 2),
        p99_us=round(float(np.percentile(lat, 99)), 2),
        mean_us=round(float(lat.mean()), 2),
        max_us=round(float(lat.max()), 2),
        n_ops=int(lat.size))

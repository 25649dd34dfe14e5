"""The CIDER batched dataplane engine (§4, TPU adaptation).

Executes one *synchronization window* — a device batch of concurrent KV ops —
against a pointer store under one of four synchronization schemes
(``SyncMode``): OSYNC (optimistic CAS-retry), SPIN (CAS spinlock + backoff),
MCS (ShiftLock), CIDER (global write-combining + contention-aware credits).

Design invariants:

* **Semantic equivalence**: all four modes produce the *same* logical store
  state and per-op results — the canonical serialization is queue order ==
  batch position (``OpBatch.pos``), which is exactly what the MCS wait queue
  enforces and what last-writer-wins combining preserves (§4.5.1).  Tests
  assert equivalence against a sequential oracle.
* **Exact I/O metering**: modes differ in the RDMA-verb I/O they would issue
  on real DM; we meter those *exactly* (closed-form per wait queue, derived
  from the protocol workflows in Figs 9-10), because memory-side NIC IOPS is
  the paper's bottleneck resource.  The protocol *simulator*
  (``repro.core.sim``) additionally models queueing delay and reproduces the
  paper's throughput/latency figures; this engine is the jit/shard_map
  production path.

Per-queue I/O cost (m = effective concurrent UPDATE writers in the window),
derived from §2.2, §2.3, §4.2, Fig 9-10:

  OSYNC : m heap WRITEs + m(m+1)/2 CAS   (worst-case synchrony; §2.2)
  SPIN  : m WRITEs + m ptr-CAS + m lock-CAS + m unlock-CAS + backoff polls
  MCS   : m enqueue-CAS + m WRITEs + m ptr-CAS + m epoch-FAA + 2(m-1) CN msgs
  CIDER : m enqueue-CAS + 1 tail-READ + 1 WRITE + 1 ptr-CAS + m epoch-FAA
          + (m+1) CN msgs                 (m>1; m==1 falls back to MCS cost)

Local WC (applied to every scheme, §5.1) first collapses same-(key, CN)
writers to one effective writer; CIDER's global WC collapses same-key writers
across CNs to one executor (§4.2.1).

SCAN (range read over [key, key+count), DESIGN.md §9): when
``EngineConfig.scan_max > 0``, each SCAN expands into up to ``scan_max``
reader probes over its contiguous leaf-slot run (step 5c).  Probes join the
per-key wait queues *as readers* at the scanning op's batch position — they
observe exactly the per-slot state at that serialization point — and bill a
per-mode traversal: OSYNC re-reads each leaf's version, SPIN lock/unlock-CASes
every leaf (a CAS spinlock has no shared mode), MCS enqueues shared + releases
per leaf, and CIDER consults the CN-local credit table so cold leaves are
traversed lock-free (only credit-hot leaves pay shared-queue verbs).

The shard_map path (``repro.dist.store``) partitions the store over the
``data`` mesh axis and calls ``apply_batch`` per shard with ``owned``/
``slot_base``: the data plane then covers only the shard's keys while the
credit plane still sees the full window (see ``apply_batch``'s docstring and
DESIGN.md §3.3).

Replication (SNAPSHOT client-centric, FUSEE; DESIGN.md §13): with
``EngineConfig.n_replicas = R > 1`` every write-class verb — WRITEs, CASes
(lock words, pointer installs, retries, SCAN lock traversals, §4.6 repair
break-CASes) and FAAs — fans out from the client to all R replica MNs, while
reads (index READs, SEARCH payloads, the CIDER coordinator lock read, the
repair stale-epoch detection read, SCAN probes) bill to one replica.  The
scaling is a static end-of-metering block on the aggregate bill: read-only
bytes are tracked separately (``ro_bytes``) so
``mn_bytes = ro + R * wr`` exactly, and R=1 skips the block entirely — the
compiled program is byte-identical to the pre-replication engine.  Results
are replica-count-invariant: replicas hold identical logical state, so
per-op outcomes, combining, ranks and waits never see R.

Crash recovery (§4.6, DESIGN.md §8): ``apply_batch`` additionally accepts a
liveness plane (``alive``/``died`` CN masks).  Ops from dead CNs are dropped
at the window boundary; the pessimistic writes a newly-died CN had in flight
strand orphaned locks, which surviving waiters detect via the stale-epoch
read and break with a repair CAS — billed exactly (``IOMetrics.repair_cas``)
with the lease wait charged to the blocked queue (``Results.orphan_wait``).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from repro.core import combine as wc
from repro.core.credits import (CreditState, credit_decide, credit_feedback,
                                credit_slot)
from repro.core.types import (NULL_PTR, EngineConfig, IOMetrics, OpBatch,
                              OpKind, SyncMode)

__all__ = ["StoreState", "Results", "store_init", "store_view", "apply_batch",
           "populate", "pack_meta"]

_NONE = jnp.int32(-1)
_BIG = 2**31 - 1   # sort key of an invalid lane: after every real slot
_LANE_SHIFT = 4    # step 5c's sort payload: lane index above 4 flag bits


_VER_MASK = jnp.int32(0xF)      # the 4-bit version field of Fig 8
_STRANDED_SHIFT = 4


def pack_meta(ver: jax.Array, stranded: jax.Array) -> jax.Array:
    """Pack the two small per-slot planes into one int32 word: the 4-bit
    DELETE version (bits 0-3, §4.2.2) and the orphaned-lock node count
    (bits 4-31, §4.6 — MCS chains bound it by the window size, far below
    2^28).  One word instead of two halves the slot-metadata footprint,
    which is what keeps the donated-buffer window scan resident at the
    multi-million-key sizes ``benchmarks/scale.py`` runs (DESIGN.md §12)."""
    return ver | (stranded << _STRANDED_SHIFT)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StoreState:
    """The memory-pool resident state (all arrays shardable over slots)."""
    ptr: jax.Array       # (n_slots,) int32 heap index, NULL_PTR if empty
    meta: jax.Array      # (n_slots,) int32 packed per-slot metadata —
                         # see ``pack_meta``: 4-bit DELETE version (§4.2.2)
                         # + orphaned-lock node count (§4.6); read through
                         # the ``ver``/``stranded`` properties
    epoch: jax.Array     # (n_slots,) int32 lock epoch (fault tolerance, §4.6)
    heap: jax.Array      # (heap_slots,) int32 out-of-place value payloads
    heap_top: jax.Array  # () int32 bump cursor

    @property
    def ver(self) -> jax.Array:
        """(n_slots,) 4-bit DELETE version, unpacked from ``meta``."""
        return self.meta & _VER_MASK

    @property
    def stranded(self) -> jax.Array:
        """(n_slots,) orphaned lock nodes on each slot — a CN died holding/
        queued on the lock and no live waiter has broken it yet (§4.6)."""
        return self.meta >> _STRANDED_SHIFT


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Results:
    ok: jax.Array           # (B,) bool — success (IDU) / found (SEARCH)
    value: jax.Array        # (B,) int32 — SEARCH payload, _NONE if absent
    pessimistic: jax.Array  # (B,) bool — CIDER path decision (Fig 14)
    combined: jax.Array     # (B,) bool — write combined away by WC
    wc_batch: jax.Array     # (B,) int32 — wait-queue length at execution
    retries: jax.Array      # (B,) int32 — CAS retries (optimistic path ops)
    rank: jax.Array         # (B,) int32 — wait-queue rank at execution
                            # (0 = queue head / uncontended); feeds the
                            # modeled-latency derivation (runner.modeled_latency)
    orphan_wait: jax.Array  # (B,) int32 — orphaned (holder-dead) locks this op
                            # waited a lease expiry on before its queue could
                            # repair them (§4.6); modeled latency charges
                            # lease_us + the repair RTTs per unit
    rows: jax.Array         # (B,) int32 — SCAN rows found in [key, key+count)
                            # at the op's serialization position (DESIGN.md
                            # §9); 0 for point ops.  Sharded runs psum the
                            # per-shard sub-run counts.


def store_init(cfg: EngineConfig) -> StoreState:
    return StoreState(
        ptr=jnp.full((cfg.n_slots,), NULL_PTR, jnp.int32),
        meta=jnp.zeros((cfg.n_slots,), jnp.int32),
        epoch=jnp.zeros((cfg.n_slots,), jnp.int32),
        heap=jnp.full((cfg.heap_slots,), _NONE, jnp.int32),
        heap_top=jnp.zeros((), jnp.int32),
    )


def populate(cfg: EngineConfig, state: StoreState, keys, values) -> StoreState:
    """Bulk-load KV pairs (the paper pre-populates 60M items, §5.1)."""
    keys = jnp.asarray(keys, jnp.int32)
    values = jnp.asarray(values, jnp.int32)
    n = keys.shape[0]
    if n > cfg.heap_slots:
        raise ValueError(
            f"populate: {n} pairs exceed heap_slots={cfg.heap_slots}")
    loc = state.heap_top + jnp.arange(n, dtype=jnp.int32)
    heap = state.heap.at[loc].set(values)
    ptr = state.ptr.at[keys].set(loc)
    return dataclasses.replace(state, ptr=ptr, heap=heap, heap_top=state.heap_top + n)


def store_view(state: StoreState) -> tuple[jax.Array, jax.Array]:
    """Logical (exists, value) view — what tests compare across sync modes."""
    exists = state.ptr != NULL_PTR
    val = jnp.where(exists, state.heap[jnp.clip(state.ptr, 0)], _NONE)
    return exists, val


# ---------------------------------------------------------------------------
# Segmented linearization: per-slot sequential semantics, fully vectorized.
# Each op is a transfer function on (exists, value).  On a 2-point domain a
# composition chain collapses to "the last op that *set* the component": the
# last INSERT/DELETE before me decides existence, and from that the
# value-writing events (INSERT into empty, UPDATE/DELETE of occupied) are
# known lane-locally, so the last such event before me decides the value.
# Two prefix cummax sweeps + gathers replace the associative_scan of packed
# transfer matrices the engine used to run (~14x cheaper; DESIGN.md §10.2),
# bit-identically — every quantity is an exact int op.
# ---------------------------------------------------------------------------

def _last_before(marker, code):
    """Per lane: the ``code`` of the last marked lane STRICTLY before it
    (globally), or -1.  ``code`` must be monotone in lane index so a running
    max finds the latest marked lane; callers then check the decoded index
    against their run start to scope the result to the lane's own run."""
    enc = jnp.where(marker, code, -1)
    g = jax.lax.cummax(enc)
    return jnp.concatenate([jnp.full((1,), -1, jnp.int32), g[:-1]])


def _probe_sweep(keys_sorted, setcode, writer, e_init, backend):
    """Dispatch the fused probe pass (existence-before + reader waits over
    sorted lanes) to the Pallas ``scan_probe`` kernel or its jnp oracle
    (DESIGN.md §10.3)."""
    impl, interpret = wc.resolve_backend(backend)
    if impl == "pallas":
        from repro.kernels.scan_probe.ops import scan_probe_op
        return scan_probe_op(keys_sorted, setcode, writer, e_init,
                             interpret=interpret)
    from repro.kernels.scan_probe.ref import scan_probe_ref
    return scan_probe_ref(keys_sorted, setcode, writer, e_init)


def _run_exists(ptr, first, width):
    """(R, width) bool: existence at window start of slots ``first[r] + j``,
    read as whole rows of ``ptr`` rather than one scattered word per slot
    (DESIGN.md §10.3).  ``ptr`` is viewed as rows of ``lanes`` words (128 on
    the 2^k planes the store uses: a free reshape of the tiled plane, so
    XLA emits one native row gather) and each run takes the ``k``
    consecutive rows that cover it.  Rows near either end of the plane are
    clamped; rotating each run's row block left by its offset puts every
    in-range slot in its column.  Columns whose slot lies outside
    ``[0, len(ptr))`` hold garbage."""
    n = ptr.shape[0]
    lanes = math.gcd(n, 128)
    k = min(-(-(width - 1) // lanes) + 1, n // lanes)
    r0 = jnp.clip(jnp.floor_divide(first, lanes), 0, n // lanes - k)
    rows = ptr.reshape(n // lanes, lanes)[r0[:, None] + jnp.arange(k)]
    m = max(k * lanes, width)
    rows = jnp.pad((rows != NULL_PTR).reshape(-1, k * lanes),
                   ((0, 0), (0, m - k * lanes)))
    # an in-range slot sits at offset + j in [0, k*lanes), inside [0, m), so
    # a rotation mod m is exact; done as log2(m) static rolls
    shift = (first - r0 * lanes) % m
    for bit in range((m - 1).bit_length()):
        rows = jnp.where(((shift >> bit) & 1)[:, None] == 1,
                         jnp.roll(rows, -(1 << bit), axis=1), rows)
    return rows[:, :width]


def _scan_probes(ptr, first, pos, valid, p_in, setcode, writer, backend):
    """The fused probe pass of step 5c over B base lanes + B*ns probe lanes,
    moved only by sorts (DESIGN.md §10.3).

    Op ``i`` (slot ``first[i]`` of this shard's plane, queue position
    ``pos[i]``) has base lane ``i`` — its own ``setcode`` transfer and
    ``writer`` lock — and probe lanes ``(i, j)`` over slot ``first[i] + j``
    at the same position, live where ``p_in``.  One sort of the combined
    lanes by (slot, pos) carries each lane's flags packed beside its lane
    index; ``scan_probe`` sweeps them; a second sort on the lane index brings
    the answers back to op order.  Returns ``(e_before, waits)``, both
    ``(B, ns)``: existence just before each probe and the exclusive holders
    ahead of it in its slot's queue (meaningful where ``p_in``)."""
    b, ns = p_in.shape
    bc = b * (1 + ns)
    if bc >= 1 << (31 - _LANE_SHIFT):
        raise ValueError(f"{bc} SCAN lanes overflow the sort payload's "
                         "lane index")
    jj = jnp.arange(ns, dtype=jnp.int32)
    slot_c = jnp.concatenate([first, (first[:, None] + jj).reshape(-1)])
    pos_c = jnp.concatenate([pos, jnp.repeat(pos, ns)])
    live = jnp.concatenate([valid, p_in.reshape(-1)])
    # a probe's e_init is read in op order as its SCAN's slot row; a base
    # lane's own e_before is never read, so it carries none
    e_init = _run_exists(ptr, first, ns).reshape(-1)
    flags = jnp.concatenate([(setcode + 1) | (writer.astype(jnp.int32) << 2),
                             e_init.astype(jnp.int32) << 3])
    payload = flags | (jnp.arange(bc, dtype=jnp.int32) << _LANE_SHIFT)
    # the payload is the third key: a SCAN's base lane and its anchor probe
    # tie on (slot, pos), and the lane index in its high bits keeps them in
    # lane order as a stable sort would, without the index operand XLA adds
    # to a stable sort
    ks, _, pay = jax.lax.sort((jnp.where(live, slot_c, _BIG), pos_c, payload),
                              num_keys=3, is_stable=False)
    e_bc, waits_s = _probe_sweep(ks, (pay & 3) - 1, (pay & 4) != 0,
                                 (pay & 8) != 0, backend)
    # back to op order by the lane index, waits (< b) and e_before riding
    # along in one word
    ans = (waits_s << 1) | e_bc.astype(jnp.int32)
    _, ans = jax.lax.sort((pay >> _LANE_SHIFT, ans), num_keys=1,
                          is_stable=False)              # lanes are unique
    ans = ans[b:].reshape(b, ns)
    return (ans & 1) == 1, ans >> 1


# ---------------------------------------------------------------------------
# Mode-specific I/O metering helpers
# ---------------------------------------------------------------------------

def _backoff_polls(wait_rounds, cap):
    """Deterministic truncated-exponential-backoff poll count while waiting
    ``wait_rounds`` service rounds: probes at 1,2,4,...,2^cap,2^cap,... ."""
    w = wait_rounds.astype(jnp.float32)
    exp_phase = jnp.ceil(jnp.log2(jnp.maximum(w, 1.0) + 1.0))
    exp_phase = jnp.minimum(exp_phase, float(cap))
    linear = jnp.maximum(w - (2.0 ** cap - 1.0), 0.0) / (2.0 ** cap)
    return jnp.where(wait_rounds > 0, exp_phase + jnp.floor(linear), 0.0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg",))
def apply_batch(cfg: EngineConfig, state: StoreState, credits: CreditState,
                batch: OpBatch, valid: jax.Array | None = None,
                owned: jax.Array | None = None,
                slot_base: jax.Array | None = None,
                alive: jax.Array | None = None,
                died: jax.Array | None = None,
                ) -> tuple[StoreState, CreditState, Results, IOMetrics]:
    """Execute one synchronization window. See module docstring.

    Sharded operation (``repro.dist.store``): ``owned`` masks the ops whose
    key's slot lives in this shard, and ``slot_base`` is the shard's first
    global slot (store arrays are indexed by ``keys - slot_base``).  The
    *data plane* (linearization, commits, results, I/O metering) runs on the
    owned subset only; the *credit plane* (contention-aware path decisions
    and AIMD feedback, §4.3) runs on the FULL batch with global keys on every
    shard, so the replicated credit table evolves identically everywhere and
    per-shard I/O sums to the single-device bill exactly.

    Liveness plane (crash recovery, §4.6 — ``repro.recovery``): ``alive`` is
    a ``(n_cns,)`` mask of compute nodes alive through this window and
    ``died`` marks CNs that crashed *at this window* (were alive at window
    start).  Ops from non-alive CNs are dropped at the window boundary —
    exactly as a real crash strands them — while the in-flight pessimistic
    writes of newly-died CNs strand orphaned locks that the next surviving
    waiter detects via the stale-epoch read and breaks with a repair CAS
    (billed into ``IOMetrics.repair_cas`` and the affected queue's
    ``Results.orphan_wait``); locks on slots with no surviving waiter stay
    recorded in ``StoreState.stranded`` until their next locker arrives.
    """
    with jax.named_scope("engine.s1_linearize"):
        b = batch.kinds.shape[0]
        kinds, keys, values, pos, cn = (batch.kinds, batch.keys, batch.values,
                                        batch.pos, batch.cn)
        if valid is None:
            valid = kinds != OpKind.NOP
        else:
            valid = valid & (kinds != OpKind.NOP)
        if cfg.scan_max == 0:
            # no probe capacity compiled in: SCAN lanes must not silently charge
            # point-op I/O and return 0 rows — they are dropped here, and the
            # point-op stores reject them loudly before ever reaching the engine
            valid = valid & (kinds != OpKind.SCAN)
        # present: ops issued into this window (including ones whose CN crashes
        # mid-window — the orphan candidates); valid: ops that complete.
        present = valid
        for name, plane in (("alive", alive), ("died", died)):
            if plane is not None and jnp.shape(plane) != (batch.n_cns,):
                raise ValueError(
                    f"{name} is {jnp.shape(plane)}, expected ({batch.n_cns},): "
                    "one bit per compute node of the batch")
        if alive is not None:
            a = jnp.asarray(alive, bool)
            valid = valid & a[jnp.clip(cn, 0, a.shape[0] - 1)]
        # valid: ops present in the window (credit plane); valid_o: ops whose
        # store state this shard owns (data plane).  Identical when not sharded.
        valid_o = valid if owned is None else valid & owned
        base = jnp.int32(0) if slot_base is None else jnp.asarray(slot_base, jnp.int32)
        is_search = (kinds == OpKind.SEARCH) & valid_o
        is_insert = (kinds == OpKind.INSERT) & valid_o
        is_update = (kinds == OpKind.UPDATE) & valid_o
        is_delete = (kinds == OpKind.DELETE) & valid_o
        upd_full = (kinds == OpKind.UPDATE) & valid

        # ---- 1. linearize: one sorted last-setter sweep serializes every
        # slot's queue (DESIGN.md §10.2) ----
        # The window's ONE (key, pos) plan covers every issued lane: the
        # lanes of other shards form key runs of their own (keys are range
        # partitioned) and a dying CN's lanes sit inside the key runs they
        # strand, so every masked question below — data plane, crash plane,
        # full-window credit feedback — is a mask over this plan.  Data-plane
        # reads mask with v_sorted.
        plan_all = wc.plan_combine(keys, pos, present, backend=cfg.kernel_backend)
        perm = plan_all.perm
        idx = jnp.arange(b, dtype=jnp.int32)
        seg_start = idx - plan_all.rank
        seg_end = seg_start + plan_all.run_length - 1
        ks = kinds[perm]
        vals = values[perm]
        v_sorted = valid_o[perm]
        ins_s = v_sorted & (ks == OpKind.INSERT)
        upd_s = v_sorted & (ks == OpKind.UPDATE)
        del_s = v_sorted & (ks == OpKind.DELETE)
        # incoming (pre-window) state per sorted element's slot (shard-local)
        slot = jnp.clip(keys[perm] - base, 0, cfg.n_slots - 1)
        p = state.ptr[slot]
        e_init = p != NULL_PTR
        v_init = jnp.where(e_init, state.heap[jnp.clip(p, 0)], _NONE)
        # existence BEFORE each op: the last INSERT (sets present) or DELETE
        # (sets absent) strictly before it in its run; else the slot's
        # pre-window bit.  UPDATE/SEARCH never flip existence.
        setcode = jnp.where(ins_s, jnp.int32(1),
                            jnp.where(del_s, jnp.int32(0), jnp.int32(-1)))
        g_excl = _last_before(setcode >= 0, 2 * idx + setcode)
        has = (g_excl >= 0) & ((g_excl >> 1) >= seg_start)
        e_before = jnp.where(has, (g_excl & 1) == 1, e_init)
        # value BEFORE each op: the last value-writing event strictly before it
        # (INSERT into empty / UPDATE of occupied write the payload, successful
        # DELETE writes the tombstone); else the slot's pre-window value.
        w_ev = (ins_s & ~e_before) | ((upd_s | del_s) & e_before)
        val_w = jnp.where(del_s, _NONE, vals)
        gv_excl = _last_before(w_ev, idx)
        hasv = (gv_excl >= 0) & (gv_excl >= seg_start)
        v_before = jnp.where(hasv, val_w[jnp.clip(gv_excl, 0)], v_init)
        # per-op success / search results (sorted order)
        ok_s = jnp.where(ks == OpKind.SEARCH, e_before,
                jnp.where(ks == OpKind.INSERT, ~e_before,
                 jnp.where((ks == OpKind.UPDATE) | (ks == OpKind.DELETE), e_before, False)))
        ok_s = ok_s & v_sorted
        val_s = jnp.where((ks == OpKind.SEARCH) & e_before & v_sorted,
                          v_before, _NONE)
        # state AFTER each op (at run tails: the new slot contents)
        e_fin = jnp.where(setcode >= 0, setcode == 1, e_before)
        v_fin = jnp.where(w_ev, val_w, v_before)
        seg_changed = ok_s & (ks != OpKind.SEARCH)          # any successful IDU
        # per-run reductions via prefix sums gathered at run bounds
        sc_i = seg_changed.astype(jnp.int32)
        cw = jnp.cumsum(sc_i)
        seg_any_write = (cw[seg_end] - (cw - sc_i)[seg_start]) > 0
    # ---- 2. commit final slot states (one out-of-place write per queue) ----
    # Out-of-bounds indices with mode="drop" mask out non-committing lanes.
    with jax.named_scope("engine.s2_commit"):
        # a run commits at its last completing lane, which lanes of a CN
        # that dies this window may follow
        cv = jnp.cumsum(v_sorted.astype(jnp.int32))
        tail = v_sorted & (cv == cv[seg_end]) & seg_any_write
        oob_h, oob_s = jnp.int32(cfg.heap_slots), jnp.int32(cfg.n_slots)
        n_commits = jnp.sum(tail.astype(jnp.int32))
        commit_rank = jnp.cumsum(tail.astype(jnp.int32)) - 1
        loc = (state.heap_top + commit_rank).astype(jnp.int32)
        heap = state.heap.at[jnp.where(tail, loc, oob_h)].set(v_fin, mode="drop")
        new_ptr_val = jnp.where(e_fin, loc, NULL_PTR)
        ptr = state.ptr.at[jnp.where(tail, slot, oob_s)].set(new_ptr_val, mode="drop")
        # version: +1 per successful DELETE (mod 16 — the 4-bit field of Fig 8)
        del_succ = del_s & ok_s
        ds_i = del_succ.astype(jnp.int32)
        cd = jnp.cumsum(ds_i)
        run_del = cd[seg_end] - (cd - ds_i)[seg_start]
        ver = (state.ver.at[jnp.where(tail, slot, oob_s)]
               .add(run_del, mode="drop")) % 16

    # ---- 3. synchronization-mode decision (CIDER credit split, §4.3) ----
    # Decided on the FULL window (upd_full, global keys): every shard's
    # replica of the credit table sees every op and stays bit-identical.
    with jax.named_scope("engine.s3_credit"):
        if cfg.mode == SyncMode.CIDER:
            credits2, pess_full = credit_decide(credits, keys, upd_full,
                                                credits.credit.shape[0])
        elif cfg.mode in (SyncMode.MCS, SyncMode.SPIN):
            credits2, pess_full = credits, upd_full
        else:  # OSYNC
            credits2, pess_full = credits, jnp.zeros_like(upd_full)
        pess = pess_full & valid_o
        opt_upd = is_update & ~pess_full

    # ---- 4. effective writers after local WC (per (key, CN) group) --------
    # Local WC combines same-CN UPDATEs (applied to every baseline, §5.1);
    # combined ops never leave the CN.  CIDER's pessimistic path does NOT
    # pre-filter: every client enqueues in the *global* MCS queue (Fig 7),
    # and global WC subsumes local WC.
    # Every grouped mask (here, step 5b's dead UPDATEs, step 6's full-window
    # optimistic writers) is a subset of `present`, so a lane's (key, CN)
    # group is its key run in plan_all times its CN: one O(B) scatter-max
    # over a (B * n_cns) cell table per mask, no sort (DESIGN.md §10.2).
    with jax.named_scope("engine.s4_local_wc"):
        run_id = wc.key_run_ids(plan_all) if cfg.local_wc else None

        def _local_exec(mask):
            if not cfg.local_wc:
                return mask
            return wc.local_executors_by_run(run_id, cn, pos, mask,
                                             batch.n_cns)
        loc_exec_opt = _local_exec(opt_upd)
        if cfg.mode in (SyncMode.CIDER, SyncMode.OSYNC):
            # CIDER: global WC subsumes local; OSYNC: pess is statically empty
            loc_exec_pess = pess
        else:
            loc_exec_pess = _local_exec(pess)

    # ---- 5. per-mode I/O metering ------------------------------------------
    with jax.named_scope("engine.s5_meter"):
        i64 = jnp.int32
        def s(x):
            return jnp.sum(x.astype(i64))

        n_found_search = jnp.sum(((ks == OpKind.SEARCH) & ok_s).astype(jnp.int32))
        reads = s(valid_o) * cfg.index_read_iops + n_found_search
        mn_bytes = (s(valid_o) * cfg.index_read_bytes + n_found_search * cfg.value_bytes)
        # read-only bytes bill to ONE replica under replication (DESIGN.md §13);
        # every other mn_bytes contribution below is write-class and fans out xR
        ro_bytes = mn_bytes
        writes = jnp.zeros((), i64)
        cas = jnp.zeros((), i64)
        faa = jnp.zeros((), i64)
        cn_msgs = jnp.zeros((), i64)
        retries_total = jnp.zeros((), i64)
        combined_total = jnp.zeros((), i64)
        per_op_retries = jnp.zeros((b,), jnp.int32)
        per_op_combined = jnp.zeros((b,), bool)
        per_op_batch = jnp.ones((b,), jnp.int32)
        per_op_rank = jnp.zeros((b,), jnp.int32)
        per_op_rows = jnp.zeros((b,), jnp.int32)

        # INSERTs: optimistic CAS on the empty pointer in every mode (§4.2.2);
        # concurrent same-key INSERTs: exactly one wins, losers fail once.
        writes += s(is_insert)
        cas += s(is_insert)
        mn_bytes += s(is_insert) * (cfg.value_bytes + cfg.ptr_bytes)

        # DELETEs: pessimistic modes lock (enqueue-CAS + ptr-CAS + epoch-FAA);
        # under OSYNC they CAS-retry in the SAME per-key optimistic queue as
        # concurrent UPDATEs (both CAS the same pointer), metered jointly below.
        n_del = s(is_delete)
        if cfg.mode != SyncMode.OSYNC:
            cas += 2 * n_del
            faa += n_del
            mn_bytes += n_del * (2 * cfg.ptr_bytes + 8)

        # Optimistic CAS path -----------------------------------------------------
        # One joint queue per key: UPDATE executors after local WC, plus (OSYNC
        # only) DELETEs — cross-kind conflicts on a pointer retry against each
        # other, so metering them as two independent queues undercounts retries.
        if cfg.mode == SyncMode.OSYNC:
            opt_queue = loc_exec_opt | is_delete
        else:
            opt_queue = loc_exec_opt
        # opt_queue/loc_exec_pess ⊆ valid_o, so their queue statistics fall out
        # of plan_all's existing sort (stats_from_plan, DESIGN.md §10.2) —
        # bit-identical to per_key_stats, minus a sort per mask.
        plan_o = wc.stats_from_plan(plan_all, opt_queue)
        m_opt_writes = s(loc_exec_opt)                   # DELETEs write no heap
        writes += m_opt_writes
        cas += s(opt_queue) + plan_o.retry_sum
        retries_total += plan_o.retry_sum
        mn_bytes += (m_opt_writes * cfg.value_bytes
                     + (s(opt_queue) + plan_o.retry_sum) * cfg.ptr_bytes)
        combined_total += s(opt_upd) - m_opt_writes      # local-WC combined
        per_op_retries = jnp.where(opt_queue, plan_o.rank_of, per_op_retries)
        per_op_rank = jnp.where(opt_queue, plan_o.rank_of, per_op_rank)
        per_op_combined = per_op_combined | (opt_upd & ~loc_exec_opt)

        # pessimistic subset
        m_pe = s(loc_exec_pess)                          # effective queued writers
        if cfg.mode == SyncMode.SPIN:
            plan_p = wc.stats_from_plan(plan_all, loc_exec_pess)
            polls = _backoff_polls(plan_p.rank_of * 3, cfg.backoff_cap)
            polls_sum = s(jnp.where(loc_exec_pess, polls, 0))
            writes += m_pe
            cas += 3 * m_pe + polls_sum                  # lock + ptr + unlock + polls
            retries_total += polls_sum
            mn_bytes += m_pe * (cfg.value_bytes + 3 * cfg.ptr_bytes) + polls_sum * cfg.ptr_bytes
            per_op_retries = jnp.where(loc_exec_pess, polls, per_op_retries)
            per_op_rank = jnp.where(loc_exec_pess, plan_p.rank_of, per_op_rank)
        elif cfg.mode == SyncMode.MCS:
            writes += m_pe
            cas += 2 * m_pe                              # enqueue masked-CAS + ptr CAS
            faa += m_pe                                  # epoch release
            plan_p = wc.stats_from_plan(plan_all, loc_exec_pess)
            cn_msgs += 2 * s(jnp.where(loc_exec_pess, (plan_p.mult_of > 1), 0))
            mn_bytes += m_pe * (cfg.value_bytes + 2 * cfg.ptr_bytes + 8)
            per_op_batch = jnp.where(loc_exec_pess, 1, per_op_batch)
            per_op_rank = jnp.where(loc_exec_pess, plan_p.rank_of, per_op_rank)
        elif cfg.mode == SyncMode.CIDER:
            # global WC: all queued writers on a key collapse to ONE executed write
            plan_p = wc.stats_from_plan(plan_all, loc_exec_pess)
            is_exec = loc_exec_pess & plan_p.is_tail     # queue tail = executor
            n_q = s(is_exec)                             # number of wait queues
            multi = loc_exec_pess & (plan_p.mult_of > 1)
            n_multi_q = s(is_exec & (plan_p.mult_of > 1))
            cas += m_pe + n_q                            # m enqueues + 1 ptr CAS per queue
            writes += n_q                                # ONE combined write per queue
            faa += m_pe                                  # every client's release FAA
            reads += n_multi_q                           # coordinator tail lookup (step 1)
            cn_msgs += s(jnp.where(is_exec & (plan_p.mult_of > 1),
                                   plan_p.mult_of + 1, 0))
            mn_bytes += (m_pe * cfg.ptr_bytes + n_q * (cfg.value_bytes + cfg.ptr_bytes)
                         + m_pe * 8 + n_multi_q * cfg.lock_bytes)
            ro_bytes += n_multi_q * cfg.lock_bytes       # coordinator tail READ
            combined_total += s(pess) - n_q
            per_op_combined = per_op_combined | (pess & ~is_exec)
            per_op_batch = jnp.where(loc_exec_pess, plan_p.mult_of, per_op_batch)
            per_op_rank = jnp.where(loc_exec_pess, plan_p.rank_of, per_op_rank)

        executed = writes

    # ---- 5b. lease/epoch orphaned-lock repair (crash recovery, §4.6) ------
    # A CN that dies mid-window strands the locks it held or was queued on.
    # The next surviving waiter notices the stale epoch (the holder stopped
    # FAA-ing), waits out the lease, and breaks the lock with a repair CAS.
    # Mode asymmetry — the recovery result: CIDER's combined queue has ONE
    # lock entry per queue and SPIN one lock word per key, while MCS strands
    # the whole chain of dead queue nodes, each repaired by its successor.
    with jax.named_scope("engine.s5b_repair"):
        per_op_orphan = jnp.zeros((b,), jnp.int32)
        repair_total = jnp.zeros((), i64)
        orphan_out = jnp.zeros((), i64)
        if cfg.mode == SyncMode.OSYNC:
            stranded = state.stranded          # lock-free: crashes strand nothing
        else:
            slot_u = jnp.clip(keys - base, 0, cfg.n_slots - 1)
            if died is None:
                add_slot = jnp.zeros((cfg.n_slots,), jnp.int32)
            else:
                d = jnp.asarray(died, bool)
                dead_w = (present & d[jnp.clip(cn, 0, d.shape[0] - 1)]
                          & ((kinds == OpKind.UPDATE) | (kinds == OpKind.DELETE)))
                if owned is not None:
                    dead_w = dead_w & owned
                if cfg.mode == SyncMode.CIDER:
                    # only writers the credit table routes pessimistically held a
                    # lock when they crashed; a dead optimistic writer strands
                    # nothing (its out-of-place CAS simply never lands) — the
                    # lock-free-crash argument FUSEE makes.  DELETEs always lock.
                    cslot = credit_slot(keys, credits.credit.shape[0])
                    dead_node = dead_w & ((credits.credit[cslot] > 0)
                                          | (kinds == OpKind.DELETE))
                else:
                    # only the local executor of each (key, CN) UPDATE group had
                    # left the crashed CN for the memory pool; DELETEs are never
                    # locally combined (they lock independently on the live path
                    # too), so each dead DELETE strands its own node
                    dead_node = (_local_exec(dead_w & (kinds == OpKind.UPDATE))
                                 | (dead_w & (kinds == OpKind.DELETE)))
                # stranded-node count per slot: dead nodes are keyed by slot
                # already, so the per-key multiplicity-at-tail reduction the
                # sort-based stats would compute IS this one scatter-add
                # (MCS strands the whole chain; SPIN/CIDER one word/entry)
                cnt = jnp.zeros((cfg.n_slots,), jnp.int32).at[slot_u].add(
                    dead_node.astype(jnp.int32))
                add_slot = (cnt if cfg.mode == SyncMode.MCS
                            else jnp.minimum(cnt, 1))
            tot = state.stranded + add_slot
            if cfg.mode != SyncMode.MCS:
                tot = jnp.minimum(tot, 1)      # one lock word/entry per key
            # any surviving locker on the slot repairs all its stranded nodes
            # this window; untouched slots stay stranded for their next locker
            surv = loc_exec_pess | is_delete
            surv_slot = jnp.zeros((cfg.n_slots,), bool).at[
                jnp.where(surv, slot_u, cfg.n_slots)].set(True, mode="drop")
            repaired = surv_slot & (tot > 0)
            n_repair = jnp.sum(jnp.where(repaired, tot, 0).astype(i64))
            stranded = jnp.where(repaired, 0, tot)
            orphan_out = jnp.sum((stranded > 0).astype(i64))
            per_op_orphan = jnp.where(surv & repaired[slot_u], tot[slot_u], 0)
            # bill: one stale-epoch READ of the lock entry + one break CAS per
            # stranded node, charged to the blocked queue
            reads += n_repair
            cas += n_repair
            repair_total += n_repair
            mn_bytes += n_repair * (cfg.lock_bytes + 8)
            ro_bytes += n_repair * cfg.lock_bytes        # stale-epoch detection READ
            if cfg.mode == SyncMode.SPIN:
                # spinners keep re-CASing the orphaned word until the lease
                # expires — MN verbs MCS/CIDER waiters never issue (they wait
                # CN-locally); these polls ARE recovery overhead, so they are
                # folded into repair_cas as well
                pollc = _backoff_polls(jnp.asarray(cfg.lease_poll_rounds, jnp.int32),
                                       cfg.backoff_cap)
                lease_polls = per_op_orphan * pollc
                polls_lease = s(lease_polls)
                cas += polls_lease
                retries_total += polls_lease
                repair_total += polls_lease
                mn_bytes += polls_lease * cfg.ptr_bytes
                per_op_retries = per_op_retries + lease_polls

    # ---- 5c. SCAN reader probes (range reads, DESIGN.md §9, §10.3) --------
    # A SCAN(key, count) expands into `count` reader probes over the
    # contiguous leaf-slot run [key, key+count), each joining its slot's wait
    # queue *as a reader* at the scanning op's batch position.  ONE sort of
    # the combined writer+probe lanes feeds the fused scan_probe pass, which
    # yields both the existence each probe observes at its serialization
    # position AND its wait rank behind exclusive lock holders; the lanes
    # move only by sorts (`_scan_probes`, DESIGN.md §10.3).  Probes
    # outside [slot_base, slot_base + n_slots) belong to another shard (or
    # fall off the keyspace end): each shard counts its own sub-run and the
    # dist psum reassembles the rows.
    with jax.named_scope("engine.s5c_scan"):
        if cfg.scan_max > 0:
            ns = cfg.scan_max
            is_scan = (kinds == OpKind.SCAN) & valid
            count = jnp.clip(values, 0, ns)               # count rides `values`
            jj = jnp.arange(ns, dtype=jnp.int32)
            pk = keys[:, None] + jj[None, :]              # (B, ns) global slots
            p_loc = pk - base
            p_in = (is_scan[:, None] & (jj[None, :] < count[:, None])
                    & (p_loc >= 0) & (p_loc < cfg.n_slots))
            keys_p = pk.reshape(b * ns)
            pv = p_in.reshape(b * ns)
            # existence transfers: only this window's INSERT/DELETE lanes set the
            # bit; probes (readers) and UPDATEs are identity — so the probe pass
            # needs no value plane at all
            setcode = jnp.where(is_insert, jnp.int32(1),
                                jnp.where(is_delete, jnp.int32(0), jnp.int32(-1)))
            lockw = loc_exec_pess | is_delete             # exclusive lock holders
            e_probe, waits_p = _scan_probes(
                state.ptr, keys - base, pos, valid_o, p_in, setcode, lockw,
                cfg.kernel_backend)
            hit = e_probe & p_in
            per_op_rows = jnp.sum(hit.astype(jnp.int32), axis=1)
            n_probes = s(pv)
            n_rows = s(hit)
            # base bill: one leaf-entry READ per probed slot + one value READ per
            # row found (every mode traverses the same run)
            reads += n_probes + n_rows
            mn_bytes += n_probes * cfg.ptr_bytes + n_rows * cfg.value_bytes
            ro_bytes += n_probes * cfg.ptr_bytes + n_rows * cfg.value_bytes
            if cfg.mode == SyncMode.OSYNC:
                # optimistic traversal must re-read each leaf's version to
                # validate against concurrent pointer swaps (§2.2's cost, paid
                # per leaf whether or not anyone wrote)
                reads += n_probes
                mn_bytes += n_probes * cfg.ptr_bytes
                ro_bytes += n_probes * cfg.ptr_bytes
            elif cfg.mode == SyncMode.SPIN:
                # a CAS spinlock has no shared mode: lock + unlock CAS per leaf
                cas += 2 * n_probes
                mn_bytes += 2 * n_probes * cfg.ptr_bytes
            elif cfg.mode == SyncMode.MCS:
                # lock-shared traversal: shared-mode enqueue CAS + release FAA
                # per leaf (the epoch heartbeat plane tracks exclusive holders
                # only — the reader FAA is billed, not recorded)
                cas += n_probes
                faa += n_probes
                mn_bytes += n_probes * (cfg.ptr_bytes + 8)
            else:  # CIDER: consult the CN-local credit table (free) — cold
                   # leaves are traversed lock-free like OSYNC *without* the
                   # re-read (the table certifies no concurrent pessimistic
                   # writer), hot leaves join the queue in shared mode
                cslot_p = credit_slot(keys_p, credits.credit.shape[0])
                hot_p = pv & (credits.credit[cslot_p] > 0)
                n_hot = s(hot_p)
                cas += n_hot
                faa += n_hot
                mn_bytes += n_hot * (cfg.lock_bytes + 8)
            if cfg.mode != SyncMode.OSYNC:
                # wait rank of the anchor-leaf reader behind exclusive holders
                # (queue order == batch position now includes reader ranks) —
                # already computed by the fused pass above
                per_op_rank = jnp.where(p_in[:, 0], waits_p[:, 0], per_op_rank)

    # ---- 5d. SNAPSHOT replica fan-out (FUSEE; DESIGN.md §13) --------------
    # Client-centric replication: the client issues every write-class verb
    # to all R replica MNs itself (no MN CPU forwards anything), so the
    # aggregate bill scales exactly xR on WRITE/CAS/FAA — including retries
    # and repair break-CASes, which are failed/extra CASes on every replica's
    # word — while reads go to one replica.  Static branch: R=1 compiles to
    # the byte-identical pre-replication program (tests/test_replication.py).
    # Per-op Results are logical-op observables and never scale.
    with jax.named_scope("engine.s5d_replicas"):
        if cfg.n_replicas > 1:
            rep = cfg.n_replicas
            writes = writes * rep
            cas = cas * rep
            faa = faa * rep
            retries_total = retries_total * rep
            repair_total = repair_total * rep
            mn_bytes = ro_bytes + rep * (mn_bytes - ro_bytes)

    # ---- 6. credit feedback (§4.3, Algorithm 1 lines 13-22) ---------------
    # Like the decision, feedback runs on the FULL window so replicated
    # credit tables stay identical across shards.  The full-window masks are
    # subsets of `present`, so plan_all and its key runs answer them; on one
    # chip they ARE step 5's owned masks and step 5's values are reused.
    with jax.named_scope("engine.s6_feedback"):
        if cfg.mode == SyncMode.CIDER:
            if owned is None:
                pess_fb, batch_fb = loc_exec_pess, per_op_batch
                opt_fb, retry_fb = loc_exec_opt | is_insert, per_op_retries
            else:
                loc_opt_full = _local_exec(upd_full & ~pess_full)
                plan_p_fb = wc.stats_from_plan(plan_all, pess_full)
                plan_o_fb = wc.stats_from_plan(plan_all, loc_opt_full)
                pess_fb = pess_full
                batch_fb = jnp.where(pess_full, plan_p_fb.mult_of, 1)
                opt_fb = loc_opt_full | ((kinds == OpKind.INSERT) & valid)
                retry_fb = jnp.where(loc_opt_full, plan_o_fb.rank_of, 0)
            credits3 = credit_feedback(
                credits2, keys, credits.credit.shape[0],
                pess=pess_fb, wc_batch=batch_fb,
                opt=opt_fb, n_retry=retry_fb,
                initial_credit=cfg.initial_credit,
                hotness_threshold=cfg.hotness_threshold,
                aimd_factor=cfg.aimd_factor)
        else:
            credits3 = credits2

    # ---- 7. epoch FAA bookkeeping (fault-tolerance heartbeat, §4.6) -------
    with jax.named_scope("engine.s7_epoch"):
        if cfg.mode in (SyncMode.MCS, SyncMode.CIDER):
            rel = loc_exec_pess | is_delete
            epoch = state.epoch.at[jnp.where(rel, keys - base, cfg.n_slots)].add(
                rel.astype(jnp.int32), mode="drop")
        else:
            epoch = state.epoch

    with jax.named_scope("engine.s8_assemble"):
        new_state = StoreState(ptr=ptr, meta=pack_meta(ver, stranded),
                               epoch=epoch, heap=heap,
                               heap_top=state.heap_top + n_commits)
        # unsort results
        ok = jnp.zeros((b,), bool).at[perm].set(ok_s)
        # SCAN succeeds when it found any row; per-shard partial counts OR
        # together under the dist psum exactly as the totals add
        ok = ok | (per_op_rows > 0)
        value = jnp.full((b,), _NONE, jnp.int32).at[perm].set(val_s)
        res = Results(ok=ok, value=value, pessimistic=pess,
                      combined=per_op_combined, wc_batch=per_op_batch,
                      retries=per_op_retries, rank=per_op_rank,
                      orphan_wait=per_op_orphan, rows=per_op_rows)
        io = IOMetrics(reads=reads, writes=writes, cas=cas, faa=faa,
                       cn_msgs=cn_msgs, mn_bytes=mn_bytes, retries=retries_total,
                       combined=combined_total, executed=executed,
                       repair_cas=repair_total, orphan_windows=orphan_out)
    return new_state, credits3, res, io

"""Core types shared by the CIDER dataplane engine and the protocol simulator.

The paper's op vocabulary (§2.2): SEARCH / INSERT / UPDATE / DELETE over a
store of data pointers; IDU = {INSERT, UPDATE, DELETE}.  SCAN (key + count)
extends it with the range read YCSB E is built from (DESIGN.md §9) — a
reader over a contiguous leaf-slot run, representable only on a
range-capable index.  One-sided RDMA verbs
(§2.1): READ / WRITE / CAS / FAA / masked-CAS (get-and-set).  We meter each
verb class separately because the paper's bottleneck argument is on
memory-node (MN) NIC *IOPS*, with client-to-client (CN<->CN) messages
explicitly off the MN NIC (the whole point of ShiftLock's handoff design).
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "OpKind", "Verb", "SyncMode", "IOMetrics", "LatencyStats", "EngineConfig",
    "OpBatch", "NULL_PTR", "UnsupportedOpError", "io_zeros", "io_add",
    "per_replica_bill",
]

# A null data pointer (empty slot). Pointers are int32 heap indices >= 0.
NULL_PTR = jnp.int32(-1)


class UnsupportedOpError(NotImplementedError):
    """An op kind the target index structure cannot serve *by design* —
    e.g. SCAN on a hash index, whose buckets scatter adjacent keys so a key
    range has no contiguous slot run (DESIGN.md §9).

    Every store raises this one type for capability rejections (enforced by
    the bill lint, ``repro.analysis.bill_lint``) so callers can catch
    "wrong index for this workload" distinctly from genuine bugs; it
    subclasses ``NotImplementedError`` for backward compatibility.
    """


class OpKind(enum.IntEnum):
    SEARCH = 0
    INSERT = 1
    UPDATE = 2
    DELETE = 3
    NOP = 4      # padding
    SCAN = 5     # range read: (key, count) — count rides OpBatch.values.
                 # Resolvable only by a range-capable (radix) index: the key
                 # run [key, key+count) must be a contiguous leaf-slot run
                 # (stores/smart_art.py); hash indexes reject it.  YCSB E's
                 # op; rows found come back in Results.rows.


class Verb(enum.IntEnum):
    """RDMA verb classes, for I/O metering."""
    READ = 0
    WRITE = 1
    CAS = 2          # includes masked-CAS (get-and-set) — same NIC cost
    FAA = 3
    CN_MSG = 4       # client<->client message: does NOT consume MN NIC IOPS


class SyncMode(enum.IntEnum):
    """The four synchronization schemes compared in the paper (§5.1)."""
    OSYNC = 0     # optimistic: out-of-place write + CAS-retry     (RACE/SMART default)
    SPIN = 1      # CAS spinlock w/ truncated exponential backoff  (SMART-framework lock)
    MCS = 2       # ShiftLock distributed MCS lock, no combining   (FAST'25)
    CIDER = 3     # MCS + global write-combining + contention-aware sync (this paper)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IOMetrics:
    """Per-verb I/O counters. ``mn_iops``/``mn_bytes`` are the bottleneck
    quantities (memory-pool NIC); ``cn_msgs`` ride client NICs."""
    reads: jax.Array      # () i64
    writes: jax.Array
    cas: jax.Array
    faa: jax.Array
    cn_msgs: jax.Array
    mn_bytes: jax.Array   # bytes moved through MN NICs
    retries: jax.Array    # redundant (failed) CAS attempts — paper Fig 1 metric
    combined: jax.Array   # ops whose write was combined away (WC rate numerator)
    executed: jax.Array   # ops that reached the store
    repair_cas: jax.Array     # orphan-repair verbs (§4.6): epoch-stale lock
                              # break CAS + (SPIN) lease-expiry polls — the
                              # recovery I/O bill; also folded into reads/cas
    orphan_windows: jax.Array  # slot-windows spent with a stranded (orphaned)
                               # lock still outstanding at window end

    @property
    def mn_iops(self) -> jax.Array:
        return self.reads + self.writes + self.cas + self.faa

    def as_dict(self) -> dict[str, Any]:
        d = {f.name: np.asarray(getattr(self, f.name)).item()
             for f in dataclasses.fields(self)}
        d["mn_iops"] = d["reads"] + d["writes"] + d["cas"] + d["faa"]
        return d


@dataclasses.dataclass(frozen=True)
class LatencyStats:
    """Modeled per-op latency percentiles (microseconds) — the paper's second
    evaluation axis next to throughput (Figs 11-12, 16-19).  Produced by
    ``repro.core.runner.modeled_latency`` / ``latency_stats`` from each op's
    exact verb bill and wait-queue rank under the ``SimParams`` cost model."""
    p50_us: float
    p99_us: float
    mean_us: float
    max_us: float
    n_ops: int

    def as_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)


def io_zeros() -> IOMetrics:
    z = jnp.zeros((), jnp.int64) if jax.config.jax_enable_x64 else jnp.zeros((), jnp.int32)
    return IOMetrics(*([z] * len(dataclasses.fields(IOMetrics))))


def io_add(a: IOMetrics, b: IOMetrics) -> IOMetrics:
    return jax.tree.map(lambda x, y: x + y, a, b)


def per_replica_bill(io_one: IOMetrics, io_r: IOMetrics,
                     n_replicas: int) -> list[dict[str, int]]:
    """Decompose a replicated bill into per-replica-MN bills (host-side).

    ``io_one`` is the ``n_replicas=1`` bill of a run and ``io_r`` the
    ``n_replicas=R`` bill of the *same* run.  Under SNAPSHOT client-centric
    replication (DESIGN.md §13) the engine fans every write-class verb
    (WRITE/CAS/FAA, their retries, and §4.6 repair break-CASes) out to all R
    replica MNs while reads — index READs, coordinator lock reads, repair
    stale-epoch detection reads, SCAN probes — go to the primary only, so
    the totals determine the split exactly:

    * every replica carries the R=1 write-class verbs and write bytes
      (``wr = (io_r.mn_bytes - io_one.mn_bytes) / (R - 1)``),
    * the primary (replica 0) additionally carries all reads, read bytes,
      and the observable-only counters (cn_msgs/combined/executed/
      orphan_windows), which are logical-op properties, not fan-out.

    Raises ``ValueError`` if the two bills are not consistent with the ×R
    contract — this is the conservation law the property tests enforce:
    summing the returned dicts field-by-field reproduces ``io_r``.
    """
    one, tot = io_one.as_dict(), io_r.as_dict()
    r = int(n_replicas)
    if r < 1:
        raise ValueError(f"n_replicas must be >= 1, got {r}")
    if r == 1:
        if one != tot:
            raise ValueError("R=1 bills differ; same run required")
        return [{k: v for k, v in one.items() if k != "mn_iops"}]
    for f in ("writes", "cas", "faa", "retries", "repair_cas"):
        if tot[f] != r * one[f]:
            raise ValueError(
                f"replicated bill violates x{r} write fan-out on '{f}': "
                f"{tot[f]} != {r} * {one[f]}")
    for f in ("reads", "cn_msgs", "combined", "executed", "orphan_windows"):
        if tot[f] != one[f]:
            raise ValueError(
                f"replicated bill changes read/observable field '{f}': "
                f"{tot[f]} != {one[f]} (reads bill to one replica)")
    wr_bytes, rem = divmod(tot["mn_bytes"] - one["mn_bytes"], r - 1)
    if rem or wr_bytes < 0 or wr_bytes > one["mn_bytes"]:
        raise ValueError(
            f"replicated byte bill inconsistent: mn_bytes {one['mn_bytes']} "
            f"-> {tot['mn_bytes']} is not ro + {r}*wr")
    secondary = {
        "reads": 0, "writes": one["writes"], "cas": one["cas"],
        "faa": one["faa"], "cn_msgs": 0, "mn_bytes": wr_bytes,
        "retries": one["retries"], "combined": 0, "executed": 0,
        "repair_cas": one["repair_cas"], "orphan_windows": 0,
    }
    primary = {k: v for k, v in one.items() if k != "mn_iops"}
    primary["cn_msgs"] = tot["cn_msgs"]
    primary["combined"] = tot["combined"]
    primary["executed"] = tot["executed"]
    primary["orphan_windows"] = tot["orphan_windows"]
    return [primary] + [dict(secondary) for _ in range(r - 1)]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class OpBatch:
    """A device batch of concurrent KV ops (one synchronization window).

    ``keys`` here are *slot indices* into the pointer store — index structures
    (hash / radix tree) resolve string keys to slots first and account their
    own I/O.  ``pos`` is the canonical serialization priority inside the batch
    (queue order == batch position, so all four modes agree on the final
    state: last-writer-wins by ``pos``).  ``cn`` is the compute-node id of the
    issuing client (local-WC combines within a CN; global WC across CNs), in
    ``[0, n_cns)``; ``n_cns`` is static, since it sizes local WC's cell table
    and the liveness planes.
    """
    kinds: jax.Array    # (B,) int32 OpKind
    keys: jax.Array     # (B,) int32 slot index
    values: jax.Array   # (B,) int32 value payload id
    pos: jax.Array      # (B,) int32 serialization priority (0..B-1)
    cn: jax.Array       # (B,) int32 compute-node id
    n_cns: int = dataclasses.field(metadata={"static": True})

    @staticmethod
    def make(kinds, keys, values, n_cns: int = 1, lanes_per_cn: int | None = None):
        kinds = jnp.asarray(kinds, jnp.int32)
        keys = jnp.asarray(keys, jnp.int32)
        values = jnp.asarray(values, jnp.int32)
        b = kinds.shape[0]
        pos = jnp.arange(b, dtype=jnp.int32)
        n_cns = max(n_cns, 1)
        if lanes_per_cn is None:
            lanes_per_cn = max(b // n_cns, 1)
        cn = (pos // lanes_per_cn) % n_cns
        return OpBatch(kinds=kinds, keys=keys, values=values, pos=pos, cn=cn,
                       n_cns=n_cns)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static configuration for the dataplane engine."""
    n_slots: int                      # pointer-array length
    heap_slots: int                   # out-of-place heap capacity (values)
    mode: SyncMode = SyncMode.CIDER
    local_wc: bool = True             # local write combining (baselines get it too, §5.1)
    value_bytes: int = 8              # payload size (paper: 8B values)
    ptr_bytes: int = 8                # data pointer (60-bit ptr + 4-bit version)
    lock_bytes: int = 16              # lock entry: 60b tail + 64b epoch + 4b version
    index_read_iops: int = 1          # per-op index I/O (pointer array: 1 READ)
    index_read_bytes: int = 8
    # SNAPSHOT client-centric replication degree (FUSEE; DESIGN.md §13).
    # Every write-class verb (WRITE/CAS/FAA, retries, §4.6 repair break-CAS)
    # fans out to all R replica MNs from the client; reads bill to one
    # replica.  R=1 compiles to the byte-identical pre-replication program
    # (the scaling block is a static Python branch), so the replica axis is
    # provably zero-cost when off (tests/test_replication.py).
    n_replicas: int = 1
    # CIDER contention-aware parameters (§4.3, Fig 15)
    initial_credit: int = 36
    hotness_threshold: int = 2
    aimd_factor: int = 2
    # SPIN backoff cap (truncated exponential), in poll-interval rounds
    backoff_cap: int = 6
    # Crash recovery (§4.6): how many poll-interval rounds a SPIN waiter
    # spends re-CASing an orphaned lock before the lease expires and the
    # repair CAS succeeds (MCS/CIDER waiters wait locally — ShiftLock's
    # design point — so only SPIN pays MN verbs for the lease).
    lease_poll_rounds: int = 16
    # SCAN support (DESIGN.md §9): static per-op leaf-run bound.  0 disables
    # the reader-probe pass entirely — the engine then compiles to exactly
    # the pre-SCAN program, so point-op-only stores pay nothing.  A SCAN's
    # count is clipped to this bound by the stores/workloads; the engine
    # expands each SCAN into `scan_max` reader probes that join the per-key
    # wait queues at the scanning op's batch position.
    scan_max: int = 0
    # Kernel dispatch seam (DESIGN.md §10): which implementation of the
    # sorted-run sweeps (wc_combine, scan_probe) the engine's consumers use.
    # "auto" = the compiled Pallas kernels on TPU, the jnp reference
    # elsewhere; "pallas" = force the kernels (interpret mode off-TPU — CI
    # exercises the exact kernel dataflow); "jnp" = force the reference.
    # All three are bit-identical by contract and by test (tests/
    # test_backend.py).  The config is hashable/static, so the choice flows
    # through jit, the fused runner scan, and dist's per-shard config cache.
    kernel_backend: str = "auto"

"""SCAN end-to-end contracts (DESIGN.md §9).

The range op must (a) observe exactly the per-slot state at its batch
position — the serialization contract now includes reader ranks — (b) be
bit-identical across all four SyncModes and between the single-device and
4-way sharded runners (runs split at partition boundaries, rows psum-
reassembled), and (c) bill the documented per-mode traversal verbs.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.core import runner
from repro.core.credits import credit_init
from repro.core.engine import apply_batch, populate, store_init, store_view
from repro.core.oracle import OracleStore
from repro.core.types import (EngineConfig, IOMetrics, OpBatch, OpKind,
                              SyncMode, UnsupportedOpError)
from repro.dist import store as dstore
from repro.launch.mesh import make_local_mesh
from repro.stores import PointerArray, RaceHash, SmartART

MODES = [SyncMode.OSYNC, SyncMode.SPIN, SyncMode.MCS, SyncMode.CIDER]
W, B, N_SLOTS, N_CNS, SCAN_MAX = 4, 128, 64, 8, 8


def _scan_ops(seed=0):
    """(W, B) mixed stream: ~30% SCANs (length in ``values``), a strided
    cross-CN hot key so CIDER goes pessimistic, and scans crossing the
    4-way shard boundaries (slots 16/32/48 of 64)."""
    rng = np.random.default_rng(seed)
    kinds = rng.choice(
        [OpKind.SEARCH, OpKind.INSERT, OpKind.UPDATE, OpKind.DELETE,
         OpKind.SCAN],
        size=(W, B), p=(.15, .1, .3, .15, .3)).astype(np.int32)
    keys = rng.integers(0, N_SLOTS, (W, B)).astype(np.int32)
    values = rng.integers(0, 10_000, (W, B)).astype(np.int32)
    scan = kinds == OpKind.SCAN
    values[scan] = rng.integers(1, SCAN_MAX + 1, scan.sum())
    keys[:, ::4] = 5
    kinds[:, ::4] = OpKind.UPDATE
    # pin a few boundary-crossing scans per window
    keys[:, 1] = 14
    kinds[:, 1] = OpKind.SCAN
    values[:, 1] = SCAN_MAX
    return kinds, keys, values


def _init(cfg):
    rng = np.random.default_rng(1)
    pop_keys = rng.choice(N_SLOTS, size=N_SLOTS // 2, replace=False)
    pop_vals = rng.integers(0, 10_000, pop_keys.shape[0])
    return (populate(cfg, store_init(cfg), pop_keys, pop_vals),
            credit_init(256), pop_keys, pop_vals)


@pytest.mark.parametrize("mode", MODES)
def test_scan_matches_oracle_per_window(mode):
    """Rows/ok against the sequential oracle: a SCAN at batch position p
    sees writes at positions < p and not those at positions > p."""
    kinds, keys, values = _scan_ops()
    cfg = EngineConfig(n_slots=N_SLOTS, heap_slots=2048, mode=mode,
                       scan_max=SCAN_MAX)
    st, cr, pop_keys, pop_vals = _init(cfg)
    oracle = OracleStore()
    oracle.populate(pop_keys, pop_vals)
    for w in range(W):
        batch = OpBatch.make(kinds[w], keys[w], values[w], n_cns=N_CNS)
        st, cr, res, io = apply_batch(cfg, st, cr, batch)
        ok_o, val_o = oracle.apply(kinds[w], keys[w], values[w],
                                   scan_max=SCAN_MAX)
        np.testing.assert_array_equal(np.asarray(res.ok), ok_o,
                                      err_msg=f"window {w} ok")
        np.testing.assert_array_equal(np.asarray(res.value), val_o,
                                      err_msg=f"window {w} value")
        np.testing.assert_array_equal(np.asarray(res.rows), oracle.rows,
                                      err_msg=f"window {w} rows")


def test_scan_results_and_state_identical_across_modes():
    """The serialization contract: rows/ok/value and the final store view
    are a function of (batch, pre-state) only — never of the SyncMode."""
    kinds, keys, values = _scan_ops()
    outs = {}
    for mode in MODES:
        cfg = EngineConfig(n_slots=N_SLOTS, heap_slots=2048, mode=mode,
                           scan_max=SCAN_MAX)
        st, cr, _, _ = _init(cfg)
        stream = runner.make_stream(kinds, keys, values, n_cns=N_CNS)
        st, cr, res, io = runner.run_windows(cfg, st, cr, stream)
        outs[mode] = (np.asarray(res.rows), np.asarray(res.ok),
                      np.asarray(res.value), store_view(st))
    ref = outs[SyncMode.OSYNC]
    assert ref[0].sum() > 0, "stream produced no scan rows — test is vacuous"
    for mode in MODES[1:]:
        rows, ok, val, view = outs[mode]
        np.testing.assert_array_equal(rows, ref[0], err_msg=f"{mode} rows")
        np.testing.assert_array_equal(ok, ref[1], err_msg=f"{mode} ok")
        np.testing.assert_array_equal(val, ref[2], err_msg=f"{mode} value")
        for a, b in zip(view, ref[3]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mode", MODES)
def test_scan_sharded_bit_equal(mode):
    """Cross-shard scans: runs split at the partition boundaries, each shard
    counts its sub-run, and the psum-reassembled Results + the verb bill are
    bit-equal to the single-device run (the dist.store contract)."""
    mesh = make_local_mesh(data=4)
    kinds, keys, values = _scan_ops()
    cfg = EngineConfig(n_slots=N_SLOTS, heap_slots=2048, mode=mode,
                       scan_max=SCAN_MAX)
    st, cr, pop_keys, pop_vals = _init(cfg)
    stream = runner.make_stream(kinds, keys, values, n_cns=N_CNS)
    st1, cr1, res1, io1 = runner.run_windows(cfg, st, cr, stream,
                                             io_per_window=True)
    sst = dstore.sharded_populate(
        cfg, 4, dstore.sharded_store_init(cfg, 4), pop_keys, pop_vals)
    st2, cr2, res2, io2 = dstore.run_windows_sharded(
        cfg, mesh, sst, credit_init(256), stream, io_per_window=True)
    # the pinned lane scans [14, 22) across the slot-15/16 shard boundary
    assert int(np.asarray(res1.rows)[:, 1].sum()) > 0
    for f in dataclasses.fields(res1):
        np.testing.assert_array_equal(
            np.asarray(getattr(res1, f.name)), np.asarray(getattr(res2, f.name)),
            err_msg=f"Results.{f.name}")
    for f in dataclasses.fields(IOMetrics):
        np.testing.assert_array_equal(
            np.asarray(getattr(io1, f.name)), np.asarray(getattr(io2, f.name)),
            err_msg=f"IOMetrics.{f.name}")
    ex1, v1 = store_view(st1)
    ex2, v2 = dstore.sharded_store_view(cfg, 4, st2)
    np.testing.assert_array_equal(np.asarray(ex1), np.asarray(ex2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(cr1.credit), np.asarray(cr2.credit))


def test_scan_verb_bill_per_mode():
    """The documented per-mode traversal bill on a pure-scan window over a
    fully-populated store: probes = sum of counts, rows = probes, and the
    mode deltas are exactly version-re-reads (OSYNC), 2 lock CAS per leaf
    (SPIN), shared CAS+FAA per leaf (MCS), nothing for cold CIDER."""
    n = 32
    counts = np.array([4, 8, 2, 1], np.int32)
    starts = np.array([0, 8, 20, 28], np.int32)
    probes = int(counts.sum())
    bills = {}
    for mode in MODES:
        cfg = EngineConfig(n_slots=n, heap_slots=128, mode=mode, scan_max=8)
        st = populate(cfg, store_init(cfg), np.arange(n), np.arange(n))
        batch = OpBatch.make(np.full(4, OpKind.SCAN, np.int32), starts, counts,
                             n_cns=2)
        _, _, res, io = apply_batch(cfg, st, credit_init(64), batch)
        np.testing.assert_array_equal(np.asarray(res.rows), counts)
        bills[mode] = io.as_dict()
    base_reads = 4 * 1 + probes + probes          # index + leaf + value reads
    assert bills[SyncMode.CIDER]["reads"] == base_reads
    assert bills[SyncMode.CIDER]["cas"] == 0      # cold credit table: lock-free
    assert bills[SyncMode.CIDER]["faa"] == 0
    assert bills[SyncMode.OSYNC]["reads"] == base_reads + probes
    assert bills[SyncMode.SPIN]["cas"] == 2 * probes
    assert bills[SyncMode.MCS]["cas"] == probes
    assert bills[SyncMode.MCS]["faa"] == probes


def test_cider_hot_leaf_scans_pay_shared_queue_verbs():
    """Once credits mark a key hot, a scan crossing it pays the shared-mode
    CAS+FAA — and only for the hot leaves, not the whole run."""
    n = 32
    cfg = EngineConfig(n_slots=n, heap_slots=4096, mode=SyncMode.CIDER,
                       scan_max=8)
    st = populate(cfg, store_init(cfg), np.arange(n), np.arange(n))
    cr = credit_init(64)
    # warm the credit table: every CN hammers key 5 for a few windows
    hot = OpBatch.make(np.full(64, OpKind.UPDATE, np.int32),
                       np.full(64, 5, np.int32),
                       np.arange(64, dtype=np.int32), n_cns=8)
    for _ in range(4):
        st, cr, _, _ = apply_batch(cfg, st, cr, hot)
    assert int(np.asarray(cr.credit).sum()) > 0
    scan = OpBatch.make(np.full(1, OpKind.SCAN, np.int32),
                        np.array([2], np.int32), np.array([8], np.int32))
    _, _, res, io = apply_batch(cfg, st, cr, scan)
    assert int(np.asarray(res.rows)[0]) == 8
    d = io.as_dict()
    # exactly the credit-hot leaves of [2, 10) pay CAS+FAA; at least key 5
    # is hot, and never the whole run (6+ cold leaves stay lock-free)
    assert 1 <= d["cas"] <= 7 and d["cas"] == d["faa"]
    cold = OpBatch.make(np.full(1, OpKind.SCAN, np.int32),
                        np.array([20], np.int32), np.array([8], np.int32))
    _, _, _, io2 = apply_batch(cfg, st, cr, cold)
    assert io2.as_dict()["cas"] == 0              # cold run: lock-free


def test_scan_truncates_at_keyspace_end():
    cfg = EngineConfig(n_slots=16, heap_slots=64, mode=SyncMode.CIDER,
                       scan_max=8)
    st = populate(cfg, store_init(cfg), np.arange(16), np.arange(16))
    batch = OpBatch.make(np.full(1, OpKind.SCAN, np.int32),
                         np.array([14], np.int32), np.array([8], np.int32))
    _, _, res, _ = apply_batch(cfg, st, credit_init(64), batch)
    assert int(np.asarray(res.rows)[0]) == 2      # slots 14, 15 only


def test_scan_count_clipped_to_scan_max():
    cfg = EngineConfig(n_slots=64, heap_slots=256, mode=SyncMode.CIDER,
                       scan_max=4)
    st = populate(cfg, store_init(cfg), np.arange(64), np.arange(64))
    batch = OpBatch.make(np.full(1, OpKind.SCAN, np.int32),
                         np.array([0], np.int32), np.array([100], np.int32))
    _, _, res, _ = apply_batch(cfg, st, credit_init(64), batch)
    assert int(np.asarray(res.rows)[0]) == 4


def test_scan_reader_rank_counts_writers_ahead():
    """Queue order == batch position now includes reader ranks: a scan's
    anchor-leaf reader sits behind exactly the pessimistic writers with
    smaller positions on that slot."""
    cfg = EngineConfig(n_slots=16, heap_slots=64, mode=SyncMode.MCS,
                       scan_max=4)
    st = populate(cfg, store_init(cfg), np.arange(16), np.arange(16))
    kinds = np.array([OpKind.UPDATE, OpKind.UPDATE, OpKind.SCAN,
                      OpKind.UPDATE], np.int32)
    keys = np.array([3, 3, 3, 3], np.int32)
    values = np.array([7, 8, 2, 9], np.int32)
    batch = OpBatch.make(kinds, keys, values, n_cns=4)
    _, _, res, _ = apply_batch(cfg, st, credit_init(64), batch)
    assert int(np.asarray(res.rank)[2]) == 2      # behind the two pos<2 writers
    assert int(np.asarray(res.rows)[2]) == 2      # [3, 5): both present


def test_point_stores_reject_scan():
    kinds = np.array([OpKind.SCAN], np.int32)
    keys = np.array([0], np.int32)
    vals = np.array([4], np.int32)
    with pytest.raises(UnsupportedOpError, match="(?i)range"):
        PointerArray.create(64).apply(OpBatch.make(kinds, keys, vals))
    with pytest.raises(UnsupportedOpError, match="radix"):
        PointerArray.create(64).apply_stream(
            runner.make_stream(kinds[None], keys[None], vals[None]))
    with pytest.raises(UnsupportedOpError, match="hash"):
        RaceHash.create(64).apply(kinds, keys, vals)
    # the shared capability-rejection type stays catchable as the old ad-hoc
    # NotImplementedError for existing callers
    assert issubclass(UnsupportedOpError, NotImplementedError)


def test_smart_art_scan_stream_matches_oracle():
    """The radix store serves mixed scan streams through the fused runner;
    key runs ARE slot runs (in-order leaf addressing)."""
    rng = np.random.default_rng(3)
    nbits, b, w = 9, 128, 3
    n = 1 << nbits
    store = SmartART.create(key_bits=nbits, mode=SyncMode.CIDER, scan_max=8)
    pop = rng.choice(n, size=n // 2, replace=False)
    store = store.populate(pop, pop)
    oracle = OracleStore()
    oracle.populate(pop, pop)
    kinds = rng.choice([OpKind.SEARCH, OpKind.UPDATE, OpKind.SCAN,
                        OpKind.DELETE], size=(w, b),
                       p=(.3, .25, .3, .15)).astype(np.int32)
    keys = rng.integers(0, n, (w, b)).astype(np.int32)
    values = rng.integers(0, 10_000, (w, b)).astype(np.int32)
    scan = kinds == OpKind.SCAN
    values[scan] = rng.integers(1, 9, scan.sum())
    store, res, io = store.apply_stream(kinds, keys, values, n_cns=8)
    for i in range(w):
        ok_o, val_o = oracle.apply(kinds[i], keys[i], values[i], scan_max=8)
        np.testing.assert_array_equal(np.asarray(res.ok)[i], ok_o)
        np.testing.assert_array_equal(np.asarray(res.rows)[i], oracle.rows)


def test_modeled_latency_scan_orderings():
    """Scan-heavy stream: CIDER's lock-free cold traversal beats the re-read
    (OSYNC) and per-leaf locking (SPIN/MCS) on the modeled tail."""
    from repro.core.simnet import SimParams
    from repro.workloads.ycsb import YCSB, generate_ycsb_stream

    p = SimParams()
    ops = generate_ycsb_stream(YCSB["E"], 4, 256, 512, 64, seed=2)
    counts = np.where(ops.kinds == OpKind.SCAN, ops.values, 0)
    p99 = {}
    for mode in MODES:
        cfg = EngineConfig(n_slots=1024, heap_slots=2048, mode=mode,
                           scan_max=16)
        st = populate(cfg, store_init(cfg), np.arange(512), np.arange(512))
        stream = runner.make_stream(ops.kinds, ops.keys, ops.values, n_cns=64)
        _, _, res, _ = runner.run_windows(cfg, st, credit_init(256), stream)
        lat = runner.modeled_latency(cfg, ops.kinds, res, p,
                                     scan_counts=counts)
        assert np.isfinite(lat[~np.isnan(lat)]).all()
        p99[mode] = runner.latency_stats(lat).p99_us
    assert p99[SyncMode.CIDER] < p99[SyncMode.OSYNC]
    assert p99[SyncMode.CIDER] < p99[SyncMode.SPIN]
    assert p99[SyncMode.CIDER] < p99[SyncMode.MCS]


# ---------------------------------------------------------------------------
# Step 5c's data movement: the sorts that carry the probe lanes against the
# formulation they replaced (one lexsort, gathers into sorted order, a gather
# of `ptr` per sorted lane, scatters back), kept here as the oracle.
# ---------------------------------------------------------------------------

def _scan_probes_by_gathers(ptr, first, pos, valid, p_in, setcode, writer,
                            backend):
    """``engine._scan_probes``'s contract, computed as step 5c did before the
    sorts carried the lanes: ``plan_combine``'s lexsort, the lanes and the
    ``ptr`` words gathered into sorted order, the answers scattered back."""
    import jax.numpy as jnp

    from repro.core import combine as wc
    from repro.core import engine
    from repro.core.types import NULL_PTR
    b, ns = p_in.shape
    bc = b * (1 + ns)
    jj = jnp.arange(ns, dtype=jnp.int32)
    keys_c = jnp.concatenate([first, (first[:, None] + jj).reshape(-1)])
    pos_c = jnp.concatenate([pos, jnp.repeat(pos, ns)])
    tvalid = jnp.concatenate([valid, p_in.reshape(-1)])
    plan = wc.plan_combine(keys_c, pos_c, tvalid, backend=backend)
    pc = plan.perm
    setcode_c = jnp.concatenate([setcode, jnp.full((b * ns,), -1, jnp.int32)])
    writer_c = jnp.concatenate([writer, jnp.zeros((b * ns,), bool)])
    slot_c = jnp.clip(keys_c[pc], 0, ptr.shape[0] - 1)
    e_init = ptr[slot_c] != NULL_PTR
    e_bc, waits_s = engine._probe_sweep(plan.keys_sorted, setcode_c[pc],
                                        writer_c[pc], e_init, backend)
    e_probe = jnp.zeros((bc,), bool).at[pc].set(e_bc & tvalid[pc])
    readers = jnp.concatenate([jnp.zeros((b,), bool), p_in.reshape(-1)])[pc]
    waits = jnp.zeros((bc,), jnp.int32).at[pc].set(
        jnp.where(readers, waits_s, 0))
    return e_probe[b:].reshape(b, ns), waits[b:].reshape(b, ns)


def _edge_scan_ops():
    """(W, B) windows for the probe-pass comparison: a strided cross-CN hot
    key (CIDER goes pessimistic from window 1), and pinned lanes giving
    every case the sorts must keep — counts 0, 1 and SCAN_MAX; inserts and
    deletes inside a scan's run both before and after its position; runs
    that start within SCAN_MAX of either end of a shard's slot range
    (shards of 16 on the 4-way mesh) and of the keyspace."""
    kinds, keys, values = _scan_ops(seed=7)
    pins = [  # (lane, kind, key, value)
        (2, OpKind.DELETE, 20, 0), (3, OpKind.INSERT, 22, 11),
        (9, OpKind.SCAN, 18, SCAN_MAX),        # run 18-25, after lanes 2, 3
        (10, OpKind.INSERT, 19, 12), (11, OpKind.DELETE, 21, 0),
        (13, OpKind.SCAN, 30, SCAN_MAX),       # crosses the 31/32 boundary
        (17, OpKind.SCAN, 45, 1), (18, OpKind.SCAN, 46, 0),
        (21, OpKind.SCAN, 60, SCAN_MAX),       # falls off the keyspace end
        (22, OpKind.SCAN, 0, SCAN_MAX), (23, OpKind.SCAN, 9, SCAN_MAX),
        (25, OpKind.DELETE, 47, 0), (26, OpKind.SCAN, 44, SCAN_MAX),
        (27, OpKind.INSERT, 49, 13), (29, OpKind.SCAN, 63, 3),
    ]
    for lane, kind, key, value in pins:
        kinds[:, lane], keys[:, lane], values[:, lane] = kind, key, value
    return kinds, keys, values


@pytest.fixture
def gathers_oracle(monkeypatch):
    """Run the engine with step 5c's old data movement; the jit caches are
    cleared on both sides so no trace of either formulation is reused."""
    import jax

    from repro.core import engine
    jax.clear_caches()
    monkeypatch.setattr(engine, "_scan_probes", _scan_probes_by_gathers)
    yield
    monkeypatch.undo()
    jax.clear_caches()


def _run_edge_stream(cfg, shards):
    kinds, keys, values = _edge_scan_ops()
    st, cr, pop_keys, pop_vals = _init(cfg)
    stream = runner.make_stream(kinds, keys, values, n_cns=N_CNS)
    if shards == 1:
        st, cr, res, io = runner.run_windows(cfg, st, cr, stream,
                                             io_per_window=True)
        view = store_view(st)
    else:
        sst = dstore.sharded_populate(
            cfg, shards, dstore.sharded_store_init(cfg, shards), pop_keys,
            pop_vals)
        st, cr, res, io = dstore.run_windows_sharded(
            cfg, make_local_mesh(data=shards), sst, credit_init(256), stream,
            io_per_window=True)
        view = dstore.sharded_store_view(cfg, shards, st)
    out = {f"Results.{f.name}": getattr(res, f.name)
           for f in dataclasses.fields(res)}
    out.update({f"IOMetrics.{f.name}": getattr(io, f.name)
                for f in dataclasses.fields(IOMetrics)})
    out.update({"view.exists": view[0], "view.value": view[1],
                "credit": cr.credit})
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("mode", MODES)
def test_scan_probe_sorts_match_the_gather_formulation(mode, backend, shards,
                                                       request):
    """Step 5c moves its probe lanes by two sorts instead of permutation
    gathers and scatters: rows (per-op hits), ranks (the anchor reader's
    wait), the 11-field bill, every other result, the store and the credit
    table are bit-equal to the gather formulation, on one device and on
    the 4-way mesh, for both kernel backends."""
    cfg = EngineConfig(n_slots=N_SLOTS, heap_slots=2048, mode=mode,
                       scan_max=SCAN_MAX, kernel_backend=backend)
    got = _run_edge_stream(cfg, shards)
    request.getfixturevalue("gathers_oracle")
    want = _run_edge_stream(cfg, shards)
    assert got["Results.rows"].sum() > 0
    if mode != SyncMode.OSYNC:
        # some anchor reader waits behind an exclusive holder
        assert (got["Results.rank"][got["Results.rows"] > 0] > 0).any()
    assert len(got) == len(want) == 9 + 11 + 3
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("mode", MODES)
def test_scan_step_moves_probe_lanes_only_by_sorts(mode, backend):
    """Step 5c's jaxpr (B = 16, scan_max = 7) holds no gather or scatter
    whose operand or index spans the B*scan_max probe lanes or the
    B*(1+scan_max) combined lanes, but CIDER's credit-table lookup, and at
    most two sorts of that size: the permutations the sorts replaced
    cannot come back unseen."""
    import math

    import jax

    from repro.analysis.jaxpr_check import (_as_jaxpr, _cfg, _engine_args,
                                            _sub_jaxprs)
    from repro.core import engine
    cfg = _cfg(mode, backend, scan_max=7)
    st, _, batch, valid, alive, died = _engine_args(cfg)
    n_credit = 256                    # apart from the 64-slot `ptr` plane
    closed = jax.make_jaxpr(lambda *a: engine.apply_batch(
        cfg, *a[:3], valid=a[3], alive=a[4], died=a[5]))(
        st, credit_init(n_credit), batch, valid, alive, died)
    b, ns = batch.keys.shape[0], cfg.scan_max
    assert (b, ns) == (16, 7)
    lanes = {b * ns, b * (1 + ns)}

    def size(v):
        return math.prod(v.aval.shape)

    moves, sorts, in_scope = [], 0, 0
    stack = [(_as_jaxpr(closed), False)]
    while stack:
        jaxpr, inside = stack.pop()
        for eqn in jaxpr.eqns:
            here = inside or "engine.s5c_scan" in str(
                eqn.source_info.name_stack)
            for sub in _sub_jaxprs(eqn):
                stack.append((sub, here))
            if not here:
                continue
            in_scope += 1
            name = eqn.primitive.name
            if name == "sort" and size(eqn.invars[0]) in lanes:
                sorts += 1
            if name == "gather" or name.startswith("scatter"):
                operand, index = eqn.invars[0], eqn.invars[1]
                if size(operand) in lanes or size(index) in lanes:
                    moves.append((name, operand.aval.shape, index.aval.shape))
    assert in_scope > 20
    credit = [m for m in moves if m[0] == "gather" and m[1] == (n_credit,)]
    assert len(credit) == (mode == SyncMode.CIDER)
    assert [m for m in moves if m not in credit] == []
    assert 1 <= sorts <= 2

"""Fused multi-window runner equivalence: ``run_windows`` (one jitted scan)
must be bit-identical, window by window, to the per-window Python loop over
``apply_batch`` — Results, I/O bill, credit table, and store view — for all
four SyncModes, unsharded and under ``dist.store.run_windows_sharded`` on a
multi-way CPU mesh.  Plus the MN-IOPS throughput model and the stacked-window
stream generator."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import runner
from repro.core.credits import credit_init
from repro.core.engine import apply_batch, populate, store_init, store_view
from repro.core.simnet import SimParams
from repro.core.types import (EngineConfig, IOMetrics, OpBatch, OpKind,
                              SyncMode)
from repro.dist import store as dstore
from repro.launch.mesh import make_local_mesh
from repro.workloads.ycsb import WORKLOADS, generate_ops, generate_window_stream

MODES = [SyncMode.OSYNC, SyncMode.SPIN, SyncMode.MCS, SyncMode.CIDER]
W, B, N_SLOTS, HEAP, N_CNS = 4, 256, 64, 1024, 4


def _ops():
    """(W, B) op arrays with a strided cross-CN hot key so CIDER's credits
    warm up and the pessimistic global-WC path actually runs (see
    tests/test_dist_store.py for why striding matters)."""
    rng = np.random.default_rng(0)
    kinds = rng.choice(
        [OpKind.SEARCH, OpKind.INSERT, OpKind.UPDATE, OpKind.DELETE],
        size=(W, B), p=(0.3, 0.15, 0.4, 0.15)).astype(np.int32)
    keys = rng.integers(0, N_SLOTS, (W, B)).astype(np.int32)
    values = rng.integers(0, 10_000, (W, B)).astype(np.int32)
    keys[:, ::4] = 5
    kinds[:, ::4] = OpKind.UPDATE
    return kinds, keys, values


def _init(cfg):
    rng = np.random.default_rng(1)
    pop_keys = rng.choice(N_SLOTS, size=N_SLOTS // 2, replace=False)
    pop_vals = rng.integers(0, 10_000, pop_keys.shape[0])
    return (populate(cfg, store_init(cfg), pop_keys, pop_vals),
            credit_init(256), pop_keys, pop_vals)


def _loop(cfg, state, credits, kinds, keys, values):
    """The reference per-window Python loop the runner replaces."""
    ress, ios = [], []
    for w in range(W):
        batch = OpBatch.make(kinds[w], keys[w], values[w], n_cns=N_CNS)
        state, credits, res, io = apply_batch(cfg, state, credits, batch)
        ress.append(res)
        ios.append(io)
    return state, credits, ress, ios


def _assert_windows_equal(ress, ios, res2, ios2, cr1, cr2):
    for w in range(W):
        for f in dataclasses.fields(ress[w]):
            np.testing.assert_array_equal(
                np.asarray(getattr(ress[w], f.name)),
                np.asarray(getattr(res2, f.name))[w],
                err_msg=f"window {w} Results.{f.name}")
        for f in dataclasses.fields(IOMetrics):
            assert (int(getattr(ios[w], f.name))
                    == int(np.asarray(getattr(ios2, f.name))[w])), \
                f"window {w} IOMetrics.{f.name}"
    np.testing.assert_array_equal(np.asarray(cr1.credit),
                                  np.asarray(cr2.credit))
    np.testing.assert_array_equal(np.asarray(cr1.retry_record),
                                  np.asarray(cr2.retry_record))


@pytest.mark.parametrize("mode", MODES)
def test_run_windows_matches_python_loop(mode):
    kinds, keys, values = _ops()
    cfg = EngineConfig(n_slots=N_SLOTS, heap_slots=HEAP, mode=mode)
    st0, cr0, _, _ = _init(cfg)
    st1, cr1, ress, ios = _loop(cfg, st0, cr0, kinds, keys, values)

    st0, cr0, _, _ = _init(cfg)   # fresh buffers: run_windows donates its args
    stream = runner.make_stream(kinds, keys, values, n_cns=N_CNS)
    st2, cr2, res2, ios2 = runner.run_windows(cfg, st0, cr0, stream,
                                              io_per_window=True)
    _assert_windows_equal(ress, ios, res2, ios2, cr1, cr2)
    ex1, v1 = store_view(st1)
    ex2, v2 = store_view(st2)
    np.testing.assert_array_equal(np.asarray(ex1), np.asarray(ex2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(np.asarray(st1.ver), np.asarray(st2.ver))
    np.testing.assert_array_equal(np.asarray(st1.epoch), np.asarray(st2.epoch))
    if mode == SyncMode.CIDER:
        assert int(np.asarray(res2.pessimistic).sum()) > 0

    # the default (summed) bill is the sum of the per-window bills
    st0, cr0, _, _ = _init(cfg)
    _, _, _, io_sum = runner.run_windows(cfg, st0, cr0, stream)
    for f in dataclasses.fields(IOMetrics):
        assert int(getattr(io_sum, f.name)) == sum(
            int(getattr(io, f.name)) for io in ios), f"summed {f.name}"


@pytest.mark.parametrize("mode", MODES)
def test_run_windows_sharded_matches_python_loop(mode):
    mesh = make_local_mesh(data=4)   # conftest pins 8 host devices
    kinds, keys, values = _ops()
    cfg = EngineConfig(n_slots=N_SLOTS, heap_slots=HEAP, mode=mode)
    st0, cr0, pop_keys, pop_vals = _init(cfg)
    st1, cr1, ress, ios = _loop(cfg, st0, cr0, kinds, keys, values)

    sst = dstore.sharded_populate(
        cfg, 4, dstore.sharded_store_init(cfg, 4), pop_keys, pop_vals)
    stream = runner.make_stream(kinds, keys, values, n_cns=N_CNS)
    st2, cr2, res2, ios2 = dstore.run_windows_sharded(
        cfg, mesh, sst, credit_init(256), stream, io_per_window=True)
    _assert_windows_equal(ress, ios, res2, ios2, cr1, cr2)
    ex1, v1 = store_view(st1)
    ex2, v2 = dstore.sharded_store_view(cfg, 4, st2)
    np.testing.assert_array_equal(np.asarray(ex1), np.asarray(ex2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def test_make_stream_matches_opbatch_make():
    kinds, keys, values = _ops()
    stream = runner.make_stream(kinds, keys, values, n_cns=N_CNS)
    for w in range(W):
        ref = OpBatch.make(kinds[w], keys[w], values[w], n_cns=N_CNS)
        for f in dataclasses.fields(OpBatch):
            if f.metadata.get("static"):
                assert getattr(stream.batch, f.name) == getattr(ref, f.name)
                continue
            np.testing.assert_array_equal(
                np.asarray(getattr(stream.batch, f.name))[w],
                np.asarray(getattr(ref, f.name)), err_msg=f.name)
    assert stream.shape == (W, B)


def test_generate_window_stream_replays_per_window_seeds():
    spec = WORKLOADS["write-intensive"]
    ops = generate_window_stream(spec, 3, 128, 512, 16, seed=7)
    assert ops.kinds.shape == (3, 128)
    for w in range(3):
        ref = generate_ops(spec, 128, 512, 16, seed=7 + w)
        np.testing.assert_array_equal(ops.kinds[w], ref.kinds)
        np.testing.assert_array_equal(ops.keys[w], ref.keys)
        np.testing.assert_array_equal(ops.values[w], ref.values)


@pytest.mark.parametrize("mode", MODES)
def test_run_windows_traced_matches_loop_and_credit_mass(mode):
    kinds, keys, values = _ops()
    cfg = EngineConfig(n_slots=N_SLOTS, heap_slots=HEAP, mode=mode)
    st0, cr0, _, _ = _init(cfg)
    st1, cr1, ress, ios = _loop(cfg, st0, cr0, kinds, keys, values)
    # credit mass AFTER each window, from the reference loop
    st0, cr0, _, _ = _init(cfg)
    cr, masses = cr0, []
    for w in range(W):
        batch = OpBatch.make(kinds[w], keys[w], values[w], n_cns=N_CNS)
        st0, cr, _, _ = apply_batch(cfg, st0, cr, batch)
        masses.append(int(jnp.sum(cr.credit)))

    st0, cr0, _, _ = _init(cfg)
    stream = runner.make_stream(kinds, keys, values, n_cns=N_CNS)
    _, cr2, res2, ios2, mass2 = runner.run_windows_traced(cfg, st0, cr0,
                                                          stream)
    _assert_windows_equal(ress, ios, res2, ios2, cr1, cr2)
    assert [int(m) for m in np.asarray(mass2)] == masses


def test_sharded_traced_matches_single_device():
    mesh = make_local_mesh(data=4)
    kinds, keys, values = _ops()
    cfg = EngineConfig(n_slots=N_SLOTS, heap_slots=HEAP, mode=SyncMode.CIDER)
    st0, cr0, pop_keys, pop_vals = _init(cfg)
    stream = runner.make_stream(kinds, keys, values, n_cns=N_CNS)
    _, cr1, res1, ios1, mass1 = runner.run_windows_traced(cfg, st0, cr0,
                                                          stream)
    sst = dstore.sharded_populate(
        cfg, 4, dstore.sharded_store_init(cfg, 4), pop_keys, pop_vals)
    _, cr2, res2, ios2, mass2 = dstore.run_windows_sharded_traced(
        cfg, mesh, sst, credit_init(256), stream)
    for f in dataclasses.fields(res1):
        np.testing.assert_array_equal(np.asarray(getattr(res1, f.name)),
                                      np.asarray(getattr(res2, f.name)),
                                      err_msg=f"Results.{f.name}")
    for f in dataclasses.fields(IOMetrics):
        np.testing.assert_array_equal(np.asarray(getattr(ios1, f.name)),
                                      np.asarray(getattr(ios2, f.name)),
                                      err_msg=f"IOMetrics.{f.name}")
    np.testing.assert_array_equal(np.asarray(mass1), np.asarray(mass2))
    np.testing.assert_array_equal(np.asarray(cr1.credit),
                                  np.asarray(cr2.credit))


def test_modeled_latency_uncontended_searches_exact():
    """Distinct populated keys, SEARCH only: latency is the closed-form
    index READ + value READ chain plus each op's place in the NIC queue."""
    p = SimParams()
    cfg = EngineConfig(n_slots=16, heap_slots=64, mode=SyncMode.CIDER)
    st = populate(cfg, store_init(cfg), np.arange(16), np.arange(16))
    kinds = np.full(8, OpKind.SEARCH, np.int32)
    kinds[-1] = OpKind.NOP
    batch = OpBatch.make(kinds, np.arange(8), np.zeros(8), n_cns=2)
    _, _, res, _ = apply_batch(cfg, st, credit_init(64), batch)
    lat = runner.modeled_latency(cfg, kinds, res, p)
    # op i: 2 RTTs (index + value read) + 2i/mn_cap backlog behind i earlier
    # 2-verb SEARCHes
    want = p.rtt * 2.0 + 2.0 * np.arange(7) / p.mn_cap
    np.testing.assert_allclose(lat[:7], want)
    assert np.isnan(lat[7])                      # NOP lane masked out
    stats = runner.latency_stats(lat)
    assert stats.n_ops == 7 and stats.p50_us == pytest.approx(want[3], abs=.2)


def _hot_update_stream(w=8, b=256, n_slots=64, seed=0):
    """SEARCH/UPDATE mix with a strided cross-CN hot key and thin CNs (the
    paper's 4-clients-per-CN shape, so local WC can't absorb the queue) —
    enough windows for CIDER's credits to warm up."""
    rng = np.random.default_rng(seed)
    kinds = rng.choice([OpKind.SEARCH, OpKind.UPDATE], size=(w, b),
                       p=(0.5, 0.5)).astype(np.int32)
    keys = rng.integers(0, n_slots, (w, b)).astype(np.int32)
    values = rng.integers(0, 10_000, (w, b)).astype(np.int32)
    keys[:, ::4] = 5
    kinds[:, ::4] = OpKind.UPDATE
    return kinds, keys, values


def test_modeled_latency_contended_ordering():
    """On a contended write stream the modeled tail must reproduce the
    paper's ordering (Figs 11-12): CIDER's combining flattens p99 below
    OSYNC's CAS-retry storm and below the lock-queue modes."""
    kinds, keys, values = _hot_update_stream()
    n_slots, heap = 64, 64 + kinds.size
    p = SimParams()
    p99, lats, ress = {}, {}, {}
    for mode in MODES:
        cfg = EngineConfig(n_slots=n_slots, heap_slots=heap, mode=mode)
        pop = np.arange(n_slots)
        st = populate(cfg, store_init(cfg), pop, pop)
        stream = runner.make_stream(kinds, keys, values, n_cns=64)
        _, _, res, _ = runner.run_windows(cfg, st, credit_init(256), stream)
        lat = runner.modeled_latency(cfg, kinds, res, p)
        assert np.isfinite(lat[~np.isnan(lat)]).all()
        p99[mode], lats[mode], ress[mode] = (runner.latency_stats(lat).p99_us,
                                             lat, res)
    assert p99[SyncMode.CIDER] < p99[SyncMode.OSYNC]
    assert p99[SyncMode.CIDER] < p99[SyncMode.SPIN]
    assert p99[SyncMode.CIDER] < p99[SyncMode.MCS]
    # rank-r optimistic writers wait r failed CAS rounds: latency grows with
    # rank on the hot key under OSYNC
    res, lat = ress[SyncMode.OSYNC], lats[SyncMode.OSYNC]
    hot = (keys == 5) & (kinds == OpKind.UPDATE) & ~np.asarray(res.combined)
    ranks = np.asarray(res.rank)[hot]
    assert np.corrcoef(ranks, lat[hot])[0, 1] > 0.9


def test_latency_stats_empty():
    stats = runner.latency_stats(np.array([np.nan, np.nan]))
    assert stats.n_ops == 0 and stats.p99_us == 0.0


def test_modeled_throughput_iops_and_bandwidth_bounds():
    p = SimParams()
    z = jnp.zeros((), jnp.int32)
    io = IOMetrics(reads=jnp.int32(3200), writes=z, cas=z, faa=z, cn_msgs=z,
                   mn_bytes=jnp.int32(100), retries=z, combined=z, executed=z,
                   repair_cas=z, orphan_windows=z)
    m = runner.modeled_throughput(io, p, n_ops=1000)
    # 3200 verbs / 32 per us = 100 us -> 10 ops/us = 10 Mops/s, IOPS-bound
    assert m["bound"] == "iops"
    assert m["modeled_ticks_us"] == pytest.approx(100.0)
    assert m["modeled_mops"] == pytest.approx(10.0)
    io_bw = dataclasses.replace(io, reads=jnp.int32(1),
                                mn_bytes=jnp.int32(2_500_000))
    m2 = runner.modeled_throughput(io_bw, p, n_ops=1000)
    assert m2["bound"] == "bandwidth"          # 2.5MB / 12500 B/us = 200 us
    assert m2["modeled_ticks_us"] == pytest.approx(200.0)
    # fewer MN verbs for the same ops => strictly higher modeled throughput
    io_less = dataclasses.replace(io, reads=jnp.int32(1600))
    assert (runner.modeled_throughput(io_less, p, 1000)["modeled_mops"]
            > m["modeled_mops"])

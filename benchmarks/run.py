"""Benchmark harness — one function per paper table/figure (§5), writing
CSV blocks to stdout and ``results/benchmarks/*.csv``.

Scaled for a single-core CPU container: 512 lanes, 8192-us windows, 1M-key
universe; the qualitative claims (collapse/scaling/ordering) and calibrated
ratios are the targets — see EXPERIMENTS.md §Paper-validation for the
side-by-side versus the paper's numbers.

    PYTHONPATH=src python -m benchmarks.run [--only fig11,fig20] [--fast]
"""
from __future__ import annotations

import os

# the ycsb_json sharded runs need >= 4 host devices, pinned BEFORE jax init
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

import argparse
import dataclasses
import time

import numpy as np

import json

import jax

from repro.core import runner
from repro.core.credits import credit_init
from repro.core.engine import populate, store_init
from repro.core.sim import SimParams, make_streams, run_sim
from repro.core.types import EngineConfig, IOMetrics, OpKind, SyncMode
from repro.dist import store as dstore
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.stores import PointerArray, RaceHash, SmartART
from repro.workloads.ycsb import (WORKLOADS, YCSB, generate_window_stream,
                                  generate_ycsb_stream)

from benchmarks.provenance import provenance

OUT = "results/benchmarks"
MODES = [SyncMode.OSYNC, SyncMode.SPIN, SyncMode.MCS, SyncMode.CIDER]
N_KEYS = 1_000_000
BASE = dict(n_lanes=512, ticks=8192, max_ops=1024)


def _emit(name: str, header: str, rows: list[str]):
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, f"{name}.csv")
    with open(path, "w") as f:
        f.write(header + "\n" + "\n".join(rows) + "\n")
    print(f"\n== {name} ==\n{header}")
    for r in rows:
        print(r)


def _sweep(p: SimParams, workload: str, counts, modes=MODES, theta=None,
           n_keys=N_KEYS):
    streams = make_streams(p, WORKLOADS[workload], n_keys, theta=theta)
    return {(m, nc): run_sim(p, m, streams, nc) for m in modes for nc in counts}


def fig11_12_throughput_latency(fast=False):
    """Figs 11+12: pointer array, 3 workloads x 4 schemes vs clients."""
    counts = [48, 512] if fast else [16, 48, 128, 256, 512]
    p = SimParams(**BASE)
    for wl in ["write-intensive", "read-intensive", "write-only"]:
        res = _sweep(p, wl, counts)
        rows = [f"{nc}," + ",".join(
            f"{res[(m, nc)].throughput_mops:.3f}" for m in MODES) +
            "," + ",".join(f"{res[(m, nc)].p99_us:.0f}" for m in MODES)
            for nc in counts]
        _emit(f"fig11_{wl}", "clients," + ",".join(f"thr_{m.name}" for m in MODES)
              + "," + ",".join(f"p99_{m.name}" for m in MODES), rows)


def fig13_skew(fast=False):
    """Fig 5/13: throughput vs Zipf theta at 512 clients."""
    thetas = [0.0, 0.8, 0.99, 1.2] if fast else [0.0, 0.5, 0.8, 0.9, 0.99, 1.1, 1.2]
    p = SimParams(**BASE)
    rows = []
    for th in thetas:
        res = _sweep(p, "write-intensive", [512], theta=th)
        rows.append(f"{th}," + ",".join(
            f"{res[(m, 512)].throughput_mops:.3f}" for m in MODES))
    _emit("fig13_skew", "theta," + ",".join(m.name for m in MODES), rows)


def fig14_accuracy(fast=False):
    """Fig 14: contention-aware identification accuracy at 512 clients."""
    p = SimParams(**BASE)
    streams = make_streams(p, WORKLOADS["write-intensive"], N_KEYS)
    ideal = run_sim(p, SyncMode.OSYNC, streams, 512).ideal_pess_ratio
    c = run_sim(p, SyncMode.CIDER, streams, 512)
    comb_of_pess = c.wc_rate_global / max(c.pess_ratio, 1e-9)
    _emit("fig14_accuracy",
          "ideal_pess_ratio,cider_pess_ratio,combined_frac_of_pess",
          [f"{ideal:.4f},{c.pess_ratio:.4f},{comb_of_pess:.3f}"])


def fig15_params(fast=False):
    """Fig 15: INITIAL_CREDIT / HOTNESS_THRESHOLD sensitivity (512 clients)."""
    rows = []
    for ic in ([8, 36] if fast else [2, 8, 36, 128]):
        p = SimParams(**BASE, initial_credit=ic)
        streams = make_streams(p, WORKLOADS["write-intensive"], N_KEYS)
        r = run_sim(p, SyncMode.CIDER, streams, 512)
        rows.append(f"initial_credit,{ic},{r.throughput_mops:.3f}")
    for ht in ([2] if fast else [1, 2, 4]):
        p = SimParams(**BASE, hotness_threshold=ht)
        streams = make_streams(p, WORKLOADS["write-intensive"], N_KEYS)
        r = run_sim(p, SyncMode.CIDER, streams, 512)
        rows.append(f"hotness_threshold,{ht},{r.throughput_mops:.3f}")
    _emit("fig15_params", "param,value,throughput_mops", rows)


def fig16_19_race_smart(fast=False):
    """Figs 16-19: end-to-end on RACE (2 bucket reads) and SMART (radix with
    client path cache) index I/O profiles."""
    counts = [48, 512] if fast else [48, 128, 512]
    for name, idx_kw in [("race", dict(index_reads=2, index_bytes=128)),
                         ("smart", dict(index_reads=1, index_bytes=64))]:
        p = SimParams(**BASE, **idx_kw)
        res = _sweep(p, "write-intensive", counts)
        rows = [f"{nc}," + ",".join(
            f"{res[(m, nc)].throughput_mops:.3f}" for m in MODES) +
            "," + ",".join(f"{res[(m, nc)].p99_us:.0f}" for m in MODES)
            for nc in counts]
        _emit(f"fig16_{name}", "clients," +
              ",".join(f"thr_{m.name}" for m in MODES) + "," +
              ",".join(f"p99_{m.name}" for m in MODES), rows)


def fig20_factor(fast=False):
    """Fig 20: factor analysis (local WC disabled for O-SYNC/ShiftLock)."""
    variants = [
        ("OSYNC_noWC", SimParams(**BASE, local_wc=False), SyncMode.OSYNC),
        ("ShiftLock_noWC", SimParams(**BASE, local_wc=False), SyncMode.MCS),
        ("CIDER_woWC", SimParams(**BASE, wc_off=True), SyncMode.CIDER),
        ("CIDER_woCAS", SimParams(**BASE, cas_off=True), SyncMode.CIDER),
        ("CIDER", SimParams(**BASE), SyncMode.CIDER),
    ]
    rows = []
    for name, p, mode in variants:
        streams = make_streams(p, WORKLOADS["write-intensive"], N_KEYS)
        r = run_sim(p, mode, streams, 512)
        rows.append(f"{name},{r.throughput_mops:.3f},{r.p50_us:.0f},"
                    f"{r.p99_us:.0f}")
    _emit("fig20_factor", "variant,throughput_mops,p50_us,p99_us", rows)


def fig21_wc_efficiency(fast=False):
    """Fig 21: WC rate + batch size: local (MCS+WC) vs global (CIDER woCAS)
    vs CIDER."""
    rows = []
    for name, p, mode in [
            ("local_wc", SimParams(**BASE), SyncMode.MCS),
            ("global_wc", SimParams(**BASE, cas_off=True), SyncMode.CIDER),
            ("cider", SimParams(**BASE), SyncMode.CIDER)]:
        streams = make_streams(p, WORKLOADS["write-intensive"], N_KEYS)
        r = run_sim(p, mode, streams, 512)
        rows.append(f"{name},{r.wc_rate:.3f},{r.avg_batch:.2f},"
                    f"{r.throughput_mops:.3f}")
    _emit("fig21_wc_efficiency", "mechanism,wc_rate,avg_batch,throughput", rows)


def fig23_array_size(fast=False):
    """Fig 23 (appendix): pointer-array size sweep at 512 clients."""
    sizes = [64, 65536, 1_000_000] if fast else [1, 64, 4096, 65536, 1_000_000]
    p = SimParams(**BASE)
    rows = []
    for n in sizes:
        res = _sweep(p, "write-intensive", [512], n_keys=n)
        rows.append(f"{n}," + ",".join(
            f"{res[(m, 512)].throughput_mops:.3f}" for m in MODES))
    _emit("fig23_array_size", "array_size," + ",".join(m.name for m in MODES),
          rows)


def fig24_value_size(fast=False):
    """Fig 24 (appendix): value-size sweep (IOPS-bound => flat)."""
    rows = []
    for vb in [8, 64, 256]:
        p = SimParams(**BASE, value_bytes=vb)
        streams = make_streams(p, WORKLOADS["write-intensive"], N_KEYS)
        for mode in ([SyncMode.OSYNC, SyncMode.CIDER] if True else MODES):
            r = run_sim(p, mode, streams, 512)
            rows.append(f"{vb},{mode.name},{r.throughput_mops:.3f}")
    _emit("fig24_value_size", "value_bytes,mode,throughput_mops", rows)


def table_engine_io(fast=False):
    """Exact per-window I/O bill from the dataplane engine (closed-form
    metering): steady-state window after the contention-aware credits warm
    up over 6 consecutive windows (CIDER's first window IS optimistic)."""
    spec = WORKLOADS["write-intensive"]
    rows = []
    for mode in MODES:
        pa = PointerArray.create(4096, mode=mode).populate(
            np.arange(4096), np.arange(4096))
        ops = generate_window_stream(spec, 6, 4096, 4096, 64)
        stream = runner.make_stream(ops.kinds, ops.keys % 4096, ops.values,
                                    n_cns=16)
        pa, res, ios = pa.apply_stream(stream, io_per_window=True)
        d = runner.io_window(ios, -1).as_dict()     # steady-state window
        rows.append(f"pointer_array,{mode.name},{d['mn_iops']},{d['writes']},"
                    f"{d['cas']},{d['retries']},{d['combined']},{d['mn_bytes']}")
    for mode in MODES:
        sa = SmartART.create(key_bits=12, mode=mode).populate(
            np.arange(4096), np.arange(4096))
        ops = generate_window_stream(spec, 6, 4096, 4096, 64)
        sa, res, ios = sa.apply_stream(ops.kinds, ops.keys % 4096, ops.values,
                                       n_cns=16, io_per_window=True)
        d = runner.io_window(ios, -1).as_dict()
        rows.append(f"smart_art,{mode.name},{d['mn_iops']},{d['writes']},"
                    f"{d['cas']},{d['retries']},{d['combined']},{d['mn_bytes']}")
    _emit("table_engine_io",
          "store,mode,mn_iops,writes,cas,retries,combined,mn_bytes", rows)


FULL_BASELINE = "BENCH_engine.json"


def bench_engine_json(fast=False, path=None):
    """Machine-readable engine benchmark — the perf trajectory file CI and
    later PRs diff against.  Per SyncMode it reports BOTH

    * device wall-clock of ONE fused ``run_windows`` scan over all windows
      (``wall_s`` / ``throughput_mops``) — a dispatch-free regression signal;
    * ``modeled_mops`` — throughput under the MN-IOPS cost model
      (``runner.modeled_throughput``), the paper's §2.3/§5 bottleneck metric,
      computed from the exact verb bill summed over all windows;
    * ``modeled_p50_us`` / ``modeled_p99_us`` — the paper's second axis:
      per-op modeled latency percentiles (``runner.modeled_latency``) from
      each op's verb chain + wait-queue rank + MN NIC queueing under the
      same ``SimParams`` cost model.

    ``--fast`` writes ``BENCH_engine.fast.json`` and refuses to overwrite the
    committed full-size baseline.
    """
    if path is None:
        path = "BENCH_engine.fast.json" if fast else FULL_BASELINE
    elif fast and os.path.abspath(path) == os.path.abspath(FULL_BASELINE):
        raise SystemExit(
            f"--fast must not overwrite the committed full-size baseline "
            f"{FULL_BASELINE}; pick another path (default: "
            f"BENCH_engine.fast.json)")
    n_slots, b = (4096, 1024) if fast else (65_536, 4096)
    windows = 4 if fast else 16
    p = SimParams()                                 # testbed cost model
    spec = WORKLOADS["write-intensive"]
    ops = generate_window_stream(spec, windows, b, n_slots, b)
    stream = runner.make_stream(ops.kinds, ops.keys % n_slots, ops.values,
                                n_cns=16)
    out = {
        "config": {"n_slots": n_slots, "batch": b, "windows": windows,
                   "workload": spec.name, "theta": spec.theta, "n_cns": 16,
                   "fast": fast, "runner": "repro.core.runner.run_windows",
                   "provenance": provenance("auto"),
                   "generated_by": "python -m benchmarks.run --only engine_json"
                                   + (" --fast" if fast else "")},
        "metrics": {
            "io_counters": "exact RDMA-verb bill SUMMED over all windows",
            "wall_s": "host-timed device wall-clock of one fused "
                      "run_windows scan executing every window",
            "throughput_mops": "windows*batch / wall_s / 1e6 — device "
                               "wall-clock throughput, gated by "
                               "check_regression.py wall floors whenever "
                               "the run's backend provenance matches the "
                               "committed baseline's (docs/METRICS.md)",
            "modeled_mops": "ops / max(mn_iops/mn_cap, mn_bytes/mn_bw) us — "
                            "MN-NIC-bound throughput, the paper's metric "
                            "(PAPER.md §2.3, §5)",
            "modeled_p50_us/p99_us": "per-op modeled latency percentiles: "
                                     "critical-path RTTs + MN NIC queueing "
                                     "under SimParams (runner."
                                     "modeled_latency, DESIGN.md §7)",
            "mn_cap_per_us": p.mn_cap, "mn_bw_bytes_per_us": p.mn_bw,
        },
    }

    def _make_store():
        return PointerArray.create(n_slots, mode=mode).populate(
            np.arange(n_slots), np.arange(n_slots))

    for mode in MODES:
        _, wres, _ = _make_store().apply_stream(stream)   # warm the jit cache
        jax.block_until_ready(wres.ok)
        pa = _make_store()          # fresh buffers: apply_stream donates
        t0 = time.perf_counter()
        pa, res, io = pa.apply_stream(stream)
        jax.block_until_ready((res.ok, io.reads))
        dt = time.perf_counter() - t0
        d = io.as_dict()
        d["throughput_mops"] = round(windows * b / dt / 1e6, 4)
        d["wall_s"] = round(dt, 4)
        d.update(runner.modeled_throughput(io, p, n_ops=windows * b))
        lat = runner.modeled_latency(pa.cfg, ops.kinds, res, p)
        d.update({f"modeled_{k}": v
                  for k, v in runner.latency_stats(lat).as_dict().items()})
        out[mode.name] = d
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"\n== engine_json -> {path} ==")
    for m in MODES:
        d = out[m.name]
        print(f"{m.name:6s} modeled={d['modeled_mops']:8.3f} Mops/s "
              f"p50={d['modeled_p50_us']:7.1f}us "
              f"p99={d['modeled_p99_us']:8.1f}us "
              f"wall={d['throughput_mops']:8.3f} Mops/s "
              f"mn_iops={d['mn_iops']:8d} combined={d['combined']:6d}")
    return out


KERNELS_PATH = "BENCH_kernels.fast.json"


def bench_kernels_json(fast=True, path=None):
    """Kernel-dispatch seam smoke (DESIGN.md §10) -> ``BENCH_kernels.fast.json``.

    Runs the fast-size engine benchmark once per kernel backend — the jnp
    reference and the forced Pallas kernels (interpret mode off-TPU, the
    compiled kernels on TPU) — and **asserts** the two verb bills and the
    full per-window Results are bit-equal per SyncMode before writing both
    wall-clocks + provenance.  Always fast-sized regardless of ``--fast``:
    this is CI's bit-identity gate on the dispatch seam, not a perf
    trajectory (that is ``BENCH_engine*.json``); the artifact is uploaded so
    a failing run shows *which* counter diverged.
    """
    path = path or KERNELS_PATH
    n_slots, b, windows = 4096, 1024, 4
    spec = WORKLOADS["write-intensive"]
    ops = generate_window_stream(spec, windows, b, n_slots, b)
    stream = runner.make_stream(ops.kinds, ops.keys % n_slots, ops.values,
                                n_cns=16)
    backends = ("jnp", "pallas")
    out = {
        "config": {"n_slots": n_slots, "batch": b, "windows": windows,
                   "workload": spec.name, "n_cns": 16,
                   "backends": {be: provenance(be) for be in backends},
                   "generated_by":
                       "python -m benchmarks.run --only kernels_json"},
        "metrics": {
            "equality": "per SyncMode, the full verb bill AND every "
                        "per-window Results leaf are asserted bit-equal "
                        "between the jnp reference and the Pallas kernel "
                        "path (DESIGN.md §10)",
            "wall_s": "host-timed fused run_windows scan per backend "
                      "(interpreted Pallas is expected to be slow on CPU "
                      "— equality is the gate here, not speed)",
        },
    }
    for mode in MODES:
        rec, trees = {}, {}
        for be in backends:
            def _mk():
                return PointerArray.create(n_slots, mode=mode,
                                           kernel_backend=be).populate(
                    np.arange(n_slots), np.arange(n_slots))
            _, wres, _ = _mk().apply_stream(stream)      # warm the jit cache
            jax.block_until_ready(wres.ok)
            pa = _mk()
            t0 = time.perf_counter()
            pa, res, io = pa.apply_stream(stream)
            jax.block_until_ready((res.ok, io.reads))
            dt = time.perf_counter() - t0
            trees[be] = (res, io)
            d = io.as_dict()
            d["wall_s"] = round(dt, 4)
            rec[be] = d
        ref_leaves = jax.tree.leaves(trees["jnp"])
        for be in backends[1:]:
            for lx, ly in zip(ref_leaves, jax.tree.leaves(trees[be])):
                assert np.array_equal(np.asarray(lx), np.asarray(ly)), \
                    f"kernels_json/{mode.name}: {be} diverged from jnp"
        out[mode.name] = rec
        print(f"{mode.name:6s} bit-equal across {backends}; wall "
              + "  ".join(f"{be}={rec[be]['wall_s']:.3f}s"
                          for be in backends), flush=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"== kernels_json -> {path} ==")
    return out


YCSB_BASELINE = "BENCH_ycsb.json"
YCSB_N_SHARDS = 4
# thin CNs (64) keep lanes-per-CN near the paper's testbed so baseline local
# WC can't absorb the hot queues (see benchmarks/scenarios.py); n_slots leaves
# headroom above the populated universe for D/E's fresh-key insert frontier
YCSB_FULL = dict(windows=16, batch=2048, n_keys=4096, n_slots=8192,
                 n_clients=64, n_cns=64, credit_table=4096, scan_max=16,
                 seed=7)
YCSB_FAST = dict(windows=8, batch=512, n_keys=1024, n_slots=2048,
                 n_clients=64, n_cns=64, credit_table=1024, scan_max=16,
                 seed=7)


def bench_ycsb_json(fast=False, path=None):
    """The full YCSB core suite (A-F) x SyncMode x {single, 4-way sharded}
    -> ``BENCH_ycsb.json`` — the paper's headline benchmark ("up to 6.6x
    under YCSB") as a committed, machine-readable artifact.

    Per cell: the exact verb bill, MN-IOPS-modeled throughput, and modeled
    latency percentiles (docs/METRICS.md documents every field).  E runs
    real ``OpKind.SCAN`` range reads through the reader-probe engine path
    (DESIGN.md §9); the sharded runs are asserted **bit-equal** to the
    single-device verb bill — including the cross-shard scan sub-runs —
    so the committed file doubles as a regression artifact for the
    partition-split traversal.  ``--fast`` writes ``BENCH_ycsb.fast.json``
    (gitignored; gated by ``check_regression.py``) and refuses to touch
    the committed baseline.

    The matrix drives the engine directly with the radix store's exact
    configuration: under SmartART's in-key-order leaf map, slot == key and
    ``index_read_iops == 1``, so this IS the radix store's bill (and the
    sharded topology has no store-level wrapper anyway).  The store-layer
    API — SmartART scan streams, PointerArray/RaceHash rejection — is
    exercised in ``tests/test_scan.py``.
    """
    if path is None:
        path = "BENCH_ycsb.fast.json" if fast else YCSB_BASELINE
    elif fast and os.path.abspath(path) == os.path.abspath(YCSB_BASELINE):
        raise SystemExit(
            f"--fast must not overwrite the committed full-size baseline "
            f"{YCSB_BASELINE}; pick another path (default: "
            f"BENCH_ycsb.fast.json)")
    c = YCSB_FAST if fast else YCSB_FULL
    p = SimParams()
    heap = c["n_slots"] + c["windows"] * c["batch"]
    heap += -heap % YCSB_N_SHARDS
    out = {
        "config": {**c, "heap_slots": heap, "n_shards": YCSB_N_SHARDS,
                   "fast": fast, "provenance": provenance("auto"),
                   "runner": "repro.core.runner.run_windows / "
                             "repro.dist.store.run_windows_sharded",
                   "generated_by": "python -m benchmarks.run --only ycsb_json"
                                   + (" --fast" if fast else "")},
        "metrics": {
            "modeled_mops": "ops / max(mn_iops/mn_cap, mn_bytes/mn_bw) us — "
                            "MN-NIC-bound throughput (PAPER.md §2.3, §5)",
            "modeled_p50_us/p99_us": "per-op modeled latency percentiles "
                                     "(runner.modeled_latency, DESIGN.md "
                                     "§7/§9)",
            "rows": "total SCAN rows returned (workload E; see "
                    "docs/METRICS.md)",
            "equality": "per workload and mode, every sharded4 verb counter "
                        "(incl. the SCAN leaf traversal) is asserted "
                        "bit-equal to the single-device bill",
            "mn_cap_per_us": p.mn_cap, "mn_bw_bytes_per_us": p.mn_bw,
        },
        "workloads": {},
    }
    bill_keys = [f.name for f in dataclasses.fields(IOMetrics)] + [
        "mn_iops", "rows", "modeled_mops", "modeled_p99_us"]
    for name, spec in YCSB.items():
        ops = generate_ycsb_stream(spec, c["windows"], c["batch"],
                                   c["n_keys"], c["n_clients"], seed=c["seed"])
        stream = runner.make_stream(ops.kinds, ops.keys, ops.values,
                                    n_cns=c["n_cns"])
        counts = np.where(ops.kinds == OpKind.SCAN, ops.values, 0)
        n_ops = int((ops.kinds != OpKind.NOP).sum())
        upd = ops.kinds == OpKind.UPDATE
        out["workloads"][name] = {}
        # compile the reader-probe pass only where SCAN lanes exist (E):
        # with no scans the pass bills nothing, so scan_max=0 is bit-identical
        # on A-D/F while skipping the b*(1+scan_max)-lane second linearization
        wl_scan_max = c["scan_max"] if spec.scan > 0 else 0
        for topo in ("single", f"sharded{YCSB_N_SHARDS}"):
            recs = {}
            for mode in MODES:
                cfg = EngineConfig(n_slots=c["n_slots"], heap_slots=heap,
                                   mode=mode, scan_max=wl_scan_max)
                credits = credit_init(c["credit_table"])
                pk = np.arange(c["n_keys"])
                if topo == "single":
                    st = populate(cfg, store_init(cfg), pk, pk)
                    _, _, res, io = runner.run_windows(cfg, st, credits,
                                                       stream)
                else:
                    mesh = make_local_mesh(data=YCSB_N_SHARDS)
                    st = dstore.sharded_populate(
                        cfg, YCSB_N_SHARDS,
                        dstore.sharded_store_init(cfg, YCSB_N_SHARDS), pk, pk)
                    _, _, res, io = dstore.run_windows_sharded(
                        cfg, mesh, st, credits, stream)
                d = io.as_dict()
                d.update(runner.modeled_throughput(io, p, n_ops=n_ops))
                lat = runner.modeled_latency(cfg, ops.kinds, res, p,
                                             scan_counts=counts)
                d.update({f"modeled_{k}": v for k, v in
                          runner.latency_stats(lat).as_dict().items()})
                d["rows"] = int(np.asarray(res.rows).sum())
                d["pess_ratio"] = round(
                    float((np.asarray(res.pessimistic) & upd).sum()
                          / max(int(upd.sum()), 1)), 4)
                recs[mode.name] = d
            out["workloads"][name][topo] = recs
        # the dist.store contract, extended to SCAN: the sharded traversal
        # bill (leaf reads, per-mode sync verbs, rows) IS the single bill
        single = out["workloads"][name]["single"]
        shard = out["workloads"][name][f"sharded{YCSB_N_SHARDS}"]
        for mode in MODES:
            for k in bill_keys:
                assert single[mode.name][k] == shard[mode.name][k], \
                    f"ycsb/{name}/{mode.name}: sharded {k} != single"
        print(f"{name}: " + "  ".join(
            f"{m.name}={single[m.name]['modeled_mops']:7.3f}"
            for m in MODES), flush=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"== ycsb_json -> {path} ==")
    return out


FIGS = {
    "fig11": fig11_12_throughput_latency,
    "engine_json": bench_engine_json,
    "kernels_json": bench_kernels_json,
    "ycsb_json": bench_ycsb_json,
    "fig13": fig13_skew,
    "fig14": fig14_accuracy,
    "fig15": fig15_params,
    "fig16": fig16_19_race_smart,
    "fig20": fig20_factor,
    "fig21": fig21_wc_efficiency,
    "fig23": fig23_array_size,
    "fig24": fig24_value_size,
    "engine_io": table_engine_io,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(FIGS)
    unknown = [n for n in names if n not in FIGS]
    if unknown:
        raise SystemExit(f"unknown figure(s) {unknown}; choose from {list(FIGS)}")
    t0 = time.time()
    for name in names:
        t1 = time.time()
        FIGS[name](fast=args.fast)
        print(f"[{name} done in {time.time() - t1:.0f}s]", flush=True)
    print(f"\nall benchmarks done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()

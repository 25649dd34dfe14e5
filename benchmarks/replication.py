"""Replication benchmark matrix -> ``BENCH_replication.json``.

Runs the engine benchmark recipe (``benchmarks/run.py bench_engine_json``:
same workload, sizes, seeds, stream) under SNAPSHOT client-centric
replication (FUSEE; DESIGN.md §13) across R in {1, 2, 3} x the 4 SyncModes
x {single, sharded4}, plus an MN-crash failover cell, and records per cell
the exact verb bill, MN-NIC-modeled throughput, and modeled latency
percentiles.  Three properties are *asserted* by the harness, so the
committed file doubles as a regression artifact:

* **R=1 bit-identity** — the ``n_replicas=1`` column is produced by the
  byte-identical program ``BENCH_engine.json`` ran (the replica fan-out is
  a Python-level branch), so its rows must reproduce the engine benchmark
  to the digit (cross-checked against the engine JSON by
  ``check_regression.check_replication``);
* **xR conservation** — every R>1 single-device cell must decompose into
  per-replica bills (``core.types.per_replica_bill``): write-class verbs
  xR, reads x1, ``mn_bytes = ro + R*wr``.  The decomposition is embedded
  in the cell (``per_replica``);
* **failover bit-equality** — the MN-crash cell (one of R=3 replicas dies
  mid-stream) runs ``recovery.run_recovery_replicated`` and is asserted
  bit-equal, per window and per field, to a plain segmented reference that
  swaps ``EngineConfig.n_replicas`` at the crash window — replica death
  costs only the control-plane ``recovery_io``, never a data-plane verb.
  The pre-crash window prefix is additionally asserted bit-equal to the
  crash-free R=3 run's prefix.

The headline the grid exists to show: replication multiplies the write
fan-out on a *fixed* MN fleet, so every mode's modeled Mops/s drops with
R — but CIDER's global write combining collapses W writes into one
replicated combined write, so its *lead* over OSYNC/SPIN/MCS grows with R
(gated per R by ``check_regression``; verdict recorded in DESIGN.md §13).

    PYTHONPATH=src python -m benchmarks.replication [--fast]

``--fast`` writes the gitignored ``BENCH_replication.fast.json`` (CI calls
this via ``make bench-replication-smoke``); the committed full-size
baseline is regenerated without ``--fast``.
"""
from __future__ import annotations

import os

# the sharded4 runs need >= 4 host devices, pinned BEFORE jax init
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

import argparse
import dataclasses
import json
import time

import numpy as np

import jax

from repro.core import runner
from repro.core.credits import credit_init
from repro.core.engine import populate, store_init, store_view
from repro.core.simnet import SimParams
from repro.core.types import (EngineConfig, IOMetrics, SyncMode,
                              per_replica_bill)
from repro.dist import store as dstore
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.recovery import mn_crash, run_recovery_replicated, slice_stream
from repro.stores import PointerArray
from repro.workloads.ycsb import WORKLOADS, generate_window_stream

from benchmarks.provenance import provenance

MODES = [SyncMode.OSYNC, SyncMode.SPIN, SyncMode.MCS, SyncMode.CIDER]
REPLICAS = (1, 2, 3)
N_SHARDS = 4
CRASH_R = 3                  # the MN-crash cell: R=3, replica 2 dies ...
CRASH_DEAD = (2,)            # ... at windows // 2, survivors (0, 1)
FULL_BASELINE = "BENCH_replication.json"
# exactly bench_engine_json's recipe — R=1 must reproduce BENCH_engine.json
FULL = dict(n_slots=65_536, batch=4096, windows=16)
FAST = dict(n_slots=4096, batch=1024, windows=4)


def _sum_io(io: IOMetrics) -> IOMetrics:
    return jax.tree.map(lambda x: np.asarray(x).sum(), io)


def _cell(cfg: EngineConfig, ops, res, io_w: IOMetrics, p: SimParams,
          n_ops: int) -> dict:
    io = _sum_io(io_w)
    d = io.as_dict()
    d.update(runner.modeled_throughput(io, p, n_ops=n_ops))
    lat = runner.modeled_latency(cfg, ops.kinds, res, p)
    d.update({f"modeled_{k}": v
              for k, v in runner.latency_stats(lat).as_dict().items()})
    return d


def _assert_window_prefix_equal(a: IOMetrics, b: IOMetrics, upto: int,
                                what: str) -> None:
    for f in dataclasses.fields(IOMetrics):
        x = np.asarray(getattr(a, f.name))[:upto]
        y = np.asarray(getattr(b, f.name))[:upto]
        assert np.array_equal(x, y), \
            f"{what}: pre-crash IOMetrics.{f.name} prefix diverged"


def _mn_crash_cell(cfg: EngineConfig, c: dict, ops, stream_fn, p: SimParams,
                   r3_io_w: IOMetrics) -> dict:
    """R=3 -> replica 2 dies at windows//2: orchestrated failover run,
    asserted bit-equal to the plain segmented n_replicas-swap reference."""
    w = c["windows"]
    wc = w // 2
    mn = mn_crash(w, CRASH_R, dead_replicas=CRASH_DEAD, at_window=wc)
    pk = np.arange(cfg.n_slots)

    run = run_recovery_replicated(
        cfg, populate(cfg, store_init(cfg), pk, pk), credit_init(4096),
        stream_fn(), mn)

    # drop-mask reference: same segments, cfg swap, no promotion step
    st = populate(cfg, store_init(cfg), pk, pk)
    cr = credit_init(4096)
    stream = stream_fn()
    ress, ios = [], []
    prev_alive = None
    for lo, hi, surv in mn.segments():
        seg = slice_stream(stream, lo, hi)
        st, cr, res, io = runner.run_windows(
            dataclasses.replace(cfg, n_replicas=len(surv)), st, cr, seg,
            io_per_window=True, prev_alive=prev_alive)
        prev_alive = seg.alive[-1]
        ress.append(res)
        ios.append(io)
    cat = lambda *xs: np.concatenate([np.asarray(x) for x in xs],  # noqa: E731
                                     axis=0)
    ref_io = jax.tree.map(cat, *ios)
    ref_res = jax.tree.map(cat, *ress)
    for f in dataclasses.fields(IOMetrics):
        a, b = np.asarray(getattr(run.io, f.name)), \
            np.asarray(getattr(ref_io, f.name))
        assert np.array_equal(a, b), \
            f"mn_crash/{cfg.mode.name}: failover IOMetrics.{f.name} " \
            f"diverged from the segmented n_replicas-swap reference"
    for f in dataclasses.fields(ref_res):
        a = np.asarray(getattr(run.results, f.name))
        b = np.asarray(getattr(ref_res, f.name))
        assert np.array_equal(a, b), \
            f"mn_crash/{cfg.mode.name}: failover Results.{f.name} diverged"
    e1, v1 = store_view(run.state)
    e2, v2 = store_view(st)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    # the crash-free R=3 run shares the pre-crash prefix bit-for-bit
    _assert_window_prefix_equal(run.io, r3_io_w, wc,
                                f"mn_crash/{cfg.mode.name}")

    io = _sum_io(run.io)
    d = io.as_dict()
    d.update(runner.modeled_throughput(io, p, n_ops=w * c["batch"]))
    d["asserted_equal"] = True
    d["recovery_io"] = run.recovery_io[0]
    d["windows"] = {"mn_iops": [int(np.asarray(
        jax.tree.map(lambda x, i=i: x[i], run.io).mn_iops))
        for i in range(w)]}
    return d


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--path", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    path = args.path or ("BENCH_replication.fast.json" if args.fast
                         else FULL_BASELINE)
    if args.fast and os.path.abspath(path) == os.path.abspath(FULL_BASELINE):
        raise SystemExit(
            f"--fast must not overwrite the committed full-size baseline "
            f"{FULL_BASELINE}; pick another path")
    c = FAST if args.fast else FULL
    n_slots, b, windows = c["n_slots"], c["batch"], c["windows"]
    spec = WORKLOADS["write-intensive"]
    ops = generate_window_stream(spec, windows, b, n_slots, b)

    def stream_fn():
        return runner.make_stream(ops.kinds, ops.keys % n_slots, ops.values,
                                  n_cns=16)

    out = {
        "config": {**c, "workload": spec.name, "theta": spec.theta,
                   "n_cns": 16, "n_shards": N_SHARDS,
                   "replicas": list(REPLICAS), "replica_rtt":
                   SimParams().replica_rtt, "fast": args.fast,
                   "mn_crash": {"n_replicas": CRASH_R,
                                "dead_replicas": list(CRASH_DEAD),
                                "crash_window": windows // 2},
                   "provenance": provenance("auto"),
                   "runner": "repro.core.runner.run_windows / "
                             "repro.dist.store.run_windows_sharded / "
                             "repro.recovery.run_recovery_replicated",
                   "generated_by": "python -m benchmarks.replication"
                                   + (" --fast" if args.fast else "")},
        "metrics": {
            "io_counters": "exact RDMA-verb bill SUMMED over all windows; "
                           "write-class verbs (writes/cas/faa/retries/"
                           "repair_cas) carry the xR SNAPSHOT fan-out, "
                           "reads bill to one replica (DESIGN.md §13)",
            "modeled_mops": "ops / max(mn_iops/mn_cap, mn_bytes/mn_bw) us "
                            "on the FIXED aggregate MN fleet — replication "
                            "consumes shared NIC budget, so Mops/s drops "
                            "with R while CIDER's combining lead grows",
            "per_replica": "R>1 single cells: per-replica-MN bill "
                           "decomposition (core.types.per_replica_bill); "
                           "entry 0 is the primary (all reads + observable "
                           "counters), entries 1..R-1 the write-only "
                           "secondaries; summing reproduces the cell bill",
            "equality": "per R and mode, every sharded4 verb counter is "
                        "asserted bit-equal to the single-device bill; the "
                        "R=1 rows are asserted equal to the engine "
                        "benchmark recipe by construction and cross-checked "
                        "against BENCH_engine*.json by check_regression",
            "mn_crash": "R=3 with replica 2 dying at windows//2 through "
                        "run_recovery_replicated, asserted bit-equal to the "
                        "segmented n_replicas-swap reference (promotion is "
                        "control-plane only: recovery_io, no data verbs)",
        },
        "replicas": {},
        "mn_crash": {"modes": {}},
    }
    bill_keys = [f.name for f in dataclasses.fields(IOMetrics)] + ["mn_iops"]
    t0 = time.time()
    io_single: dict[tuple[int, SyncMode], IOMetrics] = {}
    io_single_w: dict[tuple[int, SyncMode], IOMetrics] = {}
    for r in REPLICAS:
        out["replicas"][str(r)] = {"single": {}, f"sharded{N_SHARDS}": {}}
        p = dataclasses.replace(SimParams(), n_replicas=r)
        for mode in MODES:
            t1 = time.time()
            pa = PointerArray.create(n_slots, mode=mode,
                                     n_replicas=r).populate(
                np.arange(n_slots), np.arange(n_slots))
            cfg = pa.cfg
            pa, res, io_w = pa.apply_stream(stream_fn(), io_per_window=True)
            io_single[(r, mode)] = _sum_io(io_w)
            io_single_w[(r, mode)] = io_w
            d = _cell(cfg, ops, res, io_w, p, windows * b)
            if r > 1:
                d["per_replica"] = per_replica_bill(
                    io_single[(1, mode)], io_single[(r, mode)], r)
            out["replicas"][str(r)]["single"][mode.name] = d

            pk = np.arange(n_slots)
            sst = dstore.sharded_populate(
                cfg, N_SHARDS, dstore.sharded_store_init(cfg, N_SHARDS),
                pk, pk)
            mesh = make_local_mesh(data=N_SHARDS)
            _, _, sres, sio_w = dstore.run_windows_sharded(
                cfg, mesh, sst, credit_init(4096), stream_fn(),
                io_per_window=True)
            sd = _cell(cfg, ops, sres, sio_w, p, windows * b)
            for k in bill_keys + ["modeled_mops", "modeled_p99_us"]:
                assert sd[k] == d[k], \
                    f"R{r}/{mode.name}: sharded {k} != single"
            out["replicas"][str(r)][f"sharded{N_SHARDS}"][mode.name] = sd
            print(f"[R{r}/{mode.name}: modeled={d['modeled_mops']:8.3f} "
                  f"mn_iops={d['mn_iops']:8d} cas={d['cas']:6d} "
                  f"({time.time() - t1:.0f}s)]", flush=True)
    for mode in MODES:
        t1 = time.time()
        cfg = dataclasses.replace(
            PointerArray.create(n_slots, mode=mode).cfg, n_replicas=CRASH_R)
        p = dataclasses.replace(SimParams(), n_replicas=CRASH_R)
        out["mn_crash"]["modes"][mode.name] = _mn_crash_cell(
            cfg, c, ops, stream_fn, p, io_single_w[(CRASH_R, mode)])
        d = out["mn_crash"]["modes"][mode.name]
        print(f"[mn_crash/{mode.name}: modeled={d['modeled_mops']:8.3f} "
              f"rearm={d['recovery_io']['repair_rearm_cas']} "
              f"({time.time() - t1:.0f}s)]", flush=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"\n== replication -> {path} ({time.time() - t0:.0f}s) ==")
    for r in REPLICAS:
        row = out["replicas"][str(r)]["single"]
        cider = row["CIDER"]["modeled_mops"]
        best_rival = max(row[m.name]["modeled_mops"]
                         for m in MODES if m != SyncMode.CIDER)
        print(f"R={r}  " + "  ".join(
            f"{m.name}: {row[m.name]['modeled_mops']:8.3f}" for m in MODES)
            + f"   CIDER lead x{cider / best_rival:.2f}")


if __name__ == "__main__":
    main()

"""Crash-recovery benchmark matrix -> ``BENCH_recovery.json``.

Runs every ``repro.workloads.recovery`` scenario under every ``SyncMode``
through the liveness-aware fused runner and records the recovery bill the
paper's §4.6 epoch protocol implies but never measures:

* ``repair_cas`` — orphan-repair verbs (stale-epoch READ + break CAS, plus
  SPIN's lease-expiry polls): the recovery I/O differentiator.  CIDER's
  combined queues strand ONE lock per queue; MCS strands the whole chain
  of dead nodes; SPIN waiters burn MN CAS polls for the entire lease.
* ``p99_post_crash_us`` — modeled tail latency of the windows from the
  first crash on (lease waits charged to the blocked queues).
* ``windows_to_repair`` / ``orphan_slot_windows`` / ``stranded_final`` —
  the repair timeline (``repro.recovery.time_to_repair``).

Streams run ``warm`` windows before the measured region so the gate
compares steady-state behavior (CIDER's §4.3 credits need two hot windows
to warm up; crashes land mid-steady-state, as on a real fleet); all
metrics below are over the measured windows.

For ``crash_storm`` the harness additionally executes the 4-way *shard
failover* path (shards die at the crash window, survivors re-own their
slot partitions via ``dist.store.failover_reown``) and asserts, for every
mode, that the post-failover per-window bill and results are bit-equal to
the single-device run with the same CN drop mask — shard death costs only
the reported control-plane ``recovery_io``, never a data-plane verb.

    PYTHONPATH=src python -m benchmarks.recovery [--fast] [--only crash_storm]

``--fast`` writes the gitignored ``BENCH_recovery.fast.json`` (CI calls
this via ``make bench-recovery-smoke``); the committed full-size baseline
is regenerated without ``--fast``.
"""
from __future__ import annotations

import os

# the 4-way failover runs need >= 4 host devices, pinned BEFORE jax init
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
from repro.core.types import EngineConfig, IOMetrics, SyncMode
from repro.dist import store as dstore
from repro.launch.compile_cache import enable_compile_cache
from repro.recovery import (FailoverEvent, run_recovery, run_recovery_sharded,
                            time_to_repair)
from repro.workloads.recovery import RECOVERY_SCENARIOS

from benchmarks.provenance import provenance

MODES = [SyncMode.OSYNC, SyncMode.SPIN, SyncMode.MCS, SyncMode.CIDER]
N_SHARDS = 4
SURVIVORS = (0, 2)       # shards 1 and 3 die with the CN storm
FULL_BASELINE = "BENCH_recovery.json"
# same thin-CN shape as benchmarks/scenarios.py; `warm` windows precede the
# measured region so CIDER's credits are steady when the crash hits
FULL = dict(windows=24, warm=8, batch=2048, n_keys=4096, n_clients=64,
            n_cns=64, credit_table=4096, seed=7)
FAST = dict(windows=12, warm=4, batch=256, n_keys=512, n_clients=64,
            n_cns=64, credit_table=1024, seed=7)
# scenario-specific membership-event overrides, phased past the warm region
def _overrides(name: str, c: dict) -> dict:
    warm, meas = c["warm"], c["windows"]
    if name == "crash_storm":
        return {"crash_window": warm + meas // 3}
    if name == "rolling_restart":
        return {"start": warm + 1}
    if name == "elastic_scale":
        return {"join_window": warm + meas // 3,
                "leave_window": warm + 2 * meas // 3}
    return {}


def _cfg(mode: SyncMode, c: dict) -> EngineConfig:
    total = c["warm"] + c["windows"]
    heap = c["n_keys"] + total * c["batch"]
    heap += -heap % N_SHARDS
    return EngineConfig(n_slots=c["n_keys"], heap_slots=heap, mode=mode)


def _round(x) -> list:
    return [round(float(v), 4) for v in np.asarray(x)]


def _io_slice(io: IOMetrics, lo: int) -> IOMetrics:
    return jax.tree.map(lambda x: np.asarray(x)[lo:], io)


def _metrics(cfg: EngineConfig, c: dict, ops, run, crash_w: int | None,
             p: SimParams) -> dict:
    warm = c["warm"]
    kinds = np.asarray(ops.kinds)
    io_m = _io_slice(run.io, warm)
    io_sum = IOMetrics(**{f.name: getattr(io_m, f.name).sum()
                          for f in dataclasses.fields(IOMetrics)})
    valid_m = run.valid[warm:]
    lat = runner.modeled_latency(cfg, kinds, run.results, p, valid=run.valid)
    lat_m = lat[warm:]
    n_w = valid_m.sum(-1)
    out = runner.modeled_throughput(io_sum, p, n_ops=int(n_w.sum()))
    out.update(runner.latency_stats(lat_m).as_dict())
    ttr = time_to_repair(run.io, crash_w)
    out.update(ttr)
    out["mn_iops"] = int(np.asarray(io_sum.mn_iops))
    out["recovery_overhead"] = round(
        int(io_sum.repair_cas) / max(int(np.asarray(io_sum.mn_iops)), 1), 6)
    post = lat[crash_w:] if crash_w is not None else lat_m
    out["p99_post_crash_us"] = round(float(np.nanpercentile(post, 99)), 2)
    mops_w = [runner.modeled_throughput(
        jax.tree.map(lambda x, w=w: x[w], io_m), p,
        n_ops=int(n_w[w]))["modeled_mops"] for w in range(len(n_w))]
    out["windows"] = {
        "repair_cas": [int(v) for v in getattr(io_m, "repair_cas")],
        "orphan_windows": [int(v) for v in getattr(io_m, "orphan_windows")],
        "modeled_mops": _round(mops_w),
        "p99_us": _round(np.nanpercentile(lat_m, 99, axis=-1)),
    }
    return out


def _assert_failover_equal(cfg: EngineConfig, name: str, mode: SyncMode,
                           single, sharded) -> None:
    for f in dataclasses.fields(IOMetrics):
        a = np.asarray(getattr(single.io, f.name))
        b = np.asarray(getattr(sharded.io, f.name))
        assert (a == b).all(), \
            f"{name}/{mode.name}: failover IOMetrics.{f.name} diverged " \
            f"from the single-device drop-mask run"
    for f in dataclasses.fields(single.results):
        a = np.asarray(getattr(single.results, f.name))
        b = np.asarray(getattr(sharded.results, f.name))
        assert (a == b).all(), \
            f"{name}/{mode.name}: failover Results.{f.name} diverged"
    ex1, v1 = store_view(single.state)
    ex2, v2 = dstore.sharded_store_view(cfg, len(SURVIVORS), sharded.state)
    np.testing.assert_array_equal(np.asarray(ex1), np.asarray(ex2))
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated scenario subset")
    ap.add_argument("--path", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    path = args.path or ("BENCH_recovery.fast.json" if args.fast
                         else FULL_BASELINE)
    if args.fast and os.path.abspath(path) == os.path.abspath(FULL_BASELINE):
        raise SystemExit(
            f"--fast must not overwrite the committed full-size baseline "
            f"{FULL_BASELINE}; pick another path")
    names = args.only.split(",") if args.only else list(RECOVERY_SCENARIOS)
    unknown = [n for n in names if n not in RECOVERY_SCENARIOS]
    if unknown:
        raise SystemExit(f"unknown scenario(s) {unknown}; "
                         f"choose from {list(RECOVERY_SCENARIOS)}")
    c = FAST if args.fast else FULL
    total = c["warm"] + c["windows"]
    p = SimParams()
    out = {
        "config": {**c, "n_shards": N_SHARDS, "survivors": list(SURVIVORS),
                   "fast": args.fast, "lease_us": p.lease_us,
                   "provenance": provenance("auto"),
                   "runner": "repro.recovery.run_recovery / "
                             "run_recovery_sharded",
                   "generated_by": "python -m benchmarks.recovery"
                                   + (" --fast" if args.fast else "")},
        "metrics": {
            "repair_cas": "orphan-repair verbs over the measured windows: "
                          "stale-epoch READ + break CAS per stranded lock "
                          "node, plus SPIN lease polls (engine step 5b)",
            "p99_post_crash_us": "modeled p99 of windows >= the first crash "
                                 "(lease waits charged to blocked queues; "
                                 "OSYNC is lock-free and strands nothing — "
                                 "the paper's §2.2 tradeoff runs the other "
                                 "way on every non-crash window)",
            "windows_to_repair": "windows from the first crash until the "
                                 "last repair activity",
            "recovery_overhead": "repair_cas / mn_iops (measured windows)",
            "modeled_mops": "MN-NIC-bound throughput over the measured "
                            "(post-warm) windows",
        },
        "scenarios": {},
    }
    t0 = time.time()
    for name in names:
        sc = RECOVERY_SCENARIOS[name]
        ops, sched = sc.generate(total, c["batch"], c["n_keys"],
                                 c["n_clients"], c["n_cns"], seed=c["seed"],
                                 **_overrides(name, c))
        crash_w = sched.first_crash_window()
        pk = sc.populate_keys(c["n_keys"])
        recs: dict = {}
        for mode in MODES:
            cfg = _cfg(mode, c)
            t1 = time.time()
            stream = runner.make_stream(ops.kinds, ops.keys, ops.values,
                                        n_cns=c["n_cns"], alive=sched.alive)
            st = populate(cfg, store_init(cfg), pk, pk)
            run1 = run_recovery(cfg, st, credit_init(c["credit_table"]),
                                stream)
            recs[mode.name] = _metrics(cfg, c, ops, run1, crash_w, p)
            if name == "crash_storm":
                # shard failover rides the same storm: shards die with the
                # CNs, survivors re-own, and the bill must not move
                stream2 = runner.make_stream(ops.kinds, ops.keys, ops.values,
                                             n_cns=c["n_cns"],
                                             alive=sched.alive)
                sst = dstore.sharded_populate(
                    cfg, N_SHARDS, dstore.sharded_store_init(cfg, N_SHARDS),
                    pk, pk)
                run2 = run_recovery_sharded(
                    cfg, N_SHARDS, sst, credit_init(c["credit_table"]),
                    stream2, failovers=[FailoverEvent(crash_w, SURVIVORS)])
                _assert_failover_equal(cfg, name, mode, run1, run2)
                recs[mode.name]["failover"] = {
                    "asserted_equal": True, **run2.recovery_io[0]}
            r = recs[mode.name]
            print(f"[{name}/{mode.name}: modeled={r['modeled_mops']:.3f} "
                  f"repair_cas={r['repair_cas']} "
                  f"p99_post={r['p99_post_crash_us']:.0f}us "
                  f"ttr={r['windows_to_repair']}w "
                  f"({time.time() - t1:.0f}s)]", flush=True)
        out["scenarios"][name] = {"crash_window": crash_w,
                                  "description": sc.description,
                                  "modes": recs}
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"\n== recovery -> {path} ({time.time() - t0:.0f}s) ==")
    for name in names:
        row = out["scenarios"][name]["modes"]
        print(f"{name:16s} " + "  ".join(
            f"{m.name}: {row[m.name]['modeled_mops']:.3f}Mops "
            f"rep={row[m.name]['repair_cas']}" for m in MODES))


if __name__ == "__main__":
    main()

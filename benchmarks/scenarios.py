"""Dynamic-contention scenario matrix -> ``BENCH_scenarios.json``.

Runs every ``repro.workloads.dynamic`` scenario under every ``SyncMode`` on
both topologies (single device, 4-way sharded CPU ``data`` mesh) through the
fused traced runner, and records

* per-window trajectories — ``pess_ratio``, ``credit_mass``, ``wc_rate``,
  ``modeled_mops``, ``p99_us`` — making CIDER's AIMD adaptation (§4.3)
  visible as data;
* overall MN-IOPS-modeled throughput and modeled latency percentiles
  (``runner.modeled_throughput`` / ``modeled_latency``), the paper's two
  evaluation axes.

The sharded runs are asserted bit-equal to the single-device bill (the
``dist.store`` equivalence contract), so the committed file doubles as an
end-to-end regression artifact for the 4-way path.

    PYTHONPATH=src python -m benchmarks.scenarios [--fast] [--only churn]

``--fast`` writes the gitignored ``BENCH_scenarios.fast.json`` (CI calls
this via ``make bench-scenarios-smoke``); the committed full-size baseline
is regenerated without ``--fast``.
"""
from __future__ import annotations

import os

# the 4-way sharded runs need >= 4 host devices, pinned BEFORE jax init
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

import argparse
import dataclasses
import json
import time

import numpy as np

from repro.core import runner
from repro.core.credits import credit_init
from repro.core.engine import populate, store_init
from repro.core.simnet import SimParams
from repro.core.types import EngineConfig, IOMetrics, OpKind, SyncMode
from repro.dist import store as dstore
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.workloads.dynamic import SCENARIOS

from benchmarks.provenance import provenance

MODES = [SyncMode.OSYNC, SyncMode.SPIN, SyncMode.MCS, SyncMode.CIDER]
N_SHARDS = 4
FULL_BASELINE = "BENCH_scenarios.json"
# n_cns=64 keeps lanes-per-CN near the paper's testbed (4 clients per CN,
# §5.1): with fat CNs, baseline local WC absorbs most of the hot-key queue
# and understates the contention the paper measures
FULL = dict(windows=32, batch=2048, n_keys=4096, n_clients=64, n_cns=64,
            credit_table=4096, seed=3)
# fast keeps the full config's contention density (batch/n_keys ratio), not
# just its shape — thinner contention would flip the mode ordering CI gates on
FAST = dict(windows=12, batch=256, n_keys=512, n_clients=64, n_cns=64,
            credit_table=1024, seed=3)


def _cfg(mode: SyncMode, c: dict) -> EngineConfig:
    # heap must hold the populate load plus one commit per written key per
    # window (worst case W*B) — undersizing silently drops commits
    heap = c["n_keys"] + c["windows"] * c["batch"]
    heap += -heap % N_SHARDS
    return EngineConfig(n_slots=c["n_keys"], heap_slots=heap, mode=mode)


def _round(x) -> list:
    return [round(float(v), 4) for v in np.asarray(x)]


def _run_one(sc, mode: SyncMode, topo: str, c: dict, ops, stream,
             p: SimParams) -> dict:
    cfg = _cfg(mode, c)
    pk = sc.populate_keys(c["n_keys"])
    credits = credit_init(c["credit_table"])
    if topo == "single":
        st = populate(cfg, store_init(cfg), pk, pk)
        _, _, res, ios, mass = runner.run_windows_traced(cfg, st, credits,
                                                         stream)
    else:
        mesh = make_local_mesh(data=N_SHARDS)
        st = dstore.sharded_populate(
            cfg, N_SHARDS, dstore.sharded_store_init(cfg, N_SHARDS), pk, pk)
        _, _, res, ios, mass = dstore.run_windows_sharded_traced(
            cfg, mesh, st, credits, stream)

    kinds = np.asarray(ops.kinds)
    valid = kinds != OpKind.NOP
    upd = (kinds == OpKind.UPDATE) & valid
    writes_w = np.maximum(upd.sum(-1), 1)
    pess_w = (np.asarray(res.pessimistic) & upd).sum(-1)
    comb_w = (np.asarray(res.combined) & valid).sum(-1)
    lat = runner.modeled_latency(cfg, kinds, res, p, valid=valid)
    n_w = valid.sum(-1)
    ios_np = {f.name: np.asarray(getattr(ios, f.name))
              for f in dataclasses.fields(IOMetrics)}
    io_sum = IOMetrics(**{k: v.sum() for k, v in ios_np.items()})
    # per-window throughput via the same owned binding-constraint rule as
    # the overall number, so the trajectory can't diverge from the gated
    # metric if the cost model evolves
    mops_w = [runner.modeled_throughput(runner.io_window(ios, w), p,
                                        n_ops=int(n_w[w]))["modeled_mops"]
              for w in range(len(n_w))]
    overall = runner.modeled_throughput(io_sum, p, n_ops=int(n_w.sum()))
    overall.update(runner.latency_stats(lat).as_dict())
    overall["pess_ratio"] = round(float(pess_w.sum() / writes_w.sum()), 4)
    overall["wc_rate"] = round(float(comb_w.sum() / writes_w.sum()), 4)
    overall["mn_iops"] = int(np.asarray(io_sum.mn_iops))
    overall["retries"] = int(np.asarray(io_sum.retries))
    overall["windows"] = {
        "pess_ratio": _round(pess_w / writes_w),
        "credit_mass": [int(v) for v in np.asarray(mass)],
        "wc_rate": _round(comb_w / writes_w),
        "modeled_mops": _round(mops_w),
        "p99_us": _round(np.nanpercentile(lat, 99, axis=-1)),
    }
    return overall


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated scenario subset")
    ap.add_argument("--path", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    path = args.path or ("BENCH_scenarios.fast.json" if args.fast
                         else FULL_BASELINE)
    if args.fast and os.path.abspath(path) == os.path.abspath(FULL_BASELINE):
        raise SystemExit(
            f"--fast must not overwrite the committed full-size baseline "
            f"{FULL_BASELINE}; pick another path")
    names = args.only.split(",") if args.only else list(SCENARIOS)
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        raise SystemExit(f"unknown scenario(s) {unknown}; "
                         f"choose from {list(SCENARIOS)}")
    c = FAST if args.fast else FULL
    p = SimParams()
    out = {
        "config": {**c, "n_shards": N_SHARDS, "fast": args.fast,
                   "provenance": provenance("auto"),
                   "runner": "repro.core.runner.run_windows_traced / "
                             "repro.dist.store.run_windows_sharded_traced",
                   "generated_by": "python -m benchmarks.scenarios"
                                   + (" --fast" if args.fast else "")},
        "metrics": {
            "modeled_mops": "ops / max(mn_iops/mn_cap, mn_bytes/mn_bw) us — "
                            "MN-NIC-bound throughput (PAPER.md §2.3, §5)",
            "p50_us/p99_us": "modeled per-op latency percentiles: critical-"
                             "path RTTs + MN NIC queueing under SimParams "
                             "(runner.modeled_latency, DESIGN.md §7)",
            "windows": "per-window trajectories; credit_mass is the total "
                       "credit table mass AFTER each window (§4.3 AIMD)",
            "mn_cap_per_us": p.mn_cap, "mn_bw_bytes_per_us": p.mn_bw,
        },
        "scenarios": {},
    }
    t0 = time.time()
    for name in names:
        sc = SCENARIOS[name]
        ops = sc.generate(c["windows"], c["batch"], c["n_keys"],
                          c["n_clients"], seed=c["seed"])
        stream = runner.make_stream(ops.kinds, ops.keys, ops.values,
                                    n_cns=c["n_cns"])
        out["scenarios"][name] = {}
        for topo in ("single", f"sharded{N_SHARDS}"):
            recs = {}
            for mode in MODES:
                t1 = time.time()
                recs[mode.name] = _run_one(sc, mode, topo, c, ops, stream, p)
                print(f"[{name}/{topo}/{mode.name}: "
                      f"modeled={recs[mode.name]['modeled_mops']:.3f} Mops/s "
                      f"p99={recs[mode.name]['p99_us']:.1f}us "
                      f"({time.time() - t1:.0f}s)]", flush=True)
            out["scenarios"][name][topo] = recs
        # dist.store equivalence contract: the sharded bill IS the
        # single-device bill
        single, shard = (out["scenarios"][name]["single"],
                         out["scenarios"][name][f"sharded{N_SHARDS}"])
        for mode in MODES:
            for k in ("modeled_mops", "mn_iops", "pess_ratio", "p99_us"):
                assert single[mode.name][k] == shard[mode.name][k], \
                    f"{name}/{mode.name}: sharded {k} diverged from single"
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"\n== scenarios -> {path} ({time.time() - t0:.0f}s) ==")
    for name in names:
        row = out["scenarios"][name]["single"]
        print(f"{name:14s} " + "  ".join(
            f"{m.name}={row[m.name]['modeled_mops']:7.3f}" for m in MODES))


if __name__ == "__main__":
    main()

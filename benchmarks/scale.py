"""Weak-scaling + open-loop scale benchmark -> ``BENCH_scale.json``.

Two production-shape axes the closed-loop, fixed-mesh suites cannot see
(docs/METRICS.md documents every field; DESIGN.md §12 the window contract):

* **Weak scaling** — a fixed per-shard unit problem (slots, lanes, CNs)
  replicated over mesh sizes N ∈ {1, 4, 8, 16} (``--fast``: {1, 2, 4}),
  keys Zipf-distributed over the *global* universe so the hot head
  concentrates on shard 0 — DINOMO's load-imbalance regime.  Each mesh runs
  the sharded fused scan with ``per_shard_io=True``; the mesh's modeled
  throughput is bound by the HOTTEST shard's NIC service time (parallel MN
  NICs serve their partitions concurrently), and weak-scaling efficiency is
  ``mops_N / (N * mops_1)``.  CIDER's combined queues flatten the hot
  shard's verb bill, which is exactly why its efficiency curve must stay
  above the committed floor while the spin/CAS rivals sag.

* **Open-loop arrivals** — per-CN Poisson (and one bursty MMPP cell)
  offered-load sweeps through ``repro.workloads.openloop`` on a fixed mesh:
  latency vs offered load (the hockey stick), where queueing delay is
  backlog windows x the calibrated window length + the in-window modeled
  completion time.  All modes share one arrival draw per load point, and
  one clock: the window length is provisioned as the slowest mode's
  full-window service time, so the curves are comparable.

Both sections are exact-verb-bill modeled metrics — bit-deterministic given
the seeds, with tight regression floors (``check_regression.py --scale``).
Two bit-identity contracts are asserted on every run: the sharded bill
equals the single-device bill on the same problem, and a dense re-pack of
the partially-filled open-loop windows (valid lanes to the front, explicit
CN plane carried) leaves the bill and the store bit-identical.

    PYTHONPATH=src python -m benchmarks.scale [--fast]
"""
from __future__ import annotations

import os

# the full run scales to a 16-way simulated mesh; pinned BEFORE jax init.
# CI's bench matrix presets 4 or 8 — respected, with the gated fast meshes
# {1, 2, 4} chosen to fit the smallest leg so every leg gates identically.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=16").strip()

import argparse
import json

import numpy as np

import jax

from repro.core import runner
from repro.core.credits import credit_init
from repro.core.engine import populate, store_init
from repro.core.simnet import SimParams
from repro.core.types import EngineConfig, SyncMode
from repro.dist import store as dstore
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.workloads.openloop import (OpenLoopSpec, dense_repack,
                                      generate_openloop_stream,
                                      open_loop_latency)
from repro.workloads.ycsb import WORKLOADS, generate_window_stream

from benchmarks.provenance import provenance

MODES = [SyncMode.OSYNC, SyncMode.SPIN, SyncMode.MCS, SyncMode.CIDER]
FULL_BASELINE = "BENCH_scale.json"

# per-shard unit problem (weak scaling replicates it N times); the full unit
# puts 131072 slots on every shard, so the 16-way mesh carries a 2.09M-key
# populated store — the donated-buffer scan must stay resident, which is what
# the packed per-slot metadata word (engine.pack_meta) buys.
FULL = dict(meshes=[1, 4, 8, 16], slots1=131_072, lanes1=512, cns1=64,
            windows=12, warmup=4, theta=0.99, seed=11, ol_mesh=8,
            ol_windows=16, rhos=[0.5, 0.7, 0.85, 0.95, 1.05], mmpp_rho=0.85)
FAST = dict(meshes=[1, 2, 4], slots1=4096, lanes1=256, cns1=32,
            windows=8, warmup=4, theta=0.99, seed=11, ol_mesh=2,
            ol_windows=8, rhos=[0.6, 0.9, 1.05], mmpp_rho=0.9)

# the committed full-size artifact must demonstrate CIDER holding at least
# this weak-scaling efficiency at the largest mesh (acceptance floor; the
# CI gate floors in baselines.json are the exact measured values)
CIDER_EFF_FLOOR = 0.25


def _window_ticks(io, p: SimParams) -> np.ndarray:
    """(W,) modeled service ticks per window: shards' NICs serve their
    partitions concurrently WITHIN a window (take the hottest), windows are
    synchronization barriers (sum over them at the call site)."""
    iops = np.asarray(io.mn_iops, np.float64)
    byts = np.asarray(io.mn_bytes, np.float64)
    return np.maximum(iops / p.mn_cap, byts / p.mn_bw).max(-1)


def _assert_bill_equal(a, b, what: str):
    for f in a.__dataclass_fields__:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert np.array_equal(x, y), f"{what}: IOMetrics.{f} diverged"


def _weak_scaling(c: dict, p: SimParams, spec) -> tuple[dict, dict]:
    """mesh -> mode -> record; plus the efficiency table."""
    avail = jax.device_count()
    meshes = [n for n in c["meshes"] if n <= avail]
    if meshes != c["meshes"]:
        print(f"NOTE: only {avail} devices — meshes clamped to {meshes} "
              f"(gated meshes missing from the JSON fail the gate loudly)")
    weak: dict[str, dict] = {}
    for n in meshes:
        n_slots = c["slots1"] * n
        b = c["lanes1"] * n
        n_cns = c["cns1"] * n
        heap = n_slots + c["windows"] * b
        heap += -heap % n
        ops = generate_window_stream(spec, c["windows"], b, n_slots, n_cns,
                                     seed=c["seed"], theta=c["theta"])
        stream = runner.make_stream(ops.kinds, ops.keys % n_slots, ops.values,
                                    n_cns=n_cns)
        mesh = make_local_mesh(data=n)
        pk = np.arange(n_slots)
        n_ops = c["windows"] * b
        weak[str(n)] = {}
        wu = c["warmup"]
        n_steady = (c["windows"] - wu) * b
        for mode in MODES:
            cfg = EngineConfig(n_slots=n_slots, heap_slots=heap, mode=mode)
            st = dstore.sharded_populate(
                cfg, n, dstore.sharded_store_init(cfg, n), pk, pk)
            _, _, res, io = dstore.run_windows_sharded(
                cfg, mesh, st, credit_init(n_slots), stream,
                per_shard_io=True, io_per_window=True)
            # steady state after the AIMD credits warm up (the engine-table
            # bench gates the same regime): mops over the post-warmup windows
            win_ticks = _window_ticks(io, p)
            ticks = float(win_ticks[wu:].sum())
            lat = runner.modeled_latency(cfg, ops.kinds, res, p)[wu:]
            iops = np.asarray(io.mn_iops)[wu:]
            rec = {
                "modeled_mops": round(n_steady / ticks, 4),
                "modeled_ticks_us": round(ticks, 2),
                "modeled_mops_with_warmup": round(
                    n_ops / float(win_ticks.sum()), 4),
                "shard_mn_iops": [int(x) for x in iops.sum(0)],
                "hot_shard_imbalance": round(
                    float(iops.sum(0).max() / max(iops.sum(0).mean(), 1e-9)),
                    3),
                "mn_iops": int(iops.sum()),
                "mn_bytes": int(np.asarray(io.mn_bytes)[wu:].sum()),
                "combined": int(np.asarray(io.combined)[wu:].sum()),
                "modeled_p99_us": runner.latency_stats(lat).p99_us,
            }
            weak[str(n)][mode.name] = rec
            if n == meshes[0] and n == 1:
                # mesh bit-identity: the sharded per-shard bill must sum to
                # the single-device engine's bill on the identical problem
                st1 = populate(cfg, store_init(cfg), pk, pk)
                _, _, _, io1 = runner.run_windows(cfg, st1,
                                                  credit_init(n_slots),
                                                  stream, io_per_window=True)
                summed = jax.tree.map(lambda x: np.asarray(x).sum(-1), io)
                _assert_bill_equal(summed, io1, f"scale/mesh1/{mode.name}")
        print(f"mesh {n:2d}: " + "  ".join(
            f"{m.name}={weak[str(n)][m.name]['modeled_mops']:9.3f}"
            for m in MODES), flush=True)
    eff = {m.name: {} for m in MODES}
    base = weak.get("1", {})
    for n_str, modes in weak.items():
        n = int(n_str)
        for m in MODES:
            if n > 1 and m.name in base:
                eff[m.name][n_str] = round(
                    modes[m.name]["modeled_mops"]
                    / (n * base[m.name]["modeled_mops"]), 4)
    return weak, eff


def _open_loop(c: dict, p: SimParams, spec, window_us: float) -> dict:
    n = c["ol_mesh"]
    if n > jax.device_count():
        print(f"NOTE: open-loop mesh {n} > {jax.device_count()} devices — "
              f"section skipped")
        return {}
    n_slots = c["slots1"] * n
    n_cns = c["cns1"] * n
    lanes = c["lanes1"] // c["cns1"]
    heap = n_slots + c["ol_windows"] * n_cns * lanes
    heap += -heap % n
    mesh = make_local_mesh(data=n)
    pk = np.arange(n_slots)

    def run_mode(mode, ol):
        cfg = EngineConfig(n_slots=n_slots, heap_slots=heap, mode=mode)
        st = dstore.sharded_populate(
            cfg, n, dstore.sharded_store_init(cfg, n), pk, pk)
        stream = runner.make_stream(ol.kinds, ol.keys % n_slots, ol.values,
                                    n_cns=n_cns, lanes_per_cn=lanes,
                                    valid=ol.valid, cn=ol.cn)
        st, cr, res, io = dstore.run_windows_sharded(
            cfg, mesh, st, credit_init(n_slots), stream)
        lat = runner.modeled_latency(cfg, ol.kinds, res, p, valid=ol.valid)
        total = open_loop_latency(ol, lat, window_us)
        stats = runner.latency_stats(total)
        return cfg, st, io, {
            "rho": None,  # filled by caller
            "p50_us": stats.p50_us, "p99_us": stats.p99_us,
            "offered": ol.offered, "delivered": ol.delivered,
            "mean_delay_windows": round(
                float(ol.delay_windows[ol.valid].mean()), 3)
            if ol.delivered else 0.0,
        }

    out = {"mesh": n, "window_us": round(window_us, 2),
           "rhos": c["rhos"], "curves": {m.name: [] for m in MODES},
           "mmpp": {}}
    for rho in c["rhos"]:
        # one arrival draw per load point, shared by all four modes
        ol = generate_openloop_stream(OpenLoopSpec(
            n_cns=n_cns, lanes_per_cn=lanes, windows=c["ol_windows"],
            rho=rho, n_keys=n_slots, mix=spec, theta=c["theta"],
            seed=c["seed"] + int(rho * 100)))
        for mode in MODES:
            _, _, _, rec = run_mode(mode, ol)
            rec["rho"] = rho
            out["curves"][mode.name].append(rec)
        row = out["curves"]
        print(f"rho {rho:4.2f}: " + "  ".join(
            f"{m.name} p99={row[m.name][-1]['p99_us']:9.1f}us"
            for m in MODES), flush=True)

    # bursty MMPP cell at one load point, same mean rate as its Poisson twin
    olm = generate_openloop_stream(OpenLoopSpec(
        n_cns=n_cns, lanes_per_cn=lanes, windows=c["ol_windows"],
        rho=c["mmpp_rho"], n_keys=n_slots, mix=spec, theta=c["theta"],
        arrival="mmpp", seed=c["seed"] + 5000))
    for mode in MODES:
        _, _, _, rec = run_mode(mode, olm)
        rec["rho"] = c["mmpp_rho"]
        rec["burst_windows_frac"] = round(float(olm.phases.mean()), 3)
        out["mmpp"][mode.name] = rec

    # dense-repack bit-identity (DESIGN.md §12): pack valid lanes to the
    # front carrying the CN plane — bill and store must not move at all
    ol = generate_openloop_stream(OpenLoopSpec(
        n_cns=n_cns, lanes_per_cn=lanes, windows=c["ol_windows"],
        rho=0.8, n_keys=n_slots, mix=spec, theta=c["theta"],
        seed=c["seed"] + 9000))
    rp = dense_repack(ol)
    cfg, st_a, io_a, _ = run_mode(SyncMode.CIDER, ol)
    _, st_b, io_b, _ = run_mode(SyncMode.CIDER, rp)
    _assert_bill_equal(io_a, io_b, "scale/open_loop/dense_repack")
    ex_a, v_a = dstore.sharded_store_view(cfg, n, st_a)
    ex_b, v_b = dstore.sharded_store_view(cfg, n, st_b)
    assert (np.asarray(ex_a) == np.asarray(ex_b)).all() and \
        (np.asarray(v_a) == np.asarray(v_b)).all(), \
        "scale/open_loop/dense_repack: store view diverged"

    # sharded-vs-single bit-identity on a partially-filled stream: invalid
    # lanes bill zero verbs on both paths
    st1 = populate(cfg, store_init(cfg), pk, pk)
    stream = runner.make_stream(ol.kinds, ol.keys % n_slots, ol.values,
                                n_cns=n_cns, lanes_per_cn=lanes,
                                valid=ol.valid, cn=ol.cn)
    _, _, _, io1 = runner.run_windows(cfg, st1, credit_init(n_slots), stream)
    _assert_bill_equal(io_a, io1, "scale/open_loop/sharded_vs_single")
    out["equality"] = ("dense_repack and sharded-vs-single verb bills "
                       "asserted bit-equal on the CIDER cell")
    print("open-loop equality asserts OK", flush=True)
    return out


def bench_scale_json(fast=False, path=None):
    if path is None:
        path = "BENCH_scale.fast.json" if fast else FULL_BASELINE
    elif fast and os.path.abspath(path) == os.path.abspath(FULL_BASELINE):
        raise SystemExit(
            f"--fast must not overwrite the committed full-size baseline "
            f"{FULL_BASELINE}; pick another path (default: "
            f"BENCH_scale.fast.json)")
    c = FAST if fast else FULL
    p = SimParams()
    spec = WORKLOADS["write-intensive"]

    weak, eff = _weak_scaling(c, p, spec)

    # one clock for every open-loop curve: the window length is provisioned
    # as the SLOWEST mode's full-occupancy window service time at the
    # open-loop mesh (calibrated from the weak-scaling run above)
    ol_key = str(c["ol_mesh"])
    ol = {}
    if ol_key in weak:
        window_us = max(weak[ol_key][m.name]["modeled_ticks_us"]
                        for m in MODES) / (c["windows"] - c["warmup"])
        ol = _open_loop(c, p, spec, window_us)

    out = {
        "config": {**{k: v for k, v in c.items()},
                   "workload": spec.name, "fast": fast,
                   "gated_meshes": c["meshes"],
                   "n_slots_max": c["slots1"] * c["meshes"][-1],
                   "provenance": provenance("auto"),
                   "runner": "repro.dist.store.run_windows_sharded"
                             "(per_shard_io=True)",
                   "generated_by": "python -m benchmarks.scale"
                                   + (" --fast" if fast else "")},
        "metrics": {
            "modeled_mops": "n_ops / max-over-shards(mn_iops_s/mn_cap, "
                            "mn_bytes_s/mn_bw) us — the mesh is bound by "
                            "its hottest shard's NIC (docs/METRICS.md)",
            "efficiency": "mops_N / (N * mops_1) per mode — weak-scaling "
                          "efficiency of the replicated unit problem",
            "hot_shard_imbalance": "hottest shard's mn_iops / mean — the "
                                   "Zipf-head concentration CIDER's "
                                   "combining flattens",
            "open_loop": "p50/p99 of delay_windows*window_us + in-window "
                         "modeled latency vs offered load rho "
                         "(DESIGN.md §12); one arrival draw per rho shared "
                         "by all modes",
            "mn_cap_per_us": p.mn_cap, "mn_bw_bytes_per_us": p.mn_bw,
        },
        "weak_scaling": weak,
        "efficiency": eff,
        "open_loop": ol,
    }

    if not fast:
        top = str(c["meshes"][-1])
        got = eff.get("CIDER", {}).get(top)
        assert got is not None and got >= CIDER_EFF_FLOOR, \
            (f"committed artifact floor: CIDER weak-scaling efficiency at "
             f"mesh {top} is {got}, below {CIDER_EFF_FLOOR}")

    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"== scale -> {path} ==")
    for m in MODES:
        print(f"{m.name:6s} efficiency: " + "  ".join(
            f"N={n}:{e:.3f}" for n, e in eff[m.name].items()))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--path", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    bench_scale_json(fast=args.fast, path=args.path)


if __name__ == "__main__":
    main()

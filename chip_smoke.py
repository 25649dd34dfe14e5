#!/usr/bin/env python3
"""Smoke run of the CIDER store's main path on a TPU, checked against the
sequential oracle.

    python chip_smoke.py              # one chip: the YCSB A and YCSB E phases
    python chip_smoke.py --chips 4    # four chips: the sharded store only

One chip.  Both phases hold the paper's load (§5.1): 60 000 000 keys in a
store of 2^26 slots, driven by W=16 windows of B=4096 ops from 16 compute
nodes under every ``SyncMode``, with ``kernel_backend="auto"`` (the
compiled Pallas kernels):

* YCSB A (50 % read / 50 % update, Zipf 0.99) on ``stores.PointerArray``
  through ``apply_stream`` (``runner.run_windows``);
* YCSB E (95 % scan / 5 % insert) on ``stores.SmartART`` with
  ``scan_max=16``, so the ``scan_probe`` kernel runs too.

Four chips.  YCSB A with the store partitioned 2^24 slots per chip through
``dist.store.run_windows_sharded``, compared bit for bit with
``run_windows`` on one device of the same process.

Every op's ``ok``/``value``/``rows`` and the final store view at every
touched slot must match ``core.oracle.OracleStore`` replaying the same
stream, and all modes must leave the same store.  Any mismatch, or any
platform but ``tpu``, exits non-zero.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import combine, runner  # noqa: E402
from repro.core.credits import credit_init  # noqa: E402
from repro.core.engine import store_view  # noqa: E402
from repro.core.oracle import OracleStore  # noqa: E402
from repro.core.types import (EngineConfig, IOMetrics, OpKind,  # noqa: E402
                              SyncMode)
from repro.dist import store as dstore  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.stores import PointerArray, SmartART  # noqa: E402
from repro.workloads.ycsb import YCSB, generate_ycsb_stream  # noqa: E402

MODES = tuple(SyncMode)
CREDIT_TABLE = 4096


@dataclasses.dataclass(frozen=True)
class Size:
    """One deployment's scale: ``2**log2_slots`` slots, ``n_keys`` loaded."""
    log2_slots: int = 26
    n_keys: int = 60_000_000
    windows: int = 16
    batch: int = 4096
    n_cns: int = 16


def log(msg: str) -> None:
    print(msg, flush=True)


def load(n_keys: int, seed: int) -> tuple[jax.Array, jax.Array]:
    """The load phase's keys ``0..n_keys-1`` and random values, on device."""
    values = jax.random.randint(jax.random.key(seed), (n_keys,), 1,
                                2**31 - 1, jnp.int32)
    return jnp.arange(n_keys, dtype=jnp.int32), values


def kernels_in(hlo: str) -> set[str]:
    """Names of the Pallas kernels compiled into an optimized HLO module."""
    return {m.group(1) for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in re.finditer(r"jit\((wc_combine|scan_probe)\)", line)}


def touched_slots(kinds, keys, values, n_slots: int, scan_max: int):
    """Every slot the stream reads or writes, SCAN runs included."""
    keys = keys.astype(np.int64)
    parts = [keys[kinds != OpKind.SCAN]]
    scan = kinds == OpKind.SCAN
    for j in range(scan_max):
        parts.append((keys + j)[scan & (np.minimum(values, scan_max) > j)])
    out = np.unique(np.concatenate(parts))
    return out[out < n_slots]


def oracle_replay(gen, loaded: dict[int, int], scan_max: int | None):
    """Per-op ``(ok, value, rows)`` planes and the final key->value map."""
    orc = OracleStore()
    orc.kv = dict(loaded)
    ok, val, rows = [], [], []
    for w in range(gen.kinds.shape[0]):
        o, v = orc.apply(gen.kinds[w], gen.keys[w], gen.values[w],
                         scan_max=scan_max)
        ok.append(o)
        val.append(v)
        rows.append(orc.rows)
    return np.stack(ok), np.stack(val), np.stack(rows), orc.kv


def check_equal(label: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        bad = int(np.sum(got != want)) if got.shape == want.shape else -1
        raise AssertionError(f"{label}: {bad} entries differ from the oracle")


def memory_line(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"argument={m.argument_size_in_bytes} "
            f"output={m.output_size_in_bytes} "
            f"alias={m.alias_size_in_bytes} temp={m.temp_size_in_bytes}")


def ycsb_phase(workload: str, size: Size, seed: int, on_chip: bool,
               modes=MODES) -> dict:
    """Run one YCSB workload under every mode and check it; returns the
    timings.  ``on_chip`` also requires the compiled kernels in the HLO."""
    n_slots = 1 << size.log2_slots
    if workload == "A":
        store0 = PointerArray.create(n_slots, kernel_backend="auto",
                                     credit_table=CREDIT_TABLE)
        want_kernels = {"wc_combine"}
    else:
        store0 = SmartART.create(key_bits=size.log2_slots,
                                 kernel_backend="auto",
                                 credit_table=CREDIT_TABLE)
        want_kernels = {"wc_combine", "scan_probe"}
    scan_max = store0.cfg.scan_max
    if on_chip and combine.resolve_backend("auto") != ("pallas", False):
        raise AssertionError("kernel_backend='auto' does not resolve to the "
                             "compiled Pallas kernels on this device")

    t0 = time.perf_counter()
    keys, values = load(size.n_keys, seed)
    store0 = store0.populate(keys, values)
    jax.block_until_ready(store0.state)
    load_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    gen = generate_ycsb_stream(YCSB[workload], size.windows, size.batch,
                               size.n_keys, size.n_cns, seed=seed + 1)
    if int(gen.keys.max()) >= n_slots:
        raise ValueError("stream keys exceed the store's slots")
    touched = touched_slots(gen.kinds, gen.keys, gen.values, n_slots,
                            scan_max)
    t_loaded = touched[touched < size.n_keys]
    loaded = dict(zip(t_loaded.tolist(),
                      np.asarray(values[jnp.asarray(t_loaded)]).tolist()))
    del keys, values
    stream_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ok_w, val_w, rows_w, kv = oracle_replay(
        gen, loaded, scan_max if workload == "E" else None)
    exp_ex = np.array([int(k) in kv for k in touched])
    exp_val = np.array([kv.get(int(k), -1) for k in touched])
    oracle_s = time.perf_counter() - t0
    stream = runner.make_stream(gen.kinds, gen.keys, gen.values,
                                n_cns=size.n_cns)
    log(f"phase=ycsb_{workload.lower()} slots={n_slots} "
        f"loaded={size.n_keys} windows={size.windows} batch={size.batch} "
        f"cns={size.n_cns} scan_max={scan_max} touched={touched.size} "
        f"load_s={load_s:.3f} stream_s={stream_s:.3f} "
        f"oracle_s={oracle_s:.3f}")

    def fresh(mode):
        cfg = dataclasses.replace(store0.cfg, mode=mode)
        return dataclasses.replace(
            store0, cfg=cfg, state=jax.tree.map(jnp.copy, store0.state),
            credits=credit_init(CREDIT_TABLE))

    def run(store):
        if workload == "A":
            return store.apply_stream(stream)
        return store.apply_stream(gen.kinds, gen.keys, gen.values,
                                  n_cns=size.n_cns)

    out, view0 = {}, None
    for mode in modes:
        st = fresh(mode)
        t0 = time.perf_counter()
        compiled = runner._scan_windows.lower(
            st.cfg, st.state, st.credits, stream, stream.alive[0],
            False, False).compile()
        compile_s = time.perf_counter() - t0
        found = kernels_in(compiled.as_text())
        if on_chip and found != want_kernels:
            raise AssertionError(f"fused scan holds kernels {sorted(found)}, "
                                 f"expected {sorted(want_kernels)}")
        t0 = time.perf_counter()
        jax.block_until_ready(run(st))           # warm-up: compiles via jit
        first_s = time.perf_counter() - t0
        st = fresh(mode)
        jax.block_until_ready(st.state)
        t0 = time.perf_counter()
        st, res, io = run(st)
        jax.block_until_ready((st.state, res, io))
        run_s = time.perf_counter() - t0
        check_equal(f"{workload}/{mode.name} ok", res.ok, ok_w)
        check_equal(f"{workload}/{mode.name} value", res.value, val_w)
        check_equal(f"{workload}/{mode.name} rows", res.rows, rows_w)
        ex, val = store_view(st.state)
        idx = jnp.asarray(touched)
        check_equal(f"{workload}/{mode.name} view exists", ex[idx], exp_ex)
        check_equal(f"{workload}/{mode.name} view value", val[idx], exp_val)
        if view0 is None:
            view0 = (ex, val)
        elif not bool(jnp.array_equal(ex, view0[0])
                      & jnp.array_equal(val, view0[1])):
            raise AssertionError(f"{workload}/{mode.name}: final store view "
                                 f"differs from {modes[0].name}'s")
        n_ops = int(np.sum(gen.kinds != OpKind.NOP))
        log(f"phase=ycsb_{workload.lower()} mode={mode.name} "
            f"kernels={','.join(sorted(found)) or 'none'} "
            f"compile_s={compile_s:.3f} first_call_s={first_s:.3f} "
            f"run_s={run_s:.6f} ops={n_ops} ops_per_s={n_ops / run_s:.1f} "
            f"mn_iops={int(io.mn_iops)} oracle=match "
            f"memory[{memory_line(compiled)}]")
        out[mode.name] = {"compile_s": compile_s, "run_s": run_s}
        del st, res, io, ex, val
    stats = jax.devices()[0].memory_stats() or {}
    log(f"phase=ycsb_{workload.lower()} modes_agree=true "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}")
    return out


def sharded_phase(size: Size, seed: int, n_shards: int, on_chip: bool,
                  modes=MODES) -> dict:
    """YCSB A on the store partitioned over ``n_shards`` devices, bit-equal
    to ``run_windows`` on one device; the state must span every device
    (and, ``on_chip``, fill their memories about equally)."""
    n_slots = 1 << size.log2_slots
    mesh = make_local_mesh(data=n_shards)
    shard = NamedSharding(mesh, P("data"))
    gen = generate_ycsb_stream(YCSB["A"], size.windows, size.batch,
                               size.n_keys, size.n_cns, seed=seed + 1)
    stream = runner.make_stream(gen.kinds, gen.keys, gen.values,
                                n_cns=size.n_cns)
    log(f"phase=sharded_ycsb_a shards={n_shards} slots={n_slots} "
        f"per_shard={n_slots // n_shards} loaded={size.n_keys} "
        f"windows={size.windows} batch={size.batch} cns={size.n_cns}")
    out = {}
    for mode in modes:
        # the single-device store is made after the sharded run, so device
        # 0 holds no more than its shard when the placement is checked
        cfg = EngineConfig(n_slots=n_slots, heap_slots=4 * n_slots,
                           mode=mode, kernel_backend="auto")
        t0 = time.perf_counter()
        keys, values = load(size.n_keys, seed)
        sst = dstore.sharded_populate(
            cfg, n_shards, dstore.sharded_store_init(cfg, n_shards),
            keys, values)
        sst = jax.device_put(sst, shard)
        del keys, values
        jax.block_until_ready(sst)
        load_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        sst, scr, sres, sio = dstore.run_windows_sharded(
            cfg, mesh, sst, credit_init(CREDIT_TABLE), stream)
        jax.block_until_ready((sst, sres, sio))
        sharded_s = time.perf_counter() - t0
        devs = sst.ptr.sharding.device_set
        if devs != set(mesh.devices.flat):
            raise AssertionError(f"sharded state spans {len(devs)} devices, "
                                 f"expected {n_shards}")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in mesh.devices.flat]
        if on_chip and (min(in_use) <= 0 or max(in_use) > 2 * min(in_use)):
            raise AssertionError(f"per-device bytes_in_use {in_use} are "
                                 f"not balanced")
        single = PointerArray.create(n_slots, mode=mode, kernel_backend="auto",
                                     credit_table=CREDIT_TABLE)
        if single.cfg != cfg:
            raise AssertionError("sharded and single-device configs differ")
        keys, values = load(size.n_keys, seed)
        single = single.populate(keys, values)
        del keys, values
        t0 = time.perf_counter()
        single, res, io = single.apply_stream(stream)
        jax.block_until_ready((single.state, res, io))
        single_s = time.perf_counter() - t0
        label = f"sharded/{mode.name}"
        for f in dataclasses.fields(res):
            check_equal(f"{label} Results.{f.name}", getattr(sres, f.name),
                        getattr(res, f.name))
        for f in dataclasses.fields(IOMetrics):
            check_equal(f"{label} IOMetrics.{f.name}", getattr(sio, f.name),
                        getattr(io, f.name))
        check_equal(f"{label} credit", scr.credit, single.credits.credit)
        check_equal(f"{label} retry_record", scr.retry_record,
                    single.credits.retry_record)
        for a, b, name in zip(dstore.sharded_store_view(cfg, n_shards, sst),
                              store_view(single.state), ("exists", "value")):
            check_equal(f"{label} view {name}", a, b)
        check_equal(f"{label} ver", sst.ver, single.state.ver)
        check_equal(f"{label} epoch", sst.epoch, single.state.epoch)
        log(f"phase=sharded_ycsb_a mode={mode.name} devices={len(devs)} "
            f"bytes_in_use={in_use} load_s={load_s:.3f} "
            f"sharded_first_call_s={sharded_s:.3f} "
            f"single_first_call_s={single_s:.3f} mn_iops={int(io.mn_iops)} "
            f"bit_equal=true")
        out[mode.name] = {"bytes_in_use": in_use}
        del sst, scr, sres, sio, single, res, io
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-store phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    log(f"cache_dir={enable_compile_cache()}")
    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if args.chips == 4:
        sharded_phase(Size(), args.seed, 4, on_chip=True)
    else:
        for workload in ("A", "E"):
            ycsb_phase(workload, Size(), args.seed, on_chip=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The names the program puts on its work, as a trace sees them.

* Device: the fused window program, compiled on the CPU, puts every
  fusion, scatter, sort, gather, reduce and custom call of
  ``engine.apply_batch`` in exactly one ``engine.s*`` scope, and holds each
  scope its configuration compiles: ``s5c`` only with ``scan_max > 0``,
  ``s5d`` only with replicas.
* Host: a store call recorded under ``jax.profiler`` on the CPU holds the
  front end's program spans on the client's thread, nested as
  ``bench/program_trace.py`` reads them.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation

from bench import program_trace
from bench import trace as tracing
from repro.core import engine, runner
from repro.core.credits import credit_init
from repro.core.types import EngineConfig, OpKind, SyncMode

B, N_CNS = 256, 4
ALWAYS = {"engine.s1_linearize", "engine.s2_commit", "engine.s3_credit",
          "engine.s4_local_wc", "engine.s5_meter", "engine.s5b_repair",
          "engine.s6_feedback", "engine.s7_epoch", "engine.s8_assemble"}
WORK = ("fusion", "scatter", "sort", "gather", "reduce", "custom-call")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.-]+) = (?:\([^)]*\)|\S+) ([\w-]+)\((.*?op_name="([^"]*)")?')
_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+) ")


def _computations(hlo: str) -> dict[str, list[str]]:
    """Computation name -> its instruction lines."""
    comps, name = {}, None
    for ln in hlo.splitlines():
        if ln and not ln[0].isspace() and ln.rstrip().endswith("{"):
            m = _COMP.match(ln)
            name = m.group(1) if m else None
            comps[name] = []
        elif name is not None and " = " in ln:
            comps[name].append(ln)
    return comps


def _lone_broadcast(body: list[str]) -> bool:
    """A fused computation that only broadcasts a scalar: XLA's CPU backend
    wraps such a broadcast in a fusion of its own."""
    kinds = {m.group(2) for m in map(_INSTR.match, body) if m}
    return kinds <= {"parameter", "constant", "broadcast"}


def _scopes(hlo: str):
    """Every scope the program's metadata names, and the work ops of
    ``apply_batch`` with their scopes."""
    comps = _computations(hlo)
    names, work = set(), []
    for body in comps.values():
        for ln in body:
            m = _INSTR.match(ln)
            if not m or not m.group(4):
                continue
            op_name = m.group(4)
            found = re.findall(r"(?:^|/)(engine\.s\w+)(?=/|$)", op_name)
            names.update(found)
            if "jit(apply_batch)" not in op_name \
                    or not m.group(2).startswith(WORK):
                continue
            called = re.search(r"calls=%([\w.-]+)", ln)
            if m.group(2) == "fusion" and called \
                    and _lone_broadcast(comps.get(called.group(1), [])):
                continue
            work.append((m.group(1), found))
    return names, work


def _shapes(cfg, table=64):
    z = jnp.zeros((1, B), jnp.int32)
    return jax.eval_shape(lambda: (engine.store_init(cfg), credit_init(table),
                                   runner.make_stream(z, z, z, n_cns=N_CNS)))


def _single(cfg) -> str:
    st, cr, stream = _shapes(cfg)
    prev = jax.ShapeDtypeStruct((N_CNS,), jnp.bool_)
    return runner._scan_windows.lower(cfg, st, cr, stream, prev, False,
                                      False).compile().as_text()


def _sharded(cfg) -> str:
    from repro.dist import store as dstore
    from repro.launch.mesh import make_local_mesh
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    mesh = make_local_mesh(data=4)
    st, cr, stream = _shapes(cfg)
    st = dataclasses.replace(st, heap_top=jax.ShapeDtypeStruct((4,),
                                                               jnp.int32))
    prev = jax.ShapeDtypeStruct((N_CNS,), jnp.bool_)
    fn = dstore._sharded_stream_fn(cfg, mesh, "data", False)
    return fn.lower(st, cr, stream, prev).compile().as_text()


CONFIGS = {
    "cider_scan": (lambda: _single(EngineConfig(
        n_slots=4096, heap_slots=1 << 15, mode=SyncMode.CIDER, scan_max=8)),
        ALWAYS | {"engine.s5c_scan"}),
    "cider_point": (lambda: _single(EngineConfig(
        n_slots=4096, heap_slots=1 << 15, mode=SyncMode.CIDER)), ALWAYS),
    "cider_replicas": (lambda: _single(EngineConfig(
        n_slots=4096, heap_slots=1 << 15, mode=SyncMode.CIDER,
        n_replicas=3)), ALWAYS | {"engine.s5d_replicas"}),
    "cider_sharded_x4": (lambda: _sharded(EngineConfig(
        n_slots=1 << 14, heap_slots=1 << 16, mode=SyncMode.CIDER)), ALWAYS),
}


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_every_engine_op_sits_in_one_step_scope(config):
    compile_text, want = CONFIGS[config]
    names, work = _scopes(compile_text())
    assert names == want
    assert len(work) > 20
    unscoped = [(op, found) for op, found in work if len(found) != 1]
    assert not unscoped, unscoped[:10]


def _record(tmp_path, calls):
    d = str(tmp_path / "trace")
    with tracing.record(d):
        for call in calls:
            with TraceAnnotation("bench.issue"):
                out = call()
            jax.block_until_ready(out)
    path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(path)
    window = program_trace._window(data.planes)
    return program_trace.reduce(data.planes, window, ()).spans


def _inside(inner, outer) -> bool:
    return (outer.start_ns <= inner.start_ns and inner.start_ns + inner.dur_ns
            <= outer.start_ns + outer.dur_ns)


def _nested(spans, child: str, parent: str) -> int:
    """How many ``child`` spans lie inside a ``parent`` span."""
    parents = [s for s in spans if s.name == parent]
    kids = [s for s in spans if s.name == child]
    assert kids and all(any(_inside(k, p) for p in parents) for k in kids)
    return len(kids)


def test_a_pointer_array_call_records_its_front_end_spans(tmp_path):
    from repro.stores import PointerArray
    holder = {"s": PointerArray.create(4096, credit_table=64)}
    kinds = np.full((1, B), int(OpKind.UPDATE), np.int32)
    keys = np.arange(B, dtype=np.int32)[None]

    def call():
        stream = runner.make_stream(kinds, keys, keys, n_cns=N_CNS)
        holder["s"], res, io = holder["s"].apply_stream(stream)
        return res, io

    call()
    spans = _record(tmp_path, [call, call])
    assert {s.name for s in spans} == {"runner.make_stream",
                                       "store.apply_stream",
                                       "store.scan_check", "runner.dispatch"}
    assert _nested(spans, "store.scan_check", "store.apply_stream") == 2
    assert _nested(spans, "runner.dispatch", "store.apply_stream") == 2
    # the adapter builds the stream before the store's entry point
    assert not any(_inside(m, a) for m in spans
                   if m.name == "runner.make_stream"
                   for a in spans if a.name == "store.apply_stream")


def test_a_radix_call_records_slots_stream_and_dispatch_inside_the_store(
        tmp_path):
    from repro.stores import SmartART
    holder = {"s": SmartART.create(key_bits=12, scan_max=8, credit_table=64)}
    kinds = np.full((1, B), int(OpKind.SCAN), np.int32)
    keys = np.arange(B, dtype=np.int32)[None]

    def call():
        holder["s"], res, io = holder["s"].apply_stream(
            kinds, keys, keys % 8 + 1, n_cns=N_CNS)
        return res, io

    call()
    spans = _record(tmp_path, [call])
    for child in ("store.slots", "runner.make_stream", "runner.dispatch"):
        assert _nested(spans, child, "store.apply_stream") == 1


def test_the_sharded_launch_records_a_dispatch_span(tmp_path):
    from repro.dist import store as dstore
    from repro.launch.mesh import make_local_mesh
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = EngineConfig(n_slots=1 << 14, heap_slots=1 << 16,
                       mode=SyncMode.CIDER)
    mesh = make_local_mesh(data=4)
    holder = {"st": dstore.sharded_store_init(cfg, 4), "cr": credit_init(64)}
    kinds = np.full((1, B), int(OpKind.SEARCH), np.int32)
    keys = np.arange(B, dtype=np.int32)[None]

    def call():
        stream = runner.make_stream(kinds, keys, keys, n_cns=N_CNS)
        holder["st"], holder["cr"], res, io = dstore.run_windows_sharded(
            cfg, mesh, holder["st"], holder["cr"], stream)
        return res, io

    call()
    spans = _record(tmp_path, [call])
    assert [s.name for s in spans] == ["runner.make_stream",
                                       "runner.dispatch"]

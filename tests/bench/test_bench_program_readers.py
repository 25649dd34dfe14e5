"""The per-layer readers on a small synthetic trace written as a real
``.xplane.pb``: two devices, two calls, the harness's spans, the program's
spans, and the instructions' ``op_name``s with and without the engine's
scopes.  Every reader the benchmark had reads the same value whether or not
the program names its work, and the readers of the engine's steps and of
the store front end read the values worked out by hand below.  Last, the
call program lowered from a cell's shapes is the program its store adapter
compiles."""
from __future__ import annotations

import os

import numpy as np
import pytest

from bench import harness, program_trace, roofline
from bench import trace as tracing

ROOT = harness.ROOT
US = 1000.0                              # the trace below is in microseconds
PROGRAM = "jit__scan_windows(7)"
OLD = ("device.idle_share", "runner.device_ms_per_window",
       "wc_combine_roofline", "scan_probe_roofline",
       "dist.collective_ms_per_window", "client.host_ms_per_call",
       "client.call_ms_p50")
NEW = tuple(program_trace.SCOPES) + ("engine.unscoped_ms_per_window",
                                     "store.front_ms_per_call",
                                     "runner.dispatch_ms_per_call")

HARNESS = [("bench.next", 0, 2), ("bench.issue", 2, 10), ("bench.wait", 12, 48),
           ("bench.collect", 60, 10), ("bench.issue", 70, 10),
           ("bench.wait", 80, 40), ("bench.collect", 120, 10)]
# call 1 as the pointer-array adapter makes it, call 2 as the radix store
FRONT = [("runner.make_stream", 3, 2), ("store.apply_stream", 5, 6),
         ("store.scan_check", 5.5, 1.5), ("runner.dispatch", 8, 2),
         ("store.apply_stream", 71, 8), ("store.slots", 71, 2),
         ("runner.make_stream", 73, 2), ("runner.dispatch", 75, 3)]
BODY = "jit(_scan_windows)/while/body/closed_call/jit(apply_batch)/"
# instruction -> its step's scope in the compiled call program
STEP = {"fusion.1": "engine.s1_linearize", "wc_combine.2": "engine.s1_linearize",
        "broadcast.3": "engine.s4_local_wc", "fusion.41": "engine.s5b_repair",
        "scatter.5": "engine.s2_commit", "fusion.9": "engine.s6_feedback",
        "copy.2": ""}
DEV0 = [("%fusion.1 = s32[4096] fusion(%p)", 12, 8),
        ("%broadcast.3 = s32[1073741824] broadcast(%c)", 20, 20),
        ("%fusion.41 = s32[67108864] fusion(%a)", 40, 10),
        ("%copy.2 = s32[4096] copy(%b)", 50, 4),
        ("%wc_combine.2 = s32[32,128] custom-call(%x)", 54, 2),
        ("%iota.1 = s32[4096] iota()", 3, 1),           # an eager op
        ("%fusion.1 = s32[4096] fusion(%p)", 80, 8),
        ("%broadcast.3 = s32[1073741824] broadcast(%c)", 88, 20),
        ("%fusion.41 = s32[67108864] fusion(%a)", 108, 4),
        ("%scatter.5 = s32[67108864] scatter(%a)", 112, 4),
        ("%fusion.9 = s32[4096] fusion(%q)", 116, 1)]
DEV1 = [("%fusion.1 = s32[4096] fusion(%p)", 12, 40),
        ("%fusion.1 = s32[4096] fusion(%p)", 80, 38)]
MODULES = [("jit_iota(3)", 3, 1), (PROGRAM, 12, 46), (PROGRAM, 80, 38)]


def _op_names(named: bool) -> dict[str, str]:
    """The map the call program's HLO text gives: with the engine's scopes
    (this program) or without them (a program from before them)."""
    return {op: BODY + (scope + "/" if named and scope else "") + "add"
            for op, scope in STEP.items()}


class _Proto:
    """One plane of an XSpace text proto."""

    def __init__(self, pid: int, name: str, origin_ns: int):
        self.pid, self.name, self.origin = pid, name, origin_ns
        self.lines, self.meta = [], {}

    def line(self, name: str, events):
        """``events``: (name, start_us, dur_us)."""
        out = []
        for ev, start, dur in events:
            mid = self.meta.setdefault(ev, len(self.meta) + 1)
            out.append(f"events {{ metadata_id: {mid} offset_ps: "
                       f"{int(start * US * 1000)} duration_ps: "
                       f"{int(dur * US * 1000)} }}")
        self.lines.append(f'lines {{ id: {len(self.lines) + 1} name: "{name}" '
                          f'timestamp_ns: {self.origin} {" ".join(out)} }}')

    def text(self) -> str:
        meta = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                        f"{_quote(n)} }} }}" for n, i in self.meta.items())
        return (f'planes {{ id: {self.pid} name: "{self.name}" '
                f'{" ".join(self.lines)} {meta} }}')


def _quote(s: str) -> str:
    return '"' + s.replace('"', '\\"') + '"'


def _device(pid, name, ops, named):
    plane = _Proto(pid, name, _origin(named))
    plane.line("XLA Ops", ops)
    plane.line("XLA Modules", MODULES)
    return plane.text()


def _origin(named: bool) -> int:
    """The two traces lie apart in time, as two runs' traces do."""
    return 0 if named else 10**9


def _write(tmp_path, named: bool) -> str:
    """The synthetic trace, with or without the program's names, under a
    ``bench_trace_*`` directory as the harness records it."""
    from jax.profiler import ProfileData
    host = _Proto(3, "/host:CPU", _origin(named))
    host.line("python", HARNESS + (FRONT if named else []))
    host.line("feed", [("feed.generate", 10, 5)])
    space = " ".join([_device(1, "/device:TPU:0", DEV0, named),
                      _device(2, "/device:TPU:1", DEV1, named), host.text()])
    d = tmp_path / f"bench_trace_{'named' if named else 'plain'}"
    run = d / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(space))
    return str(d)


def _readings(tmp_path, monkeypatch, named: bool) -> dict:
    monkeypatch.setattr(program_trace, "_names",
                        lambda conf_json: _op_names(named))
    s = tracing.summarize(tracing.load(_write(tmp_path, named)))
    ctx = harness.Context(summary=s, calls=2, call_s=[0.02, 0.5, 0.03],
                          conf={"batch": 4096, "scan_max": 0}, mix=None,
                          device_kind="TPU v5 lite",
                          program_names=("_scan_windows",), temp_bytes=1)
    out = {}
    for m in OLD + NEW:
        reader = harness.load_module(harness.find(ROOT, "metrics", m, ".py"))
        out[m] = reader.read(ctx)
    return out


@pytest.fixture
def in_tmp(tmp_path, monkeypatch):
    import tempfile
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    program_trace._load.cache_clear()
    yield tmp_path
    program_trace._load.cache_clear()


def test_the_readers_the_benchmark_had_read_the_same_with_program_names(
        in_tmp, monkeypatch):
    plain = _readings(in_tmp, monkeypatch, named=False)
    named = _readings(in_tmp, monkeypatch, named=True)
    assert {m: named[m] for m in OLD} == {m: plain[m] for m in OLD}
    busy = ((44 + 37 + 1) + (40 + 38)) / 2               # union of op time
    assert named["device.idle_share"] == pytest.approx(100 * (1 - busy / 130))
    assert named["runner.device_ms_per_window"] == pytest.approx(84 / 2 * 1e-3)
    assert named["client.host_ms_per_call"] == pytest.approx(20e-3)
    assert named["client.call_ms_p50"] == pytest.approx(30.0)
    assert named["scan_probe_roofline"] is None
    assert named["dist.collective_ms_per_window"] is None
    least = roofline.wc_combine_bytes(4096) / 819e9
    assert named["wc_combine_roofline"] == pytest.approx(
        100 * least / (2 / 2 * US * 1e-9 / 2))
    assert all(plain[m] is None for m in NEW)


def test_the_new_readers_read_the_steps_and_the_front_end(in_tmp,
                                                         monkeypatch):
    r = _readings(in_tmp, monkeypatch, named=True)
    per_window = 2 * 2 * US / 1e6          # ms of 1 us over 2 devices, 2 calls
    assert r["engine.linearize_ms_per_window"] == pytest.approx(
        (8 + 2 + 8 + 40 + 38) / per_window * 1e-6)
    assert r["engine.local_wc_ms_per_window"] == pytest.approx(
        40 / per_window * 1e-6)
    assert r["engine.repair_ms_per_window"] == pytest.approx(
        14 / per_window * 1e-6)
    assert r["engine.commit_ms_per_window"] == pytest.approx(
        4 / per_window * 1e-6)
    assert r["engine.credit_ms_per_window"] == pytest.approx(
        1 / per_window * 1e-6)
    assert r["engine.meter_ms_per_window"] is None     # no s5 op here
    assert r["engine.scan_ms_per_window"] is None
    # 84 us of module per device; scoped 77 on device 0 and 78 on device 1
    assert r["engine.unscoped_ms_per_window"] == pytest.approx(
        (7 + 6) / per_window * 1e-6)
    assert sum(r[m] for m in NEW[:-2] if r[m]) == pytest.approx(
        r["runner.device_ms_per_window"])
    # call 1: [3, 11) less dispatch 2; call 2: [71, 79) less dispatch 3
    assert r["store.front_ms_per_call"] == pytest.approx((6 + 5) / 2 * 1e-3)
    assert r["runner.dispatch_ms_per_call"] == pytest.approx(2.5e-3)


def test_self_time_of_the_store_entry_point(in_tmp):
    _write(in_tmp, named=True)
    s = tracing.summarize(tracing.load(os.path.join(in_tmp,
                                                    "bench_trace_named")))
    prog = program_trace._load(s.window, ("_scan_windows",))
    inner = ("store.scan_check", "store.slots", "runner.")
    assert prog.self_ns(("store.apply_stream",), inner) == pytest.approx(
        ((6 - 1.5 - 2) + (8 - 2 - 2 - 3)) * US)
    assert [e.name for e in prog.spans] == [n for n, _, _ in sorted(
        FRONT, key=lambda f: f[1])]


def test_a_summary_of_another_trace_finds_nothing(in_tmp):
    _write(in_tmp, named=True)
    assert program_trace._load((0.0, 1.0), ("_scan_windows",)) is None


def test_a_map_that_misses_the_program_reads_nothing(in_tmp, monkeypatch):
    names = _op_names(True)
    del names["broadcast.3"]                  # 40 of 84 us on device 0
    monkeypatch.setattr(program_trace, "_names", lambda conf_json: names)
    s = tracing.summarize(tracing.load(_write(in_tmp, named=True)))
    prog = program_trace._load(s.window, ("_scan_windows",))
    assert prog.scope_ns("/device:TPU:1", names) is not None
    assert prog.scope_ns("/device:TPU:0", names) is None
    ctx = harness.Context(summary=s, calls=2, call_s=[0.02],
                          conf={"batch": 4096}, mix=None,
                          device_kind="TPU v5 lite",
                          program_names=("_scan_windows",), temp_bytes=1)
    assert program_trace.step_ms_per_window(
        ctx, "engine.linearize_ms_per_window") is None
    assert program_trace.unscoped_ms_per_window(ctx) is None


@pytest.mark.parametrize("workload", ["ycsb_a.pointer_array",
                                      "ycsb_e.smart_art"])
def test_the_call_program_lowered_from_shapes_is_the_adapters(
        tiny_root, no_cache, workload):
    from repro.core import runner
    cell = harness.load_cell(tiny_root, workload)
    conf = cell.conf
    served = harness.load_module(harness.find(
        ROOT, "stores", conf["store"], ".py")).open_store(conf, 2**31 + 5)
    try:
        store = served.store
        z = np.zeros((1, conf["batch"]), np.int32)
        keys = store.slots(z) if conf["scan_max"] else z
        stream = runner.make_stream(z + 4, keys, z, n_cns=conf["n_cns"])
        want = runner._scan_windows.lower(
            store.cfg, store.state, store.credits, stream, stream.alive[0],
            False, False).compile().as_text()
    finally:
        served.close()
    got = program_trace.call_program_hlo(conf)
    assert got == want
    scopes = {program_trace.scope_of(n)
              for n in program_trace.op_names(got).values()}
    assert ("engine.s5c_scan" in scopes) == (conf["scan_max"] > 0)
    assert "engine.s4_local_wc" in scopes


def test_every_instruction_of_the_hlo_text_is_named():
    """An instruction the compiler made with no metadata (a prefetch's
    ``copy-start`` on the TPU) is in the map, with no scope."""
    hlo = """ENTRY %main (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  %copy-start.4 = (s32[4]{0}, s32[4]{0}, u32[]) copy-start(s32[4]{0} %p)
  ROOT %fusion.1 = s32[4]{0} fusion(s32[4]{0} %p), kind=kLoop, calls=%f, metadata={op_name="jit(f)/jit(apply_batch)/engine.s2_commit/add" source_file="e.py"}
}"""
    names = program_trace.op_names(hlo)
    assert names == {"p": "", "copy-start.4": "",
                     "fusion.1": "jit(f)/jit(apply_batch)/engine.s2_commit/add"}
    assert [program_trace.scope_of(n) for n in names.values()] == [
        "", "", "engine.s2_commit"]

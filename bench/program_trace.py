"""What the program names in a traced run: the engine's steps on the
device and the store front end on the host.

``bench/trace.py`` reduces the harness's own spans and the device's ops;
this module reads the same ``.xplane.pb`` for what the program adds to it:

* device: ``core.engine.apply_batch`` wraps each numbered step in a
  ``jax.named_scope`` (``engine.s1_linearize`` ... ``engine.s8_assemble``).
  The compiled call program keeps the scope in each instruction's
  ``op_name`` metadata.  A trace op carries its instruction's name but not
  that metadata (``ProfileData`` gives an event's name and its own stats
  only), so the instruction -> ``op_name`` map comes from the call
  program's HLO text, lowered again from the cell's shapes exactly as the
  store adapters lower it (the compile cache holds it).  A fusion belongs
  to the scope of its own instruction's metadata, which is its root's.
  Only ops inside the call program's module count;
* host: the program's ``TraceAnnotation`` spans on the client's thread,
  ``store.*`` and ``runner.*``, nested inside ``bench.issue``.

The trace is the one the per-layer readers' summary was reduced from: the
harness records it under a ``bench_trace_*`` directory of the temporary
directory, and this module takes the trace there whose harness spans give
the summary's window exactly.  A program without scopes or spans (an
older commit) reads as nothing, and its readers return ``None``.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import json
import os
import re
import tempfile

from bench.trace import SPAN_PREFIX, Event, clip, instruction, merge

PROGRAM_PREFIXES = ("store.", "runner.")
_SCOPE = re.compile(r"(?:^|/)(engine\.s\w+)(?=/|$)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: least share of the program's op time whose instruction the HLO text
#: must hold, or the text is not this program's and nothing is read
COVERED = 0.99

#: per-layer metric -> the engine scopes whose device time it reads
SCOPES = {
    "engine.linearize_ms_per_window": ("engine.s1_linearize",),
    "engine.commit_ms_per_window": ("engine.s2_commit", "engine.s7_epoch",
                                    "engine.s8_assemble"),
    "engine.credit_ms_per_window": ("engine.s3_credit",
                                    "engine.s6_feedback"),
    "engine.local_wc_ms_per_window": ("engine.s4_local_wc",),
    "engine.meter_ms_per_window": ("engine.s5_meter", "engine.s5d_replicas"),
    "engine.repair_ms_per_window": ("engine.s5b_repair",),
    "engine.scan_ms_per_window": ("engine.s5c_scan",),
}


def op_names(hlo_text: str) -> dict[str, str]:
    """Instruction name -> its ``op_name`` metadata (``""`` for one the
    compiler made with none, such as a prefetch's ``copy-start``), from a
    compiled program's HLO text."""
    out = {}
    for name, rest in _INSTR.findall(hlo_text):
        m = _OP_NAME.search(rest)
        out[name] = m.group(1) if m else ""
    return out


def scope_of(op_name: str) -> str:
    """The ``engine.s*`` scope an ``op_name`` passes through, else ``""``."""
    m = _SCOPE.search(op_name)
    return m.group(1) if m else ""


@dataclasses.dataclass
class Program:
    """The program's names in one trace."""
    spans: list[Event]                  # store.*/runner.* on the client thread
    op_ns: dict[str, dict[str, float]]  # device -> op -> ns, in the program

    def scope_ns(self, device: str, names: dict[str, str]
                 ) -> dict[str, float] | None:
        """The device's program time by scope (``""``: none), given each
        instruction's ``op_name``; ``None`` if ``names`` lacks the
        instructions of more than ``1 - COVERED`` of that time."""
        out: dict[str, float] = collections.defaultdict(float)
        known = 0.0
        for op, ns in self.op_ns.get(device, {}).items():
            name = names.get(instruction(op))
            known += ns if name is not None else 0.0
            out[scope_of(name or "")] += ns
        total = sum(out.values())
        return dict(out) if known >= COVERED * total else None

    def self_ns(self, names, minus) -> float:
        """Time of the union of the spans that start with any of ``names``,
        less the part of it that spans starting with ``minus`` (a prefix or
        a tuple of them) cover: the layer's self time, summed over the
        trace."""
        mine = merge([(e.start_ns, e.start_ns + e.dur_ns) for e in self.spans
                      if e.name.startswith(tuple(names))])
        inner = merge([(e.start_ns, e.start_ns + e.dur_ns) for e in self.spans
                       if e.name.startswith(minus)])
        return sum(e - s for s, e in mine) - sum(
            b - a for s, e in mine for a, b in clip(inner, s, e))

    def span_mean_ns(self, name: str) -> float | None:
        ns = [e.dur_ns for e in self.spans if e.name == name]
        return sum(ns) / len(ns) if ns else None


def reduce(planes, window: tuple[float, float], program_names) -> Program:
    """The program's names in ``planes`` (``ProfileData`` planes) inside
    ``window``."""
    lo, hi = window
    spans: list[Event] = []
    op_ns: dict[str, dict[str, float]] = {}
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            runs = sorted(
                (e.start_ns, e.start_ns + e.duration_ns)
                for e in (lines["XLA Modules"].events
                          if "XLA Modules" in lines else ())
                if lo <= e.start_ns < hi
                and any(n in e.name for n in program_names))
            starts = [s for s, _ in runs]
            per = collections.defaultdict(float)
            for e in lines["XLA Ops"].events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                if i >= 0 and e.start_ns < runs[i][1]:
                    per[e.name] += e.duration_ns
            op_ns[plane.name] = dict(per)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = [Event(e.name, float(e.start_ns), float(e.duration_ns),
                             {}) for e in ln.events]
                if any(e.name.startswith(SPAN_PREFIX) for e in evs):
                    spans += [e for e in evs
                              if e.name.startswith(PROGRAM_PREFIXES)
                              and lo <= e.start_ns < hi]
    spans.sort(key=lambda e: e.start_ns)
    return Program(spans=spans, op_ns=op_ns)


def _window(planes) -> tuple[float, float] | None:
    """The harness's window in a trace, as ``trace.summarize`` sets it."""
    lo = hi = None
    for plane in planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = float(e.start_ns)
                        t = s + float(e.duration_ns)
                        lo = s if lo is None else min(lo, s)
                        hi = t if hi is None else max(hi, t)
    return None if lo is None else (lo, hi)


@functools.lru_cache(maxsize=1)
def _load(window: tuple[float, float], program_names: tuple[str, ...]
          ) -> Program | None:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(tempfile.gettempdir(), "bench_trace_*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        data = ProfileData.from_file(path)
        if _window(data.planes) == window:
            return reduce(data.planes, window, program_names)
    return None


def of(ctx) -> Program | None:
    """The program's names in the trace ``ctx.summary`` was reduced from."""
    return _load(tuple(ctx.summary.window), tuple(ctx.program_names))


def call_program_hlo(conf: dict) -> str | None:
    """HLO text of a one-chip cell's compiled call program
    (``core.runner._scan_windows`` over one window), lowered from shapes
    alone with the configuration's store built as ``bench/stores`` builds
    it; ``None`` for a store sharded over several chips or another store."""
    import jax
    import jax.numpy as jnp
    from repro.core import runner
    from repro.core.types import SyncMode
    from repro.stores import PointerArray, SmartART
    if conf["shards"] != 1:
        return None
    mode = SyncMode[conf["mode"]]
    if conf["store"] == "pointer_array":
        def make():
            return PointerArray.create(
                conf["slots"], mode=mode, heap_slots=conf["heap_slots"],
                credit_table=conf["credit_table"], kernel_backend="auto")
    elif conf["store"] == "smart_art":
        def make():
            return SmartART.create(
                key_bits=int(conf["slots"]).bit_length() - 1, mode=mode,
                kernel_backend="auto", credit_table=conf["credit_table"],
                scan_max=conf["scan_max"])
    else:
        return None
    holder = {}

    def shapes():
        store = make()
        holder["cfg"] = store.cfg
        z = jnp.zeros((1, conf["batch"]), jnp.int32)
        return store.state, store.credits, runner.make_stream(
            z, z, z, n_cns=conf["n_cns"])

    state, credits, stream = jax.eval_shape(shapes)
    prev = jax.ShapeDtypeStruct(stream.alive.shape[1:], jnp.bool_)
    return runner._scan_windows.lower(holder["cfg"], state, credits, stream,
                                      prev, False, False).compile().as_text()


@functools.lru_cache(maxsize=1)
def _names(conf_json: str) -> dict[str, str]:
    hlo = call_program_hlo(json.loads(conf_json))
    return op_names(hlo) if hlo else {}


# -- the readers -----------------------------------------------------------

def _by_scope(ctx):
    """Per device of the trace, the call program's time by scope; ``None``
    when there is nothing to read."""
    prog = of(ctx)
    devs = sorted(ctx.summary.module_ns)
    if prog is None or not devs or ctx.calls <= 0:
        return None, devs
    names = _names(json.dumps(ctx.conf, sort_keys=True))
    per = [prog.scope_ns(d, names) for d in devs]
    return (None if any(p is None for p in per) else per), devs


def step_ms_per_window(ctx, metric: str) -> float | None:
    """Device time per traced call of the metric's scopes, mean over
    devices, as ``runner.device_ms_per_window`` reads the whole program's."""
    per, _ = _by_scope(ctx)
    if per is None:
        return None
    ns = [sum(d.get(s, 0.0) for s in SCOPES[metric]) for d in per]
    if not any(ns):
        return None             # a step this program does not compile
    return sum(ns) / len(ns) / ctx.calls * 1e-6


def unscoped_ms_per_window(ctx) -> float | None:
    """The call program's module time per traced call less the time of its
    scoped ops, mean over devices."""
    per, devs = _by_scope(ctx)
    if per is None:
        return None
    scoped = [sum(ns for s, ns in d.items() if s) for d in per]
    module = [ctx.summary.modules_ns(d, ctx.program_names) for d in devs]
    if not any(scoped) or not any(module):
        return None             # a program with no scopes
    return (sum(module) - sum(scoped)) / len(devs) / ctx.calls * 1e-6

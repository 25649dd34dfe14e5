"""Jaxpr/HLO invariant auditor (analysis pass 1, DESIGN.md §11).

Traces the three entry points every benchmark number flows through —
``engine.apply_batch``, ``runner.run_windows`` (+ traced), and
``dist.store.run_windows_sharded`` (+ ``apply_batch_sharded``) — for all
four ``SyncMode``s and both kernel backends, then audits the closed jaxpr
and the compiled HLO:

* **dtype discipline** — the engine graph is integer/bool arithmetic with
  one documented f32 island (SPIN's truncated-exponential backoff): any
  f64/f16/bf16/complex value, or a weak-typed *output* (a promotion hazard
  for every downstream consumer), is a violation.
* **no host callbacks** — a ``pure_callback``/``io_callback`` inside the
  fused scan would serialize every window through the host and invalidate
  the wall-clock floors.
* **buffer donation** — the store/credit carries of the fused scans are
  declared donated (``donate_argnums``); this pass proves donation *took
  effect* by counting ``input_output_alias`` pairs in the compiled module
  (one per Store/Credit leaf) and by treating any "donated buffer was not
  usable" compile warning as a violation.  A silent copy here doubles
  steady-state memory and breaks the ROADMAP's multi-million-key sizing.
* **collective contract** — the sharded path's credit plane is replicated,
  so the ONLY cross-shard traffic is the final result/bill assembly: one
  ``psum`` per ``Results`` field + one per ``IOMetrics`` field (counts
  derived from the dataclasses, so adding a field updates the contract),
  nothing inside the window scan body, and nothing but ``all-reduce`` in
  the optimized HLO (audited via ``rooflines.hlo_parser``).
* **jit-cache stability** — configs that must share a compile cache
  (``kernel_backend="auto"`` vs its resolved backend; same-shape streams
  with different contents) must produce byte-identical jaxprs; a hash
  mismatch means a silent recompile per window/stream.

Pure functions (``audit_graph``/``collective_census``/``donation_pairs``/
``jaxpr_digest``) are exported for the injected-violation fixtures in
``tests/test_analysis.py``; ``run()`` applies them to the real codebase.
"""
from __future__ import annotations

import dataclasses
import hashlib
import warnings
from collections import Counter

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis import Violation
from repro.core import engine, runner
from repro.core.combine import resolve_backend
from repro.core.credits import CreditState, credit_init
from repro.core.engine import Results, StoreState
from repro.core.types import EngineConfig, IOMetrics, OpBatch, SyncMode

from jax.extend import core as jcore

__all__ = [
    "ALLOWED_DTYPES", "FORBIDDEN_DTYPES", "CALLBACK_PRIMS", "COMM_PRIMS",
    "audit_graph", "collective_census", "donation_pairs", "jaxpr_digest",
    "expected_donation_pairs", "expected_psums", "run",
]

# The engine is int32/bool arithmetic end to end (exact verb counting needs
# no floats); SPIN's truncated-exponential backoff is the one documented f32
# island and CIDER's combine kernels stage uint32 sort keys.  Everything
# else — and especially f64, which would silently double mn_bytes-adjacent
# buffer traffic and break bit-equality across backends — is a violation.
ALLOWED_DTYPES = frozenset({"bool", "int32", "uint32", "float32"})
FORBIDDEN_DTYPES = frozenset({
    "float64", "int64", "uint64", "float16", "bfloat16",
    "complex64", "complex128",
})
# Host-callback primitives: any of these inside the engine graph serializes
# the fused scan through Python once per window.
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
})
# Cross-device communication primitives (jaxpr level).  ``axis_index`` is
# deliberately separate: it reads the mesh coordinate without traffic.
COMM_PRIMS = frozenset({
    "psum", "pmin", "pmax", "all_gather", "all_to_all", "ppermute",
    "psum_scatter", "pbroadcast", "reduce_scatter",
})
# Primitives whose bodies execute once per carried iteration: a collective
# inside one would turn the per-stream assembly psum into per-window traffic.
_LOOP_PRIMS = frozenset({"scan", "while"})


def expected_psums() -> int:
    """The credit-plane collective contract, derived from the dataclasses:
    one result-assembly psum per ``Results`` field plus one bill psum per
    ``IOMetrics`` field (``dist.store._psum_results`` + the io tree-map)."""
    return len(dataclasses.fields(Results)) + len(dataclasses.fields(IOMetrics))


def expected_donation_pairs() -> int:
    """One ``input_output_alias`` pair per donated carry leaf: the whole
    ``StoreState`` + ``CreditState`` (both fused scans donate exactly
    these two trees)."""
    return (len(dataclasses.fields(StoreState))
            + len(dataclasses.fields(CreditState)))


def _as_jaxpr(obj):
    """Normalize make_jaxpr output / raw jaxprs to an open ``Jaxpr``."""
    if isinstance(obj, jcore.ClosedJaxpr):
        return obj.jaxpr
    return obj


def _sub_jaxprs(eqn):
    """All jaxprs nested in an eqn's params (scan/while/cond/pjit/pallas)."""
    subs = []
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for it in items:
            if isinstance(it, (jcore.ClosedJaxpr, jcore.Jaxpr)):
                subs.append(_as_jaxpr(it))
    return subs


def iter_eqns(closed, in_loop: bool = False):
    """Yield ``(eqn, in_loop)`` over a jaxpr and everything nested in it;
    ``in_loop`` is True inside any scan/while body (i.e. code that runs
    once per carried iteration)."""
    stack = [(_as_jaxpr(closed), in_loop)]
    while stack:
        jaxpr, loop = stack.pop()
        for eqn in jaxpr.eqns:
            yield eqn, loop
            sub_loop = loop or eqn.primitive.name in _LOOP_PRIMS
            for sub in _sub_jaxprs(eqn):
                stack.append((sub, sub_loop))


def _avals_of(eqn):
    for v in list(eqn.outvars) + list(eqn.invars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


def audit_graph(closed, target: str,
                allowed=ALLOWED_DTYPES) -> list[Violation]:
    """Dtype / weak-type / callback audit of one closed jaxpr.

    Flags (a) any value whose dtype is outside ``allowed`` (f64 promotion,
    x64 leaks, half-precision surprises), (b) weak-typed *outputs* — inner
    weak scalars are fine, but a weak output propagates promotion hazards
    to every consumer — and (c) host-callback primitives.
    """
    out = []
    bad_dtypes: set[str] = set()
    callbacks: set[str] = set()
    for eqn, _ in iter_eqns(closed):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS:
            callbacks.add(name)
        for aval in _avals_of(eqn):
            d = str(aval.dtype)
            if d not in allowed:
                bad_dtypes.add(d)
    for d in sorted(bad_dtypes):
        kind = "forbidden" if d in FORBIDDEN_DTYPES else "undeclared"
        out.append(Violation("jaxpr_check", target,
                             f"{kind} dtype {d} in engine graph "
                             f"(allowed: {sorted(allowed)})"))
    for name in sorted(callbacks):
        out.append(Violation("jaxpr_check", target,
                             f"host callback primitive '{name}' in engine "
                             f"graph — serializes the fused scan through "
                             f"the host"))
    avals = getattr(closed, "out_avals", None) or []
    weak = sorted({str(a.dtype) for a in avals
                   if getattr(a, "weak_type", False)})
    if weak:
        out.append(Violation("jaxpr_check", target,
                             f"weak-typed output(s) of dtype {weak} — "
                             f"promotion hazard for every consumer"))
    return out


def collective_census(closed, in_loop_only: bool = False) -> dict[str, int]:
    """Count communication primitives (plus ``axis_index``) in a jaxpr.
    ``in_loop_only=True`` restricts to scan/while bodies — code that would
    pay the collective once per window."""
    census: Counter[str] = Counter()
    for eqn, loop in iter_eqns(closed):
        if in_loop_only and not loop:
            continue
        name = eqn.primitive.name
        if name in COMM_PRIMS or name == "axis_index":
            census[name] += 1
    return dict(census)


def donation_pairs(hlo_text: str) -> int:
    """Number of input/output buffer aliases the compiled module declares.

    Donation that *took effect* shows up in the optimized module header as
    ``input_output_alias={ {0}: (0, {}, may-alias), ... }`` — one pair per
    successfully-donated leaf.  A donated-but-copied buffer is absent here,
    which is exactly the silent failure this check exists to catch.
    """
    header = hlo_text.split("\n", 1)[0]
    if "input_output_alias" not in header:
        # some jax versions put the alias map on its own frontend_attributes
        # line; fall back to scanning the whole text's first occurrence
        idx = hlo_text.find("input_output_alias")
        if idx < 0:
            return 0
        header = hlo_text[idx:hlo_text.find("}}", idx) + 2]
    import re
    return len(re.findall(r"\(\d+,\s*\{", header))


def jaxpr_digest(closed) -> str:
    """Stable digest of a traced graph: equal digests <=> the two traces
    share a jit cache entry's program (same eqns, shapes, consts)."""
    return hashlib.sha256(str(closed).encode()).hexdigest()


# ---------------------------------------------------------------------------
# Real-codebase audit
# ---------------------------------------------------------------------------

_MODES = (SyncMode.OSYNC, SyncMode.SPIN, SyncMode.MCS, SyncMode.CIDER)


def _cfg(mode: SyncMode, backend: str = "auto",
         scan_max: int = 0) -> EngineConfig:
    return EngineConfig(n_slots=64, heap_slots=128, mode=mode,
                        kernel_backend=backend, scan_max=scan_max)


def _batch(scan_max: int = 0, seed: int = 0, b: int = 16,
           n_cns: int = 4) -> OpBatch:
    """A small deterministic batch covering every OpKind with key contention
    (collisions on 8 slots) so the queue/combine paths are in the graph."""
    rng = np.random.default_rng(seed)
    kinds = np.array([0, 1, 2, 2, 3, 2, 0, 4] * (b // 8), np.int32)
    if scan_max:
        kinds[5::8] = 5  # SCAN lanes only when the probe pass is compiled in
    keys = (rng.integers(0, 8, size=b) * 2).astype(np.int32)
    values = rng.integers(0, 100, size=b).astype(np.int32)
    return OpBatch.make(kinds, keys, np.where(kinds == 5, 3, values),
                        n_cns=n_cns)


def _engine_args(cfg: EngineConfig, seed: int = 0, n_cns: int = 4):
    batch = _batch(cfg.scan_max, seed=seed, n_cns=n_cns)
    state = engine.store_init(cfg)
    state = engine.populate(cfg, state, np.arange(0, 16, 2, np.int32),
                            np.arange(8, dtype=np.int32))
    credits = credit_init(cfg.n_slots)
    alive = np.ones((n_cns,), bool)
    alive[-1] = False  # a dead CN keeps the §4.6 repair path in the graph
    died = np.zeros((n_cns,), bool)
    died[-1] = True
    valid = batch.kinds != 4
    return state, credits, batch, valid, jnp.asarray(alive), jnp.asarray(died)


def _trace_apply_batch(cfg: EngineConfig, seed: int = 0):
    state, credits, batch, valid, alive, died = _engine_args(cfg, seed)
    fn = lambda st, cr, b, v, a, d: engine.apply_batch(  # noqa: E731
        cfg, st, cr, b, valid=v, alive=a, died=d)
    return jax.make_jaxpr(fn)(state, credits, batch, valid, alive, died)


def _check_engine_graphs() -> list[Violation]:
    """Dtype/callback/collective audit of ``engine.apply_batch`` for every
    SyncMode x kernel backend x {point-only, SCAN-enabled} engine."""
    out = []
    for mode in _MODES:
        for backend in ("jnp", "pallas"):
            for scan_max in (0, 2):
                cfg = _cfg(mode, backend, scan_max)
                tgt = (f"engine.apply_batch[mode={mode.name},"
                       f"backend={backend},scan_max={scan_max}]")
                closed = _trace_apply_batch(cfg)
                out += audit_graph(closed, tgt)
                census = collective_census(closed)
                if census:
                    out.append(Violation(
                        "jaxpr_check", tgt,
                        f"single-device engine graph contains collectives "
                        f"{census} — cross-device traffic belongs only in "
                        f"dist.store"))
                prims = {e.primitive.name for e, _ in iter_eqns(closed)}
                wants_pallas = resolve_backend(backend)[0] == "pallas"
                if wants_pallas and "pallas_call" not in prims:
                    out.append(Violation(
                        "jaxpr_check", tgt,
                        "kernel_backend resolves to pallas but the graph "
                        "has no pallas_call — the dispatch seam is dead"))
                if not wants_pallas and "pallas_call" in prims:
                    out.append(Violation(
                        "jaxpr_check", tgt,
                        "kernel_backend resolves to jnp but the graph "
                        "contains pallas_call"))
    return out


def _stream(cfg: EngineConfig, w: int = 3, seed: int = 0):
    b, n_cns = 16, 4
    rng = np.random.default_rng(seed)
    kinds = np.stack([np.asarray(_batch(cfg.scan_max, seed=seed + i).kinds)
                      for i in range(w)])
    keys = rng.integers(0, 16, size=(w, b)).astype(np.int32)
    values = rng.integers(0, 100, size=(w, b)).astype(np.int32)
    alive = np.ones((w, n_cns), bool)
    alive[-1, -1] = False  # one CN dies at the last window
    return runner.make_stream(kinds, keys, np.where(kinds == 5, 2, values),
                              n_cns=n_cns, alive=alive)


def _compile_capture(lower_fn):
    """Lower + compile, capturing jax's donation warnings: a 'donated buffer
    was not usable' warning means the alias silently degraded to a copy."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        compiled = lower_fn().compile()
    donation_warns = [str(c.message) for c in caught
                      if "donat" in str(c.message).lower()]
    return compiled, donation_warns


def _check_runner() -> list[Violation]:
    """Donation + dtype + cache-stability audit of the fused window scan."""
    out = []
    want = expected_donation_pairs()
    for mode in _MODES:
        cfg = _cfg(mode)
        stream = _stream(cfg)
        state = engine.store_init(cfg)
        credits = credit_init(cfg.n_slots)
        prev = np.ones((4,), bool)
        tgt = f"runner.run_windows[mode={mode.name}]"
        for io_pw, traced in ((False, False), (True, True)):
            label = tgt if not traced else tgt + ".traced"
            compiled, warns = _compile_capture(
                lambda: runner._scan_windows.lower(
                    cfg, state, credits, stream, jnp.asarray(prev),
                    io_pw, traced))
            got = donation_pairs(compiled.as_text())
            if got < want:
                out.append(Violation(
                    "jaxpr_check", label,
                    f"only {got}/{want} donated carry leaves aliased in the "
                    f"compiled module — the scan is silently copying "
                    f"store/credit buffers"))
            for w in warns:
                out.append(Violation("jaxpr_check", label,
                                     f"donation degraded to a copy: {w}"))
            if "f64[" in compiled.as_text():
                out.append(Violation("jaxpr_check", label,
                                     "f64 buffer in compiled HLO"))
        closed = jax.make_jaxpr(
            lambda st, cr: runner.run_windows(cfg, st, cr, stream))(
                state, credits)
        out += audit_graph(closed, tgt)
        census = collective_census(closed)
        if census:
            out.append(Violation(
                "jaxpr_check", tgt,
                f"single-device runner graph contains collectives {census}"))
    return out


def _check_cache_stability() -> list[Violation]:
    """Traces that must share a jit cache entry must hash identically:
    (a) ``kernel_backend='auto'`` vs its resolved concrete backend — the
    dispatch seam promises 'auto' adds no recompiles; (b) same-shape
    streams with different contents — contents must never leak into the
    traced program (a leak = one recompile per window batch)."""
    out = []
    resolved = resolve_backend("auto")[0]
    for mode in (SyncMode.CIDER, SyncMode.OSYNC):
        d_auto = jaxpr_digest(_trace_apply_batch(_cfg(mode, "auto")))
        d_conc = jaxpr_digest(_trace_apply_batch(_cfg(mode, resolved)))
        tgt = f"engine.apply_batch[mode={mode.name}]"
        if d_auto != d_conc:
            out.append(Violation(
                "jaxpr_check", tgt,
                f"kernel_backend='auto' traces a different program than its "
                f"resolved backend '{resolved}' — the seam costs a recompile"))
        d_a = jaxpr_digest(_trace_apply_batch(_cfg(mode), seed=1))
        d_b = jaxpr_digest(_trace_apply_batch(_cfg(mode), seed=2))
        if d_a != d_b:
            out.append(Violation(
                "jaxpr_check", tgt,
                "same-shape batches with different contents trace different "
                "programs — batch contents leaked into the compile cache key"))
    return out


def _check_sharded(notes: list[str]) -> list[Violation]:
    """Donation + exact collective contract on the shard_map path."""
    from jax.sharding import Mesh

    from repro.dist import store as dstore

    n_dev = len(jax.devices())
    if n_dev < 2:
        notes.append(
            "sharded-path audit SKIPPED: single device (run via tools/"
            "analyze.py, which forces a multi-device host platform)")
        return []
    n_shards = 4 if n_dev >= 4 else 2
    mesh = Mesh(np.array(jax.devices()[:n_shards]), ("data",))
    out = []
    want_psum = expected_psums()
    want_alias = expected_donation_pairs()
    for mode in _MODES:
        cfg = _cfg(mode)
        stream = _stream(cfg)
        state = dstore.sharded_store_init(cfg, n_shards)
        credits = credit_init(cfg.n_slots)
        prev = jnp.ones((4,), bool)
        for traced in (False, True):
            tgt = (f"dist.run_windows_sharded[mode={mode.name}"
                   + (",traced]" if traced else "]"))
            fn = dstore._sharded_stream_fn(cfg, mesh, "data", traced, traced)
            closed = jax.make_jaxpr(fn)(state, credits, stream, prev)
            out += audit_graph(closed, tgt)
            census = collective_census(closed)
            expect = {"axis_index": 1, "psum": want_psum}
            if census != expect:
                out.append(Violation(
                    "jaxpr_check", tgt,
                    f"collective census {census} != documented credit-plane "
                    f"contract {expect} (one psum per Results field + one "
                    f"per IOMetrics field, axis_index once)"))
            in_scan = collective_census(closed, in_loop_only=True)
            if in_scan:
                out.append(Violation(
                    "jaxpr_check", tgt,
                    f"collectives {in_scan} inside the window scan body — "
                    f"the contract pays collectives once per stream, not "
                    f"per window"))
            compiled, warns = _compile_capture(
                lambda: fn.lower(state, credits, stream, prev))
            text = compiled.as_text()
            got = donation_pairs(text)
            if got < want_alias:
                out.append(Violation(
                    "jaxpr_check", tgt,
                    f"only {got}/{want_alias} donated carry leaves aliased "
                    f"in the compiled sharded module"))
            for w in warns:
                out.append(Violation("jaxpr_check", tgt,
                                     f"donation degraded to a copy: {w}"))
            from repro.rooflines.hlo_parser import parse_hlo
            kinds = set(parse_hlo(text).coll_by_kind)
            if not kinds <= {"all-reduce"}:
                out.append(Violation(
                    "jaxpr_check", tgt,
                    f"compiled HLO contains collective kinds {sorted(kinds)} "
                    f"— the contract allows only all-reduce (psum)"))
        # single-window variant shares the same contract
        tgt = f"dist.apply_batch_sharded[mode={mode.name}]"
        state2 = dstore.sharded_store_init(cfg, n_shards)
        batch = _batch()
        valid = batch.kinds != 4
        fn1 = dstore._sharded_fn(cfg, mesh, "data")
        closed = jax.make_jaxpr(fn1)(state2, credit_init(cfg.n_slots), batch, valid)
        census = collective_census(closed)
        expect = {"axis_index": 1, "psum": want_psum}
        if census != expect:
            out.append(Violation(
                "jaxpr_check", tgt,
                f"collective census {census} != contract {expect}"))
        out += audit_graph(closed, tgt)
    return out


def run(notes: list[str] | None = None) -> list[Violation]:
    """Audit the real codebase; returns all violations (empty == pass)."""
    notes = notes if notes is not None else []
    out = []
    out += _check_engine_graphs()
    out += _check_runner()
    out += _check_cache_stability()
    out += _check_sharded(notes)
    return out

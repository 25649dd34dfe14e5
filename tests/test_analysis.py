"""Tests for the repro.analysis invariant auditor (DESIGN.md §11).

Two obligations per pass: it is CLEAN on the real tree, and it FAILS LOUDLY
on an injected violation — a gate that cannot fail proves nothing.  The
injections are fixtures (in-memory sources for the lint, toy jitted
functions for the jaxpr audit, seeded-bug ``ModelFlags`` for the model
checker); the real tree is never mutated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from benchmarks.provenance import provenance
from repro.analysis import ANALYSIS_VERSION, PASSES, analysis_provenance
from repro.analysis import bill_lint, jaxpr_check, race_check
from repro.analysis.race_check import (
    ModelFlags, ReplScenario, Scenario, explore, explore_replicated,
    repl_scenarios)
from repro.core.types import OpKind, SyncMode

# ---------------------------------------------------------------- plumbing


def test_provenance_names_the_passes():
    p = analysis_provenance()
    assert p["version"] == ANALYSIS_VERSION
    assert tuple(p["passes"]) == PASSES == (
        "jaxpr_check", "bill_lint", "race_check")
    # and the benchmark config blocks carry it (satellite: every BENCH_*
    # JSON records which invariant gates its generating tree was under)
    assert provenance()["analysis"] == p


# ---------------------------------------------------------------- bill lint

METRICS_OK = """
## 1. IOMetrics

| field | unit | meaning |
|---|---|---|
| `reads` | verbs | pointer READs |
| `faa` | verbs | credit FAAs |

## 2. other
"""

RUNNER_OK = """
def modeled_throughput(io: IOMetrics):
    return io.reads + io.mn_iops
"""

TYPES_OK = """
class IOMetrics:
    @property
    def mn_iops(self):
        return self.reads + self.writes + self.cas + self.faa
"""


def test_bill_lint_clean_on_real_tree():
    assert bill_lint.run() == []


def test_bill_lint_rejects_undocumented_field():
    out = bill_lint.lint_sources(
        {"src/repro/core/engine.py": "io = IOMetrics(reads=r, cas=c)"},
        metrics_md=METRICS_OK, runner_source=RUNNER_OK,
        types_source=TYPES_OK, store_sources={},
        whitelist={})
    assert any("'cas'" in v.message and "no row" in v.message for v in out)


def test_bill_lint_rejects_unconsumed_unwhitelisted_field():
    md = METRICS_OK.replace(
        "| `faa` | verbs | credit FAAs |",
        "| `faa` | verbs | credit FAAs |\n| `retries` | count | waste |")
    src = "io = IOMetrics(reads=r, retries=w)"
    out = bill_lint.lint_sources(
        {"src/repro/core/engine.py": src}, metrics_md=md,
        runner_source=RUNNER_OK, types_source=TYPES_OK,
        store_sources={}, whitelist={})
    assert any("'retries'" in v.message and "never consumed" in v.message
               for v in out)
    # whitelisting with a reason silences exactly that violation
    ok = bill_lint.lint_sources(
        {"src/repro/core/engine.py": src}, metrics_md=md,
        runner_source=RUNNER_OK, types_source=TYPES_OK,
        store_sources={}, whitelist={"retries": "waste diagnostic"})
    assert not any("'retries'" in v.message for v in ok)


def test_bill_lint_rejects_stale_whitelist_entry():
    out = bill_lint.lint_sources(
        {}, metrics_md=METRICS_OK, runner_source=RUNNER_OK,
        types_source=TYPES_OK, store_sources={},
        whitelist={"not_a_field": "stale"})
    assert any("stale whitelist" in v.message for v in out)


def test_bill_lint_rejects_bare_notimplementederror_in_stores():
    src = ("def apply(self, kinds):\n"
           "    raise NotImplementedError('no SCAN')\n")
    out = bill_lint.lint_sources(
        {}, metrics_md=METRICS_OK, runner_source=RUNNER_OK,
        types_source=TYPES_OK,
        store_sources={"src/repro/stores/toy.py": src})
    assert any("UnsupportedOpError" in v.message for v in out)


def test_bill_lint_consumption_via_derived_metric_and_annotation_guard():
    derived = bill_lint.derived_field_map(
        open("src/repro/core/types.py").read())
    assert derived["mn_iops"] == {"reads", "writes", "cas", "faa"}
    # attribute reads on a non-IOMetrics-annotated param must NOT count
    sneaky = """
def modeled_throughput(res, io: IOMetrics):
    return res.retries + io.reads
"""
    got = bill_lint.consumed_fields(sneaky, derived={})
    assert got == {"reads"}


# ---------------------------------------------------------------- jaxpr pass


def test_jaxpr_contract_constants_match_types():
    # 5 StoreState + 2 CreditState donated leaves (ver+stranded packed into
    # one meta word); 9 Results + 11 IOMetrics psums — derived from the live
    # dataclasses, so a new field moves both the contract and the audit
    # together
    assert jaxpr_check.expected_donation_pairs() == 7
    assert jaxpr_check.expected_psums() == 20


def test_jaxpr_audit_flags_injected_f64():
    def leaky(x):
        return x.astype("float64") * 2.0

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(leaky)(jnp.ones((4,), jnp.float32))
        viols = jaxpr_check.audit_graph(closed, "toy")
    assert any("float64" in v.message for v in viols)


def test_jaxpr_audit_clean_on_allowed_dtypes():
    def fine(x):
        return (x * 2).astype(jnp.uint32)

    closed = jax.make_jaxpr(fine)(jnp.ones((4,), jnp.int32))
    assert jaxpr_check.audit_graph(closed, "toy") == []


def test_jaxpr_census_counts_injected_extra_psum():
    from jax.sharding import Mesh, PartitionSpec as P

    if jax.device_count() < 2:
        pytest.skip("needs >=2 devices")
    mesh = Mesh(jax.devices()[:2], ("data",))

    def one_psum(x):
        return jax.lax.psum(x, "data")

    def two_psums(x):
        return jax.lax.psum(x, "data") + jax.lax.psum(x * 2, "data")

    arg = jnp.ones((2, 4), jnp.float32)
    for fn, want in ((one_psum, 1), (two_psums, 2)):
        sharded = jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                                out_specs=P(), check_vma=False)
        census = jaxpr_check.collective_census(jax.make_jaxpr(sharded)(arg))
        assert census.get("psum", 0) == want
    # the contract comparison is exact: an extra collective is a mismatch
    assert {"psum": 2} != {"psum": 1}


def test_jaxpr_donation_detector():
    @jax.jit
    def f(a, b):
        return a + b

    args = (jnp.ones((8,), jnp.float32),) * 2
    plain = f.lower(*args).compile().as_text()
    assert jaxpr_check.donation_pairs(plain) == 0
    donated = jax.jit(lambda a, b: a + b, donate_argnums=(0,)).lower(
        *args).compile().as_text()
    assert jaxpr_check.donation_pairs(donated) == 1


def test_jaxpr_digest_is_stable_and_discriminating():
    def f(x):
        return x * 3 + 1

    a = jaxpr_check.jaxpr_digest(jax.make_jaxpr(f)(jnp.ones((4,), jnp.int32)))
    b = jaxpr_check.jaxpr_digest(jax.make_jaxpr(f)(jnp.ones((4,), jnp.int32)))
    c = jaxpr_check.jaxpr_digest(
        jax.make_jaxpr(f)(jnp.ones((5,), jnp.int32)))
    assert a == b != c


# ------------------------------------------------------------- race checker


def _clean(sc):
    viols, states = explore(sc)
    assert viols == [], [str(v) for v in viols]
    return states


def test_race_check_clean_on_real_machines_subset():
    u0, d0, i0 = (OpKind.UPDATE, 0), (OpKind.DELETE, 0), (OpKind.INSERT, 0)
    for mode in SyncMode:
        hot = (True, True) if mode == SyncMode.CIDER else (False, False)
        _clean(Scenario(mode, (u0, d0), (0,), hot))
        _clean(Scenario(mode, (i0, i0), (), hot))
        _clean(Scenario(mode, (u0, u0, d0), (0,), hot))
    # SCAN vs concurrent INSERT/DELETE replays exactly against the oracle
    _clean(Scenario(SyncMode.CIDER, ((OpKind.SCAN, 0), i0,
                                     (OpKind.DELETE, 1)), (1,),
                    (True, True)))


def test_race_check_detects_lost_delete_bug():
    sc = Scenario(SyncMode.CIDER, ((OpKind.UPDATE, 0), (OpKind.DELETE, 0)),
                  (0,), hot=(True, True),
                  flags=ModelFlags(combine_covers_deletes=True))
    viols, _ = explore(sc)
    assert any("0 committed events" in v.message and "DELETE" in v.message
               for v in viols), [str(v) for v in viols]


def test_race_check_detects_live_lock_break():
    for mode, needle in ((SyncMode.SPIN, "mutual exclusion"),
                         (SyncMode.MCS, "wait-queue rank")):
        sc = Scenario(mode, ((OpKind.UPDATE, 0), (OpKind.UPDATE, 0)), (0,),
                      flags=ModelFlags(repair_requires_dead_holder=False))
        viols, _ = explore(sc)
        msgs = [v.message for v in viols]
        assert any(needle in m for m in msgs), msgs
        assert any("LIVE lock" in m for m in msgs), msgs


def test_race_check_crash_repair_is_safe():
    # crash-at-any-step exploration: every recorded §4.6 repair names a
    # crashed owner, and survivors still serialize per the oracle
    for mode in (SyncMode.SPIN, SyncMode.MCS, SyncMode.CIDER):
        hot = (True, True) if mode == SyncMode.CIDER else (False, False)
        sc = Scenario(mode, ((OpKind.UPDATE, 0),) * 3, (0,), hot)
        viols, states = explore(sc, allow_crash=True)
        assert viols == [], [str(v) for v in viols]
        assert states > 100   # crash branching actually explored


def test_race_check_replicated_clean_on_real_machine():
    # the full quick replicated space (DESIGN.md §13 client-centric
    # replication, crash-at-any-step between primary CAS and fan-out)
    # is clean under the REAL flags, and the crash branching is explored
    n = states = 0
    for sc in repl_scenarios(quick=True):
        viols, s = explore_replicated(sc)
        assert viols == [], (sc.describe(), [str(v) for v in viols])
        n += 1
        states += s
    assert n >= 100 and states > 5_000


def test_race_check_replicated_crash_leaves_repairable_divergence():
    # a writer crashing between primary CAS and fan-out leaves the
    # replicas divergent; the REAL reader must resolve max-version and
    # roll the committed write forward — zero violations, and the crash
    # branch is genuinely in the explored space
    sc = ReplScenario(((OpKind.UPDATE, 0), (OpKind.SEARCH, 0)), (0,))
    viols, states = explore_replicated(sc, allow_crash=True)
    assert viols == [], [str(v) for v in viols]
    no_crash_states = explore_replicated(sc, allow_crash=False)[1]
    assert states > no_crash_states


def test_race_check_detects_stale_replica_read():
    # seeded bug: a read served from one arbitrary replica instead of
    # max-version resolution — caught twice (oracle replay divergence +
    # an explicit record naming the divergent replicas), and the
    # interleaving alone exposes it even with crashes disabled
    sc = ReplScenario(((OpKind.UPDATE, 0), (OpKind.SEARCH, 0)), (0,),
                      flags=ModelFlags(stale_replica_read=True))
    for allow_crash in (True, False):
        viols, _ = explore_replicated(sc, allow_crash=allow_crash)
        msgs = [v.message for v in viols]
        assert any("stale-replica read" in m and "replicas diverge" in m
                   for m in msgs), msgs
        assert any("oracle replay diverges" in m for m in msgs), msgs


def test_race_check_tick_conformance():
    # the shipped del_q gate on the REAL protocol.tick machine agrees with
    # the model: no combined batch over a queued DELETE, gate drains, and
    # the delete-free control still combines
    assert race_check._sim_conformance(None) == []

"""Engine correctness: all four sync modes vs the sequential oracle, exact
I/O metering formulas, and write-combining invariants (hypothesis-based)."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import combine as wc
from repro.core.credits import credit_init
from repro.core.engine import apply_batch, populate, store_init, store_view
from repro.core.oracle import OracleStore
from repro.core.types import EngineConfig, OpBatch, OpKind, SyncMode

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover
    HAVE_HYP = False

MODES = [SyncMode.OSYNC, SyncMode.SPIN, SyncMode.MCS, SyncMode.CIDER]

# Compilation dominates this file's wall-clock: apply_batch retraces per
# (mode, batch shape, store shape).  Every test below therefore sticks to
# ONE canonical (n_slots, b) = (N_SLOTS, B) where the scenario allows, so
# the four mode compiles from the first test are reused everywhere else.
N_SLOTS, B = 32, 256


def _cfg(mode, n_slots=64, heap=4096, **kw):
    return EngineConfig(n_slots=n_slots, heap_slots=heap, mode=mode, **kw)


def _run(mode, kinds, keys, values, n_slots=64, pop_keys=None, pop_vals=None,
         n_cns=4, **kw):
    cfg = _cfg(mode, n_slots=n_slots, **kw)
    state = store_init(cfg)
    if pop_keys is not None:
        state = populate(cfg, state, pop_keys, pop_vals)
    credits = credit_init(256)
    batch = OpBatch.make(kinds, keys, values, n_cns=n_cns)
    state, credits, res, io = apply_batch(cfg, state, credits, batch)
    return state, res, io


def _oracle(kinds, keys, values, n_slots=64, pop_keys=None, pop_vals=None):
    o = OracleStore()
    if pop_keys is not None:
        o.populate(pop_keys, pop_vals)
    ok, val = o.apply(kinds, keys, values)
    ex, v = o.view(n_slots)
    return ok, val, ex, v


def _random_ops(rng, b, n_slots, p_kinds=(0.3, 0.15, 0.4, 0.15)):
    kinds = rng.choice([OpKind.SEARCH, OpKind.INSERT, OpKind.UPDATE, OpKind.DELETE],
                       size=b, p=p_kinds).astype(np.int32)
    keys = rng.integers(0, n_slots, b).astype(np.int32)
    values = rng.integers(0, 10_000, b).astype(np.int32)
    return kinds, keys, values


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_mode_matches_oracle_mixed_idu(mode, seed):
    rng = np.random.default_rng(seed)
    n_slots, b = N_SLOTS, B
    pop_keys = rng.choice(n_slots, size=n_slots // 2, replace=False)
    pop_vals = rng.integers(0, 10_000, pop_keys.shape[0])
    kinds, keys, values = _random_ops(rng, b, n_slots)
    state, res, io = _run(mode, kinds, keys, values, n_slots=n_slots,
                          pop_keys=pop_keys, pop_vals=pop_vals)
    ok_o, val_o, ex_o, v_o = _oracle(kinds, keys, values, n_slots=n_slots,
                                     pop_keys=pop_keys, pop_vals=pop_vals)
    np.testing.assert_array_equal(np.asarray(res.ok), ok_o)
    np.testing.assert_array_equal(np.asarray(res.value), val_o)
    ex, v = store_view(state)
    np.testing.assert_array_equal(np.asarray(ex), ex_o)
    np.testing.assert_array_equal(np.asarray(v), v_o)


def test_all_modes_agree_on_final_state():
    rng = np.random.default_rng(7)
    n_slots, b = N_SLOTS, B   # same shapes as above -> shared jit cache
    pop_keys = np.arange(n_slots)
    pop_vals = rng.integers(0, 10_000, n_slots)
    kinds, keys, values = _random_ops(rng, b, n_slots)
    views = []
    for mode in MODES:
        state, _, _ = _run(mode, kinds, keys, values, n_slots=n_slots,
                           pop_keys=pop_keys, pop_vals=pop_vals)
        ex, v = store_view(state)
        views.append((np.asarray(ex), np.asarray(v)))
    for ex, v in views[1:]:
        np.testing.assert_array_equal(ex, views[0][0])
        np.testing.assert_array_equal(v, views[0][1])


def test_osync_quadratic_retries_single_hot_key():
    """Paper §2.2: n perfectly-synchronized writers on one key -> n(n-1)/2
    redundant CAS retries under optimistic synchronization."""
    n = 64
    kinds = np.full(n, OpKind.UPDATE, np.int32)
    keys = np.zeros(n, np.int32)
    values = np.arange(n, dtype=np.int32)
    # one client per CN => local WC cannot combine anything
    _, res, io = _run(SyncMode.OSYNC, kinds, keys, values, n_cns=n,
                      pop_keys=[0], pop_vals=[1])
    assert int(io.retries) == n * (n - 1) // 2
    assert int(io.writes) == n
    assert int(io.cas) == n * (n + 1) // 2


def test_cider_combines_hot_key_to_one_write():
    """§4.2.1: one executed write per wait queue regardless of batch size."""
    n = 64
    kinds = np.full(n, OpKind.UPDATE, np.int32)
    keys = np.zeros(n, np.int32)
    values = np.arange(n, dtype=np.int32)
    cfg = _cfg(SyncMode.CIDER)
    state = populate(cfg, store_init(cfg), [0], [1])
    credits = credit_init(256)
    credits.credit = credits.credit.at[:].set(100)  # force pessimistic path
    batch = OpBatch.make(kinds, keys, values, n_cns=n)
    state, credits, res, io = apply_batch(cfg, state, credits, batch)
    assert int(io.writes) == 1            # ONE combined write
    assert int(io.retries) == 0           # no redundant CAS
    assert int(io.combined) == n - 1
    ex, v = store_view(state)
    assert bool(ex[0]) and int(v[0]) == n - 1   # last writer wins
    # every client enqueues + FAAs (per-op lock cost still paid once each)
    assert int(io.cas) == n + 1
    assert int(io.faa) == n


def test_mcs_linear_io_no_combining():
    n = 64   # same batch shape as the other hot-key tests (shared compile)
    kinds = np.full(n, OpKind.UPDATE, np.int32)
    keys = np.zeros(n, np.int32)
    values = np.arange(n, dtype=np.int32)
    _, res, io = _run(SyncMode.MCS, kinds, keys, values, n_cns=n,
                      pop_keys=[0], pop_vals=[1])
    assert int(io.writes) == n
    assert int(io.cas) == 2 * n
    assert int(io.faa) == n
    assert int(io.retries) == 0


def test_local_wc_combines_within_cn():
    """Fig 4: local WC combines same-CN writers; cross-CN redundancy remains."""
    n, n_cns = 64, 4
    kinds = np.full(n, OpKind.UPDATE, np.int32)
    keys = np.zeros(n, np.int32)
    values = np.arange(n, dtype=np.int32)
    _, res, io = _run(SyncMode.OSYNC, kinds, keys, values, n_cns=n_cns,
                      pop_keys=[0], pop_vals=[1])
    m = n_cns  # one effective writer per CN
    assert int(io.writes) == m
    assert int(io.retries) == m * (m - 1) // 2
    assert int(io.combined) == n - m


def test_insert_delete_versioning():
    cfg = _cfg(SyncMode.CIDER, n_slots=8)
    state = store_init(cfg)
    credits = credit_init(64)
    kinds = np.array([OpKind.INSERT, OpKind.DELETE, OpKind.INSERT, OpKind.UPDATE],
                     np.int32)
    keys = np.zeros(4, np.int32)
    values = np.array([10, 0, 20, 30], np.int32)
    batch = OpBatch.make(kinds, keys, values)
    state, credits, res, io = apply_batch(cfg, state, credits, batch)
    np.testing.assert_array_equal(np.asarray(res.ok), [True] * 4)
    assert int(state.ver[0]) == 1         # one successful DELETE
    ex, v = store_view(state)
    assert bool(ex[0]) and int(v[0]) == 30


def test_search_sees_serialized_prefix():
    cfg = _cfg(SyncMode.MCS, n_slots=4)
    state = populate(cfg, store_init(cfg), [0], [5])
    credits = credit_init(64)
    kinds = np.array([OpKind.SEARCH, OpKind.UPDATE, OpKind.SEARCH], np.int32)
    keys = np.zeros(3, np.int32)
    values = np.array([0, 99, 0], np.int32)
    state, _, res, _ = apply_batch(cfg, state, credits,
                                   OpBatch.make(kinds, keys, values))
    assert int(res.value[0]) == 5
    assert int(res.value[2]) == 99


if HAVE_HYP:
    # Shape variety is capped (2 slot counts x 2 batch sizes) so the worst
    # case is 16 apply_batch compiles, not 36; deadline=None because a cold
    # compile on one example would otherwise flake the whole test.
    @settings(max_examples=8, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.sampled_from(MODES),
           st.sampled_from([1, 6]), st.sampled_from([1, 64]))
    def test_property_oracle_equivalence(seed, mode, n_slots, b):
        rng = np.random.default_rng(seed)
        kinds, keys, values = _random_ops(rng, b, n_slots)
        state, res, io = _run(mode, kinds, keys, values, n_slots=n_slots)
        ok_o, val_o, ex_o, v_o = _oracle(kinds, keys, values, n_slots=n_slots)
        np.testing.assert_array_equal(np.asarray(res.ok), ok_o)
        ex, v = store_view(state)
        np.testing.assert_array_equal(np.asarray(ex), ex_o)
        np.testing.assert_array_equal(np.asarray(v), v_o)
        # I/O sanity: every mode's MN IOPS >= one write per unique written key
        assert int(io.mn_iops) >= 0


def test_combine_plan_invariants():
    rng = np.random.default_rng(3)
    keys = jnp.asarray(rng.integers(0, 10, 64), jnp.int32)
    pos = jnp.arange(64, dtype=jnp.int32)
    valid = jnp.asarray(rng.random(64) < 0.8)
    plan = wc.plan_combine(keys, pos, valid)
    ks = np.asarray(plan.keys_sorted)
    assert (np.diff(ks) >= 0).all()
    # run_length sums to B; exactly one is_last per run
    assert int(np.asarray(plan.is_last).sum()) == int(np.asarray(plan.is_first).sum())
    stats = wc.per_key_stats(keys, pos, valid)
    # executor of each key is the max-pos valid op on that key
    k_np, v_np = np.asarray(keys), np.asarray(valid)
    for k in np.unique(k_np[v_np]):
        members = np.where((k_np == k) & v_np)[0]
        tail = members.max()
        assert bool(np.asarray(stats.is_tail)[tail])
        assert int(np.asarray(stats.mult_of)[tail]) == len(members)


# ---------------------------------------------------------------------------
# Local-WC grouping (step 4): the key-run cell table against numpy.
# ---------------------------------------------------------------------------

def _wc_window(seed, n_keys, n_cns, key_lo=0, b=B):
    """A duplicate-heavy window: few keys, random CNs, a shuffled unique
    ``pos`` and some invalid lanes."""
    rng = np.random.default_rng(seed)
    kinds = rng.choice([OpKind.SEARCH, OpKind.INSERT, OpKind.UPDATE,
                        OpKind.DELETE, OpKind.NOP], size=b,
                       p=(0.15, 0.1, 0.55, 0.1, 0.1)).astype(np.int32)
    keys = rng.integers(key_lo, key_lo + n_keys, b).astype(np.int32)
    batch = OpBatch(kinds=jnp.asarray(kinds), keys=jnp.asarray(keys),
                    values=jnp.asarray(rng.integers(0, 10_000, b), jnp.int32),
                    pos=jnp.asarray(rng.permutation(b), jnp.int32),
                    cn=jnp.asarray(rng.integers(0, n_cns, b), jnp.int32),
                    n_cns=n_cns)
    valid = jnp.asarray(rng.random(b) < 0.85)
    return batch, valid, rng


def _last_per_group(keys, cn, pos, mask):
    """Numpy reference: the max-``pos`` masked lane of each (key, cn)."""
    out = np.zeros(mask.shape, bool)
    for k, c in set(zip(keys[mask].tolist(), cn[mask].tolist())):
        lanes = np.flatnonzero(mask & (keys == k) & (cn == c))
        out[lanes[np.argmax(pos[lanes])]] = True
    return out


def _queue_rank(keys, pos, mask):
    """Numpy reference: masked lanes ahead of each masked lane on its key."""
    return np.array([int((mask & (keys == keys[i]) & (pos < pos[i])).sum())
                     if mask[i] else 0 for i in range(mask.shape[0])])


def _check_combine_grouping(seed, n_cns):
    batch, valid, rng = _wc_window(seed, n_keys=6, n_cns=n_cns)
    keys, cn, pos = batch.keys, batch.cn, batch.pos
    run_id = wc.key_run_ids(wc.plan_combine(keys, pos, valid))
    strict = valid & jnp.asarray(rng.random(B) < 0.5)
    for mask in (valid, strict, jnp.zeros((B,), bool)):
        got = wc.local_executors_by_run(run_id, cn, pos, mask, n_cns)
        np.testing.assert_array_equal(
            np.asarray(got), _last_per_group(*map(np.asarray, (keys, cn, pos,
                                                                mask))))


def _check_engine_grouping(mode, sharded):
    """apply_batch's local WC against numpy: which lanes ``Results.combined``
    marks, the writes bill and every queue rank — sharded with a nonzero
    base and half the window's keys out of shard, where CIDER's
    full-window credit feedback must also match the unsharded engine's."""
    n_cns = 4
    base = N_SLOTS if sharded else 0
    batch, valid, _ = _wc_window(7, n_keys=2 * N_SLOTS if sharded else 8,
                                 n_cns=n_cns)
    cfg = _cfg(mode, n_slots=N_SLOTS)
    kw = {}
    if sharded:
        kw = dict(owned=(batch.keys >= base) & (batch.keys < base + N_SLOTS),
                  slot_base=jnp.int32(base))
    state = populate(cfg, store_init(cfg), np.arange(0, N_SLOTS, 2),
                     np.arange(N_SLOTS // 2))
    cr0 = credit_init(64)
    cr0 = dataclasses.replace(cr0, credit=cr0.credit.at[::2].set(100))
    _, credits, res, io = apply_batch(cfg, state, cr0, batch, valid=valid,
                                      **kw)
    kinds, keys, cn, pos = (np.asarray(x) for x in (batch.kinds, batch.keys,
                                                    batch.cn, batch.pos))
    live = np.asarray(valid) & (kinds != OpKind.NOP)
    if sharded:
        live &= np.asarray(kw["owned"])
    pess = np.asarray(res.pessimistic)
    upd, ins = live & (kinds == OpKind.UPDATE), live & (kinds == OpKind.INSERT)
    dele = live & (kinds == OpKind.DELETE)
    if mode in (SyncMode.SPIN, SyncMode.MCS):
        np.testing.assert_array_equal(pess, upd)
        loc = _last_per_group(keys, cn, pos, pess)
        combined, writes = np.zeros_like(upd), ins.sum() + loc.sum()
        rank = _queue_rank(keys, pos, loc)
    else:
        assert pess.any() == (mode == SyncMode.CIDER)
        opt = upd & ~pess
        loc = _last_per_group(keys, cn, pos, opt)
        executor = _last_per_group(keys, 0 * cn, pos, pess)   # global WC
        combined = (opt & ~loc) | (pess & ~executor)
        writes = ins.sum() + loc.sum() + executor.sum()
        queue = loc | dele if mode == SyncMode.OSYNC else loc
        rank = _queue_rank(keys, pos, queue) + _queue_rank(keys, pos, pess)
    assert loc.sum() < (opt if mode in (SyncMode.OSYNC, SyncMode.CIDER)
                        else pess).sum()           # local WC combined some
    np.testing.assert_array_equal(np.asarray(res.combined), combined)
    np.testing.assert_array_equal(np.asarray(res.rank), rank)
    assert int(io.writes) == int(writes)
    if sharded and mode == SyncMode.CIDER:
        cfg1 = _cfg(mode, n_slots=2 * N_SLOTS)
        _, credits1, _, _ = apply_batch(cfg1, store_init(cfg1), cr0, batch,
                                        valid=valid)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), credits, credits1)


def _check_recovery_grouping(mode):
    """Step 5b with a ``died`` plane: the dead CNs' UPDATEs strand one node
    per (key, CN) local executor, dead DELETEs one each; live lockers repair
    them (MCS counts the chain, SPIN one word per key)."""
    n_cns = 4
    batch, _, _ = _wc_window(11, n_keys=8, n_cns=n_cns)
    died_np = np.array([False, True, False, True])
    kinds, cn, pos = (np.asarray(batch.kinds), np.asarray(batch.cn),
                      np.asarray(batch.pos))
    # dead CNs write keys [4, 12): keys 8-11 have no live locker to repair them
    keys = np.asarray(batch.keys) + 4 * died_np[cn]
    batch = dataclasses.replace(batch, keys=jnp.asarray(keys, jnp.int32))
    cfg = _cfg(mode, n_slots=N_SLOTS)
    state, _, res, io = apply_batch(
        cfg, store_init(cfg), credit_init(64), batch,
        alive=jnp.asarray(~died_np), died=jnp.asarray(died_np))
    upd, dele = kinds == OpKind.UPDATE, kinds == OpKind.DELETE
    dead, live = died_np[cn], ~died_np[cn]
    dead_node = _last_per_group(keys, cn, pos, dead & upd) | (dead & dele)
    tot = np.bincount(keys[dead_node], minlength=N_SLOTS)
    if mode == SyncMode.SPIN:
        tot = np.minimum(tot, 1)
    surv = _last_per_group(keys, cn, pos, live & upd) | (live & dele)
    repaired = np.zeros(N_SLOTS, bool)
    repaired[keys[surv]] = True
    repaired &= tot > 0
    np.testing.assert_array_equal(np.asarray(state.stranded),
                                  np.where(repaired, 0, tot))
    np.testing.assert_array_equal(np.asarray(res.orphan_wait),
                                  np.where(surv & repaired[keys], tot[keys], 0))
    assert int(io.orphan_windows) == int(((~repaired) & (tot > 0)).sum())
    if mode == SyncMode.MCS:
        assert int(io.repair_cas) == int(tot[repaired].sum())
    assert int(tot.sum()) > int(tot[repaired].sum()) > 0   # both outcomes seen


def _check_dead_tail_commit(mode):
    """A CN that dies this window owns the last lane of key runs that
    commit: the commits still land at the run's last completing lane, so
    the store view, ``ptr``, ``ver`` and heap equal those of the all-alive
    window without that CN's lanes."""
    n_cns, dead_cn = 4, 3
    batch, valid, _ = _wc_window(13, n_keys=8, n_cns=n_cns)
    pos = np.asarray(batch.pos)
    # the dying CN issues exactly the window's 16 latest lanes
    cn = np.where(pos >= B - 16, dead_cn, np.asarray(batch.cn) % dead_cn)
    batch = dataclasses.replace(batch, cn=jnp.asarray(cn, jnp.int32))
    dead = cn == dead_cn
    cfg = _cfg(mode, n_slots=N_SLOTS)
    state0 = populate(cfg, store_init(cfg), np.arange(0, N_SLOTS, 2),
                      np.arange(N_SLOTS // 2))
    died = np.arange(n_cns) == dead_cn
    crash = apply_batch(cfg, state0, credit_init(64), batch, valid=valid,
                        alive=jnp.asarray(~died), died=jnp.asarray(died))
    ref = apply_batch(cfg, state0, credit_init(64), batch,
                      valid=valid & jnp.asarray(~dead),
                      alive=jnp.ones(n_cns, bool), died=jnp.zeros(n_cns, bool))
    (st_c, _, res_c, _), (st_r, _, res_r, _) = crash, ref
    keys = np.asarray(batch.keys)
    issued = np.asarray(valid) & (np.asarray(batch.kinds) != OpKind.NOP)
    dead_last = [k for k in set(keys[issued].tolist())
                 if dead[issued & (keys == k)][np.argmax(
                     pos[issued & (keys == k)])]]
    moved = np.asarray(st_r.ptr) != np.asarray(state0.ptr)
    assert moved[dead_last].any()        # such a run commits
    for a, b in zip(store_view(st_c), store_view(st_r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for f in ("ptr", "ver", "heap", "heap_top"):
        np.testing.assert_array_equal(np.asarray(getattr(st_c, f)),
                                      np.asarray(getattr(st_r, f)), err_msg=f)
    for f in ("ok", "value"):
        np.testing.assert_array_equal(np.asarray(getattr(res_c, f)),
                                      np.asarray(getattr(res_r, f)), err_msg=f)


@pytest.mark.parametrize("case", [
    ("combine", 0, 1), ("combine", 1, 4), ("combine", 2, 16), ("combine", 3, 4),
    *[("engine", m, sharded) for m in MODES for sharded in (False, True)],
    ("recovery", SyncMode.MCS), ("recovery", SyncMode.SPIN),
    *[("dead_tail", m) for m in MODES],
], ids=str)
def test_local_wc_grouping_matches_sort(case):
    kind, *args = case
    {"combine": _check_combine_grouping, "engine": _check_engine_grouping,
     "recovery": _check_recovery_grouping,
     "dead_tail": _check_dead_tail_commit}[kind](*args)


@pytest.mark.parametrize("sharded", [False, True])
@pytest.mark.parametrize("mode", MODES)
def test_apply_batch_sorts_the_window_once(mode, sharded):
    """With a CN dying in the window and ``scan_max`` 0, apply_batch's jaxpr
    holds exactly one sort, step 1's (key, pos) plan, sharded or not: local
    WC, the crash plane and the full-window credit feedback are masks over
    that plan."""
    from repro.analysis.jaxpr_check import _cfg as _jcfg
    from repro.analysis.jaxpr_check import _engine_args, iter_eqns
    cfg = _jcfg(mode)
    args = _engine_args(cfg)
    if sharded:
        keys = args[2].keys
        args += (keys >= 8, jnp.int32(8))

    def fn(st, cr, b, v, a, d, owned=None, slot_base=None):
        return apply_batch(cfg, st, cr, b, valid=v, alive=a, died=d,
                           owned=owned, slot_base=slot_base)
    closed = jax.make_jaxpr(fn)(*args)
    sorts = [e for e, _ in iter_eqns(closed) if e.primitive.name == "sort"]
    assert len(sorts) == 1

"""CPU rehearsal of ``chip_smoke.py``: its phases, run at a tiny size on
the CPU (jnp kernels, virtual devices for the mesh), agree with the oracle
and across modes, and its entry point refuses any platform but ``tpu``.
DESIGN.md §6 (the fused runner the smoke run drives)."""
from __future__ import annotations

import importlib.util
import os
import sys

import pytest

import jax

from repro.core.types import SyncMode

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
cs = importlib.util.module_from_spec(_spec)
sys.modules["chip_smoke"] = cs         # dataclasses resolve through it
_spec.loader.exec_module(cs)

TINY = cs.Size(log2_slots=12, n_keys=3000, windows=3, batch=256, n_cns=4)


@pytest.mark.parametrize("workload", ["A", "E"])
def test_phase_matches_oracle_on_cpu(workload):
    out = cs.ycsb_phase(workload, TINY, seed=5, on_chip=False)
    assert set(out) == {m.name for m in SyncMode}


def test_sharded_phase_bit_equal_on_cpu_mesh():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    out = cs.sharded_phase(TINY, seed=5, n_shards=4, on_chip=False,
                           modes=(SyncMode.OSYNC, SyncMode.CIDER))
    assert set(out) == {"OSYNC", "CIDER"}


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_a_non_tpu_platform(argv, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main(argv) != 0
    assert capsys.readouterr().out == ""       # no result line


def test_kernels_in_reads_the_custom_calls():
    hlo = "\n".join([
        '%a = s32[32,128]{1,0} custom-call(%x), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(f)/jit(wc_combine)/'
        'pallas_call"}',
        '%b = s32[8] fusion(%y), metadata={op_name="jit(scan_probe)"}',
    ])
    assert cs.kernels_in(hlo) == {"wc_combine"}


def test_touched_slots_cover_scan_runs():
    import numpy as np
    kinds = np.array([cs.OpKind.SEARCH, cs.OpKind.SCAN, cs.OpKind.SCAN])
    keys = np.array([7, 10, 4094])
    counts = np.array([0, 3, 5])
    got = cs.touched_slots(kinds, keys, counts, n_slots=4096, scan_max=4)
    np.testing.assert_array_equal(got, [7, 10, 11, 12, 4094, 4095])


def test_compile_cache_location(monkeypatch):
    """Entry points keep JAX's cache where ``JAX_COMPILATION_CACHE_DIR``
    says, else at the fixed, git-ignored ``.jax_cache/`` of the checkout."""
    from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache
    assert CACHE_DIR == os.path.join(_ROOT, ".jax_cache")
    assert ".jax_cache/" in open(os.path.join(_ROOT, ".gitignore")).read()
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was   # left to JAX
    finally:
        jax.config.update("jax_compilation_cache_dir", was)

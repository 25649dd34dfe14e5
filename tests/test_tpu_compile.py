"""Compile-only checks for a TPU v5e chip that is described, not attached.

The Pallas kernels of the main path (``wc_combine``, ``scan_probe``) and
one fused ``runner._scan_windows`` holding them must pass the chip's own
compiler (Mosaic + XLA:TPU) at cell sizes: interpret-mode tests cannot see
tiling, lowering or memory refusals (DESIGN.md §10.1).  Nothing runs, so
these tests say nothing about results or times.

This is the only file that describes the topology.  It does so inside a
module-scoped fixture, never at import: only one process may load the TPU
library, and test workers import every test file.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import combine, engine, runner
from repro.core.credits import credit_init
from repro.core.types import EngineConfig, SyncMode
from repro.kernels.scan_probe.ops import scan_probe_op
from repro.kernels.wc_combine.ops import wc_combine_op

B, SCAN_MAX, N_CNS = 4096, 16, 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n", [B, B * SCAN_MAX, B * (1 + SCAN_MAX)])
def test_wc_combine_compiles_for_v5e(one_chip, n):
    fn = jax.jit(lambda k: wc_combine_op(k, interpret=False))
    hlo = fn.lower(_spec((n,), one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n", [B, B * SCAN_MAX, B * (1 + SCAN_MAX)])
def test_scan_probe_compiles_for_v5e(one_chip, n):
    fn = jax.jit(lambda k, s, w, e: scan_probe_op(k, s, w, e,
                                                  interpret=False))
    x = _spec((n,), one_chip)
    hlo = fn.lower(x, x, x, x).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_fused_scan_compiles_with_both_kernels(one_chip, monkeypatch):
    """One YCSB E-shaped window scan (B lanes, scan_max probes per lane)
    with ``auto`` steered to the compiled kernels, as on a chip."""
    monkeypatch.setattr(combine, "resolve_backend",
                        lambda backend: ("pallas", False))
    jax.clear_caches()           # no CPU trace of this config may be reused
    cfg = EngineConfig(n_slots=1 << 16, heap_slots=1 << 18,
                       mode=SyncMode.CIDER, scan_max=SCAN_MAX)
    z = jnp.zeros((2, B), jnp.int32)
    shapes = jax.eval_shape(lambda: (
        engine.store_init(cfg), credit_init(4096),
        runner.make_stream(z, z, z, n_cns=N_CNS)))
    args = jax.tree.map(lambda s: _spec(s.shape, one_chip, s.dtype), shapes)
    prev = _spec((N_CNS,), one_chip, jnp.bool_)
    hlo = runner._scan_windows.lower(cfg, *args, prev, False,
                                     False).compile().as_text()
    kernel_lines = [ln for ln in hlo.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in ln]
    assert any("jit(wc_combine)" in ln for ln in kernel_lines)
    assert any("jit(scan_probe)" in ln for ln in kernel_lines)


def test_fused_scan_temp_is_window_sized(one_chip, monkeypatch):
    """YCSB A's call program at the cell's shape (2^26 slots, B lanes,
    16 CNs, CIDER, no SCAN) holds less than one byte of temporaries per
    (slot, CN) pair, so no (slots x CNs) cell table of any element type
    can come back: local WC groups by key run.  What remains (0.74 GB) is
    step 5b's per-slot planes."""
    monkeypatch.setattr(combine, "resolve_backend",
                        lambda backend: ("pallas", False))
    jax.clear_caches()
    cfg = EngineConfig(n_slots=1 << 26, heap_slots=1 << 28,
                       mode=SyncMode.CIDER, scan_max=0)
    z = jnp.zeros((1, B), jnp.int32)
    shapes = jax.eval_shape(lambda: (
        engine.store_init(cfg), credit_init(4096),
        runner.make_stream(z, z, z, n_cns=N_CNS)))
    args = jax.tree.map(lambda s: _spec(s.shape, one_chip, s.dtype), shapes)
    prev = _spec((N_CNS,), one_chip, jnp.bool_)
    compiled = runner._scan_windows.lower(cfg, *args, prev, False,
                                          False).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < cfg.n_slots * N_CNS

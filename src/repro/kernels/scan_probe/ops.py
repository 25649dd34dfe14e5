"""Jit'd wrapper for scan_probe.

DESIGN.md §10.3 (fused SCAN reader-probe pass): public wrapper with the
same padded tile layout as wc_combine — any N is padded with (+inf key,
setcode -1, no writer, absent) lanes, which open or extend a trailing
sentinel run *after* every real lane; both outputs are prefix sweeps, so
slicing back to N needs no fix-up.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.scan_probe.ref import scan_probe_ref
from repro.kernels.scan_probe.scan_probe import scan_probe
from repro.kernels.tile_scan import layout, to_tiles

__all__ = ["scan_probe_op", "scan_probe_ref"]

_BIG = 2**31 - 1   # python int: this module may first be imported inside a jit trace


def scan_probe_op(keys_sorted, setcode, writer, e_init,
                  block=4096, interpret=None):
    """``(e_before bool, waits int32)`` per sorted lane — the contract of
    ``scan_probe_ref``."""
    n = keys_sorted.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lanes, rows, n_rows = layout(n, block, interpret)
    planes = [to_tiles(x.astype(jnp.int32), fill, lanes, n_rows)
              for x, fill in ((keys_sorted, _BIG), (setcode, -1),
                              (writer, 0), (e_init, 0))]
    e_before, waits = scan_probe(*planes, rows=rows, interpret=interpret)
    return e_before.reshape(-1)[:n] != 0, waits.reshape(-1)[:n]

"""Jit'd wrapper for wc_combine.

DESIGN.md §2.1 (the combine primitive): public jit wrapper for the
wc_combine kernel.  Any N is padded with the +inf invalid-key sentinel to
whole tile-aligned blocks and the tail sliced off (DESIGN.md §10.1), so
odd batch sizes (elastic-membership runs shrink B) dispatch instead of
crashing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.tile_scan import layout, to_tiles
from repro.kernels.wc_combine.ref import wc_combine_ref
from repro.kernels.wc_combine.wc_combine import wc_combine

__all__ = ["wc_combine_op", "wc_combine_ref"]

_BIG = 2**31 - 1   # python int: this module may first be imported inside a jit trace


def wc_combine_op(keys_sorted, block=4096, interpret=None):
    """``(is_first, is_last, rank)`` of an ascending ``(N,)`` key array —
    the contract of ``wc_combine_ref``."""
    keys_sorted = keys_sorted.astype(jnp.int32)
    n = keys_sorted.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lanes, rows, n_rows = layout(n, block, interpret)
    # The +inf padding sorts after every real key, so it opens its own
    # trailing run or extends a sentinel run: the real prefix's ranks are
    # untouched.
    rank = wc_combine(to_tiles(keys_sorted, _BIG, lanes, n_rows), rows=rows,
                      interpret=interpret).reshape(-1)[:n]
    first = rank == 0
    # a run's tail is the lane before the next head; lane n-1 always is
    last = jnp.concatenate([first[1:], jnp.ones((1,), bool)])
    return first, last, rank

"""Prefix sweeps over a ``(rows, lanes)`` block in row-major (flat) order,
built only from ops Mosaic lowers for the TPU: ``pltpu.roll`` shifts,
iota masks, elementwise max/add and reductions.

DESIGN.md §10.1 (padded block dispatch): the sorted-run kernels
(``wc_combine``, ``scan_probe``) view an ``(N,)`` lane vector as an
``(N // lanes, lanes)`` int32 array so each block is tile-aligned.  A flat
prefix scan is then a log-step scan along the lanes of every row, followed
by a log-step scan of the row totals down the sublanes.  ``lax.cummax`` /
``cumsum`` and vector-to-scalar reads at non-zero offsets have no Mosaic
lowering; nothing here uses them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

__all__ = ["LANES", "NEG", "layout", "to_tiles", "flat_index", "shift1",
           "cumulative", "tail"]

LANES = 128                    # TPU vreg lane width
SUBLANES = 8                   # int32 rows per vreg
NEG = -2**31 + 1               # python int: jnp constants would be captured


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def layout(n: int, block: int, interpret: bool) -> tuple[int, int, int]:
    """``(lanes, rows_per_block, n_rows)`` for sweeping ``n`` lanes in blocks
    of about ``block`` elements.  Compiled blocks are whole ``(8, 128)``
    tiles; interpret mode (CPU tests) also takes narrower blocks so small
    inputs still cross several block boundaries.  A short input is one
    block that spans the whole array."""
    lanes = min(LANES, block) if interpret else LANES
    sub = 1 if interpret else SUBLANES
    rows = _round_up(max(block // lanes, 1), sub)
    n_rows = _round_up(-(-n // lanes), sub)
    if n_rows <= rows:
        return lanes, n_rows, n_rows
    return lanes, rows, _round_up(n_rows, rows)


def to_tiles(x: jax.Array, fill: int, lanes: int, n_rows: int) -> jax.Array:
    """Pad ``(n,)`` int32 ``x`` with ``fill`` and view it as
    ``(n_rows, lanes)``."""
    pad = n_rows * lanes - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, jnp.int32)])
    return x.reshape(n_rows, lanes)


def flat_index(shape) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``(row, lane, flat)`` int32 index planes of a ``(rows, lanes)`` block."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return row, lane, row * shape[1] + lane


def _roll(x, shift, axis):
    return pltpu.roll(x, shift % x.shape[axis], axis)


def shift1(x, fill, row, lane):
    """Flat shift by one: ``out[i] = x[i - 1]``, ``out[0] = fill``."""
    a = _roll(x, 1, 1)                       # a[r, l] = x[r, l - 1]
    if x.shape[0] > 1:
        a = jnp.where(lane == 0, _roll(a, 1, 0), a)   # x[r - 1, lanes - 1]
    return jnp.where((row == 0) & (lane == 0), fill, a)


def cumulative(x, op, identity, row, lane):
    """Inclusive flat prefix ``op``-scan (``op`` is ``jnp.maximum`` or
    ``jnp.add``, ``identity`` its neutral element)."""
    rows, lanes = x.shape
    s = 1
    while s < lanes:                          # within each row
        x = op(x, jnp.where(lane >= s, _roll(x, s, 1), identity))
        s *= 2
    if rows == 1:
        return x
    # row totals (each row's last lane), broadcast along the lanes,
    # scanned down the rows
    t = jnp.broadcast_to(
        jnp.sum(jnp.where(lane == lanes - 1, x, 0), axis=1, keepdims=True),
        x.shape)
    s = 1
    while s < rows:
        t = op(t, jnp.where(row >= s, _roll(t, s, 0), identity))
        s *= 2
    return op(x, jnp.where(row >= 1, _roll(t, 1, 0), identity))


def tail(x, flat):
    """The block's last flat element as a scalar (a masked reduction, so
    no vector-to-scalar read at a non-zero offset)."""
    return jnp.sum(jnp.where(flat == x.size - 1, x, 0))

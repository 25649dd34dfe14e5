"""Global write-combining Pallas kernel: one VMEM pass over a SORTED key run
emitting, per element, its rank within its run — the materialized wait
queues of §4.2 (detect + combine in one sweep).  ``rank == 0`` marks a run
head, and a run tail is the element before the next head, so the rank
plane alone carries ``(is_first, is_last, rank)``.

The ``(N,)`` keys are viewed as an ``(N // lanes, lanes)`` int32 array and
swept in tile-aligned ``(rows, lanes)`` blocks (``kernels/tile_scan.py``).
Cross-block runs are handled by a sequential grid with an SMEM carry
(previous block's last key + its accumulated run length): TPU grid
execution is ordered, so block i reads the carry block i-1 wrote.

DESIGN.md §2.1 (the combine primitive): Pallas twin of
core/combine.plan_combine — identical contract, fused VMEM pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tile_scan import NEG, cumulative, flat_index, shift1, tail


def _kernel(keys_ref, rank_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[0] = jnp.int32(NEG)          # "no previous key"
        carry_ref[1] = jnp.int32(0)            # run length so far

    k = keys_ref[...]                          # (rows, lanes) int32
    prev_len = carry_ref[1]
    row, lane, idx = flat_index(k.shape)
    first = k != shift1(k, carry_ref[0], row, lane)
    # rank within run: idx - start_of_run; a run continued from the previous
    # block "starts" prev_len lanes before this block
    start = cumulative(jnp.where(first, idx, -prev_len), jnp.maximum, NEG,
                       row, lane)
    rank = idx - start
    rank_ref[...] = rank
    # carry out: last key + length of its (possibly continued) run
    carry_ref[0] = tail(k, idx)
    carry_ref[1] = tail(rank, idx) + 1


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def wc_combine(keys_sorted, *, rows, interpret=False):
    """keys_sorted: ``(R, lanes)`` int32, ascending in row-major order, with
    ``R`` a multiple of ``rows`` (the block height).  Returns the in-run
    rank plane, same shape."""
    n_rows, lanes = keys_sorted.shape
    spec = pl.BlockSpec((rows, lanes), lambda i: (i, 0))
    return pl.pallas_call(
        _kernel,
        grid=(n_rows // rows,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(keys_sorted.shape, jnp.int32),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(keys_sorted)

"""Fused SCAN reader-probe Pallas kernel: one VMEM pass over lanes SORTED
by (key, pos) emitting, per lane, the existence bit observed just before it
(``e_before``) and the count of writer lanes strictly ahead in its key run
(``waits``) — the engine's step-5c probe resolution and ``reader_waits``
rank in a single sweep (DESIGN.md §10.3), replacing two full sorts.

Layout and cross-block carry follow wc_combine (DESIGN.md §2.1): the lanes
are swept as tile-aligned ``(rows, lanes)`` int32 blocks on a sequential
grid, and block i reads the SMEM carry block i-1 wrote.  The carry holds
(previous block's last key, the last setcode seen in its still-open run
[-1 if none], the writer count so far in that run).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tile_scan import NEG, cumulative, flat_index, shift1, tail


def _kernel(keys_ref, set_ref, writer_ref, einit_ref,
            eb_ref, waits_ref, carry_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[0] = jnp.int32(NEG)    # "no previous key"
        carry_ref[1] = jnp.int32(-1)     # open run has no setter yet
        carry_ref[2] = jnp.int32(0)      # writers so far in open run

    k = keys_ref[...]                    # (rows, lanes) int32
    sc = set_ref[...]                    # int32 in {-1, 0, 1}
    w = writer_ref[...]                  # int32 in {0, 1}
    ei = einit_ref[...]                  # int32 in {0, 1}
    carry_set = carry_ref[1]
    carry_w = carry_ref[2]
    row, lane, idx = flat_index(k.shape)
    first = k != shift1(k, carry_ref[0], row, lane)
    start = cumulative(jnp.where(first, idx, NEG), jnp.maximum, NEG,
                       row, lane)
    in_carry = start == NEG              # run continues from previous block
    start_c = jnp.where(in_carry, 0, start)
    # last setter at or before me (g), strictly before me (g_excl), in-run
    enc = jnp.where(sc >= 0, 2 * idx + sc, -1)
    g = cumulative(enc, jnp.maximum, -1, row, lane)
    g_excl = shift1(g, -1, row, lane)
    has = (g_excl >= 0) & ((g_excl >> 1) >= start_c)
    # no in-run setter yet: the carried run's setter, else the initial bit
    # (int32 throughout: Mosaic cannot broadcast a scalar bool)
    run_set_in = jnp.where(in_carry, carry_set, -1)
    e_b = jnp.where(has, g_excl & 1,
                    jnp.where(run_set_in >= 0, run_set_in, ei))
    # writers strictly ahead of me in my run
    cex = cumulative(w, jnp.add, 0, row, lane) - w
    base = cumulative(jnp.where(first, cex, 0), jnp.maximum, 0, row, lane)
    waits = cex - jnp.where(in_carry, 0, base) + jnp.where(in_carry, carry_w, 0)
    eb_ref[...] = e_b
    waits_ref[...] = waits
    # carry out: tail lane's key + its run's last setcode and writer count
    has_inc = (g >= 0) & ((g >> 1) >= start_c)
    run_set = jnp.where(has_inc, g & 1, run_set_in)
    carry_ref[0] = tail(k, idx)
    carry_ref[1] = tail(run_set, idx)
    carry_ref[2] = tail(waits + w, idx)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def scan_probe(keys_sorted, setcode, writer, e_init, *, rows,
               interpret=False):
    """All inputs ``(R, lanes)`` int32 with ``R`` a multiple of ``rows``
    (the block height), sorted by (key, pos) in row-major order;
    ``writer``/``e_init`` are 0/1.  Returns int32 ``(e_before, waits)``."""
    n_rows, lanes = keys_sorted.shape
    spec = pl.BlockSpec((rows, lanes), lambda i: (i, 0))
    out = jax.ShapeDtypeStruct(keys_sorted.shape, jnp.int32)
    return pl.pallas_call(
        _kernel,
        grid=(n_rows // rows,),
        in_specs=[spec, spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[out, out],
        scratch_shapes=[pltpu.SMEM((3,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(keys_sorted, setcode, writer, e_init)

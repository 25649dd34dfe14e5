"""One-pass shard_map decode attention over a sequence-sharded KV cache.

``models.attention.decode_attention`` is the XLA-SPMD reference: plain
reductions whose softmax max/sum lower to all-reduces.  This module is the
explicit-collective variant: the cache's ``smax`` axis is block-partitioned
over one mesh axis, each shard computes its local scores in one pass, and
exactly three collectives (pmax for the running max, psum for the normalizer
and the weighted values) produce the identical result — the communication
pattern the reference only reaches after XLA's partitioner gets it right.

DESIGN.md §3 (distribution layer): shard_map decode attention over a
sequence-sharded KV cache, exact vs the SPMD reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.attention import NEG_INF, decode_attention

__all__ = ["decode_attention_spmd"]


def decode_attention_spmd(mesh, q, k_cache, v_cache, length, *,
                          seq_axis: str = "model"):
    """q: (B, 1, H, D); caches: (B, Smax, K, D); attend over pos < ``length``.

    The cache sequence axis is sharded ``mesh.shape[seq_axis]`` ways; q is
    replicated (one token).  Falls back to the reference when Smax is not
    divisible by the mesh axis.
    """
    b, _, h, d = q.shape
    smax, kh = k_cache.shape[1], k_cache.shape[2]
    n = int(mesh.shape[seq_axis])
    if n <= 1 or smax % n != 0:
        return decode_attention(q, k_cache, v_cache, length)
    g = h // kh
    scale = d ** -0.5
    length = jnp.asarray(length, jnp.int32)

    def local(qs, ks, vs, ln):
        s_loc = ks.shape[1]
        offs = jax.lax.axis_index(seq_axis) * s_loc
        qg = qs.reshape(b, kh, g, d).astype(jnp.float32) * scale
        sc = jnp.einsum("bkgd,bskd->bkgs", qg, ks.astype(jnp.float32))
        pos = offs + jnp.arange(s_loc)
        sc = jnp.where(pos[None, None, None, :] < ln, sc, NEG_INF)
        m = jax.lax.pmax(jnp.max(sc, -1), seq_axis)
        p = jnp.exp(sc - m[..., None])
        denom = jax.lax.psum(jnp.sum(p, -1), seq_axis)
        num = jax.lax.psum(
            jnp.einsum("bkgs,bskd->bkgd", p, vs.astype(jnp.float32)), seq_axis)
        out = num / jnp.maximum(denom, 1e-30)[..., None]
        return out.reshape(b, 1, h, d).astype(qs.dtype)

    rep = P(None, None, None, None)
    kv = P(None, seq_axis, None, None)
    fn = jax.shard_map(local, mesh=mesh, in_specs=(rep, kv, kv, P()),
                       out_specs=rep, check_vma=False)
    return fn(q, k_cache, v_cache, length)

"""Flash-decoding over a sequence-sharded KV cache (beyond-paper §Perf).

At decode time the KV cache dominates memory; sharding its *sequence* dim
over the `model` axis divides it 16-way, but naive jnp attention then
forces XLA to all-gather the cache every step.  This module computes
attention WITHOUT gathering: each shard produces a partial softmax
(local max, local sum-exp, local weighted values) over its KV slice and
the shards combine with two tiny collectives (pmax + psum of (B,H,hd)) —
the TPU analogue of flash-decoding / paged attention.

Wire cost per step: psum of o_partial (B,H,hd) + scalars, vs all-gather
of the cache (B,K,S,hd) — a ~S/hd reduction in collective bytes.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG = -1e30


def _partial_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       valid: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Local partial softmax over this shard's KV slice.

    q: (B, K, G, hd); k/v: (B, K, S_loc, hd); valid: (B, S_loc) bool.
    Returns (o_partial (B,K,G,hd) — exp-weighted values, m (B,K,G),
    l (B,K,G) — local sum-exp)."""
    hd = q.shape[-1]
    s = jnp.einsum("bkgh,bksh->bkgs", q, k) / jnp.sqrt(hd).astype(q.dtype)
    s = jnp.where(valid[:, None, None, :], s.astype(jnp.float32), NEG)
    m = jnp.max(s, axis=-1)                                   # (B,K,G)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bksh->bkgh", p.astype(v.dtype), v)
    return o.astype(jnp.float32), m, l


def sharded_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                             v_cache: jnp.ndarray, pos: jnp.ndarray,
                             mesh: Mesh, seq_axis: str = "model",
                             batch_axis: Optional[str] = "data"
                             ) -> jnp.ndarray:
    """q: (B, H, hd); k/v_cache: (B, K, S, hd) with S sharded over
    `seq_axis`; pos: (B,) current positions.  → (B, H, hd).

    Each shard sees S/n contiguous slots; validity is computed from the
    global slot index (cache is linear layout: slot t ≤ pos is valid).
    """
    B, H, hd = q.shape
    K = k_cache.shape[1]
    S = k_cache.shape[2]
    G = H // K
    n_shards = mesh.shape[seq_axis]
    s_loc = S // n_shards

    baxis = batch_axis if (batch_axis in mesh.shape.keys()
                           and B % mesh.shape[batch_axis] == 0) else None
    qspec = P(baxis, None, None, None)
    cspec = P(baxis, None, seq_axis, None)
    pspec = P(baxis)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(qspec, cspec, cspec, pspec),
             out_specs=P(baxis, None, None, None),
             check_vma=False)
    def body(qg, k, v, p_):
        shard = jax.lax.axis_index(seq_axis)
        base = shard * s_loc
        idx = base + jnp.arange(s_loc)
        valid = idx[None, :] <= p_[:, None]                    # (B_loc, s_loc)
        o, m, l = _partial_attention(qg, k, v, valid)
        # combine partial softmaxes across shards (flash-decoding merge)
        m_g = jax.lax.pmax(m, seq_axis)                        # (B,K,G)
        scale = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * scale, seq_axis)
        o_g = jax.lax.psum(o * scale[..., None], seq_axis)
        return (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(qg.dtype)

    qg = q.reshape(B, K, G, hd)
    out = body(qg, k_cache, v_cache, pos)
    return out.reshape(B, H, hd)


def reference_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                               v_cache: jnp.ndarray,
                               pos: jnp.ndarray) -> jnp.ndarray:
    """Unsharded oracle for the combine math."""
    B, H, hd = q.shape
    K = k_cache.shape[1]
    S = k_cache.shape[2]
    qg = q.reshape(B, K, H // K, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qg, k_cache) / jnp.sqrt(hd)
    valid = jnp.arange(S)[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s.astype(jnp.float32), NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksh->bkgh", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, H, hd)

"""Pallas TPU kernels: client-update compression (encode/decode pair).

FedLess (arXiv:2111.03396) measures update-transfer size as the dominant
serverless FL cost driver; this module shrinks the per-round client
payload 10-50x with two schemes, both exact enough to keep the delta
MergePipeline (Reddi et al., arXiv:2003.00295) parity-correct when
combined with client-side error feedback (core/compress.py):

  int8 per-chunk quantization — the flattened update is cut into fixed
      chunks; each chunk carries one fp32 scale = absmax/127 and int8
      codes q = round(x/scale).  Payload: 1 byte/param + 4 bytes/chunk.
  top-k sparsification — keep the k largest-|x| entries (ties broken
      deterministically toward the LOWEST index, matching lax.top_k), zero
      the rest.  Payload: 8 bytes/kept entry (int32 index + fp32 value).

The kernels operate on the server-side *decode* representation (a dense
(P,) vector) because everything downstream — fed_agg, fed_agg_apply, the
sharded merge — consumes dense flats; the wire format is a simulation
quantity (payload_bytes on ClientUpdate), not a serialized artifact.

Like fed_agg, blocks are 2D (rows × lanes) so Mosaic lowering gets the
(8, 128)-friendly layouts it wants; iota is always built 2D per the
Pallas TPU rules.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

COMPRESS_SCHEMES = ("none", "topk", "int8")


# ------------------------------------------------------------ int8
def _int8_encode_kernel(x_ref, q_ref, scale_ref):
    """One (TR, C) block of chunk-rows → int8 codes + per-row scale.

    scale = absmax/127 (1.0 when the chunk is all-zero, so decode is
    exact 0 and no NaN/inf ever enters the payload path); codes use
    round-half-to-even, matching jnp.round in the oracle bit-for-bit.
    """
    x = x_ref[...].astype(jnp.float32)                       # (TR, C)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)      # (TR, 1)
    scale = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("chunk", "tile_r", "interpret"))
def int8_encode(x: jnp.ndarray, chunk: int = 256, tile_r: int = 8,
                interpret: bool = True):
    """x: (P,) float → (q: (n_chunks, chunk) int8, scale: (n_chunks,) f32).

    P is zero-padded up to a whole number of chunks (pad codes decode to
    exact 0 and are sliced away by int8_decode), chunk rows are padded to
    a tile_r multiple for the grid.
    """
    P = x.shape[0]
    n_chunks = -(-P // chunk)
    n_rows = -(-n_chunks // tile_r) * tile_r
    xm = jnp.pad(x.astype(jnp.float32),
                 (0, n_rows * chunk - P)).reshape(n_rows, chunk)

    q, scale = pl.pallas_call(
        _int8_encode_kernel,
        grid=(n_rows // tile_r,),
        in_specs=[pl.BlockSpec((tile_r, chunk), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tile_r, chunk), lambda i: (i, 0)),
                   pl.BlockSpec((tile_r, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_rows, chunk), jnp.int8),
                   jax.ShapeDtypeStruct((n_rows, 1), jnp.float32)],
        interpret=interpret,
        name="int8_encode",
    )(xm)
    return q[:n_chunks], scale[:n_chunks, 0]


def _int8_decode_kernel(q_ref, scale_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[...]


@functools.partial(jax.jit, static_argnames=("length", "tile_r", "interpret"))
def int8_decode(q: jnp.ndarray, scale: jnp.ndarray, length: int,
                tile_r: int = 8, interpret: bool = True) -> jnp.ndarray:
    """Inverse of int8_encode: (n_chunks, chunk) int8 + (n_chunks,) f32
    scales → dense (length,) f32."""
    n_chunks, chunk = q.shape
    n_rows = -(-n_chunks // tile_r) * tile_r
    qm = jnp.pad(q, ((0, n_rows - n_chunks), (0, 0)))
    sm = jnp.pad(scale.astype(jnp.float32),
                 (0, n_rows - n_chunks)).reshape(n_rows, 1)

    out = pl.pallas_call(
        _int8_decode_kernel,
        grid=(n_rows // tile_r,),
        in_specs=[pl.BlockSpec((tile_r, chunk), lambda i: (i, 0)),
                  pl.BlockSpec((tile_r, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_r, chunk), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, chunk), jnp.float32),
        interpret=interpret,
        name="int8_decode",
    )(qm, sm)
    return out.reshape(-1)[:length]


# ------------------------------------------------------------ top-k
def _topk_mask_kernel(scal_ref, idx_ref, x_ref, out_ref):
    """One P-tile: keep x where |x| exceeds the threshold, plus the
    tie-breaking entries |x| == tau at global index ≤ last_keep (lowest-
    index-wins, the lax.top_k order), zero elsewhere."""
    tau = scal_ref[0, 0]
    last_keep = idx_ref[0, 0]
    x = x_ref[...]                                           # (1, TP)
    tp = x.shape[1]
    gidx = (pl.program_id(0) * tp
            + jax.lax.broadcasted_iota(jnp.int32, (1, tp), 1))
    ax = jnp.abs(x)
    keep = (ax > tau) | ((ax == tau) & (gidx <= last_keep))
    out_ref[...] = jnp.where(keep, x, 0.0)


@functools.partial(jax.jit, static_argnames=("tile_p", "interpret"))
def topk_mask(x: jnp.ndarray, tau: jnp.ndarray, last_keep: jnp.ndarray,
              tile_p: int = 2048, interpret: bool = True) -> jnp.ndarray:
    """Dense top-k decode given a threshold: x (P,) f32, tau the k-th
    largest |x|, last_keep the largest kept global index among the
    |x| == tau ties.  Zero-padded tail lanes have |x| = 0 ≤ tau and a
    value of 0 either way, so they never contaminate the output."""
    P = x.shape[0]
    tile_p = min(tile_p, P)
    n_tiles = -(-P // tile_p)
    pad = n_tiles * tile_p - P
    xr = jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(1, -1)
    scal = jnp.full((1, 8), tau, jnp.float32)
    idx = jnp.full((1, 8), last_keep, jnp.int32)

    out = pl.pallas_call(
        _topk_mask_kernel,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((1, 8), lambda i: (0, 0)),
                  pl.BlockSpec((1, 8), lambda i: (0, 0)),
                  pl.BlockSpec((1, tile_p), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, tile_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_tiles * tile_p), jnp.float32),
        interpret=interpret,
        name="topk_mask",
    )(scal, idx, xr)
    return out[0, :P]


@functools.partial(jax.jit, static_argnames=("k", "tile_p", "interpret"))
def topk_encode(x: jnp.ndarray, k: int, tile_p: int = 2048,
                interpret: bool = True):
    """x: (P,) float → (idx (k,) int32, vals (k,) f32, decoded (P,) f32).

    lax.top_k on |x| supplies the threshold and the deterministic
    tie-break order (equal magnitudes keep the lowest index); the Pallas
    mask kernel then materializes the dense decode in one pass without a
    (P,)-sized scatter.
    """
    P = x.shape[0]
    xf = x.astype(jnp.float32)
    if k >= P:                      # degenerate: keep everything
        idx = jnp.arange(P, dtype=jnp.int32)
        return idx, xf, xf
    mags, idx = jax.lax.top_k(jnp.abs(xf), k)
    tau = mags[k - 1]
    last_keep = jnp.max(jnp.where(mags == tau, idx, -1)).astype(jnp.int32)
    decoded = topk_mask(xf, tau, last_keep, tile_p=tile_p,
                        interpret=interpret)
    return idx.astype(jnp.int32), xf[idx], decoded


@functools.partial(jax.jit, static_argnames=("length",))
def topk_decode(idx: jnp.ndarray, vals: jnp.ndarray,
                length: int) -> jnp.ndarray:
    """Scatter the (idx, vals) wire format back to a dense (length,) f32
    vector — the oracle counterpart of the masked decode."""
    return jnp.zeros((length,), jnp.float32).at[idx].set(
        vals.astype(jnp.float32))

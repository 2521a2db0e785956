"""Pallas TPU kernels: staleness-weighted federated aggregation.

The FL server's hotspot (paper §V-D, Eq. 3): the weighted sum of K client
updates, w = Σ_k c_k · W_k, where c_k = (t_k/t)·(n_k/n).  On GPU this is a
grid-stride loop; on TPU we tile the stacked update matrix (K, P) into
VMEM blocks along P, broadcast the (K,) coefficient vector, and fuse the
multiply+reduce on the VPU in fp32 regardless of update dtype.

`fed_agg_apply` extends the same (K, P) layout into the full server-side
merge step of the delta pipeline (core/merge.py): one kernel dispatch
computes the weighted sum, forms the pseudo-gradient
Δ = mix·(Σ_k c_k·W_k − w), folds Δ into the server optimizer's moment
buffers (FedAvgM / FedAdagrad / FedAdam / FedYogi — Reddi et al.,
arXiv:2003.00295), and applies the optimizer step to the global model —
plus a per-tile Σ Δ² side output so ‖Δ‖₂ diagnostics cost no extra pass.
The optimizer family is a *static* argument (the branch is resolved at
trace time); the hyperparameters (lr, mix, β₁, β₂, ε) travel as a tiny
runtime vector so staleness-dependent mixing rates never retrace.

`fed_agg_sharded` / `fed_agg_apply_sharded` dispatch the same kernels
under shard_map on a device mesh: the flat P dim is split over every
mesh axis (sharding/rules.merge_axes), each device runs the kernel on
its slab, and only the scalar ‖Δ‖² crosses the mesh (one psum) — the
merge itself is embarrassingly parallel along P.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from ..sharding.rules import merge_axes

# optimizer families the fused apply kernel can lower; "sgd"/"fedavgm"
# share the heavy-ball branch (momentum 0 reduces to plain server-SGD)
APPLY_OPTS = ("sgd", "fedavgm", "fedadagrad", "fedadam", "fedyogi")
# TPU vector lane width: the narrowest output block Mosaic accepts
_LANES = 128


def _fed_agg_kernel(coeff_ref, upd_ref, out_ref):
    """One P-tile: out[tile] = Σ_k coeff[k] · upd[k, tile] (fp32 acc)."""
    upd = upd_ref[...].astype(jnp.float32)          # (K, TP)
    coeff = coeff_ref[...].astype(jnp.float32)      # (K, 1)
    out_ref[...] = jnp.sum(upd * coeff, axis=0,
                           keepdims=True).astype(out_ref.dtype)


def _fed_agg_impl(updates: jnp.ndarray, coeffs: jnp.ndarray,
                  tile_p: int = 2048,
                  interpret: bool = True) -> jnp.ndarray:
    """updates: (K, P); coeffs: (K,) → (P,).

    P is padded to a tile multiple; each grid step owns one P tile with
    the full K rows resident in VMEM (K is tens of clients — a (K, 2048)
    fp32 block is ≤ a few hundred KB, well inside the ~16 MB VMEM).
    """
    K, P = updates.shape
    tile_p = min(tile_p, P)
    n_tiles = -(-P // tile_p)
    pad = n_tiles * tile_p - P
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
    coeffs2 = coeffs.reshape(K, 1).astype(jnp.float32)

    out = pl.pallas_call(
        _fed_agg_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, tile_p), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, tile_p), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_tiles * tile_p), updates.dtype),
        interpret=interpret,
        name="fed_agg",
    )(coeffs2, updates)
    return out[0, :P]


# jit twins: same trace, the donated one hands the (K, P) update matrix's
# buffer back to XLA for in-place reuse.  Donation picks the variant at
# the *python* level so the static signature (and the compiled cache key)
# stays identical whether the caller donates or not.
_fed_agg_jit = jax.jit(_fed_agg_impl,
                       static_argnames=("tile_p", "interpret"))
_fed_agg_donated = jax.jit(_fed_agg_impl,
                           static_argnames=("tile_p", "interpret"),
                           donate_argnums=(0,))


def _can_donate() -> bool:
    """CPU XLA ignores donation (and warns per dispatch) — only offer
    buffers on accelerator backends."""
    return jax.default_backend() != "cpu"


def fed_agg(updates: jnp.ndarray, coeffs: jnp.ndarray,
            tile_p: int = 2048, interpret: bool = True,
            donate: bool = False) -> jnp.ndarray:
    """Weighted sum of K stacked updates; see ``_fed_agg_impl``.

    ``donate=True`` promises ``updates`` is a fresh temporary (e.g. the
    merge matrix gathered from a ``DeviceUpdateBatch``) that the caller
    never touches again, letting XLA recycle the K·P buffer in place.
    """
    fn = _fed_agg_donated if (donate and _can_donate()) else _fed_agg_jit
    return fn(updates, coeffs, tile_p=tile_p, interpret=interpret)


def _make_apply_kernel(opt: str):
    """Build the fused merge kernel body for one optimizer family.

    Per P-tile, entirely on the VPU in fp32:

        s     = Σ_k coeff[k] · upd[k, tile]          (weighted sum)
        Δ     = mix · (s − g)                        (pseudo-gradient)
        m, v  = moment update (family-specific)
        out   = g + lr · step(m, v)

    Zero-padded tail lanes are harmless: upd/g/m/v pads are 0, so Δ, the
    moments, and the Σ Δ² side output all stay 0 there.
    """

    def kernel(scal_ref, coeff_ref, upd_ref, g_ref, m_ref, v_ref,
               out_ref, m_out_ref, v_out_ref, sq_ref):
        lr = scal_ref[0, 0]
        mix = scal_ref[0, 1]
        b1 = scal_ref[0, 2]
        b2 = scal_ref[0, 3]
        eps = scal_ref[0, 4]
        upd = upd_ref[...].astype(jnp.float32)          # (K, TP)
        coeff = coeff_ref[...].astype(jnp.float32)      # (K, 1)
        g = g_ref[...].astype(jnp.float32)              # (1, TP)
        s = jnp.sum(upd * coeff, axis=0, keepdims=True)
        delta = mix * (s - g)
        # one lane-aligned (1, 128) block per tile (Mosaic refuses (1, 1)
        # blocks); every lane holds the tile's Σ Δ², lane 0 is read back
        sq_ref[...] = jnp.full(sq_ref.shape, jnp.sum(delta * delta),
                               jnp.float32)
        if opt in ("sgd", "fedavgm"):
            # heavy-ball: m ← β·m + Δ (β = server momentum; 0 → plain Δ)
            m = b1 * m_ref[...] + delta
            v = v_ref[...]
            step = m
        else:
            m = b1 * m_ref[...] + (1.0 - b1) * delta
            dsq = delta * delta
            if opt == "fedadagrad":
                v = v_ref[...] + dsq
            elif opt == "fedadam":
                v = b2 * v_ref[...] + (1.0 - b2) * dsq
            else:                                        # fedyogi
                v0 = v_ref[...]
                v = v0 - (1.0 - b2) * dsq * jnp.sign(v0 - dsq)
            step = m / (jnp.sqrt(v) + eps)
        out_ref[...] = g + lr * step
        m_out_ref[...] = m
        v_out_ref[...] = v

    return kernel


def _fed_agg_apply_impl(updates: jnp.ndarray, coeffs: jnp.ndarray,
                        params: jnp.ndarray, m: jnp.ndarray,
                        v: jnp.ndarray, lr, mix, b1, b2, eps, *,
                        opt: str = "fedadam", tile_p: int = 2048,
                        interpret: bool = True):
    """Fused server-update step on the flattened model.

    updates: (K, P); coeffs: (K,); params/m/v: (P,) fp32 moment buffers.
    Returns ``(new_params, new_m, new_v, update_norm)`` where
    ``update_norm = ‖Δ‖₂`` of the pseudo-gradient Δ = mix·(Σ c·W − w).
    """
    if opt not in APPLY_OPTS:
        raise ValueError(f"unknown server opt {opt!r}; "
                         f"available: {APPLY_OPTS}")
    K, P = updates.shape
    tile_p = min(tile_p, P)
    n_tiles = -(-P // tile_p)
    pad = n_tiles * tile_p - P
    if pad:
        updates = jnp.pad(updates, ((0, 0), (0, pad)))
    row = lambda x: jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(1, -1)
    g2, m2, v2 = row(params), row(m), row(v)
    coeffs2 = coeffs.reshape(K, 1).astype(jnp.float32)
    scal = jnp.stack([jnp.float32(lr), jnp.float32(mix), jnp.float32(b1),
                      jnp.float32(b2), jnp.float32(eps),
                      jnp.float32(0.0), jnp.float32(0.0),
                      jnp.float32(0.0)]).reshape(1, 8)

    vec = jax.ShapeDtypeStruct((1, n_tiles * tile_p), jnp.float32)
    vec_spec = pl.BlockSpec((1, tile_p), lambda i: (0, i))
    out, m_new, v_new, sq = pl.pallas_call(
        _make_apply_kernel(opt),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, 8), lambda i: (0, 0)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
            pl.BlockSpec((K, tile_p), lambda i: (0, i)),
            vec_spec, vec_spec, vec_spec,
        ],
        out_specs=[vec_spec, vec_spec, vec_spec,
                   pl.BlockSpec((1, _LANES), lambda i: (0, i))],
        out_shape=[vec, vec, vec,
                   jax.ShapeDtypeStruct((1, n_tiles * _LANES), jnp.float32)],
        interpret=interpret,
        name="fed_agg_apply",
    )(scal, coeffs2, updates, g2, m2, v2)
    norm = jnp.sqrt(jnp.sum(sq.reshape(n_tiles, _LANES)[:, 0]))
    return out[0, :P], m_new[0, :P], v_new[0, :P], norm


# donation twin: hand back the update matrix (0) and the moment buffers
# m/v (3, 4) — but NEVER params (2): strategies retain global_params, and
# on single-leaf models the raveled view can alias the live tree's leaf.
_fed_agg_apply_jit = jax.jit(
    _fed_agg_apply_impl,
    static_argnames=("opt", "tile_p", "interpret"))
_fed_agg_apply_donated = jax.jit(
    _fed_agg_apply_impl,
    static_argnames=("opt", "tile_p", "interpret"),
    donate_argnums=(0, 3, 4))


def fed_agg_apply(updates: jnp.ndarray, coeffs: jnp.ndarray,
                  params: jnp.ndarray, m: jnp.ndarray, v: jnp.ndarray,
                  lr, mix, b1, b2, eps, *, opt: str = "fedadam",
                  tile_p: int = 2048, interpret: bool = True,
                  donate: bool = False):
    """Fused server merge; see ``_fed_agg_apply_impl``.

    ``donate=True`` recycles the update matrix and the flat m/v moment
    buffers in place (the merge pipeline rebuilds fresh flats for the
    next round from its pytree state, so the old ones are dead after the
    dispatch).  ``params`` is never donated.
    """
    fn = (_fed_agg_apply_donated if (donate and _can_donate())
          else _fed_agg_apply_jit)
    return fn(updates, coeffs, params, m, v, lr, mix, b1, b2, eps,
              opt=opt, tile_p=tile_p, interpret=interpret)


# ------------------------------------------------------------ sharded
def _pad_p(arr: jnp.ndarray, mult: int) -> jnp.ndarray:
    """Zero-pad the trailing (P) dim to a multiple of ``mult``."""
    pad = (-arr.shape[-1]) % mult
    if not pad:
        return arr
    width = [(0, 0)] * (arr.ndim - 1) + [(0, pad)]
    return jnp.pad(arr, width)


def fed_agg_sharded(updates: jnp.ndarray, coeffs: jnp.ndarray, mesh,
                    tile_p: int = 2048,
                    interpret: bool = True) -> jnp.ndarray:
    """fed_agg with the P dim sharded over every axis of ``mesh``.

    updates (K, P) shard as (replicated, all-axes); coeffs replicate; the
    output gathers back to a dense (P,).  Zero padding up to the device
    count is numerically inert (0·c contributes 0).
    """
    axes = merge_axes(mesh)
    n = int(mesh.size)
    if n <= 1:
        return fed_agg(updates, coeffs, tile_p=tile_p, interpret=interpret)
    Pdim = updates.shape[1]
    upd = _pad_p(updates, n)

    f = jax.shard_map(
        functools.partial(fed_agg, tile_p=tile_p, interpret=interpret),
        mesh=mesh,
        in_specs=(P(None, axes), P(None)),
        out_specs=P(axes), check_vma=False)
    return f(upd, coeffs)[:Pdim]


def fed_agg_apply_sharded(updates: jnp.ndarray, coeffs: jnp.ndarray,
                          params: jnp.ndarray, m: jnp.ndarray,
                          v: jnp.ndarray, lr, mix, b1, b2, eps, *,
                          opt: str = "fedadam", mesh,
                          tile_p: int = 2048, interpret: bool = True):
    """fed_agg_apply with the P dim sharded over every axis of ``mesh``.

    Each device owns a P slab of updates/params/moments and runs the
    fused kernel locally; the only cross-device traffic is the scalar
    Σ Δ² psum for the update-norm diagnostic.  Zero-padded slab tails
    keep params/moments/Δ at exact 0 (see the kernel docstring), so the
    sharded result matches the single-device merge to fp32 tolerance.
    """
    axes = merge_axes(mesh)
    n = int(mesh.size)
    if n <= 1:
        return fed_agg_apply(updates, coeffs, params, m, v,
                             lr, mix, b1, b2, eps, opt=opt,
                             tile_p=tile_p, interpret=interpret)
    Pdim = updates.shape[1]
    upd = _pad_p(updates, n)
    g2, m2, v2 = (_pad_p(x.astype(jnp.float32), n) for x in (params, m, v))

    def local(u, c, g, mm, vv):
        out, m_new, v_new, norm = fed_agg_apply(
            u, c, g, mm, vv, lr, mix, b1, b2, eps, opt=opt,
            tile_p=tile_p, interpret=interpret)
        sumsq = jax.lax.psum(norm * norm, axes)
        return out, m_new, v_new, jnp.sqrt(sumsq)

    vec = P(axes)
    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(None, axes), P(None), vec, vec, vec),
                      out_specs=(vec, vec, vec, P()), check_vma=False)
    out, m_new, v_new, norm = f(upd, coeffs, g2, m2, v2)
    return out[:Pdim], m_new[:Pdim], v_new[:Pdim], norm

"""Pallas TPU kernel: the backward pass of one client's dense weight and
its Adam step, in one pass over the weight.

For a dense layer ``y = h·W + b`` trained on a batch, the backward pass
needs W twice: for the input's cotangent ``dx = dy·Wᵀ`` and for the
optimizer step, whose gradient is the rank-B product ``g = hᵀ·dy`` of
the layer's input ``h`` (B, n_in) and the cotangent of its
pre-activation ``dy`` (B, n_out).  Here each (tm, tn) tile of W, m and
v is read once: the tile's share of ``dx`` is summed on the MXU, its
tile of ``g`` is formed on the MXU in VMEM and consumed at once by Adam,
and the new W, m and v are written over the old (``input_output_aliases``).
Neither ``g`` nor a second read of W crosses HBM.

Adam is the repo's (`optim.optimizers.adam`), in its expression order:

    m' = b1·m + (1 − b1)·g
    v' = b2·v + (1 − b2)·g²
    W' = W + (−lr·(m'/bc1) / (sqrt(v'/bc2) + eps))

The bias corrections ``bc1``, ``bc2`` depend on the step count and
enter as SMEM scalars, so one compiled kernel serves every step; lr,
b1, b2 and eps are compile-time constants.

Both products run at the default matmul precision, as XLA runs them:
on the TPU that multiplies float32 operands in one bfloat16 pass, so on
the chip the factors are rounded to bfloat16 (Mosaic would otherwise
multiply at full float32); in interpret mode (CPU) they stay float32,
as XLA's CPU ``dot`` keeps them.  The batch dimension is zero-padded to
the MXU's 128: zero rows add exact zeros to ``g`` and are cut from
``dx``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the MXU's contraction width: the batch dim is zero-padded to it
_MXU = 128
# sublanes of a bfloat16 tile (16, 128): row tiles are multiples of it
_ROWS = 16


def _tile(n: int, target: int, align: int) -> int:
    """The largest multiple of ``align`` that divides ``n`` and is at most
    ``target``; the whole of ``n`` when it fits in ``target``; else
    ``target`` rounded down to ``align`` (the grid then has an edge
    tile, whose rows or lanes beyond ``n`` are never written)."""
    if n <= target:
        return n
    for t in range(target - target % align, 0, -align):
        if n % t == 0:
            return t
    return max(align, target - target % align)


def _mxu_dtype(interpret: bool):
    """float32 operands the default precision rounds to bfloat16 on the
    TPU; kept float32 in interpret mode or when JAX is set to a higher
    default precision."""
    default = jax.config.jax_default_matmul_precision
    if interpret or default not in (None, "default", "bfloat16",
                                    "fastest"):
        return jnp.float32
    return jnp.bfloat16


def _kernel(sc_ref, ht_ref, dy_ref, w_ref, m_ref, v_ref,
            dxt_out, w_out, m_out, v_out, *, lr, b1, b2, eps, precision,
            n_out):
    w = w_ref[...]
    dy = dy_ref[...]
    w_dx = w
    if n_out % w.shape[1]:
        # the last column tile runs past n_out: its lanes there hold
        # whatever the block read, kept out of dx's sum
        col = (pl.program_id(1) * w.shape[1]
               + jax.lax.broadcasted_iota(jnp.int32, w.shape, 1))
        w_dx = jnp.where(col < n_out, w, 0.0)

    # dxᵀ = W·dyᵀ, summed over the column tiles j (the inner grid axis)
    @pl.when(pl.program_id(1) == 0)
    def _():
        dxt_out[...] = jnp.zeros_like(dxt_out)

    dxt_out[...] += jax.lax.dot_general(
        w_dx.astype(dy.dtype), dy, (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    g = jnp.dot(ht_ref[...], dy, precision=precision,
                preferred_element_type=jnp.float32)
    bc1 = sc_ref[0]
    bc2 = sc_ref[1]
    m = b1 * m_ref[...] + (1 - b1) * g
    v = b2 * v_ref[...] + (1 - b2) * (g * g)
    m_out[...] = m
    v_out[...] = v
    w_out[...] = w + (-lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps))


def dense_adam(h: jnp.ndarray, dy: jnp.ndarray, w: jnp.ndarray,
               m: jnp.ndarray, v: jnp.ndarray, bc1, bc2, *, lr: float,
               b1: float, b2: float, eps: float, tm: int = 256,
               tn: int = 2048, interpret: bool = True):
    """The input's cotangent and one Adam step of a dense weight, from
    its gradient's factors.

    h: (B, n_in) the layer's input; dy: (B, n_out) the cotangent of its
    pre-activation; w, m, v: (n_in, n_out) float32; bc1, bc2: the step's
    bias corrections (float32 scalars).  Returns ``(dx, w, m, v)``:
    ``dx = dy·Wᵀ`` (B, n_in) float32 at the old W, and the new state,
    each written over its input's buffer where XLA can donate it.
    """
    batch = h.shape[0]
    n_in, n_out = w.shape
    dtype = _mxu_dtype(interpret)
    rows = -(-batch // _MXU) * _MXU
    tm = _tile(n_in, tm, _ROWS)
    tn = _tile(n_out, tn, _MXU)
    # zero rows (and zero lanes of an edge column tile) add nothing
    ht = jnp.pad(h.astype(dtype), ((0, rows - batch), (0, 0))).T
    dyp = jnp.pad(dy.astype(dtype), ((0, rows - batch),
                                     (0, -n_out % tn)))
    sc = jnp.stack([bc1, bc2]).astype(jnp.float32)
    tile = pl.BlockSpec((tm, tn), lambda i, j: (i, j))
    rows_of_i = pl.BlockSpec((tm, rows), lambda i, j: (i, 0))
    state = jax.ShapeDtypeStruct(w.shape, jnp.float32)
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else None)
    kernel = functools.partial(_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                               precision=precision, n_out=n_out)
    # six (tm, tn) float32 tiles in and out, double-buffered, and the
    # body's temporaries; dxᵀ's and dy's blocks: past 16 MiB
    vmem = 4 * tm * tn * 20 + 8 * tm * rows + 4 * rows * tn + 4 * 2 ** 20
    dxt, w, m, v = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n_in, tm), pl.cdiv(n_out, tn)),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            rows_of_i,
            pl.BlockSpec((rows, tn), lambda i, j: (0, j)),
            tile, tile, tile,
        ],
        out_specs=[rows_of_i, tile, tile, tile],
        out_shape=[jax.ShapeDtypeStruct((n_in, rows), jnp.float32),
                   state, state, state],
        input_output_aliases={3: 1, 4: 2, 5: 3},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
        interpret=interpret,
        name="dense_adam",
    )(sc, ht, dyp, w, m, v)
    return dxt[:, :batch].T, w, m, v

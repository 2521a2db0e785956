"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


# ------------------------------------------------------------ fed_agg
def fed_agg_ref(updates: jnp.ndarray, coeffs: jnp.ndarray) -> jnp.ndarray:
    """Staleness-weighted aggregation (paper Eq. 3 inner sum).

    updates: (K, P) stacked flattened client updates;
    coeffs:  (K,)  staleness × cardinality weights.
    → (P,) aggregated parameter vector, accumulated in fp32.
    """
    acc = jnp.einsum("kp,k->p", updates.astype(jnp.float32),
                     coeffs.astype(jnp.float32))
    return acc.astype(updates.dtype)


def fed_agg_apply_ref(updates: jnp.ndarray, coeffs: jnp.ndarray,
                      params: jnp.ndarray, m: jnp.ndarray, v: jnp.ndarray,
                      lr, mix, b1, b2, eps, opt: str = "fedadam"):
    """Oracle for the fused server-update kernel (fed_agg_apply).

    Weighted sum → pseudo-gradient Δ = mix·(Σ c·W − w) → moment update →
    optimizer step, all in fp32.  Returns (out, m, v, ‖Δ‖₂).
    """
    s = jnp.einsum("kp,k->p", updates.astype(jnp.float32),
                   coeffs.astype(jnp.float32))
    g = params.astype(jnp.float32)
    delta = jnp.float32(mix) * (s - g)
    lr, b1, b2, eps = (jnp.float32(x) for x in (lr, b1, b2, eps))
    m = m.astype(jnp.float32)
    v = v.astype(jnp.float32)
    if opt in ("sgd", "fedavgm"):
        m = b1 * m + delta
        step = m
    else:
        m = b1 * m + (1.0 - b1) * delta
        dsq = delta * delta
        if opt == "fedadagrad":
            v = v + dsq
        elif opt == "fedadam":
            v = b2 * v + (1.0 - b2) * dsq
        elif opt == "fedyogi":
            v = v - (1.0 - b2) * dsq * jnp.sign(v - dsq)
        else:
            raise ValueError(f"unknown server opt {opt!r}")
        step = m / (jnp.sqrt(v) + eps)
    return g + lr * step, m, v, jnp.sqrt(jnp.sum(delta * delta))


# ------------------------------------------------------------ dense_adam
def dense_adam_ref(h: jnp.ndarray, dy: jnp.ndarray, w: jnp.ndarray,
                   m: jnp.ndarray, v: jnp.ndarray, bc1, bc2, *, lr: float,
                   b1: float, b2: float, eps: float):
    """Oracle for the fused dense-weight backward and Adam step: the
    input's cotangent dy·Wᵀ, the gradient hᵀ·dy, then `optim.adam`'s
    moment updates and step.  Returns (dx, w, m, v)."""
    dx = dy @ w.T
    g = h.T @ dy
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    return dx, w + (-lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)), m, v


# ------------------------------------------------------------ compress
def int8_encode_ref(x: jnp.ndarray, chunk: int = 256):
    """Per-chunk int8 quantization oracle: scale = absmax/127 (1.0 for
    all-zero chunks), q = round(x/scale) clipped to ±127.  Returns
    (q (n_chunks, chunk) int8, scale (n_chunks,) f32)."""
    P = x.shape[0]
    n_chunks = -(-P // chunk)
    xm = jnp.pad(x.astype(jnp.float32),
                 (0, n_chunks * chunk - P)).reshape(n_chunks, chunk)
    absmax = jnp.max(jnp.abs(xm), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xm / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale[:, 0]


def int8_decode_ref(q: jnp.ndarray, scale: jnp.ndarray,
                    length: int) -> jnp.ndarray:
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[:, None]).reshape(-1)[:length]


def topk_ref(x: jnp.ndarray, k: int):
    """Dense top-k decode oracle via lax.top_k + scatter (lowest index
    wins on magnitude ties).  Returns (idx, vals, decoded)."""
    xf = x.astype(jnp.float32)
    P = xf.shape[0]
    k = min(k, P)
    _, idx = jax.lax.top_k(jnp.abs(xf), k)
    vals = xf[idx]
    decoded = jnp.zeros((P,), jnp.float32).at[idx].set(vals)
    return idx.astype(jnp.int32), vals, decoded


# ------------------------------------------------------------ attention
def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True,
                        window: Optional[int] = None,
                        softcap: float = 0.0) -> jnp.ndarray:
    """Reference attention. q: (B, H, S, d); k/v: (B, Hkv, S, d) (GQA:
    H % Hkv == 0).  fp32 softmax, optional sliding window + logit cap."""
    B, H, S, d = q.shape
    Hkv = k.shape[1]
    g = H // Hkv
    qg = q.reshape(B, Hkv, g, S, d)
    scores = jnp.einsum("bkgqd,bksd->bkgqs", qg, k) / jnp.sqrt(d)
    scores = scores.astype(jnp.float32)
    if softcap:
        scores = softcap * jnp.tanh(scores / softcap)
    idx = jnp.arange(S)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= idx[:, None] >= idx[None, :]
    if window:
        mask &= (idx[:, None] - idx[None, :]) < window
    scores = jnp.where(mask, scores, -2.3819763e38)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bksd->bkgqd", probs, v)
    return out.reshape(B, H, S, d)


# ------------------------------------------------------------ ssd
def ssd_ref(x: jnp.ndarray, a_dt: jnp.ndarray, B: jnp.ndarray,
            C: jnp.ndarray) -> jnp.ndarray:
    """Sequential SSD recurrence (the ground truth the chunked forms must
    match).  x: (b, l, h, p) pre-scaled by dt; a_dt: (b, l, h);
    B, C: (b, l, h, n).  Returns y: (b, l, h, p)."""
    b, l, h, p = x.shape
    n = B.shape[-1]

    def step(state, inp):
        x_t, a_t, B_t, C_t = inp
        state = (state * jnp.exp(a_t)[..., None, None]
                 + x_t[..., :, None] * B_t[..., None, :])
        y_t = jnp.einsum("bhpn,bhn->bhp", state, C_t)
        return state, y_t

    s0 = jnp.zeros((b, h, p, n), jnp.float32)
    xs = (jnp.moveaxis(x, 1, 0).astype(jnp.float32),
          jnp.moveaxis(a_dt, 1, 0).astype(jnp.float32),
          jnp.moveaxis(B, 1, 0).astype(jnp.float32),
          jnp.moveaxis(C, 1, 0).astype(jnp.float32))
    _, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)

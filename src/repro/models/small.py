"""The paper's client model architectures (§VI-A2), from scratch in JAX.

- MNIST:    2×[conv5x5 + maxpool2x2] → FC(512) → FC(10)
- FEMNIST:  2×[conv5x5 + maxpool2x2] → FC(2048) → FC(62)
- Shakespeare: embed(8) → 2×LSTM(256) → FC(82)
- Speech:   2×[conv3x3, conv3x3, maxpool, dropout(.25)] → avgpool → FC(35)

Functional (init, apply) pairs; params are plain dict pytrees.

Each LSTM layer runs time-major, (T, B, ·).  The input projection of all
T·B positions is one product before the forward time loop, which adds
only ``h @ wh`` per step; in the backward loop (a ``custom_vjp``) the
input gradient ``dg @ wx.T`` is likewise one product after it.  The weight
and bias gradients stay per-step sums in the loop, in the order plain
autodiff of the per-step LSTM adds them, so training rounds exactly as it
does.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

Pytree = Any


class ModelDef(NamedTuple):
    init: Callable[..., Pytree]
    apply: Callable[..., jnp.ndarray]
    name: str


# ---------------------------------------------------------------- helpers
def _dense_init(rng, n_in, n_out):
    k1, _ = jax.random.split(rng)
    scale = jnp.sqrt(2.0 / n_in)
    return {"w": jax.random.normal(k1, (n_in, n_out)) * scale,
            "b": jnp.zeros((n_out,))}


def _dense(p, x):
    return x @ p["w"] + p["b"]


def _conv_init(rng, kh, kw, cin, cout):
    scale = jnp.sqrt(2.0 / (kh * kw * cin))
    return {"w": jax.random.normal(rng, (kh, kw, cin, cout)) * scale,
            "b": jnp.zeros((cout,))}


def _conv(p, x):  # NHWC, SAME padding
    y = lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _maxpool(x, k=2):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                             (1, k, k, 1), "VALID")


# ---------------------------------------------------------------- CNNs
def make_cnn(image_size: int = 28, channels: int = 1, n_classes: int = 10,
             fc_width: int = 512, name: str = "mnist_cnn") -> ModelDef:
    """The paper's LEAF-style 2-layer 5x5 CNN (MNIST: fc=512/10 classes,
    FEMNIST: fc=2048/62 classes)."""
    pooled = image_size // 4  # two 2x2 maxpools

    def init(rng):
        ks = jax.random.split(rng, 4)
        return {
            "conv1": _conv_init(ks[0], 5, 5, channels, 32),
            "conv2": _conv_init(ks[1], 5, 5, 32, 64),
            "fc1": _dense_init(ks[2], pooled * pooled * 64, fc_width),
            "out": _dense_init(ks[3], fc_width, n_classes),
        }

    def apply(params, x):
        h = jax.nn.relu(_conv(params["conv1"], x))
        h = _maxpool(h)
        h = jax.nn.relu(_conv(params["conv2"], h))
        h = _maxpool(h)
        h = h.reshape(h.shape[0], -1)
        h = jax.nn.relu(_dense(params["fc1"], h))
        return _dense(params["out"], h)

    return ModelDef(init, apply, name)


# ---------------------------------------------------------------- LSTM
def _lstm_init(rng, n_in, hidden):
    k1, k2 = jax.random.split(rng)
    s_in = jnp.sqrt(1.0 / n_in)
    s_h = jnp.sqrt(1.0 / hidden)
    return {"wx": jax.random.normal(k1, (n_in, 4 * hidden)) * s_in,
            "wh": jax.random.normal(k2, (hidden, 4 * hidden)) * s_h,
            "b": jnp.zeros((4 * hidden,))}


def _lstm_cell(z, c):
    """One timestep from its gate pre-activations ``z``: gate order
    (i, f, g, o), +1.0 on the forget gate's pre-activation."""
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    return jax.nn.sigmoid(o) * jnp.tanh(c), c


def _lstm_run(p, xs, keep):
    """Scan over time-major ``xs`` (T, B, n_in).  The input projection of
    every timestep is one product before the loop; each step adds only
    ``h @ wh``.  ``keep(h, c_prev, z)`` picks what each step stacks."""
    gx = xs @ p["wx"]

    def step(carry, gx_t):
        h, c_prev = carry
        z = gx_t + h @ p["wh"] + p["b"]
        h, c = _lstm_cell(z, c_prev)
        return (h, c), keep(h, c_prev, z)

    zero = jnp.zeros((xs.shape[1], p["wh"].shape[0]), xs.dtype)
    return lax.scan(step, (zero, zero), gx)[1]


@jax.custom_vjp
def _lstm_scan(p, xs):
    """xs: (T, B, n_in), time-major → hidden states (T, B, hidden)."""
    return _lstm_run(p, xs, lambda h, c_prev, z: h)


def _lstm_scan_fwd(p, xs):
    hs, c_prev, zs = _lstm_run(p, xs, lambda h, c_prev, z: (h, c_prev, z))
    return hs, (p, xs, hs, c_prev, zs)


def _lstm_scan_bwd(res, d_hs):
    """The reverse scan adds each step's weight and bias gradients to its
    carry in the order plain autodiff of the per-step LSTM does, so the
    sums round alike; the input gradient is one product after it."""
    p, xs, hs, c_prev, zs = res
    h_prev = jnp.concatenate([jnp.zeros_like(hs[:1]), hs[:-1]])

    def step(carry, t_in):
        dh, dc, dwh, dwx, db = carry
        dh_t, z_t, c_p, h_p, x_t = t_in
        dz, dc = jax.vjp(_lstm_cell, z_t, c_p)[1]((dh + dh_t, dc))
        return (dz @ p["wh"].T, dc, dwh + h_p.T @ dz, dwx + x_t.T @ dz,
                db + jnp.sum(dz, axis=0)), dz

    zero = jnp.zeros_like(hs[0])
    init = (zero, zero) + tuple(jnp.zeros_like(p[k]) for k in ("wh", "wx", "b"))
    (_, _, dwh, dwx, db), dz = lax.scan(
        step, init, (d_hs, zs, c_prev, h_prev, xs), reverse=True)
    return {"wx": dwx, "wh": dwh, "b": db}, dz @ p["wx"].T


_lstm_scan.defvjp(_lstm_scan_fwd, _lstm_scan_bwd)


def make_char_lstm(vocab: int = 82, embed: int = 8,
                   hidden: int = 256, name: str = "shakespeare_lstm") -> ModelDef:
    """embed(8) → LSTM(256) ×2 → FC(vocab): predict next char from 80 chars."""

    def init(rng):
        ks = jax.random.split(rng, 4)
        return {
            "embed": jax.random.normal(ks[0], (vocab, embed)) * 0.1,
            "lstm1": _lstm_init(ks[1], embed, hidden),
            "lstm2": _lstm_init(ks[2], hidden, hidden),
            "out": _dense_init(ks[3], hidden, vocab),
        }

    def apply(params, tokens):  # (B, T) int32 → (B, vocab)
        # gathered batch-major, so the embedding gradient's scatter-add
        # keeps its order; the layers run time-major, (T, B, embed)
        h = jnp.swapaxes(params["embed"][tokens], 0, 1)
        h = _lstm_scan(params["lstm1"], h)
        h = _lstm_scan(params["lstm2"], h)
        return _dense(params["out"], h[-1])

    return ModelDef(init, apply, name)


# ---------------------------------------------------------------- speech
def make_speech_cnn(frames: int = 32, mels: int = 32, n_classes: int = 35,
                    name: str = "speech_cnn") -> ModelDef:
    """Paper §VI-A2: two blocks of [conv3x3, conv3x3, maxpool, dropout] →
    average pool → FC(35).  Dropout is inference-scaled (applied only when
    a dropout rng is passed)."""

    def init(rng):
        ks = jax.random.split(rng, 5)
        return {
            "c1a": _conv_init(ks[0], 3, 3, 1, 32),
            "c1b": _conv_init(ks[1], 3, 3, 32, 32),
            "c2a": _conv_init(ks[2], 3, 3, 32, 64),
            "c2b": _conv_init(ks[3], 3, 3, 64, 64),
            "out": _dense_init(ks[4], 64, n_classes),
        }

    def apply(params, x, *, dropout_rng=None, rate: float = 0.25):
        def block(h, pa, pb):
            h = jax.nn.relu(_conv(pa, h))
            h = jax.nn.relu(_conv(pb, h))
            h = _maxpool(h)
            if dropout_rng is not None:
                keep = jax.random.bernoulli(dropout_rng, 1 - rate, h.shape)
                h = jnp.where(keep, h / (1 - rate), 0.0)
            return h

        h = block(x, params["c1a"], params["c1b"])
        h = block(h, params["c2a"], params["c2b"])
        h = h.mean(axis=(1, 2))  # global average pool
        return _dense(params["out"], h)

    return ModelDef(init, apply, name)


SMALL_MODELS = {
    "mnist_cnn": lambda: make_cnn(28, 1, 10, 512, "mnist_cnn"),
    "femnist_cnn": lambda: make_cnn(28, 1, 62, 2048, "femnist_cnn"),
    "shakespeare_lstm": lambda: make_char_lstm(),
    "speech_cnn": lambda: make_speech_cnn(),
}

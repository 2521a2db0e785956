"""Plain float32 reference of the Shakespeare LSTM (LEAF; FedLesScan
Table I): embedding 8 -> 2 x LSTM 256 -> FC to 82 characters, reading 80
characters and predicting the next from the last hidden state.

Written from the published description with jax.numpy and lax alone; it
imports nothing of the program.  The gate order is (i, f, g, o) and the
forget gate's pre-activation gets +1.0, the layout and convention the
program's parameters use, so that its state can be compared leaf by leaf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def init(key, cfg):
    vocab, e, h = cfg["vocab"], cfg["embed"], cfg["hidden"]
    ks = jax.random.split(key, 6)

    def lstm(k1, k2, n_in):
        return {"wx": jax.random.normal(k1, (n_in, 4 * h), jnp.float32)
                * jnp.sqrt(1.0 / n_in),
                "wh": jax.random.normal(k2, (h, 4 * h), jnp.float32)
                * jnp.sqrt(1.0 / h),
                "b": jnp.zeros((4 * h,), jnp.float32)}

    return {
        "embed": jax.random.normal(ks[0], (vocab, e), jnp.float32) * 0.1,
        "lstm1": lstm(ks[1], ks[2], e),
        "lstm2": lstm(ks[3], ks[4], h),
        "out": {"w": jax.random.normal(ks[5], (h, vocab), jnp.float32)
                * jnp.sqrt(2.0 / h),
                "b": jnp.zeros((vocab,), jnp.float32)},
    }


def _layer(p, xs):
    """xs (T, B, n_in) -> hidden states (T, B, H)."""
    h_size = p["wh"].shape[0]
    b = xs.shape[1]

    def step(carry, x_t):
        h, c = carry
        z = x_t @ p["wx"] + h @ p["wh"] + p["b"]
        i = jax.nn.sigmoid(z[:, :h_size])
        f = jax.nn.sigmoid(z[:, h_size:2 * h_size] + 1.0)
        g = jnp.tanh(z[:, 2 * h_size:3 * h_size])
        o = jax.nn.sigmoid(z[:, 3 * h_size:])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    zero = jnp.zeros((b, h_size), xs.dtype)
    _, hs = lax.scan(step, (zero, zero), xs)
    return hs


def apply(params, tokens, cfg):
    """(B, T) int tokens -> (B, vocab) logits for the next character."""
    xs = jnp.swapaxes(params["embed"][tokens], 0, 1)    # (T, B, E)
    hs = _layer(params["lstm2"], _layer(params["lstm1"], xs))
    return hs[-1] @ params["out"]["w"] + params["out"]["b"]


def param_count(cfg):
    vocab, e, h = cfg["vocab"], cfg["embed"], cfg["hidden"]
    return (vocab * e + (e + h + 1) * 4 * h + (h + h + 1) * 4 * h
            + h * vocab + vocab)


def forward_flops(cfg):
    """Multiply-adds x 2 of one sample's forward pass: each LSTM layer's
    input and recurrent products at every one of the 80 steps, and the
    output layer once.  The embedding is a lookup; gate nonlinearities
    and elementwise products are left out, as model-FLOP counts do."""
    e, h, t = cfg["embed"], cfg["hidden"], cfg["seq_len"]
    per_step = (e + h) * 4 * h + (h + h) * 4 * h
    return 2 * (t * per_step + h * cfg["vocab"])

"""Plain float32 reference of the FEMNIST CNN (LEAF; FedLesScan Table I):
2 x [conv 5x5 SAME + ReLU + maxpool 2x2] with 32 and 64 channels, then
FC 2048 + ReLU, then FC to 62 classes.  NHWC images.

Written from the published description with jax.numpy and lax alone; it
imports nothing of the program.  Parameters use the layout
{conv1, conv2, fc1, out} x {w, b} so that the program's state can be
compared leaf by leaf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def init(key, cfg):
    """He-normal weights, zero biases, float32."""
    c1, c2 = cfg["conv_channels"]
    k, ch = cfg["kernel_size"], cfg["channels"]
    pooled = cfg["image_size"] // (cfg["pool"] ** 2)
    flat = pooled * pooled * c2
    ks = jax.random.split(key, 4)

    def he(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(
            2.0 / fan_in)

    return {
        "conv1": {"w": he(ks[0], (k, k, ch, c1), k * k * ch),
                  "b": jnp.zeros((c1,), jnp.float32)},
        "conv2": {"w": he(ks[1], (k, k, c1, c2), k * k * c1),
                  "b": jnp.zeros((c2,), jnp.float32)},
        "fc1": {"w": he(ks[2], (flat, cfg["fc_width"]), flat),
                "b": jnp.zeros((cfg["fc_width"],), jnp.float32)},
        "out": {"w": he(ks[3], (cfg["fc_width"], cfg["n_classes"]),
                        cfg["fc_width"]),
                "b": jnp.zeros((cfg["n_classes"],), jnp.float32)},
    }


def apply(params, x, cfg):
    """(B, H, W, C) images -> (B, n_classes) logits, in params' dtype."""
    p = cfg["pool"]

    def conv(layer, h):
        y = lax.conv_general_dilated(
            h, layer["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y + layer["b"])

    def pool(h):
        return lax.reduce_window(h, -jnp.inf, lax.max, (1, p, p, 1),
                                 (1, p, p, 1), "VALID")

    h = x.astype(params["conv1"]["w"].dtype)
    h = pool(conv(params["conv1"], h))
    h = pool(conv(params["conv2"], h))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    return h @ params["out"]["w"] + params["out"]["b"]


def param_count(cfg):
    c1, c2 = cfg["conv_channels"]
    k, ch = cfg["kernel_size"], cfg["channels"]
    pooled = cfg["image_size"] // (cfg["pool"] ** 2)
    flat = pooled * pooled * c2
    return (k * k * ch * c1 + c1 + k * k * c1 * c2 + c2
            + flat * cfg["fc_width"] + cfg["fc_width"]
            + cfg["fc_width"] * cfg["n_classes"] + cfg["n_classes"])


def forward_flops(cfg):
    """Multiply-adds x 2 of one sample's forward pass: the two
    convolutions (SAME, stride 1) and the two dense layers.  Bias adds,
    ReLU and pooling are left out, as model-FLOP counts do."""
    c1, c2 = cfg["conv_channels"]
    k, ch, s = cfg["kernel_size"], cfg["channels"], cfg["image_size"]
    s2 = s // cfg["pool"]
    flat = (s2 // cfg["pool"]) ** 2 * c2
    return 2 * (s * s * c1 * k * k * ch
                + s2 * s2 * c2 * k * k * c1
                + flat * cfg["fc_width"]
                + cfg["fc_width"] * cfg["n_classes"])

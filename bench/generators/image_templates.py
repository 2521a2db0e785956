"""FEMNIST-shaped client data, one client per writer as LEAF splits it:
every writer fills in the same form, so each writer's labels are drawn
over all classes, and the writer's hand shifts every character it draws
(feature skew, not label skew).

Images are class templates plus pixel noise, as the program's
`data/synthetic.make_image_classification` makes them (copied here, so
that the benchmark's inputs do not move when the program's generators
do).  A writer's style is one more coarse 7x7 pattern, upsampled like the
templates and added to every image the writer draws, train and test
alike.  Every client holds exactly `train_per_client` and
`test_per_client` samples.
"""
from __future__ import annotations

import numpy as np


def _upsample(coarse, size):
    reps = size // coarse.shape[-2]
    up = np.kron(coarse, np.ones((1,) * (coarse.ndim - 3) + (reps, reps, 1)))
    return up[..., :size, :size, :]


def generate(config: dict, traffic: dict, seed: int):
    """-> (train, test): lists of (x, y) numpy pairs, one per client."""
    data = config["data"]
    size, channels = config["image_size"], config["channels"]
    n_classes = config["n_classes"]
    n_clients = traffic["population"]
    rng = np.random.default_rng(seed)
    templates = _upsample(rng.normal(size=(n_classes, 7, 7, channels)), size)
    style = data["style"] * _upsample(
        rng.normal(size=(n_clients, 7, 7, channels)), size)

    def draw(n):
        y = rng.integers(0, n_classes, size=(n_clients, n))
        x = (templates[y] + style[:, None]
             + data["noise"] * rng.normal(size=(n_clients, n, size, size,
                                                channels)))
        x, y = x.astype(np.float32), y.astype(np.int32)
        return [(x[c], y[c]) for c in range(n_clients)]

    return draw(traffic["train_per_client"]), draw(traffic["test_per_client"])

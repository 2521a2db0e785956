"""Training tasks: model + loss + local-training loop for FL clients.

A Task turns a ModelDef into the jit'd pieces Client_Update needs:
`init_params`, `local_train` (with FedProx proximal hook) and `evaluate`.
One jit cache is shared across all clients of an experiment (same HLO,
different data) — mirroring how FedLess ships one function image.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import spans
from ..data.loader import batches
from ..data.synthetic import ArrayDataset
from ..models.small import ModelDef
from ..optim import apply_updates, make_optimizer, proximal_grad

Pytree = Any


@dataclass(frozen=True)
class TaskConfig:
    epochs: int = 5
    batch_size: int = 10
    learning_rate: float = 1e-3
    optimizer: str = "adam"
    per_sample_time_s: float = 0.01   # nominal seconds/sample/epoch (sim)


class ClassificationTask:
    """Cross-entropy classification (covers CNNs, speech and char-LM —
    the LSTM predicts the next char, which is also a classification)."""

    def __init__(self, model: ModelDef, config: TaskConfig):
        self.model = model
        self.config = config
        self.optimizer = make_optimizer(config.optimizer,
                                        config.learning_rate)
        self._train_step = jax.jit(self._train_step_impl,
                                   static_argnums=(5,))  # mu: python float
        self._eval_batch = jax.jit(self._eval_batch_impl)

    # ------------------------------------------------------------------
    def init_params(self, seed: int = 0) -> Pytree:
        return self.model.init(jax.random.PRNGKey(seed))

    # ------------------------------------------------------------------
    def _loss(self, params, x, y):
        logits = self.model.apply(params, x)
        logp = jax.nn.log_softmax(logits)
        ce = -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()
        return ce, logits

    def _train_step_impl(self, params, opt_state, global_params, x, y, mu):
        (loss, _), grads = jax.value_and_grad(self._loss, has_aux=True)(
            params, x, y)
        grads = proximal_grad(grads, params, global_params, mu)
        updates, opt_state = self.optimizer.update(grads, opt_state, params)
        return apply_updates(params, updates), opt_state, loss

    def local_train(self, global_params: Pytree, ds: ArrayDataset,
                    mu: float = 0.0, seed: int = 0) -> Tuple[Pytree, float]:
        """Run `epochs` local epochs from the global model. Returns the new
        local params and the mean training loss."""
        cfg = self.config
        rng = np.random.default_rng(seed)
        params = global_params
        opt_state = self.optimizer.init(params)
        losses = []
        for _ in range(cfg.epochs):
            for x, y in batches(ds, cfg.batch_size, rng):
                params, opt_state, loss = self._train_step(
                    params, opt_state, global_params,
                    jnp.asarray(x), jnp.asarray(y), float(mu))
                with spans.sync("loss", loss.nbytes):
                    losses.append(float(loss))
        return params, float(np.mean(losses)) if losses else 0.0

    # ------------------------------------------------------------------
    def _eval_batch_impl(self, params, x, y):
        logits = self.model.apply(params, x)
        pred = jnp.argmax(logits, axis=-1)
        logp = jax.nn.log_softmax(logits)
        ce = -jnp.take_along_axis(logp, y[:, None], axis=-1).sum()
        return (pred == y).sum(), ce

    def evaluate(self, params: Pytree, ds: ArrayDataset,
                 batch_size: int = 256) -> Tuple[float, float]:
        """Returns (accuracy, mean loss)."""
        correct, loss_sum, n = 0.0, 0.0, 0
        for i in range(0, len(ds), batch_size):
            x = jnp.asarray(ds.x[i:i + batch_size])
            y = jnp.asarray(ds.y[i:i + batch_size])
            c, l = self._eval_batch(params, x, y)
            with spans.sync("eval", c.nbytes + l.nbytes):
                correct += float(c)
                loss_sum += float(l)
            n += x.shape[0]
        return correct / max(1, n), loss_sum / max(1, n)

    # ------------------------------------------------------------------
    def nominal_work_seconds(self, ds: ArrayDataset) -> float:
        """Ideal training duration used by the virtual-time simulation:
        proportional to epochs × samples (plus model/data load overhead)."""
        cfg = self.config
        load_overhead = 2.0  # model + dataset fetch (paper Alg.1 line 19)
        return load_overhead + cfg.epochs * len(ds) * cfg.per_sample_time_s

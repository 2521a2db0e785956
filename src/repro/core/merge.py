"""Unified delta-based merge pipeline with pluggable server optimizers.

Every strategy's model merge — FedAvg's cardinality-weighted average,
Eq. 3's staleness damping, FedAsync's mixing-rate merge, FedBuff's
buffered flush — is one algebraic shape:

    w' = ServerOpt(w, Δ),   Δ = mix · (Σ_k c_k · W_k − w)

i.e. a weighted sum of client updates forms a *pseudo-gradient* Δ against
the current global model, and a server-side optimizer decides how to fold
it in (Reddi et al., "Adaptive Federated Optimization", arXiv:2003.00295).
`mix` is 1 for the barrier strategies (the weighted sum replaces the
model outright when ServerOpt is the identity), FedAsync's staleness-
damped α_s, or FedBuff's server rate η.

`MergePipeline` owns that step for all strategies (core/strategies.py
constructs one per strategy from `StrategyConfig.server_opt*`):

* the **identity** server optimizer (``sgd`` with lr=1 and no momentum —
  the default) takes a fast path that reproduces the pre-pipeline
  behaviour *byte-identically*: the weighted sum (with the global model
  folded in as an anchor row when mix < 1) runs through the same
  `core.aggregation.aggregate` call, i.e. the Pallas `fed_agg` kernel;
* the adaptive optimizers — ``fedavgm`` (server momentum),
  ``fedadagrad``, ``fedadam``, ``fedyogi`` — keep fp32 moment pytrees
  (structure-sharing the model params, so checkpoints snapshot them with
  the existing array machinery) and dispatch the whole
  weighted-sum → Δ → moment-update → apply step as one fused Pallas
  kernel (`kernels.fed_agg_apply`); ``REPRO_AGG_KERNEL=0`` (or
  ``use_kernel=False``) reverts to a per-leaf `tree_map` twin built on
  the shared `optim.optimizers` pytree helpers.

Empty merges are uniform across strategies and training modes: no
updates → the global model is returned unchanged and ``last_update_norm``
reads 0.0 (the driver's aggregation trace record becomes the zero-delta
record).  `last_update_norm` always carries ‖Δ‖₂ of the latest merge on
the optimizer path — the fused kernel emits it as a per-tile Σ Δ² side
output, so the diagnostic costs no extra pass over the model.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from ..analysis import gates
from ..optim.optimizers import global_norm, zeros_like_f32
from . import spans
from .aggregation import (ClientUpdate, aggregate, aggregate_reference,
                          flat_update_matrix)

Pytree = Any

SERVER_OPTS = ("sgd", "fedavgm", "fedadagrad", "fedadam", "fedyogi")
# second-moment families (need the v buffer)
_ADAPTIVE = ("fedadagrad", "fedadam", "fedyogi")


@dataclass(frozen=True)
class ServerOptConfig:
    """Server optimizer family + hyperparameters (FedOpt conventions:
    no bias correction; `eps` is the adaptivity degree τ)."""
    name: str = "sgd"
    lr: float = 1.0
    momentum: float = 0.0         # heavy-ball β for sgd / fedavgm
    b1: float = 0.9               # first-moment decay (adaptive families)
    b2: float = 0.99              # second-moment decay (fedadam/fedyogi)
    eps: float = 1e-3

    def normalized(self) -> "ServerOptConfig":
        if self.name not in SERVER_OPTS:
            raise ValueError(f"unknown server optimizer {self.name!r}; "
                             f"available: {SERVER_OPTS}")
        # fedavgm *is* momentum — picking it with β=0 means the caller
        # wants the family default, not a silent plain-SGD
        if self.name == "fedavgm" and self.momentum == 0.0:
            return replace(self, momentum=0.9)
        return self

    @property
    def is_identity(self) -> bool:
        """Plain server-SGD with lr=1 and no momentum: w' = w + Δ, i.e.
        exactly the pre-pipeline replace-with-weighted-average."""
        return (self.name == "sgd" and self.lr == 1.0
                and self.momentum == 0.0)


class MergePipeline:
    """Delta-based merge: weighted sum → pseudo-gradient → server opt."""

    def __init__(self, config: Optional[ServerOptConfig] = None,
                 use_kernel: Optional[bool] = None,
                 mesh=None):
        self.config = (config or ServerOptConfig()).normalized()
        self.use_kernel = use_kernel    # None → REPRO_AGG_KERNEL env
        # jax.sharding.Mesh (>1 devices) → the flat weighted-sum and
        # fused-apply dispatches shard the P dim across it (shard_map);
        # None keeps the single-device path bit-for-bit
        self.mesh = mesh
        self.steps = 0                  # server-optimizer steps taken
        self.last_update_norm: Optional[float] = None   # ‖Δ‖₂
        self._m: Optional[Pytree] = None    # fp32 moment pytrees,
        self._v: Optional[Pytree] = None    # params tree structure
        self._unravel32 = None              # cached f32 unravel (kernel)

    @property
    def is_identity(self) -> bool:
        return self.config.is_identity

    def _kernel_enabled(self) -> bool:
        if self.use_kernel is not None:
            return self.use_kernel
        return gates.agg_kernel_enabled()

    # ------------------------------------------------------------------
    def merge(self, global_params: Optional[Pytree],
              updates: Sequence[ClientUpdate], coeffs,
              mix: float = 1.0) -> Optional[Pytree]:
        """Fold `updates` into `global_params`.

        coeffs are the caller's weighted-sum coefficients over `updates`
        (fedavg / staleness / buffer weights); `mix` scales the resulting
        pseudo-gradient (barrier strategies: 1.0, FedAsync: α_s,
        FedBuff: η).  With no updates the global model is returned
        unchanged — the unified empty-cohort / zero-update path.
        """
        if not updates:
            self.last_update_norm = 0.0
            return global_params
        coeffs = np.asarray(coeffs, dtype=np.float64)
        if self.is_identity:
            self.last_update_norm = None    # not computed on the fast path
            return self._merge_identity(global_params, list(updates), coeffs,
                                        mix)
        if global_params is None:
            raise ValueError(
                f"server optimizer {self.config.name!r} is delta-based and "
                f"needs the current global params")
        new_params = self._merge_opt(global_params, list(updates), coeffs,
                                     float(mix))
        self.steps += 1
        return new_params

    # ---- identity fast path (byte-identical legacy behaviour) --------
    def _merge_identity(self, global_params, updates: List[ClientUpdate],
                        coeffs: np.ndarray, mix: float) -> Pytree:
        if mix >= 1.0:
            # w' = w + (Σ c·W − w) = Σ c·W — the exact pre-pipeline call
            return aggregate(updates, coeffs, use_kernel=self.use_kernel,
                             mesh=self.mesh)
        if global_params is None:
            raise ValueError("mix < 1 folds the global model in as an "
                             "anchor; global params are required")
        anchor = ClientUpdate("__global__", global_params, num_samples=0,
                              round_number=updates[0].round_number)
        folded = np.concatenate(([1.0 - mix], mix * coeffs))
        return aggregate([anchor] + updates, folded,
                         use_kernel=self.use_kernel, mesh=self.mesh)

    # ---- optimizer path ----------------------------------------------
    def _merge_opt(self, global_params, updates: List[ClientUpdate],
                   coeffs: np.ndarray, mix: float) -> Pytree:
        # no fallback: a kernel lowering or compile error propagates, so
        # a run never drops to the tree_map twin without saying so
        if self._kernel_enabled():
            return self._apply_kernel(global_params, updates, coeffs, mix)
        return self._apply_tree(global_params, updates, coeffs, mix)

    def _kernel_scalars(self):
        c = self.config
        b1 = c.momentum if c.name in ("sgd", "fedavgm") else c.b1
        return c.lr, b1, c.b2, c.eps

    def _apply_kernel(self, global_params, updates, coeffs, mix):
        # deferred import: kernels pull in pallas
        from ..kernels import fed_agg_apply, fed_agg_apply_sharded

        flat_g, unravel = ravel_pytree(global_params)
        # zero-copy on the device pipeline: batch-backed updates gather
        # rows straight out of the executor's (K, P) matrix
        mat, _ = flat_update_matrix(updates)
        if mat.shape[1] != flat_g.shape[0]:
            raise RuntimeError(
                f"update/global size mismatch: updates ravel to "
                f"{mat.shape[1]} parameters, global model to "
                f"{flat_g.shape[0]}")
        # distinct fresh zero buffers — m and v are donated separately,
        # so they must never share storage
        flat_m = (ravel_pytree(self._m)[0] if self._m is not None
                  else jnp.zeros_like(flat_g, dtype=jnp.float32))
        flat_v = (ravel_pytree(self._v)[0] if self._v is not None
                  else jnp.zeros_like(flat_g, dtype=jnp.float32))
        lr, b1, b2, eps = self._kernel_scalars()
        if self.mesh is not None and int(self.mesh.size) > 1:
            out, m_new, v_new, norm = fed_agg_apply_sharded(
                mat, jnp.asarray(coeffs, dtype=jnp.float32), flat_g,
                flat_m, flat_v, lr, mix, b1, b2, eps,
                opt=self.config.name, mesh=self.mesh)
        else:
            # donate the merge matrix and the flat moment buffers (all
            # rebuilt fresh next round) — NEVER flat_g: the caller's
            # strategy retains global_params across the merge
            out, m_new, v_new, norm = fed_agg_apply(
                mat, jnp.asarray(coeffs, dtype=jnp.float32), flat_g,
                flat_m, flat_v, lr, mix, b1, b2, eps,
                opt=self.config.name, donate=True)
        # moments unravel through an f32 view of the params structure:
        # the params-derived `unravel` would round-trip every leaf via
        # the param dtype, silently quantizing fp32 moment state for
        # low-precision models (the view is cached — the tree structure
        # is fixed for the pipeline's lifetime)
        if self._unravel32 is None:
            _, self._unravel32 = ravel_pytree(zeros_like_f32(global_params))
        self._m = self._unravel32(m_new)
        if self.config.name in _ADAPTIVE:
            self._v = self._unravel32(v_new)
        with spans.sync("merge_norm", norm.nbytes):
            self.last_update_norm = float(norm)
        # cast to the *promoted* flat dtype; unravel itself restores each
        # leaf's own dtype (mixed-precision trees keep full precision)
        return unravel(out.astype(flat_g.dtype))

    def _apply_tree(self, global_params, updates, coeffs, mix):
        """Per-leaf `tree_map` twin of the fused kernel (the validation
        path, selected by ``REPRO_AGG_KERNEL=0`` or ``use_kernel=False``)."""
        c = self.config
        tm = jax.tree_util.tree_map
        avg = aggregate_reference(updates, coeffs)
        delta = tm(lambda a, g: jnp.float32(mix)
                   * (a.astype(jnp.float32) - g.astype(jnp.float32)),
                   avg, global_params)
        if self._m is None:
            self._m = zeros_like_f32(global_params)
        if c.name in ("sgd", "fedavgm"):
            self._m = tm(lambda m, d: c.momentum * m + d, self._m, delta)
            step = self._m
        else:
            if self._v is None:
                self._v = zeros_like_f32(global_params)
            self._m = tm(lambda m, d: c.b1 * m + (1.0 - c.b1) * d,
                         self._m, delta)
            if c.name == "fedadagrad":
                self._v = tm(lambda v, d: v + d * d, self._v, delta)
            elif c.name == "fedadam":
                self._v = tm(lambda v, d: c.b2 * v + (1.0 - c.b2) * d * d,
                             self._v, delta)
            else:                                           # fedyogi
                self._v = tm(
                    lambda v, d: v - (1.0 - c.b2) * d * d
                    * jnp.sign(v - d * d), self._v, delta)
            step = tm(lambda m, v: m / (jnp.sqrt(v) + c.eps),
                      self._m, self._v)
        norm = global_norm(delta)
        with spans.sync("merge_norm", norm.nbytes):
            self.last_update_norm = float(norm)
        return tm(lambda g, s: (g.astype(jnp.float32)
                                + c.lr * s).astype(g.dtype),
                  global_params, step)

    # ---- checkpoint surface (fl/checkpointing.py) --------------------
    def state_dict(self, arrays: Optional[dict] = None) -> dict:
        """Moment pytrees go into `arrays` (they share the global model's
        tree structure, so the checkpointer's array store handles them)."""
        arrays = {} if arrays is None else arrays
        state = {"name": self.config.name, "steps": self.steps}
        if self._m is not None:
            arrays["server_opt/m"] = self._m
            state["has_m"] = True
        if self._v is not None:
            arrays["server_opt/v"] = self._v
            state["has_v"] = True
        return state

    def load_state_dict(self, state: dict,
                        arrays: Optional[dict] = None) -> None:
        """Missing state (moment-free checkpoints from before the merge
        pipeline) restores as a fresh optimizer — the documented
        migration: moments re-accumulate from the resume point."""
        arrays = {} if arrays is None else arrays
        if not state:
            return
        name = state.get("name")
        if name is not None and name != self.config.name:
            raise ValueError(f"checkpoint was written with server "
                             f"optimizer {name!r}, pipeline runs "
                             f"{self.config.name!r}")
        self.steps = int(state.get("steps", 0))
        as_f32 = lambda t: jax.tree_util.tree_map(
            lambda l: jnp.asarray(l, dtype=jnp.float32), t)
        self._m = (as_f32(arrays["server_opt/m"])
                   if state.get("has_m") else None)
        self._v = (as_f32(arrays["server_opt/v"])
                   if state.get("has_v") else None)

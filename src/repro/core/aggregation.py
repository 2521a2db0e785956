"""Aggregation schemes — FedAvg and the paper's staleness-aware Eq. 3.

    w_{t+1} = Σ_k (t_k / t) · (n_k / n) · w^k_{t_k}

where t is the current round, t_k the round client k's update was produced
in, n_k the client dataset cardinality and n the total cardinality of the
aggregated clients.  Updates with t − t_k ≥ τ are discarded (τ = 2 in the
paper).  For t_k = t the scheme reduces exactly to FedAvg.

Updates are JAX pytrees.  `aggregate` has two paths:

  * the **flattened fast path** (default): every update is ravelled into
    one flat vector, the K vectors stacked into a (K, P) matrix, and the
    whole weighted sum dispatched as a single Pallas `fed_agg` kernel
    call (kernels/fed_agg.py — lowered to Mosaic on TPU; on CPU it runs
    through the Pallas interpreter, which validates the kernel but is
    slower than the reference path), then unravelled back to the
    original tree structure;
  * the per-leaf `tree_map` reference path, kept for validation
    (``REPRO_AGG_KERNEL=0`` or ``use_kernel=False``).  There is no
    silent fallback: a kernel lowering or compile error propagates.
"""
from __future__ import annotations

from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from ..analysis import gates

Pytree = Any


class ClientUpdate:
    """One client's local model update as stored in the parameter server.

    When the client path runs update compression (core/compress.py),
    `params` holds the server-side *decode* — the exact pytree the merge
    consumes — and the wire size travels alongside as `payload_bytes`
    (encoded) / `dense_bytes` (what the plaintext fp32 update would have
    cost).  Both stay None on the uncompressed path so dense runs are
    indistinguishable from pre-compression builds.

    On the device-resident round pipeline (core/device_batch.py) an
    update is born as a *row reference* into its group's stacked (K, P)
    matrix: ``batch``/``batch_row`` are set, the concrete pytree is NOT
    built up front, and ``params`` materializes it lazily on first
    access (trace digests, the eager parity path, checkpointed in-flight
    updates).  The merge fast paths read ``flat_params()`` instead and
    never materialize at all.  Assigning ``params`` detaches the update
    from its batch — the explicit tree becomes authoritative.
    """

    __slots__ = ("client_id", "num_samples", "round_number",
                 "training_time", "payload_bytes", "dense_bytes",
                 "batch", "batch_row", "_params")

    def __init__(self, client_id: str, params: Pytree = None,
                 num_samples: int = 0, round_number: int = 0,
                 training_time: float = 0.0,
                 payload_bytes: Optional[int] = None,
                 dense_bytes: Optional[int] = None,
                 batch=None, batch_row: int = -1):
        self.client_id = client_id
        self._params = params
        self.num_samples = num_samples
        self.round_number = round_number   # t_k — round the update is for
        self.training_time = training_time
        self.payload_bytes = payload_bytes  # encoded wire size (simulated)
        self.dense_bytes = dense_bytes      # uncompressed fp32 wire size
        self.batch = batch                  # DeviceUpdateBatch, or None
        self.batch_row = batch_row
        if params is None and batch is None:
            raise ValueError(f"update {client_id!r} needs either concrete "
                             f"params or a device-batch row reference")

    @property
    def params(self) -> Pytree:
        if self._params is None:
            self._params = self.batch.tree(self.batch_row)
        return self._params

    @params.setter
    def params(self, value: Pytree) -> None:
        self._params = value
        self.batch = None           # the explicit tree is now authoritative
        self.batch_row = -1

    def flat_params(self) -> jnp.ndarray:
        """The flat (P,) ravel_pytree view of this update — a zero-copy
        row read on the device pipeline, a ravel otherwise."""
        if self._params is None and self.batch is not None:
            return self.batch.row(self.batch_row)
        return ravel_pytree(self.params)[0]

    def __repr__(self) -> str:
        src = (f"batch_row={self.batch_row}"
               if self._params is None else "params=<tree>")
        return (f"ClientUpdate({self.client_id!r}, {src}, "
                f"n={self.num_samples}, round={self.round_number})")


def update_to_record(update: ClientUpdate) -> dict:
    """JSON-ready metadata of one update (checkpoint surface) — the
    params pytree travels separately in the checkpoint's array store."""
    rec = {"client_id": update.client_id,
           "num_samples": update.num_samples,
           "round_number": update.round_number,
           "training_time": update.training_time}
    # only-when-set: dense checkpoints stay byte-identical to older builds
    if update.payload_bytes is not None:
        rec["payload_bytes"] = update.payload_bytes
        rec["dense_bytes"] = update.dense_bytes
    return rec


def update_from_record(rec: dict, params: Pytree) -> ClientUpdate:
    """Inverse of `update_to_record`; keys it does not write (such as
    an older build's ``dispatch_s``) are ignored."""
    return ClientUpdate(params=params, client_id=rec["client_id"],
                        num_samples=rec["num_samples"],
                        round_number=rec["round_number"],
                        training_time=rec.get("training_time", 0.0),
                        payload_bytes=rec.get("payload_bytes"),
                        dense_bytes=rec.get("dense_bytes"))


@partial(jax.jit, static_argnums=())
def _weighted_sum(stacked: Pytree, coeffs: jnp.ndarray) -> Pytree:
    """Σ_k coeffs[k] · leaf[k] for every leaf of a stacked pytree."""
    def one(leaf):
        c = coeffs.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
        return jnp.sum(c * leaf, axis=0)
    return jax.tree_util.tree_map(one, stacked)


def _stack(updates: Sequence[Pytree]) -> Pytree:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs, axis=0), *updates)


def fedavg_coefficients(updates: Sequence[ClientUpdate]) -> np.ndarray:
    n = float(sum(u.num_samples for u in updates)) or 1.0
    return np.array([u.num_samples / n for u in updates], dtype=np.float64)


def staleness_coefficients(updates: Sequence[ClientUpdate],
                           current_round: int) -> np.ndarray:
    """Eq. 3 coefficients (t_k/t)·(n_k/n). Round numbers are 0-based in the
    runtime, so the damping ratio uses (t_k+1)/(t+1)."""
    n = float(sum(u.num_samples for u in updates)) or 1.0
    t = float(current_round + 1)
    return np.array(
        [((u.round_number + 1) / t) * (u.num_samples / n) for u in updates],
        dtype=np.float64)


def aggregate_reference(updates: Sequence[ClientUpdate],
                        coeffs: np.ndarray) -> Pytree:
    """Per-leaf tree_map weighted sum (the validation twin)."""
    stacked = _stack([u.params for u in updates])
    return _weighted_sum(stacked, jnp.asarray(coeffs, dtype=jnp.float32))


def flat_update_matrix(updates: Sequence[ClientUpdate]
                       ) -> Tuple[jnp.ndarray, Any]:
    """(K, P) stacked flat updates + the shared ``unravel`` handle.

    Zero-copy on the device pipeline: when every update references the
    same ``DeviceUpdateBatch``, the rows are gathered straight out of
    the executor's matrix — no per-client unflatten/re-ravel.  Mixed or
    legacy updates fall back to per-update ``flat_params()`` (itself a
    row read for batch-backed members, a ravel for concrete ones).  The
    returned matrix is always a fresh device array, safe to donate to
    the aggregation kernel.
    """
    first = updates[0]
    b = getattr(first, "batch", None)
    if (b is not None
            and all(getattr(u, "batch", None) is b for u in updates)):
        return (b.gather([u.batch_row for u in updates]), b.unravel)
    if b is not None:
        # mixed cohort (e.g. straggler arrivals spanning rounds): stay on
        # flat rows — the batch already knows the layout, no need to
        # materialize first's pytree just to recover the unravel handle
        flat0, unravel = first.flat_params(), b.unravel
    else:
        flat0, unravel = ravel_pytree(first.params)
    rows = [flat0] + [u.flat_params().astype(flat0.dtype)
                      for u in updates[1:]]
    return jnp.stack(rows), unravel


def _aggregate_flat(updates: Sequence[ClientUpdate],
                    coeffs: np.ndarray, mesh=None) -> Pytree:
    """Stack K flat updates into a (K, P) matrix (a device-side gather on
    the zero-copy pipeline, a ravel+stack otherwise) and run the weighted
    sum as one Pallas kernel dispatch, then unravel the result.  With a
    `mesh` of >1 devices the dispatch shards the P dim across it
    (kernels.fed_agg_sharded)."""
    from ..kernels import fed_agg, fed_agg_sharded   # deferred: pallas

    mat, unravel = flat_update_matrix(updates)
    out_dtype = mat.dtype
    cf = jnp.asarray(coeffs, dtype=jnp.float32)
    if mesh is not None and int(mesh.size) > 1:
        out = fed_agg_sharded(mat, cf, mesh)
    else:
        # mat is a fresh stack/gather nobody retains — donate it so XLA
        # reuses the K·P buffer in place (no-op on CPU)
        out = fed_agg(mat, cf, donate=True)
    return unravel(out.astype(out_dtype))


def aggregate(updates: Sequence[ClientUpdate], coeffs: np.ndarray,
              use_kernel: Optional[bool] = None, mesh=None) -> Pytree:
    """Weighted sum Σ_k c_k · W_k over client updates."""
    if use_kernel is None:
        # call-time read (REPRO_AGG_KERNEL=0 reverts to tree_map) so a
        # per-test env flip reaches this default like every other gate
        use_kernel = gates.agg_kernel_enabled()
    if use_kernel:
        return _aggregate_flat(updates, coeffs, mesh=mesh)
    return aggregate_reference(updates, coeffs)


def fedavg_aggregate(updates: Sequence[ClientUpdate]) -> Pytree:
    """Plain FedAvg: Σ (n_k/n) w_k."""
    if not updates:
        raise ValueError("fedavg_aggregate needs at least one update")
    return aggregate(updates, fedavg_coefficients(updates))


def staleness_aggregate(updates: Sequence[ClientUpdate], current_round: int,
                        tau: int = 2) -> Optional[Pytree]:
    """Paper Eq. 3 with max-age cutoff τ: drop updates with t − t_k ≥ τ.

    Returns None when every update was discarded (caller keeps the old
    global model for this round).
    """
    fresh = [u for u in updates if (current_round - u.round_number) < tau]
    if not fresh:
        return None
    return aggregate(fresh, staleness_coefficients(fresh, current_round))


class RunningAggregator:
    """FedLess §III-A 'running average model aggregation': accumulate
    updates one by one in O(1) memory instead of stacking all K.

    Eq. 3 factorises as (Σ_k (t_k/t)·n_k·w_k) / (Σ_k n_k), so the server
    can fold each update into a numerator/denominator pair as it arrives
    — the production path when K × model-size doesn't fit the aggregator
    function's memory (paper: 7 GB aggregation function limit).
    """

    def __init__(self, current_round: int, tau: int = 2):
        self.current_round = current_round
        self.tau = tau
        self._num: Optional[Pytree] = None
        self._den: float = 0.0
        self.accepted = 0
        self.rejected = 0

    def add(self, update: ClientUpdate) -> bool:
        """Fold one update in; returns False if discarded by τ."""
        if (self.current_round - update.round_number) >= self.tau:
            self.rejected += 1
            return False
        damp = (update.round_number + 1) / (self.current_round + 1)
        scale = jnp.float32(damp * update.num_samples)

        def fold(acc, leaf):
            return acc + scale * leaf.astype(jnp.float32)

        if self._num is None:
            self._num = jax.tree_util.tree_map(
                lambda l: scale * l.astype(jnp.float32), update.params)
        else:
            self._num = jax.tree_util.tree_map(fold, self._num,
                                               update.params)
        self._den += float(update.num_samples)
        self.accepted += 1
        return True

    def finalize(self) -> Optional[Pytree]:
        if self._num is None or self._den == 0.0:
            return None
        inv = jnp.float32(1.0 / self._den)
        return jax.tree_util.tree_map(lambda l: l * inv, self._num)


class UpdateStore:
    """Parameter-server-side store of pending client updates.

    Slow clients push updates after their round finished (semi-async);
    those stale updates are *included the next time aggregation runs*
    (paper §V-D) and dropped once older than τ.  Each update carries an
    arrival time (the client's virtual finish time): an update is only
    visible to aggregations that happen after it physically arrived —
    very slow clients therefore age across multiple rounds and τ
    genuinely discards them.
    """

    def __init__(self, tau: int = 2):
        self.tau = tau
        self._pending: List[tuple] = []   # (arrival_time, ClientUpdate)

    def push(self, update: ClientUpdate,
             arrival_time: float = 0.0) -> None:
        self._pending.append((arrival_time, update))

    def pop_for_round(self, current_round: int,
                      now: Optional[float] = None) -> List[ClientUpdate]:
        """Return fresh-enough *arrived* updates; keep future arrivals."""
        taken, kept = [], []
        for arrival, u in self._pending:
            if now is not None and arrival > now:
                kept.append((arrival, u))       # still in flight
            elif (current_round - u.round_number) < self.tau:
                taken.append(u)
            # else: aged out — dropped (paper §V-D)
        self._pending = kept
        return taken

    def __len__(self) -> int:
        return len(self._pending)

    # ---- checkpoint surface (fl/checkpointing.py) --------------------
    def state_dict(self, arrays: dict,
                   prefix: str = "strategy/pending") -> List[dict]:
        """Snapshot the pending entries; update pytrees go into `arrays`
        under `prefix`-keyed slots (the store owns its own layout — the
        strategies just forward the call)."""
        out = []
        for i, (arrival, update) in enumerate(self._pending):
            arrays[f"{prefix}/{i}"] = update.params
            rec = update_to_record(update)
            rec["arrival"] = arrival
            out.append(rec)
        return out

    def load_state_dict(self, entries: List[dict], arrays: dict,
                        prefix: str = "strategy/pending") -> None:
        self._pending = [
            (float(rec["arrival"]),
             update_from_record(rec, arrays[f"{prefix}/{i}"]))
            for i, rec in enumerate(entries)]

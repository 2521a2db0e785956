"""Device-resident round pipeline: the stacked (K, P) update batch.

Before this module, the vectorized executor's output took a scenic tour
of the host: each client's params were sliced out of the vmapped stack
one at a time (``tree_map(lambda l: l[k])``), packaged as K separate
pytrees, then immediately re-ravelled and re-stacked by the aggregation
layer before the Pallas ``fed_agg`` kernel saw them — 2·K full-model
reorderings per round that do zero useful work.

``DeviceUpdateBatch`` is the zero-copy alternative: the executor hands
over the *flattened* (K, P) matrix it already holds on device (plus the
``unravel`` handle to rebuild any single client's tree), and everything
downstream — ``ClientPool.package_update``, the event engine's per-round
work cache, ``UpdateCompressor`` (which reads rows directly), and the
``MergePipeline``/``fed_agg_apply`` dispatch — operates on rows of that
one matrix.  Per-client pytrees are materialized *lazily*, only when a
consumer genuinely needs tree structure (trace digests, the eager
``work_fn`` parity path, checkpointed in-flight updates).

The flattened layout is bit-for-bit the ``ravel_pytree`` layout, so a
merge over gathered rows is byte-identical to the legacy
materialize→ravel→stack path — only the redundant transforms disappear.

``REPRO_DEVICE_PIPELINE=0`` reverts every consumer to the legacy
per-client path (the kill switch mirrors ``REPRO_AGG_KERNEL``).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..analysis import gates
from . import spans

Pytree = Any


def pipeline_enabled() -> bool:
    """The device-pipeline kill switch (checked at call time, so tests
    can flip it per-case)."""
    return gates.device_pipeline_enabled()


# host-transfer accounting — the counters live in core/spans.py with
# the round's other counters.  Counts bytes that cross the
# executor→merge boundary as *per-client* materializations (row
# unravels / full-tree rebuilds); the device pipeline's claim is that
# the dense path drops from 2·K·model-size to ≤ 1·model-size.
def transfer_stats() -> Dict[str, int]:
    """Every round counter (core/spans.py), the transfer ones included."""
    return spans.counters()


def reset_transfer_stats() -> None:
    spans.reset_counters()


def count_materialization(nbytes: int, rows: int = 1) -> None:
    spans.count("materialize_bytes", nbytes)
    spans.count("materialize_rows", rows)


class DeviceUpdateBatch:
    """One executor group's trained updates as a device-resident matrix.

    * ``mat`` — (K_bucket, P) flat update matrix (rows beyond
      ``len(cids)`` are vmap-bucket padding and are never addressed);
    * ``cids`` — the real clients, row i of ``mat`` belongs to
      ``cids[i]``;
    * ``unravel`` — the ``ravel_pytree`` inverse for one row (shared by
      every client of the group: same model structure);
    * ``losses`` — (K_bucket,) per-client mean training loss, fetched
      host-side with ONE ``np.asarray`` on first access instead of K
      blocking per-scalar transfers.

    Rows can be *replaced* (``set_row``) — the compression stage swaps a
    row for its server-side decode w + decode(encode(δ)) without ever
    building the per-client pytree.  ``gather`` assembles the merge
    matrix for any subset of rows as a fresh device array (safe to
    donate to the aggregation kernel).
    """

    def __init__(self, mat: jnp.ndarray, cids: Sequence[str],
                 unravel: Callable[[jnp.ndarray], Pytree],
                 losses: Optional[jnp.ndarray] = None):
        if mat.ndim != 2 or mat.shape[0] < len(cids):
            raise ValueError(f"update matrix {mat.shape} cannot hold "
                             f"{len(cids)} client rows")
        self.mat = mat
        self.cids = tuple(cids)
        self.unravel = unravel
        self._losses = losses
        self._losses_np: Optional[np.ndarray] = None
        self._row_override: Dict[int, jnp.ndarray] = {}
        self._trees: Dict[int, Pytree] = {}

    # ------------------------------------------------------------------
    @property
    def num_clients(self) -> int:
        return len(self.cids)

    @property
    def num_params(self) -> int:
        return int(self.mat.shape[1])

    def row(self, i: int) -> jnp.ndarray:
        """Client i's flat (P,) update vector (stays on device)."""
        if not 0 <= i < len(self.cids):
            raise IndexError(f"row {i} out of range for "
                             f"{len(self.cids)} clients")
        override = self._row_override.get(i)
        return override if override is not None else self.mat[i]

    def set_row(self, i: int, flat: jnp.ndarray) -> None:
        """Replace client i's update (compression decode) in place —
        consumers that already materialized the old tree are invalidated."""
        if flat.shape != (self.mat.shape[1],):
            raise ValueError(f"row shape {flat.shape} != "
                             f"({self.mat.shape[1]},)")
        self._row_override[i] = flat
        self._trees.pop(i, None)

    def gather(self, rows: Sequence[int]) -> jnp.ndarray:
        """(len(rows), P) merge matrix — always a fresh device array
        (never an alias of ``mat``), so callers may donate it."""
        rows = list(rows)
        if self._row_override and any(r in self._row_override
                                      for r in rows):
            return jnp.stack([self.row(r) for r in rows])
        return jnp.take(self.mat, jnp.asarray(rows, dtype=jnp.int32),
                        axis=0)

    def tree(self, i: int) -> Pytree:
        """Materialize client i's pytree (lazy; cached per row).  This is
        the only point where per-client structure is rebuilt — trace
        digests, the eager parity path, and checkpointed in-flight
        updates all funnel through here."""
        tree = self._trees.get(i)
        if tree is None:
            flat = self.row(i)
            tree = self.unravel(flat)
            self._trees[i] = tree
            count_materialization(flat.size * flat.dtype.itemsize)
        return tree

    def loss(self, i: int) -> float:
        """Client i's mean training loss — the whole loss vector crosses
        the device boundary once, on first access."""
        if self._losses is None:
            return 0.0
        if self._losses_np is None:
            with spans.sync("loss", self._losses.nbytes):
                self._losses_np = np.asarray(self._losses)
            spans.count("loss_syncs")
        return float(self._losses_np[i])

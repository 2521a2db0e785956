"""Central registry for the ``REPRO_*`` environment gates.

Every runtime kill switch the simulation honours is declared here, with
its default and what flipping it reverts.  All reads go through this
module — ``repro-lint``'s ``env-gate-registry`` rule (GATE001) flags any
``os.environ`` access to a ``REPRO_*`` name anywhere else in ``src/``,
so a new gate cannot be introduced without documenting it in ``GATES``.

Reads happen at *call* time (no import-time caching) so tests can flip a
gate per-case with ``monkeypatch.setenv`` and every consumer — the
aggregation default, the merge pipeline, the compressor, the device
pipeline — sees the same value.

Import discipline: this module depends only on the stdlib.  Simulation
packages (``core/``, ``faas/``, ``fl/``, ``kernels/``) import it at
module load, so it must never import the lint engine (or jax) back.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional

# names that tell the truth in an env listing: every gate is REPRO_*
AGG_KERNEL = "REPRO_AGG_KERNEL"
COMPRESS = "REPRO_COMPRESS"
DEVICE_PIPELINE = "REPRO_DEVICE_PIPELINE"
OVERLAP_DISPATCH = "REPRO_OVERLAP_DISPATCH"
PALLAS_INTERPRET = "REPRO_PALLAS_INTERPRET"


@dataclass(frozen=True)
class Gate:
    """One documented environment kill switch."""
    name: str
    default: Optional[str]      # value assumed when the var is unset
    doc: str


GATES: Dict[str, Gate] = {g.name: g for g in (
    Gate(AGG_KERNEL, "1",
         "Pallas fed_agg / fed_agg_apply aggregation kernels; 0 reverts "
         "to the tree_map reference path (core/aggregation.py, "
         "core/merge.py)."),
    Gate(COMPRESS, "1",
         "Client-update compression (top-k / int8 codecs with error "
         "feedback); 0 forces dense updates even when a scheme is "
         "configured (core/compress.py)."),
    Gate(DEVICE_PIPELINE, "1",
         "Device-resident round pipeline (zero-copy executor→merge "
         "handoff via DeviceUpdateBatch); 0 reverts every consumer to "
         "the legacy per-client materialize path "
         "(core/device_batch.py)."),
    Gate(OVERLAP_DISPATCH, "1",
         "Overlapped executor dispatch: the vectorized cohort training "
         "launch is not blocked on — results flow back as async "
         "DeviceUpdateBatch handles while event/trace/billing "
         "bookkeeping proceeds; 0 blocks until the device compute "
         "finishes before the round's events run (fl/executor.py). "
         "Byte-inert either way: virtual time never reads the wall "
         "clock."),
    Gate(PALLAS_INTERPRET, None,
         "Pallas interpret-mode override: 1 forces the interpreter, 0 "
         "forces Mosaic lowering; unset picks interpret on CPU and "
         "Mosaic on TPU.  Read at every kernel call "
         "(kernels/ops.resolve_interpret); 1 on a TPU backend raises."),
)}


def raw(name: str) -> Optional[str]:
    """The gate's raw env value (or its declared default when unset).

    Raises ``KeyError`` for names not declared in ``GATES`` — reading an
    undeclared ``REPRO_*`` var is exactly the drift this registry
    exists to prevent.
    """
    gate = GATES[name]
    return os.environ.get(name, gate.default)


def enabled(name: str) -> bool:
    """Boolean gates follow one convention: anything but ``"0"`` is on."""
    return raw(name) != "0"


# ---- per-gate helpers (the call sites read as prose) -----------------
def agg_kernel_enabled() -> bool:
    return enabled(AGG_KERNEL)


def compress_enabled() -> bool:
    return enabled(COMPRESS)


def device_pipeline_enabled() -> bool:
    return enabled(DEVICE_PIPELINE)


def overlap_dispatch_enabled() -> bool:
    return enabled(OVERLAP_DISPATCH)


def pallas_interpret_override() -> Optional[bool]:
    """Three-state: None (backend decides) / True / False."""
    value = raw(PALLAS_INTERPRET)
    if value is None:
        return None
    return value != "0"

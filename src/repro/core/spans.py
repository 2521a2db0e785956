"""Spans and counters of the FL round, on the profiler's clock.

Every span is a `jax.profiler.TraceAnnotation`, so it lands in the same
trace as the device's XLA events when a profiler is running
(`jax.profiler.trace(...)`) and costs about a microsecond when none is.
Host spans are named ``fl.*`` (README "Tracing" lists them); their stats
are host-known values only — shapes, counts, round numbers — never a
read of a device array.  A stat known only at a span's end goes on with
``TraceAnnotation.set_metadata``.

The counters are plain process-wide integers, always on:

* ``materialize_bytes`` / ``materialize_rows`` — per-client rows rebuilt
  from a `DeviceUpdateBatch` (core/device_batch.py);
* ``loss_syncs`` — batched loss-vector fetches;
* ``staged_bytes`` — cohort tensors staged host→device by the executor;
* ``host_syncs`` — blocking device→host reads (one per ``fl.sync``);
* ``events`` — event-queue pops;
* ``compiles`` — new executor dispatch shapes (`compile_count` steps);
* ``fused_adam_steps`` — per-client Adam steps of dense weights taken by
  `kernels.dense_adam` (bucket × local steps × engaged weights, counted
  at each dispatch).
"""
from __future__ import annotations

import contextlib
from typing import Dict, Iterator

import jax

_COUNTERS: Dict[str, int] = {
    "materialize_bytes": 0, "materialize_rows": 0, "loss_syncs": 0,
    "staged_bytes": 0, "host_syncs": 0, "events": 0, "compiles": 0,
    "fused_adam_steps": 0}


def counters() -> Dict[str, int]:
    return dict(_COUNTERS)


def reset_counters() -> None:
    for k in _COUNTERS:
        _COUNTERS[k] = 0


def count(name: str, n: int = 1) -> None:
    _COUNTERS[name] += int(n)


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` carrying ``stats``; use it as a context
    manager (``with span(...) as s: s.set_metadata(k=v)``)."""
    return jax.profiler.TraceAnnotation(name, **stats)


@contextlib.contextmanager
def sync(what: str, nbytes: int) -> Iterator[None]:
    """Wrap one blocking device→host read: an ``fl.sync`` span and one
    ``host_syncs`` count.  ``what`` names the read (loss, eval,
    merge_norm, block); ``nbytes`` is what crosses, from shapes."""
    _COUNTERS["host_syncs"] += 1
    with jax.profiler.TraceAnnotation("fl.sync", what=what,
                                      bytes=int(nbytes)):
        yield

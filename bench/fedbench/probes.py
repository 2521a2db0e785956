"""What the benchmark's files put around the program's calls.

Probes run in every run: they keep what the window's sampled rounds
produced for the correctness check after the window, count the clients
trained, and stamp each round's start on the host clock.  By default they
keep references to the device arrays (no copy, no host sync); in host mode
(harness.check_sizes: where device references would take too much of the
chip) each capture starts an asynchronous copy to the host, which the start of the next round collects
as numpy, dropping the device reference.  Either way they count the device
bytes they hold.  Spans (`jax.profiler.TraceAnnotation`) are installed
by the per-layer metric files and the harness in the traced run only.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import numpy as np


def wrap_method(obj, attr: str, before=None, after=None, span=None):
    """Replace obj.attr (an instance or a class) by a wrapper that runs
    `before(*args)`, the call (inside a TraceAnnotation named `span` when
    given) and `after(result, *args)`.  Returns a function that puts the
    attribute back as it was."""
    inner = getattr(obj, attr)
    own = vars(obj).get(attr)

    def call(*args, **kwargs):
        if before is not None:
            before(*args, **kwargs)
        if span is None:
            out = inner(*args, **kwargs)
        else:
            with jax.profiler.TraceAnnotation(span):
                out = inner(*args, **kwargs)
        if after is not None:
            after(out, *args, **kwargs)
        return out

    # a plain function set on a class is bound like any method
    setattr(obj, attr, call)

    def undo():
        if own is None:
            delattr(obj, attr)
        else:
            setattr(obj, attr, own)
    return undo


class Env:
    """Handles a metric file's `install(env)` uses to put its span in."""

    def __init__(self, task, executor, undo: list):
        self.task = task
        self.executor = executor
        self.undo = undo

    def wrap(self, obj, attr: str, span: str) -> None:
        self.undo.append(wrap_method(obj, attr, span=span))


class HostRows:
    """A core/device_batch.DeviceUpdateBatch copied to the host: its
    matrix with any replaced rows, and its losses, behind the batch's
    `row` and `loss`."""

    def __init__(self, batch):
        self.mat = np.asarray(batch.mat)
        if batch._row_override:
            self.mat = self.mat.copy()
            for i, flat in batch._row_override.items():
                self.mat[i] = np.asarray(flat)
        self.losses = (None if batch._losses is None
                       else np.asarray(batch._losses))

    def row(self, i: int):
        return self.mat[i]

    def loss(self, i: int) -> float:
        return 0.0 if self.losses is None else float(self.losses[i])


def device_bytes(arrays) -> Dict[Any, int]:
    """device -> bytes that `arrays` occupy on it."""
    out: Dict[Any, int] = {}
    for a in arrays:
        shard = a.sharding.shard_shape(a.shape)
        n = int(np.prod(shard, dtype=np.int64)) * a.dtype.itemsize
        for d in a.sharding.device_set:
            out[d] = out.get(d, 0) + n
    return out


class Capture:
    """What one study's sampled rounds produced, for the reference.

    round_t: the round whose client updates and merge are checked; the
    updates of round_t - 1 are kept too (stragglers of the round before
    merge in round_t).  round_e: the round after which the evaluation is
    checked.

    By default the update matrices are kept by reference, not copied: the
    merge never donates them, it merges a fresh gather of their rows
    (core/device_batch.DeviceUpdateBatch.gather).  With `host`, what a
    round captures is copied to the host by the next round's start (or
    the study's end, `collect`), and only then let go of on the device.
    `peak_bytes` holds the most device bytes the probes have held at once
    on one device, and `peak_total` on all devices together."""

    def __init__(self, round_t: int, round_e: int, host: bool = False):
        self.round_t, self.round_e = round_t, round_e
        self.host = host
        self.round = -1
        self.round_starts: List[float] = []
        self.trained: List[int] = []          # clients trained per call
        self.peak_bytes = self.peak_total = 0
        self.reset()

    def reset(self) -> None:
        self.params: Dict[int, Any] = {}      # round -> global params in
        self.seeds: Dict[int, Dict[str, int]] = {}
        self.rows: Dict[int, Dict[str, tuple]] = {}   # cid -> (batch, row)
        self.evals: List[tuple] = []          # (ds, accuracy, loss)
        self.client_ids: List[str] = []       # the pool's client order
        self.result = None
        self.pending: List[int] = []          # rounds not yet on the host

    def held(self) -> list:
        """The distinct device arrays the probes hold."""
        leaves = jax.tree_util.tree_leaves(self.params)
        for rows in self.rows.values():
            for batch, _ in rows.values():
                if not isinstance(batch, HostRows):
                    leaves += [batch.mat, batch._losses,
                               *batch._row_override.values()]
        return list({id(a): a for a in leaves
                     if isinstance(a, jax.Array)}.values())

    def _count(self) -> None:
        per = device_bytes(self.held())
        if per:
            self.peak_bytes = max(self.peak_bytes, max(per.values()))
            self.peak_total = max(self.peak_total, sum(per.values()))

    def collect(self) -> None:
        """Host mode: the captures still on the device, as numpy."""
        for r in self.pending:
            if r in self.params:
                self.params[r] = jax.tree_util.tree_map(np.asarray,
                                                        self.params[r])
            if r in self.rows:
                host = {}
                for batch, _ in self.rows[r].values():
                    if id(batch) not in host:
                        host[id(batch)] = HostRows(batch)
                self.rows[r] = {c: (host[id(b)], i)
                                for c, (b, i) in self.rows[r].items()}
        self.pending = []

    # -- executor.run_clients(pool, cids, global_params, round_number) --
    def after_run_clients(self, out, pool, cids, global_params,
                          round_number):
        self.trained.append(len(cids))
        self.client_ids = list(pool.client_ids)
        r = round_number
        kept = False
        if r in (self.round_t, self.round_t + 1, self.round_e + 1):
            self.params[r] = global_params
            kept = True
        if r in (self.round_t - 1, self.round_t):
            self.seeds[r] = {c: pool.client_seed(c, r) for c in cids}
            self.rows[r] = {c: (u.batch, u.batch_row)
                            for c, (u, _) in out.items()}
            kept = True
        if self.host and kept:
            self.pending.append(r)
            for a in self.held():
                a.copy_to_host_async()
        self._count()

    # -- task.evaluate(params, ds) --
    def after_evaluate(self, out, params, ds, *args, **kwargs):
        if self.round == self.round_e:
            self.evals.append((ds, float(out[0]), float(out[1])))

    # -- TrainingDriver.run_round(self, global_params, round_number) --
    def before_run_round(self, driver, global_params, round_number):
        self.round = round_number
        self.round_starts.append(time.perf_counter())
        if self.pending:
            self.collect()


def install_probes(cap: Capture, task, executor) -> list:
    """-> the functions that take the probes out again."""
    from repro.fl.controller import TrainingDriver
    return [wrap_method(executor, "run_clients", after=cap.after_run_clients),
            wrap_method(task, "evaluate", after=cap.after_evaluate),
            wrap_method(TrainingDriver, "run_round",
                        before=cap.before_run_round)]

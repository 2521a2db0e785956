"""What the benchmark's files put around the program's calls.

Probes run in every run: they keep references to what the window's
sampled rounds produced (no copy, no host sync) for the correctness check
after the window, count the clients trained, and stamp each round's start
on the host clock.  Spans (`jax.profiler.TraceAnnotation`) are installed
by the per-layer metric files and the harness in the traced run only.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax


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


class Capture:
    """References to one study's sampled rounds, for the reference.

    round_t: the round whose client updates and merge are checked; the
    updates of round_t - 1 are kept too (stragglers of the round before
    merge in round_t).  round_e: the round after which the evaluation is
    checked.

    The update matrices are kept by reference, not copied: the merge
    never donates them, it merges a fresh gather of their rows
    (core/device_batch.DeviceUpdateBatch.gather)."""

    def __init__(self, round_t: int, round_e: int):
        self.round_t, self.round_e = round_t, round_e
        self.round = -1
        self.round_starts: List[float] = []
        self.trained: List[int] = []          # clients trained per call
        self.reset()

    def reset(self) -> None:
        self.params: Dict[int, Any] = {}      # round -> global params in
        self.seeds: Dict[int, Dict[str, int]] = {}
        self.rows: Dict[int, Dict[str, tuple]] = {}   # cid -> (batch, row)
        self.evals: List[tuple] = []          # (ds, accuracy, loss)
        self.client_ids: List[str] = []       # the pool's client order
        self.result = None

    # -- executor.run_clients(pool, cids, global_params, round_number) --
    def after_run_clients(self, out, pool, cids, global_params,
                          round_number):
        self.trained.append(len(cids))
        self.client_ids = list(pool.client_ids)
        r = round_number
        if r in (self.round_t, self.round_t + 1, self.round_e + 1):
            self.params[r] = global_params
        if r in (self.round_t - 1, self.round_t):
            self.seeds[r] = {c: pool.client_seed(c, r) for c in cids}
            self.rows[r] = {c: (u.batch, u.batch_row)
                            for c, (u, _) in out.items()}

    # -- task.evaluate(params, ds) --
    def after_evaluate(self, out, params, ds, *args, **kwargs):
        if self.round == self.round_e:
            self.evals.append((ds, float(out[0]), float(out[1])))

    # -- TrainingDriver.run_round(self, global_params, round_number) --
    def before_run_round(self, driver, global_params, round_number):
        self.round = round_number
        self.round_starts.append(time.perf_counter())


def install_probes(cap: Capture, task, executor) -> list:
    """-> the functions that take the probes out again."""
    from repro.fl.controller import TrainingDriver
    return [wrap_method(executor, "run_clients", after=cap.after_run_clients),
            wrap_method(task, "evaluate", after=cap.after_evaluate),
            wrap_method(TrainingDriver, "run_round",
                        before=cap.before_run_round)]

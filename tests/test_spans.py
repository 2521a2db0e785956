"""Spans and counters of the FL round (core/spans.py).

A 2-round vectorized experiment with a tiny CNN runs under
``jax.profiler.trace``; the ``fl.*`` host spans are read back from the
trace file with ``ProfileData`` and checked against the dataset's
shapes and the process counters.  The same experiment without the
profiler moves the counters the same way (the spans add no host sync),
and the named scopes inside the group-train program change its HLO
only in metadata.
"""
import contextlib
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import spans
from repro.data import make_image_classification
from repro.data.synthetic import ArrayDataset
from repro.fl.executor import VectorizedExecutor
from repro.fl.experiment import ExperimentConfig, run_experiment
from repro.fl.tasks import ClassificationTask, TaskConfig
from repro.models.small import make_cnn

CLIENTS, SAMPLES, COHORT, BUCKET, BATCH = 8, 40, 3, 4, 16
STEPS = -(-SAMPLES // BATCH)                  # one epoch
ROUNDS = 2
# the counters that must not depend on whether a profiler is running
SAME = ("loss_syncs", "host_syncs", "events", "staged_bytes",
        "materialize_bytes", "materialize_rows")


def _data():
    full = make_image_classification(CLIENTS * SAMPLES + CLIENTS * 8,
                                      image_size=14, n_classes=5, seed=0)
    x, y = np.asarray(full.x), np.asarray(full.y)
    train = {f"c{i}": ArrayDataset(x[i * SAMPLES:(i + 1) * SAMPLES],
                                   y[i * SAMPLES:(i + 1) * SAMPLES])
             for i in range(CLIENTS)}
    off = CLIENTS * SAMPLES
    test = {f"c{i}": ArrayDataset(x[off + 8 * i:off + 8 * (i + 1)],
                                  y[off + 8 * i:off + 8 * (i + 1)])
            for i in range(CLIENTS)}
    return train, test


def _task():
    return ClassificationTask(
        make_cnn(14, 1, 5, 16, "tiny"),
        TaskConfig(epochs=1, batch_size=BATCH, per_sample_time_s=0.05))


def _config():
    # fedadam merges through the server optimizer, whose update norm is
    # read back to the host; evaluation reads every batch's answers
    return ExperimentConfig(strategy="fedlesscan", n_rounds=ROUNDS,
                            clients_per_round=COHORT, eval_every=1, seed=3,
                            vectorized=True, server_opt="fedadam",
                            server_opt_lr=0.01)


def _run(task, train, test):
    before = spans.counters()
    run_experiment(task, train, test, _config(),
                   initial_params=task.init_params(0))
    after = spans.counters()
    return {k: after[k] - before[k] for k in after}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(host fl.* events, counter deltas traced, counter deltas untraced,
    train partitions)."""
    train, test = _data()
    task = _task()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        deltas = _run(task, train, test)
    plain = _run(task, train, test)
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    events = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("fl."):
                    events.append((e.name, e.start_ns, e.end_ns,
                                   dict(e.stats)))
    events.sort(key=lambda ev: (ev[1], -ev[2]))
    return events, deltas, plain, train


def _named(events, name):
    return [e for e in events if e[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _round_of(events, ev):
    (rnd,) = [r for r in _named(events, "fl.round") if _inside(ev, r)]
    return rnd


@pytest.mark.parametrize("name,keys", [
    ("fl.round", {"round", "selected", "events", "syncs", "staged_bytes",
                  "compiles"}),
    ("fl.schedule", {"round", "want"}),
    ("fl.stage", {"round", "clients", "bucket", "bytes"}),
    ("fl.gather", set()),
    ("fl.put", {"bytes"}),
    ("fl.dispatch", {"round", "bucket", "new_shape", "fused_adam"}),
    ("fl.package", {"round", "clients"}),
    ("fl.merge", {"round", "k"}),
    ("fl.eval", {"clients", "samples"}),
    ("fl.sync", {"what", "bytes"}),
])
def test_every_span_appears_with_its_stats(traced, name, keys):
    events = traced[0]
    found = _named(events, name)
    assert found, name
    for ev in found:
        assert set(ev[3]) == keys, (name, ev[3])
    if "round" in keys:
        assert sorted({ev[3]["round"] for ev in found}) == list(range(ROUNDS))


@pytest.mark.parametrize("name", ["fl.schedule", "fl.stage", "fl.dispatch",
                                  "fl.package", "fl.merge"])
def test_round_spans_nest_inside_their_round(traced, name):
    events = traced[0]
    for ev in _named(events, name):
        assert _round_of(events, ev)[3]["round"] == ev[3]["round"]


def test_stage_bytes_are_the_padded_cohort_tensors(traced):
    events, deltas, _, train = traced
    ds = next(iter(train.values()))
    per_row = STEPS * BATCH * (
        int(np.prod(ds.x.shape[1:])) * ds.x.dtype.itemsize
        + ds.y.dtype.itemsize + np.dtype(np.float32).itemsize)
    stages = _named(events, "fl.stage")
    assert len(stages) == ROUNDS
    for ev in stages:
        assert ev[3]["clients"] == COHORT and ev[3]["bucket"] == BUCKET
        assert ev[3]["bytes"] == BUCKET * per_row
        (put,) = [p for p in _named(events, "fl.put") if _inside(p, ev)]
        (gather,) = [g for g in _named(events, "fl.gather")
                     if _inside(g, ev)]
        assert put[3]["bytes"] == ev[3]["bytes"]
        assert gather[2] <= put[1]
    assert deltas["staged_bytes"] == ROUNDS * BUCKET * per_row


def test_syncs_match_the_counter_and_round_stats(traced):
    events, deltas, _, _ = traced
    syncs = _named(events, "fl.sync")
    assert len(syncs) == deltas["host_syncs"]
    # nothing reads the loss vector on the device pipeline
    assert {s[3]["what"] for s in syncs} == {"eval", "merge_norm"}
    rounds = _named(events, "fl.round")
    for rnd in rounds:
        inside = [s for s in syncs if _inside(s, rnd)]
        assert rnd[3]["syncs"] == len(inside)
        staged = sum(s[3]["bytes"] for s in _named(events, "fl.stage")
                     if _inside(s, rnd))
        assert rnd[3]["staged_bytes"] == staged
        assert rnd[3]["selected"] == COHORT
    # every queue pop happens inside a barrier round
    assert sum(r[3]["events"] for r in rounds) == deltas["events"]
    assert sum(r[3]["compiles"] for r in rounds) == deltas["compiles"]
    # a fresh task: the first round compiles the cohort shape, the second
    # reuses it
    assert [r[3]["compiles"] for r in rounds] == [1, 0]
    assert [d[3]["new_shape"] for d in _named(events, "fl.dispatch")] \
        == [1, 0]
    # evaluation's reads are its own: each batch is one fl.sync inside
    # fl.eval, outside any round
    for ev in _named(events, "fl.eval"):
        inner = [s for s in syncs if _inside(s, ev)]
        assert inner and all(s[3]["what"] == "eval" for s in inner)
        assert not any(_inside(ev, r) for r in rounds)


@pytest.mark.parametrize("counter", SAME)
def test_profiler_changes_no_counter(traced, counter):
    _, deltas, plain, _ = traced
    assert deltas[counter] == plain[counter]


@pytest.mark.parametrize("gate,what", [("REPRO_OVERLAP_DISPATCH", "block"),
                                       ("REPRO_DEVICE_PIPELINE", "loss")])
def test_gated_paths_add_one_sync_per_round(tmp_path, monkeypatch, gate,
                                            what):
    """Overlap off blocks on each group dispatch; the pipeline off
    fetches each group's loss vector: one fl.sync per round either way."""
    train, test = _data()
    task = _task()
    base = _run(task, train, test)
    monkeypatch.setenv(gate, "0")
    with jax.profiler.trace(str(tmp_path)):
        gated = _run(task, train, test)
    assert gated["host_syncs"] - base["host_syncs"] == ROUNDS
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    whats = [dict(e.stats)["what"] for plane in pd.planes
             if plane.name == "/host:CPU" for line in plane.lines
             for e in line.events if e.name == "fl.sync"]
    assert whats.count(what) == ROUNDS
    assert len(whats) == gated["host_syncs"]


def _lowered():
    """The group-train program for a bucket of 4 clients with FedProx's
    proximal term (mu 0.01), lowered on a fresh executor (nothing
    cached)."""
    train, _ = _data()
    task = _task()
    ex = VectorizedExecutor(task)
    xs, ys, ms = ex._stage(list(train.values())[:BUCKET], [1, 2, 3, 4], 0)
    return ex._group_fn(0.01).lower(task.init_params(0), xs, ys, ms)


def test_group_program_carries_scopes_and_same_hlo(monkeypatch):
    scoped = _lowered()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _lowered()
    dbg = scoped.as_text(debug_info=True)
    plain_dbg = plain.as_text(debug_info=True)
    for scope in ("loss_grad", "proximal", "optimizer"):
        # the scan body's locations read loc("optimizer/add"(...)), ...
        assert f'"{scope}/' in dbg
        assert f'"{scope}/' not in plain_dbg
    # without locations the program is the same, as StableHLO and as HLO
    assert scoped.as_text() == plain.as_text()
    assert scoped.as_text(dialect="hlo") == plain.as_text(dialect="hlo")

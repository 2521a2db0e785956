"""The correctness check fails what it must, at a size a CPU test holds.

Each case drives a whole run of a cell (set-up, window, check) without
the look for a chip, on a population of 8 clients of 23 samples, a
cohort of 4, 5 rounds, and the FEMNIST CNN narrowed to an FC width of 64
(the LSTM to 16 hidden units), with the program's timed path broken
underneath.  The limits are the cell's own, from bench/limits/.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedbench import checks, harness
from fedbench.registry import Cell

WORKLOAD = "femnist-fedlesscan-s30"
TRAFFIC = dict(population=8, train_per_client=23, test_per_client=10,
               cohort=4, rounds_per_study=5)
# FEMNIST's faults show in its cohort's training loss, which needs more
# local steps than 23 samples give
SAMPLES = {"femnist-fedlesscan-s30": 100}
CONFIGS = {"femnist-fedlesscan-s30": {"fc_width": 64,
                                      "program.kwargs.fc_width": 64},
           "shakespeare-fedlesscan-s30": {"hidden": 16,
                                          "program.kwargs.hidden": 16}}
CONFIG = CONFIGS[WORKLOAD]


def run(seed=3, workload=WORKLOAD):
    return harness.run_cell(workload, seed, 0.1, False,
                            t0=time.perf_counter(), require_chip=False,
                            traffic_overrides=dict(
                                TRAFFIC, train_per_client=SAMPLES.get(
                                    workload, TRAFFIC["train_per_client"])),
                            config_overrides=CONFIGS[workload])


def _break_group_fn(monkeypatch, transform):
    """Wrap the executor's compiled group-train function: `transform`
    gets (fn, global params, xs, ys, masks) and returns its outputs."""
    from repro.fl.executor import VectorizedExecutor
    orig = VectorizedExecutor._group_fn

    def broken(self, mu):
        fn = orig(self, mu)
        return lambda gp, xs, ys, ms: transform(fn, gp, xs, ys, ms)
    monkeypatch.setattr(VectorizedExecutor, "_group_fn", broken)


def dropped(fn, gp, xs, ys, ms):
    """Local training that runs, reports its losses, and hands back the
    params it was sent."""
    params, losses = fn(gp, xs, ys, ms)
    return jax.tree_util.tree_map(
        lambda p, g: jnp.broadcast_to(g, p.shape), params, gp), losses


def half_batch(fn, gp, xs, ys, ms):
    """Half of every batch left out; the loss is the mean over the rest."""
    b = ms.shape[-1]
    return fn(gp, xs, ys, ms * (jnp.arange(b) < b // 2))


@pytest.mark.parametrize("workload", sorted(CONFIGS))
def test_sound_run_is_correct(workload):
    result = run(workload=workload)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 5


def frozen(monkeypatch):
    """Every local step returns the params it was given: the program's
    task is built with a learning rate of 0 (the reference keeps its)."""
    orig = harness.build_task

    def build(cfg):
        cfg = harness.merged(cfg, {"local.learning_rate": 0.0})
        return orig(cfg)
    monkeypatch.setattr(harness, "build_task", build)


# FEMNIST's half-batch fault moves its cohort's loss by 2e-4 to 0.03 at
# sizes a CPU test holds, below the limit set at the cell's size, where
# it reads 0.33 or more on the chip (PERF.md): it is shown there only
@pytest.mark.parametrize("fault, workload", [
    ("frozen", "femnist-fedlesscan-s30"),
    ("frozen", "shakespeare-fedlesscan-s30"),
    ("half_batch", "shakespeare-fedlesscan-s30")])
def test_broken_training_is_not_correct(monkeypatch, fault, workload):
    if fault == "frozen":
        frozen(monkeypatch)
    else:
        _break_group_fn(monkeypatch, half_batch)
    result = run(workload=workload)
    assert not result["correct"], result["checks"]


def test_dropped_update_is_not_correct(monkeypatch):
    """Shakespeare's update_gap catches it.  FEMNIST compares no
    client's update (PERF.md, Open questions), so there it passes."""
    _break_group_fn(monkeypatch, dropped)
    result = run(workload="shakespeare-fedlesscan-s30")
    assert not result["correct"], result["checks"]


def test_altered_merge_is_not_correct(monkeypatch):
    """Half of the updates left out of the merge, the mean over the rest."""
    from repro.core import merge as merge_mod
    orig = merge_mod.MergePipeline.merge

    def dropped(self, global_params, updates, coeffs, mix=1.0):
        keep = max(1, len(updates) // 2) if updates else 0
        c = np.asarray(coeffs, np.float64)[:keep]
        return orig(self, global_params, list(updates)[:keep],
                    c / c.sum() if keep else c, mix)
    monkeypatch.setattr(merge_mod.MergePipeline, "merge", dropped)
    assert not run()["correct"]


def test_merge_records_the_scenario_forbids_are_not_correct(monkeypatch):
    """Every round reports one more client as merged in time: one that
    was not invoked in it."""
    from repro.fl import controller as ctl
    orig = ctl.Controller.run_round

    def forged(self, global_params, round_number):
        params, stats = orig(self, global_params, round_number)
        idle = [c for c in self.pool.client_ids if c not in stats.selected]
        stats.successes = stats.successes + idle[:1]
        return params, stats
    monkeypatch.setattr(ctl.Controller, "run_round", forged)
    result = run(seed=4)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_scenario_draw_is_the_programs(seed):
    """The check's copy of the straggler draw names the clients the
    program makes slow or crash."""
    from fedbench import scenario
    from repro.fl.experiment import ScenarioConfig, make_straggler_profiles
    traffic = Cell(WORKLOAD).traffic
    ids = [f"client_{i}" for i in range(traffic["population"])]
    s = seed % 2 ** 31
    prog = make_straggler_profiles(ids, ScenarioConfig(
        straggler_fraction=traffic["straggler_fraction"],
        slow_share=traffic["slow_share"], seed=s))
    kinds = {c: (scenario.CRASH if p.crash else scenario.SLOW)
             for c, p in prog.items()}
    mine = scenario.profiles(ids, traffic, s)
    assert {c: k for c, k in mine.items() if k != scenario.HEALTHY} == kinds


def test_altered_accuracy_is_not_correct(monkeypatch):
    """An evaluation's answer altered where it is produced."""
    from repro.fl.tasks import ClassificationTask
    orig = ClassificationTask.evaluate

    def altered(self, params, ds, batch_size=256):
        acc, loss = orig(self, params, ds, batch_size)
        return min(1.0, acc + 0.1), loss
    monkeypatch.setattr(ClassificationTask, "evaluate", altered)
    assert not run()["correct"]


def test_control_is_not_correct():
    """The bfloat16 reference put in the program's place fails a limit."""
    cell = Cell(WORKLOAD)
    bench = harness.Bench(cell, TRAFFIC, CONFIG)
    bench.prepare(5)
    bench.study()
    nums = bench.checker().numbers(bench.cap, impl="control")
    assert any(nums[k] > cell.limits[k] for k in checks.NUMBERS
               if cell.limits[k] is not None), nums

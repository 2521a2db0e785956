"""The correctness check fails what it must, at a size a CPU test holds.

Each case drives a whole run of a cell (set-up, window, check) without
the look for a chip, on a population of 8 clients of 23 samples, a
cohort of 4, 5 rounds, and the FEMNIST CNN narrowed to an FC width of 64
(the LSTM to 16 hidden units), with the program's timed path broken
underneath.  The limits are the cell's own, from bench/limits/.
"""
import os
import time
from pathlib import Path

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


# the check as it fits a chip too small for blocks of 8 clients and for
# device-held captures (harness.check_sizes): the reference trains one
# client at a time, the probes keep host copies
def small_chip(monkeypatch, block=1, host=True):
    monkeypatch.setattr(harness, "check_sizes",
                        lambda cfg, traffic, device: (block, host))


@pytest.mark.parametrize("workload", sorted(CONFIGS))
def test_sound_run_is_correct_on_a_small_chip(monkeypatch, workload):
    small_chip(monkeypatch)
    result = run(workload=workload)
    assert result["correct"], result["checks"]


def test_probes_on_the_host_read_the_same_numbers(monkeypatch):
    """Host copies hold the same values as the device arrays they copy, so
    the six numbers do not move."""
    device = run()
    small_chip(monkeypatch, block=8)
    host = run()
    assert host["checks"] == device["checks"]


@pytest.mark.parametrize("fault, workload", [
    ("frozen", "femnist-fedlesscan-s30"),
    ("frozen", "shakespeare-fedlesscan-s30"),
    ("half_batch", "shakespeare-fedlesscan-s30")])
def test_broken_training_is_not_correct_on_a_small_chip(
        monkeypatch, fault, workload):
    small_chip(monkeypatch)
    if fault == "frozen":
        frozen(monkeypatch)
    else:
        _break_group_fn(monkeypatch, half_batch)
    result = run(workload=workload)
    assert not result["correct"], result["checks"]


def test_control_is_not_correct_on_a_small_chip(monkeypatch):
    from fedbench.probes import HostRows
    small_chip(monkeypatch)
    cell = Cell(WORKLOAD)
    bench = harness.Bench(cell, TRAFFIC, CONFIG)
    assert bench.block == 1 and bench.cap.host
    bench.prepare(5)
    bench.study()
    assert all(isinstance(b, HostRows) for rows in bench.cap.rows.values()
               for b, _ in rows.values())
    nums = bench.checker().numbers(bench.cap, impl="control")
    assert any(nums[k] > cell.limits[k] for k in checks.NUMBERS
               if cell.limits[k] is not None), nums


GB = 10 ** 9


@pytest.mark.parametrize("workload, config, traffic, chip, sizes", [
    # the committed cells on a 16 GB chip: today's check
    ("femnist-fedlesscan-s30", {}, {}, 16 * GB, (8, False)),
    ("shakespeare-fedlesscan-s30", {}, {}, 16 * GB, (8, False)),
    # a device that reports no memory (the CPU)
    ("femnist-fedlesscan-s30", {}, {}, None, (8, False)),
    # FEMNIST's cohort of 128 over 4 chips: 4 P (2 x 32 + 3) = 1.77 GB
    # of captures per chip, under an eighth of 16 GB
    ("femnist-fedlesscan-s30", {}, {"cohort": 128, "executor_devices": 4},
     16 * GB, (8, False)),
    # a client of 528 M parameters under Adam: 8.45 GB of training state,
    # over a quarter of the chip alone; its captures pass an eighth
    ("femnist-fedlesscan-s30", {"params": 528_000_000},
     {"cohort": 4, "executor_devices": 4}, 16 * GB, (1, True)),
    # 50 M parameters under Adam: 0.8 GB each, 5 fit in 4 GB
    ("femnist-fedlesscan-s30", {"params": 50_000_000}, {}, 16 * GB,
     (5, True)),
])
def test_check_sizes(monkeypatch, workload, config, traffic, chip, sizes):
    monkeypatch.setattr(harness, "chip_bytes", lambda device: chip)
    cell = Cell(workload)
    assert harness.check_sizes(harness.merged(cell.config, config),
                               harness.merged(cell.traffic, traffic),
                               None) == sizes


def four_devices(*args):
    """Runs of four_devices.py in a process of its own -> (fault, host
    copies) -> its result line."""
    import json
    import subprocess
    import sys
    script = Path(__file__).with_name("four_devices.py")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run([sys.executable, str(script), *args], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    runs = [json.loads(line) for line in out.stdout.strip().splitlines()]
    assert {r["device_count"] for r in runs} == {4}
    return {(r["fault"], r["host"]): r for r in runs}


def test_four_chip_cell_on_four_virtual_devices():
    """FEMNIST's cell on four CPU devices at this file's size, its cohort
    and merge sharded over them: a sound run is correct; the merge with
    the exchange between chips left out, and local steps that return
    their params, are not."""
    runs = four_devices("none", "no_exchange", "frozen")
    assert runs[("none", False)]["correct"], runs[("none", False)]["checks"]
    for fault in ("no_exchange", "frozen"):
        assert not runs[(fault, False)]["correct"], runs[(fault, False)]


def test_round_0_on_four_virtual_devices_with_host_copies():
    """The checked round is the first, trained from the initial params:
    the sound run is correct, and reads the same numbers where the
    probes keep host copies of each chip's rows."""
    runs = four_devices("--round", "0", "none", "--host", "none")
    sound = runs[("none", False)]
    assert sound["correct"], sound["checks"]
    assert runs[("none", True)]["checks"] == sound["checks"]

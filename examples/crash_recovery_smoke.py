"""Crash-recovery smoke test: SIGKILL a training run mid-round, resume
from its last checkpoint, and assert the recovered run reproduces an
uninterrupted same-seed run exactly.

Exercises the full-fidelity checkpoint path end-to-end across *process*
boundaries.  The parent never touches JAX — an accelerator belongs to
one process at a time — and runs each phase as a child, one after the
other:

    1. a clean same-seed reference run → metrics + trace;
    2. the same experiment with checkpointing on, SIGKILLed once a
       checkpoint pair lands on disk;
    3. a resume from the last checkpoint → metrics + trace.

The parent then compares the final metrics (and the replayed rounds)
with the clean reference.  CI runs this as the crash-recovery job and
uploads the two JSONL traces as artifacts when the comparison fails.

    PYTHONPATH=src python examples/crash_recovery_smoke.py
    PYTHONPATH=src python examples/crash_recovery_smoke.py --phase resume out/
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

N_ROUNDS = 12
CHECKPOINT_EVERY = 2
PHASES = ("reference", "checkpointing", "resume")
ROUND_ATTRS = ("selected", "successes", "late", "crashed", "duration_s",
               "cost")


def build_experiment():
    from repro.data import label_sorted_shards, make_image_classification
    from repro.data.synthetic import ArrayDataset
    from repro.fl.tasks import ClassificationTask, TaskConfig
    from repro.models.small import make_cnn

    full = make_image_classification(320, image_size=14, n_classes=3, seed=0)
    train = ArrayDataset(full.x[:240], full.y[:240])
    test = ArrayDataset(full.x[240:], full.y[240:])
    parts = label_sorted_shards(train, 6, 2, seed=0)
    test_parts = label_sorted_shards(test, 6, 2, seed=0)
    task = ClassificationTask(
        make_cnn(14, 1, 3, 8),
        TaskConfig(epochs=1, batch_size=32, per_sample_time_s=0.05))
    return task, parts, test_parts


def config(**kw):
    from repro.fl.experiment import ExperimentConfig, ScenarioConfig
    return ExperimentConfig(
        strategy="fedlesscan", n_rounds=N_ROUNDS, clients_per_round=4,
        eval_every=0, seed=0,
        scenario=ScenarioConfig(straggler_fraction=0.3, slow_factor=6.0,
                                round_timeout_s=60.0, seed=0), **kw)


def run_phase(phase: str, workdir: Path) -> None:
    """Child body: one phase of the story.  The reference and resume
    phases write their metrics to ``<workdir>/<phase>.json``."""
    from repro.fl.experiment import run_experiment

    task, parts, test_parts = build_experiment()
    if phase == "reference":
        cfg = config(trace_path=str(workdir / "clean_trace.jsonl"))
    elif phase == "checkpointing":
        # runs until SIGKILLed; reaching the end just means the kill
        # raced past it, and the resume still starts from the last pair
        cfg = config(checkpoint_dir=str(workdir / "ck"),
                     checkpoint_every=CHECKPOINT_EVERY)
    else:
        cfg = config(resume_from=str(workdir / "ck"),
                     trace_path=str(workdir / "resumed_trace.jsonl"))
    res = run_experiment(task, parts, test_parts, cfg)
    if phase == "checkpointing":
        return
    summary = {"final_accuracy": res.final_accuracy,
               "rounds": [{"round_number": r.round_number,
                           **{a: getattr(r, a) for a in ROUND_ATTRS}}
                          for r in res.rounds]}
    (workdir / f"{phase}.json").write_text(json.dumps(summary))


def spawn(phase: str, workdir: Path) -> subprocess.Popen:
    env = dict(os.environ)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = (src + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else src)
    return subprocess.Popen(
        [sys.executable, __file__, "--phase", phase, str(workdir)], env=env)


def run_to_end(phase: str, workdir: Path) -> dict:
    proc = spawn(phase, workdir)
    if proc.wait() != 0:
        raise RuntimeError(f"{phase} phase exited with {proc.returncode}")
    return json.loads((workdir / f"{phase}.json").read_text())


def wait_for_checkpoint(ckdir: Path, proc, timeout_s: float = 300.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        pairs = {p.stem for p in ckdir.glob("round_*.json")} \
            & {p.stem for p in ckdir.glob("round_*.npz")}
        if pairs:
            return
        if proc.poll() is not None:
            return                      # child finished before the kill
        time.sleep(0.2)
    raise RuntimeError(f"no checkpoint appeared in {ckdir} "
                       f"within {timeout_s}s")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workdir", default="results/crash_recovery")
    ap.add_argument("--phase", nargs=2, metavar=("PHASE", "WORKDIR"),
                    help=f"internal: run one phase {PHASES} as a child")
    args = ap.parse_args()

    if args.phase:
        run_phase(args.phase[0], Path(args.phase[1]))
        return 0

    workdir = Path(args.workdir)
    workdir.mkdir(parents=True, exist_ok=True)

    print("[1/3] clean same-seed reference run")
    clean = run_to_end("reference", workdir)

    print("[2/3] child run with checkpointing — SIGKILL mid-round")
    proc = spawn("checkpointing", workdir)
    wait_for_checkpoint(workdir / "ck", proc)
    if proc.poll() is None:
        proc.send_signal(signal.SIGKILL)
    proc.wait()
    print(f"    child exited with {proc.returncode} "
          f"(negative = killed by signal)")

    print("[3/3] resume from the last checkpoint and compare")
    resumed = run_to_end("resume", workdir)

    failures = []
    if resumed["final_accuracy"] != clean["final_accuracy"]:
        failures.append(f"final_accuracy {resumed['final_accuracy']!r} != "
                        f"clean {clean['final_accuracy']!r}")
    clean_by_round = {r["round_number"]: r for r in clean["rounds"]}
    for r in resumed["rounds"]:
        want = clean_by_round.get(r["round_number"])
        if want is None:
            failures.append(f"resumed produced unknown round "
                            f"{r['round_number']}")
            continue
        for attr in ROUND_ATTRS:
            if r[attr] != want[attr]:
                failures.append(
                    f"round {r['round_number']} {attr}: "
                    f"{r[attr]!r} != {want[attr]!r}")
    report = {
        "clean_final_accuracy": clean["final_accuracy"],
        "resumed_final_accuracy": resumed["final_accuracy"],
        "resumed_rounds": [r["round_number"] for r in resumed["rounds"]],
        "failures": failures,
    }
    (workdir / "report.json").write_text(json.dumps(report, indent=2))
    if failures:
        print("FAIL: recovered run diverged from the clean run:")
        for f in failures:
            print("  -", f)
        return 1
    print(f"OK: resumed rounds {report['resumed_rounds']} replay the "
          f"clean run exactly (final acc {clean['final_accuracy']:.3f})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Device-resident round pipeline benchmark — handoff + end-to-end.

Two measurements, both pipeline-on vs pipeline-off
(``REPRO_DEVICE_PIPELINE``):

* **handoff cells** — the executor→merge handoff in isolation, cohort
  K ∈ {16, 64, 256} × {small-CNN-sized pytree, gemma3-1b-scale flat
  shard}.  The legacy path materializes one pytree per client from the
  stacked training output, then ``flat_update_matrix`` re-ravels and
  re-stacks them inside the merge (2·K·P extra device copies per
  round); the pipeline path flattens the stack once into a
  ``DeviceUpdateBatch`` and the merge gathers rows straight out of it
  with the update matrix donated to the fused server-update kernel.
  Both paths end in the same ``MergePipeline.merge`` (fedadam) and are
  timed to ``block_until_ready``.

* **end-to-end cell** (small CNN only) — the full FedLesScan experiment
  with the vectorized driver, identical seed/task/stragglers, toggling
  only the env gate; records wall-clock per round and the host-transfer
  byte counters from ``core.device_batch.transfer_stats`` (dense path:
  pipeline materializes ~0 bytes vs the legacy 2·K·model-size churn).

The gemma-scale cells run on a ``GEMMA_P``-element shard (the per-
element handoff cost is flat in P, same slab convention as
``bench_compression``); they are tier-2: run with ``--model gemma``
(CI runs ``--model small`` only).

Results land in ``results/BENCH_round_pipeline.json``.  The parent
process never touches JAX (an accelerator belongs to one process at a
time): every measurement runs in a worker subprocess, one at a time.

Run: ``PYTHONPATH=src python -m benchmarks.bench_round_pipeline``
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

RESULTS = Path(__file__).resolve().parent.parent / "results"
OUT = RESULTS / "BENCH_round_pipeline.json"

COHORTS = (16, 64, 256)
GEMMA_P = 1 << 22          # 4M-element shard of the 1B-param model
E2E_ROUNDS = 4
E2E_COHORT = 6
N_CLIENTS = 18

# leaf shapes mimicking the small CNN's pytree structure (P ≈ 71k)
SMALL_LEAVES = {"conv1": (3, 3, 1, 32), "conv2": (3, 3, 32, 32),
                "dense": (1568, 32), "head": (32, 5)}


def _time_best(fn, iters: int = 3) -> float:
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


# ----------------------------------------------------------------------
# handoff cells: stacked training output → (K, P) merge-ready matrix
# (→ merged params when include_merge)
# ----------------------------------------------------------------------
def _handoff_cell(k: int, leaves: dict, include_merge: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.flatten_util import ravel_pytree

    from repro.core.aggregation import (ClientUpdate, fedavg_coefficients,
                                        flat_update_matrix)
    from repro.core.device_batch import DeviceUpdateBatch
    from repro.core.merge import MergePipeline, ServerOptConfig
    from repro.fl.executor import VectorizedExecutor

    rng = np.random.default_rng(0)
    stacked = {name: jnp.asarray(
        rng.normal(size=(k,) + shape).astype(np.float32))
        for name, shape in leaves.items()}
    gp = jax.tree_util.tree_map(lambda l: l[0] * 0.0, stacked)
    p_total = sum(int(np.prod(s)) for s in leaves.values())
    cids = [f"c{i}" for i in range(k)]
    flatten = jax.jit(VectorizedExecutor._flatten_stacked)
    _, unravel = ravel_pytree(gp)

    def finish(updates):
        if include_merge:
            merger = MergePipeline(ServerOptConfig(name="fedadam", lr=0.1))
            out = merger.merge(gp, updates, fedavg_coefficients(updates))
            jax.block_until_ready(jax.tree_util.tree_leaves(out))
        else:
            # handoff only: stop at the merge-ready matrix — on CPU the
            # interpret-mode merge kernel would drown the copy traffic
            # this cell isolates (2·K·P legacy churn vs flatten+gather)
            mat, _ = flat_update_matrix(updates)
            jax.block_until_ready(mat)

    def legacy_round():
        finish([
            ClientUpdate(cid,
                         jax.tree_util.tree_map(lambda l, i=i: l[i], stacked),
                         10, 0)
            for i, cid in enumerate(cids)])

    def pipeline_round():
        batch = DeviceUpdateBatch(flatten(stacked), cids, unravel)
        finish([ClientUpdate(cid, num_samples=10, round_number=0,
                             batch=batch, batch_row=i)
                for i, cid in enumerate(cids)])

    legacy_round(); pipeline_round()          # compile outside the timing
    # the gemma-scale legacy cells run minutes per call at K=256 — one
    # post-warmup measurement there, best-of-3 at small scale
    iters = 3 if include_merge else 1
    legacy_s = _time_best(legacy_round, iters)
    pipeline_s = _time_best(pipeline_round, iters)
    return {"cohort": k, "param_count": p_total,
            "includes_merge": include_merge,
            "legacy_s": round(legacy_s, 5),
            "pipeline_s": round(pipeline_s, 5),
            "speedup": round(legacy_s / pipeline_s, 3)}


def _handoff_grid(model: str) -> list:
    # small cells run handoff + fused merge end to end; the gemma-scale
    # cells time the handoff alone (see _handoff_cell)
    leaves = (SMALL_LEAVES if model == "small"
              else {"shard": (GEMMA_P,)})
    cells = []
    for k in COHORTS:
        cell = _handoff_cell(k, leaves, include_merge=(model == "small"))
        cells.append(cell)
        print(f"{model}/handoff K={k:4d} P={cell['param_count']:9d} "
              f"legacy={cell['legacy_s']:.4f}s "
              f"pipeline={cell['pipeline_s']:.4f}s "
              f"-> {cell['speedup']:.2f}x", flush=True)
    return cells


def _run_worker(*argv, env=None):
    """Run one measurement in a fresh worker process; echo its progress
    lines and return the JSON record on its last line."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_round_pipeline", *argv],
        capture_output=True, text=True, env=env or dict(os.environ),
        check=True)
    lines = proc.stdout.strip().splitlines()
    for line in lines[:-1]:
        print(line, flush=True)
    return json.loads(lines[-1])


# ----------------------------------------------------------------------
# end-to-end small-CNN experiment, env gate toggled; each gate runs in
# its own subprocess so neither inherits the other's in-process JIT
# cache (compile costs would otherwise all land on whichever runs first)
# ----------------------------------------------------------------------
def _e2e_worker(rounds: int, seed: int) -> None:
    from repro.core.device_batch import (reset_transfer_stats,
                                         transfer_stats)
    from repro.data import label_sorted_shards, make_image_classification
    from repro.data.synthetic import ArrayDataset
    from repro.fl.experiment import (ExperimentConfig, ScenarioConfig,
                                     run_experiment)
    from repro.fl.tasks import ClassificationTask, TaskConfig
    from repro.models.small import make_cnn

    full = make_image_classification(1000, image_size=14, n_classes=5,
                                     seed=seed)
    train = ArrayDataset(full.x[:850], full.y[:850])
    test = ArrayDataset(full.x[850:], full.y[850:])
    parts = label_sorted_shards(train, N_CLIENTS, 2, seed=seed)
    test_parts = label_sorted_shards(test, N_CLIENTS, 2, seed=seed)
    task = ClassificationTask(
        make_cnn(14, 1, 5, 32, "bench_pipeline_cnn"),
        TaskConfig(epochs=1, batch_size=32, per_sample_time_s=0.05))
    import jax
    P = sum(int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(task.init_params(seed)))

    cfg = ExperimentConfig(
        strategy="fedlesscan", n_rounds=rounds,
        clients_per_round=E2E_COHORT, eval_every=0, seed=seed,
        vectorized=True, executor_warmup=True,
        scenario=ScenarioConfig(straggler_fraction=0.3,
                                round_timeout_s=30.0, seed=seed))
    run_experiment(task, parts, test_parts, cfg)   # warm every dispatch
    reset_transfer_stats()
    t0 = time.perf_counter()
    res = run_experiment(task, parts, test_parts, cfg)
    wall = time.perf_counter() - t0
    stats = transfer_stats()
    print(json.dumps({
        "param_count": P,
        "wall_s": round(wall, 3),
        "round_s": round(wall / rounds, 4),
        "materialize_bytes": stats["materialize_bytes"],
        "materialize_rows": stats["materialize_rows"],
        "loss_syncs": stats["loss_syncs"],
        "accuracy": res.final_accuracy,
    }))


def _e2e_cell(rounds: int, seed: int) -> dict:
    out = {"rounds": rounds, "cohort": E2E_COHORT}
    for label, gate in (("pipeline", "1"), ("legacy", "0")):
        env = dict(os.environ)
        env["REPRO_DEVICE_PIPELINE"] = gate
        rec = _run_worker("--e2e-worker", str(rounds), str(seed), env=env)
        out[label] = rec
        print(f"e2e/{label:8s} wall={rec['wall_s']:.2f}s "
              f"materialized={rec['materialize_bytes']} bytes "
              f"loss_syncs={rec['loss_syncs']}")
    P = out["pipeline"]["param_count"]
    out["round_speedup"] = round(
        out["legacy"]["wall_s"] / out["pipeline"]["wall_s"], 3)
    # the dense-path transfer claim: pipeline materializes ≤ 1 model of
    # bytes per round vs the legacy 2·K·P·4 analytic churn
    out["model_bytes"] = P * 4
    out["legacy_transfer_bytes_analytic"] = 2 * E2E_COHORT * P * 4 * rounds
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=E2E_ROUNDS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model", choices=("small", "gemma", "both"),
                    default="small")
    ap.add_argument("--e2e-worker", nargs=2, type=int,
                    metavar=("ROUNDS", "SEED"), help=argparse.SUPPRESS)
    ap.add_argument("--handoff-worker", choices=("small", "gemma"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.e2e_worker:
        _e2e_worker(*args.e2e_worker)
        return
    if args.handoff_worker:
        print(json.dumps(_handoff_grid(args.handoff_worker)))
        return

    grid: dict = {"cohorts": list(COHORTS)}
    if args.model in ("small", "both"):
        grid["small_cnn"] = {"handoff": _run_worker("--handoff-worker",
                                                    "small"),
                             "e2e": _e2e_cell(args.rounds, args.seed)}
    if args.model in ("gemma", "both"):
        grid["gemma3-1b_shard"] = {"shard_p": GEMMA_P,
                                   "handoff": _run_worker(
                                       "--handoff-worker", "gemma")}

    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(grid, indent=1))
    print(f"\nwrote {OUT}")


if __name__ == "__main__":
    main()

"""Update-compression benchmark — 4 schemes × 2 model scales.

Every scheme cell measures the three costs the compression stage trades
against each other:

* **bytes/round** — encoded wire size of one cohort's updates (for the
  small CNN, read back from the experiment's egress records; dense is
  the analytic ``P × 4`` fp32 payload);
* **encode/decode wall-time** — kernel-level micro-bench of the Pallas
  encode/decode pair on a flat parameter-sized vector;
* **merge wall-time vs device count** — one ``fed_agg_apply`` server
  update timed single-device and under the mesh-sharded ``shard_map``
  path (subprocess workers with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``, so each mesh
  size sees a fresh jax runtime).

The small-CNN cells additionally run the full FedLesScan experiment per
scheme (same seed/task/straggler profile, only the compressor varies) so
the JSON records the accuracy/cost impact next to the byte savings.

The gemma3-1b cells time encode/decode shard-wise (a real compressor
operates per-tensor) over ``--gemma-shards`` measured shards and scale
to the architecture's analytic ``param_count``; the JSON records both
the measured and the extrapolated figures.  Gemma cells are tier-2: run
with ``--model gemma`` (CI runs ``--model small`` only).

Results land in ``results/BENCH_compression.json`` (experiment traces
under ``results/compression_traces/``).  The parent process never
touches JAX (an accelerator belongs to one process at a time): every
measurement runs in a worker subprocess, one at a time.

Run: ``PYTHONPATH=src python -m benchmarks.bench_compression``
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
OUT = RESULTS / "BENCH_compression.json"
TRACES = RESULTS / "compression_traces"

# (name, scheme, topk_ratio)
SCHEMES = (
    ("dense", "none", 0.0),
    ("topk@1%", "topk", 0.01),
    ("topk@0.1%", "topk", 0.001),
    ("int8", "int8", 0.0),
)

N_CLIENTS = 18
N_ROUNDS = 6
COHORT = 6
CHUNK = 256
MESH_SIZES = (1, 2)
# sharded-merge slab cap: interpret-mode Pallas over the full 1B gemma
# vector is pointless on CPU; the per-element merge cost is flat in P
GEMMA_MERGE_P = 1 << 22
GEMMA_SHARD = 1 << 22


def _time_best(fn, iters: int = 3) -> float:
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


# ----------------------------------------------------------------------
# kernel-level encode/decode micro-bench on one flat P-vector
# ----------------------------------------------------------------------
def _bench_codec(x: np.ndarray, scheme: str, topk_ratio: float) -> dict:
    import jax
    from repro.kernels import ops

    P = int(x.size)
    xs = jax.numpy.asarray(x)
    if scheme == "none":
        return {"payload_bytes": P * 4, "encode_s": 0.0, "decode_s": 0.0}
    if scheme == "topk":
        k = max(1, min(P, int(round(P * topk_ratio))))

        def enc():
            idx, vals, _ = ops.topk_encode(xs, k)
            jax.block_until_ready(vals)
            return idx, vals

        idx, vals = enc()
        dec = lambda: jax.block_until_ready(ops.topk_decode(idx, vals, P))
        return {"payload_bytes": k * 8, "encode_s": _time_best(enc),
                "decode_s": _time_best(dec)}
    # int8
    n_chunks = -(-P // CHUNK)

    def enc():
        q, scale = ops.int8_encode(xs, chunk=CHUNK)
        jax.block_until_ready(q)
        return q, scale

    q, scale = enc()
    dec = lambda: jax.block_until_ready(ops.int8_decode(q, scale, P))
    return {"payload_bytes": P + n_chunks * 4, "encode_s": _time_best(enc),
            "decode_s": _time_best(dec)}


# ----------------------------------------------------------------------
# merge wall-time vs mesh size (subprocess per device count: the host
# device count is fixed at first jax init, so each N needs its own
# process with XLA_FLAGS set before import)
# ----------------------------------------------------------------------
def _merge_worker(k: int, p: int) -> None:
    import jax
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh

    devices = len(jax.devices())
    rng = np.random.default_rng(0)
    upd = jax.numpy.asarray(rng.normal(size=(k, p)).astype(np.float32))
    coeffs = jax.numpy.asarray(np.full(k, 1.0 / k, dtype=np.float32))
    params = jax.numpy.asarray(rng.normal(size=p).astype(np.float32))
    m = jax.numpy.zeros(p, np.float32)
    v = jax.numpy.zeros(p, np.float32)

    if devices > 1:
        mesh = make_host_mesh(data=devices)
        run = lambda: ops.fed_agg_apply_sharded(
            upd, coeffs, params, m, v, 0.1, 1.0, 0.9, 0.99, 1e-3,
            opt="fedadam", mesh=mesh)
    else:
        run = lambda: ops.fed_agg_apply(
            upd, coeffs, params, m, v, 0.1, 1.0, 0.9, 0.99, 1e-3,
            opt="fedadam")

    jax.block_until_ready(run())          # compile outside the timing
    wall = _time_best(lambda: jax.block_until_ready(run()))
    print(json.dumps({"devices": devices, "wall_s": wall}))


def _run_worker(*argv, env=None):
    """Run one measurement in a fresh worker process; echo its progress
    lines and return the JSON record on its last line."""
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_compression", *argv],
        capture_output=True, text=True, env=env or dict(os.environ),
        check=True)
    lines = proc.stdout.strip().splitlines()
    for line in lines[:-1]:
        print(line, flush=True)
    return json.loads(lines[-1])


def _bench_merge(k: int, p: int) -> dict:
    out = {}
    for n in MESH_SIZES:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + f" --xla_force_host_platform_device_count={n}")
        rec = _run_worker("--merge-worker", str(k), str(p), env=env)
        out[str(n)] = rec["wall_s"]
        print(f"  merge K={k} P={p} devices={n}: {rec['wall_s']:.4f}s")
    return out


# ----------------------------------------------------------------------
# small-CNN cells: full experiment per scheme + codec micro-bench
# ----------------------------------------------------------------------
def _small_cells(rounds: int, seed: int, trace_dir: Path) -> dict:
    import jax
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
        make_cnn(14, 1, 5, 32, "bench_compress_cnn"),
        TaskConfig(epochs=1, batch_size=32, per_sample_time_s=0.05))
    params = task.init_params(seed)
    flat = np.concatenate([np.ravel(np.asarray(l))
                           for l in jax.tree_util.tree_leaves(params)])
    P = int(flat.size)

    cells = {}
    for name, scheme, ratio in SCHEMES:
        trace = trace_dir / f"small_{scheme}_{ratio}.jsonl"
        cfg = ExperimentConfig(
            strategy="fedlesscan", n_rounds=rounds,
            clients_per_round=COHORT, eval_every=0, seed=seed,
            compress_scheme=scheme, compress_topk_ratio=ratio,
            compress_chunk=CHUNK, trace_path=str(trace),
            scenario=ScenarioConfig(straggler_fraction=0.3,
                                    round_timeout_s=30.0, seed=seed))
        t0 = time.perf_counter()
        res = run_experiment(task, parts, test_parts, cfg)
        wall_s = time.perf_counter() - t0
        recs = [json.loads(line) for line in trace.open()]
        payload = [r["payload_bytes"] for r in recs
                   if r["type"] == "aggregation" and "payload_bytes" in r]
        bytes_per_round = (float(np.mean(payload)) if payload
                           else COHORT * P * 4.0)
        codec = _bench_codec(flat.astype(np.float32), scheme, ratio)
        cells[name] = {
            "scheme": scheme, "topk_ratio": ratio, "param_count": P,
            "bytes_per_round": bytes_per_round,
            "dense_bytes_per_round": COHORT * P * 4.0,
            "compression_ratio": round(COHORT * P * 4.0 / bytes_per_round,
                                       3),
            "encode_s": round(codec["encode_s"], 5),
            "decode_s": round(codec["decode_s"], 5),
            "accuracy": res.final_accuracy,
            "cost_usd": res.total_cost,
            "eur": res.mean_eur,
            "wall_s": round(wall_s, 3),
        }
        print(f"small/{name:10s} bytes/round={bytes_per_round:12.0f} "
              f"ratio={cells[name]['compression_ratio']:7.1f}x "
              f"acc={res.final_accuracy:.3f}")
    return {"cells": cells, "param_count": P}


# ----------------------------------------------------------------------
# gemma3-1b cells: shard-wise codec timing scaled to the full model
# ----------------------------------------------------------------------
def _gemma_cells(seed: int, shards: int) -> dict:
    from repro.configs.registry import get_config
    from repro.models.config import param_count

    P_total = int(param_count(get_config("gemma3-1b")))
    n_shards_total = -(-P_total // GEMMA_SHARD)
    shards = min(shards, n_shards_total)
    rng = np.random.default_rng(seed)

    cells = {}
    for name, scheme, ratio in SCHEMES:
        enc_s = dec_s = 0.0
        payload = 0
        for _ in range(shards):
            x = rng.normal(size=GEMMA_SHARD).astype(np.float32)
            codec = _bench_codec(x, scheme, ratio)
            enc_s += codec["encode_s"]
            dec_s += codec["decode_s"]
            payload += codec["payload_bytes"]
        scale = n_shards_total / shards
        cells[name] = {
            "scheme": scheme, "topk_ratio": ratio,
            "param_count": P_total,
            "measured_shards": shards, "total_shards": n_shards_total,
            "bytes_per_round": payload * scale * COHORT,
            "dense_bytes_per_round": float(P_total) * 4.0 * COHORT,
            "compression_ratio": round(
                P_total * 4.0 / (payload * scale), 3),
            "encode_s_extrapolated": round(enc_s * scale, 3),
            "decode_s_extrapolated": round(dec_s * scale, 3),
        }
        print(f"gemma/{name:10s} ratio="
              f"{cells[name]['compression_ratio']:7.1f}x "
              f"encode~{cells[name]['encode_s_extrapolated']:.1f}s")
    print(f"  (gemma merge slab capped at P={GEMMA_MERGE_P}; "
          f"codec measured on {shards}/{n_shards_total} shards)")
    return {"cells": cells, "merge_p": GEMMA_MERGE_P}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=N_ROUNDS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model", choices=("small", "gemma", "both"),
                    default="small")
    ap.add_argument("--gemma-shards", type=int, default=4)
    ap.add_argument("--merge-worker", nargs=2, type=int, metavar=("K", "P"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--cells-worker", choices=("small", "gemma"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.merge_worker:
        _merge_worker(*args.merge_worker)
        return
    if args.cells_worker == "small":
        TRACES.mkdir(parents=True, exist_ok=True)
        print(json.dumps(_small_cells(args.rounds, args.seed, TRACES)))
        return
    if args.cells_worker == "gemma":
        print(json.dumps(_gemma_cells(args.seed, args.gemma_shards)))
        return

    grid: dict = {"mesh_sizes": list(MESH_SIZES)}
    if args.model in ("small", "both"):
        small = _run_worker("--cells-worker", "small",
                            "--rounds", str(args.rounds),
                            "--seed", str(args.seed))
        small["merge_wall_s"] = _bench_merge(COHORT, small["param_count"])
        grid["small_cnn"] = small
    if args.model in ("gemma", "both"):
        gemma = _run_worker("--cells-worker", "gemma",
                            "--seed", str(args.seed),
                            "--gemma-shards", str(args.gemma_shards))
        gemma["merge_wall_s"] = _bench_merge(COHORT, GEMMA_MERGE_P)
        grid["gemma3-1b"] = gemma

    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(grid, indent=1))
    print(f"\nwrote {OUT}")


if __name__ == "__main__":
    main()

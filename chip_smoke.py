#!/usr/bin/env python3
"""Smoke run of the serverless FL round on a TPU, through `run_experiment`.

The paper's FEMNIST client model at published widths (femnist_cnn:
28x28x1, conv 32/64, FC 2048, 62 classes, P = 6,603,710) on synthetic
FEMNIST-shaped data made from ``--seed`` (64 clients, ~204 samples
each), with the Table I local hyperparameters (5 epochs, batch 10,
Adam 1e-3).  FedLesScan (semi-async), 16 clients per round, 30 %
stragglers, evaluation every round, executor warm-up on.  Three short
runs, each a few rounds:

  (a) the default identity merge        -> Pallas ``fed_agg``
  (b) ``server_opt="fedyogi"``          -> Pallas ``fed_agg_apply``
  (c) ``compress_scheme="topk"``        -> Pallas ``topk_encode``

Checks (any failure exits non-zero): the vectorized executor ran every
round, its compile count stays flat after the first round, each kernel's
compiled text holds ``tpu_custom_call`` (Mosaic, not the interpreter),
one merge matches the ``kernels/ref.py`` oracle on the same (K, P)
matrix at fp32 tolerance, and one client's vectorized update matches
``task.local_train`` for that client.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # 4-device executor + merge vs 1 device

With ``--chips 4`` only the multi-device comparison runs: the same
experiment with ``executor_devices=4`` and ``merge_devices=4`` against
the one-device run, final params within 1e-5.

Everything runs in this one process (the chip belongs to one process).
The numbers printed are a smoke run's, not benchmark numbers.  The last
line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_CLIENTS = 64
COHORT = 16


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"smoke: [{time.perf_counter() - _T0:7.1f} s] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    log(f"ok    {what}")


class RoundLog:
    """Records the executor's compile count after each round's cohort
    dispatch (one `run_clients` call per barrier round)."""

    def __init__(self, executor):
        self.executor = executor
        self.counts = []
        inner = executor.run_clients

        def run_clients(*args, **kwargs):
            out = inner(*args, **kwargs)
            self.counts.append(executor.compile_count)
            return out

        executor.run_clients = run_clients

    def take(self):
        counts, self.counts = self.counts, []
        return counts


def experiment_config(args, **kw):
    from repro.fl.experiment import ExperimentConfig, ScenarioConfig
    return ExperimentConfig(
        strategy="fedlesscan", n_rounds=args.rounds,
        clients_per_round=COHORT, eval_every=1, seed=args.seed,
        executor_warmup=True,
        scenario=ScenarioConfig(straggler_fraction=0.3, seed=args.seed),
        **kw)


def flat(tree):
    from jax.flatten_util import ravel_pytree
    return ravel_pytree(tree)[0]


def evaluate(task, params, test_parts):
    """(accuracy, mean loss) of `params` on every client's test split."""
    import numpy as np
    from repro.data.synthetic import ArrayDataset
    parts = [test_parts[c] for c in sorted(test_parts)]
    ds = ArrayDataset(np.concatenate([p.x for p in parts]),
                      np.concatenate([p.y for p in parts]))
    return task.evaluate(params, ds)


def run(label, task, parts, test_parts, cfg, rounds_log):
    import jax
    from repro.fl.experiment import run_experiment

    t0 = time.perf_counter()
    res = run_experiment(task, parts, test_parts, cfg)
    jax.block_until_ready(res.final_params)
    wall = time.perf_counter() - t0
    counts = rounds_log.take()
    acc, loss = evaluate(task, res.final_params, test_parts)
    leaves = jax.tree_util.tree_leaves(res.final_params)
    finite = all(bool(jax.numpy.isfinite(l).all()) for l in leaves)
    log(f"run {label}: wall {wall:.3f} s for {cfg.n_rounds} rounds, "
        f"executor compile_count per round {counts}, "
        f"sampled accuracy {res.final_accuracy:.4f}, "
        f"test accuracy {acc:.4f}, test loss {loss:.4f}, "
        f"mean EUR {res.mean_eur:.3f}")
    check(finite, f"run {label}: final params are finite")
    check(len(counts) == cfg.n_rounds,
          f"run {label}: the vectorized executor trained every round "
          f"({len(counts)}/{cfg.n_rounds})")
    check(all(c == counts[0] for c in counts[1:]),
          f"run {label}: compile_count flat after the first round")
    return res, wall


def compiled_text(fn, *shapes):
    import jax
    return jax.jit(fn).lower(*shapes).compile().as_text()


def one_chip(args, task, parts, test_parts, rounds_log):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import kernels
    from repro.kernels import ref

    # ---- (a) identity merge: fed_agg -----------------------------------
    cfg_a = experiment_config(args)
    res_a, wall_cold = run("a/cold (sgd identity -> fed_agg)", task, parts,
                           test_parts, cfg_a, rounds_log)
    res_a2, wall_warm = run("a/warm (same run, compiled)", task, parts,
                            test_parts, cfg_a, rounds_log)
    steady_round = wall_warm / cfg_a.n_rounds
    log(f"compile and warm-up time (cold run minus warm run): "
        f"{wall_cold - wall_warm:.3f} s")
    log(f"steady round wall time: {steady_round:.4f} s")
    same = all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(
        jax.tree_util.tree_leaves(res_a.final_params),
        jax.tree_util.tree_leaves(res_a2.final_params)))
    check(same, "same-seed reruns give bitwise-equal final params")

    # ---- (b) fedyogi: fed_agg_apply ------------------------------------
    run("b (fedyogi -> fed_agg_apply)", task, parts, test_parts,
        experiment_config(args, server_opt="fedyogi", server_opt_lr=0.01),
        rounds_log)

    # ---- (c) top-k compression: topk_encode ----------------------------
    run("c (topk@1% -> topk_encode)", task, parts, test_parts,
        experiment_config(args, compress_scheme="topk"), rounds_log)

    # ---- kernels lowered by Mosaic ---------------------------------------
    gp = task.init_params(args.seed)
    P = int(flat(gp).shape[0])
    k = max(1, round(P * 0.01))
    mat = jax.ShapeDtypeStruct((COHORT, P), jnp.float32)
    vec_k = jax.ShapeDtypeStruct((COHORT,), jnp.float32)
    vec_p = jax.ShapeDtypeStruct((P,), jnp.float32)
    texts = {
        "fed_agg": compiled_text(kernels.fed_agg, mat, vec_k),
        "fed_agg_apply[fedyogi]": compiled_text(
            lambda u, c, g, m, v: kernels.fed_agg_apply(
                u, c, g, m, v, 0.01, 1.0, 0.9, 0.99, 1e-3, opt="fedyogi"),
            mat, vec_k, vec_p, vec_p, vec_p),
        "topk_encode": compiled_text(
            lambda x: kernels.topk_encode(x, k), vec_p),
    }
    for name, text in texts.items():
        check("tpu_custom_call" in text,
              f"{name} at (K={COHORT}, P={P}) lowers to tpu_custom_call")

    # ---- merge vs the kernels/ref.py oracle on one real (K, P) matrix ----
    ex = rounds_log.executor
    cids = sorted(parts)[:COHORT]
    datasets = [parts[c] for c in cids]
    seeds = [args.seed * 1000 + i for i in range(COHORT)]
    batch = ex.run_group_batch(cids, datasets, gp, 0.0, seeds)
    upd = batch.mat[:COHORT]
    sizes = np.array([len(d) for d in datasets], np.float64)
    coeffs = jnp.asarray(sizes / sizes.sum(), jnp.float32)
    flat_g = flat(gp).astype(jnp.float32)
    m0 = jnp.zeros_like(flat_g)
    hyper = (0.01, 1.0, 0.9, 0.99, 1e-3)
    got = kernels.fed_agg(upd, coeffs)
    got_apply = kernels.fed_agg_apply(upd, coeffs, flat_g, m0, m0, *hyper,
                                      opt="fedyogi")
    with jax.default_matmul_precision("highest"):
        want = ref.fed_agg_ref(upd, coeffs)
        want_apply = ref.fed_agg_apply_ref(upd, coeffs, flat_g, m0, m0,
                                           *hyper, opt="fedyogi")
    err = float(jnp.max(jnp.abs(got - want)))
    log(f"fed_agg vs fed_agg_ref: max abs err {err:.3e}")
    check(np.allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                      atol=1e-5),
          "fed_agg matches fed_agg_ref (rtol 1e-5, atol 1e-5)")
    for name, a, b in zip(("params", "m", "v", "norm"), got_apply,
                          want_apply):
        e = float(jnp.max(jnp.abs(a - b)))
        log(f"fed_agg_apply[fedyogi] vs ref, {name}: max abs err {e:.3e}")
        check(np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                          atol=1e-5),
              f"fed_agg_apply[fedyogi] {name} matches the oracle")

    # ---- one client's vectorized update vs task.local_train --------------
    # both paths at full fp32 matmul precision, so the comparison sees
    # batch order / masking / optimizer semantics, not bf16 pass rounding
    cid, ds, seed = cids[0], datasets[0], seeds[0]
    with jax.default_matmul_precision("highest"):
        vec_row = ex.run_group_batch([cid], [ds], gp, 0.0, [seed]).row(0)
        eager, _ = task.local_train(gp, ds, mu=0.0, seed=seed)
    eager = flat(eager)
    rel = float(jnp.linalg.norm(vec_row - eager)
                / jnp.linalg.norm(eager - flat_g))
    log(f"client {cid}: |vectorized - local_train| / |local_train - global| "
        f"= {rel:.3e}, max abs err "
        f"{float(jnp.max(jnp.abs(vec_row - eager))):.3e}")
    check(rel < 1e-2, "vectorized client update matches task.local_train "
          "(relative L2 of the update < 1e-2)")


def four_chips(args, task, parts, test_parts, rounds_log):
    import jax
    import numpy as np

    res_1, _ = run("1 device", task, parts, test_parts,
                   experiment_config(args), rounds_log)
    res_4, _ = run("4 devices (executor_devices=4, merge_devices=4)", task,
                   parts, test_parts,
                   experiment_config(args, executor_devices=4,
                                     merge_devices=4), rounds_log)
    same_rounds = all(
        (r1.selected, r1.successes, r1.late, r1.crashed)
        == (r4.selected, r4.successes, r4.late, r4.crashed)
        for r1, r4 in zip(res_1.rounds, res_4.rounds))
    check(same_rounds, "4-device run picks and completes the same cohorts")
    a = np.asarray(flat(jax.device_get(res_1.final_params)))
    b = np.asarray(flat(jax.device_get(res_4.final_params)))
    log(f"final params, 4 devices vs 1: max abs diff "
        f"{float(np.max(np.abs(a - b))):.3e}")
    check(np.allclose(a, b, rtol=1e-5, atol=1e-5),
          "4-device final params match the 1-device run within 1e-5")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found "
              f"{devices[0].platform!r} devices", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.fl.client import ClientPool
    from repro.launch.compile_cache import enable_compilation_cache
    from repro.launch.train import build_dataset

    log("smoke run: the times below are not benchmark numbers")
    log(f"device {devices[0].device_kind}, {len(devices)} device(s), "
        f"jax {jax.__version__}")
    log(f"compilation cache: {enable_compilation_cache()}")
    task, parts, test_parts = build_dataset("femnist", N_CLIENTS,
                                            seed=args.seed)
    P = int(flat(task.init_params(args.seed)).shape[0])
    log(f"femnist_cnn P={P}, {len(parts)} clients x "
        f"{len(parts[sorted(parts)[0]])} samples")
    # the executor is created here (and cached on the task) so its
    # per-round compile count can be recorded; run_experiment reuses it
    rounds_log = RoundLog(ClientPool(task, parts).executor)

    if args.chips == 4:
        four_chips(args, task, parts, test_parts, rounds_log)
    else:
        one_chip(args, task, parts, test_parts, rounds_log)

    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

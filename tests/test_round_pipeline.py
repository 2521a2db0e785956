"""Device-resident round pipeline (core/device_batch.py, PR 8).

The contract under test: with ``REPRO_DEVICE_PIPELINE`` enabled (the
default) the vectorized executor hands downstream consumers a zero-copy
``DeviceUpdateBatch`` view of its stacked (K, P) update matrix — and
every observable output (golden traces, round stats, final params) is
**byte-identical** to the legacy per-client materialize path, across all
three training modes, with and without compression, through checkpoint/
resume with in-flight updates.  Plus the riding satellites: the
vectorized ``_batch_indices`` is draw-for-draw equal to the old loop,
losses sync host-side in one batched transfer, and the recompile counter
stays flat across rounds whose cohorts share a power-of-two bucket.
"""
import hashlib
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from fleet_parity_common import GOLDEN_DIR, run_scenario

from repro.core import (ClientHistoryDB, ClientUpdate, DeviceUpdateBatch,
                        StrategyConfig, make_strategy, pipeline_enabled,
                        reset_transfer_stats, transfer_stats)
from repro.core.aggregation import (aggregate, aggregate_reference,
                                    fedavg_coefficients, flat_update_matrix)
from repro.core.compress import CompressionConfig, UpdateCompressor
from repro.core.merge import MergePipeline, ServerOptConfig
from repro.data import make_image_classification
from repro.data.synthetic import ArrayDataset
from repro.faas import CostMeter, FaaSConfig, MockInvoker, SimulatedFaaSPlatform
from repro.faas.platform import ClientProfile
from repro.faas.trace import TraceRecorder
from repro.fl.checkpointing import RoundCheckpointer
from repro.fl.client import ClientPool
from repro.fl.controller import TrainingDriver
from repro.fl.executor import VectorizedExecutor, _batch_indices
from repro.fl.tasks import ClassificationTask, TaskConfig
from repro.models.small import make_cnn


# ----------------------------------------------------------------------
# shared real-task fixture: 8 clients, equal shards, tiny CNN
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    full = make_image_classification(360, image_size=14, n_classes=5,
                                     seed=0)
    x, y = np.asarray(full.x), np.asarray(full.y)
    parts = {f"c{i}": ArrayDataset(x[i * 40:(i + 1) * 40],
                                   y[i * 40:(i + 1) * 40])
             for i in range(8)}
    model = make_cnn(14, 1, 5, 16, "tiny")
    task = ClassificationTask(
        model, TaskConfig(epochs=1, batch_size=16, per_sample_time_s=0.05))
    return task, parts


def _driver(task, parts, strategy_name, mode, seed=0, compress=None,
            server_opt="sgd", trace=None, profiles=None,
            round_timeout_s=30.0):
    history = ClientHistoryDB()
    history.ensure(parts.keys())
    strategy = make_strategy(
        strategy_name,
        StrategyConfig(clients_per_round=4, max_rounds=10, buffer_k=3,
                       server_opt=server_opt),
        history, seed=seed)
    compressor = None
    if compress:
        compressor = UpdateCompressor(CompressionConfig(
            scheme=compress, topk_ratio=0.05))
    pool = ClientPool(task, parts, None, proximal_mu=strategy.proximal_mu(),
                      seed=seed, compressor=compressor)
    platform = SimulatedFaaSPlatform(
        FaaSConfig(cold_start_median_s=2.0, cold_start_sigma=0.3,
                   perf_variation=(0.9, 1.1), failure_rate=0.0,
                   network_jitter_s=0.4),
        seed=seed, recorder=trace)
    invoker = MockInvoker(platform, pool.work_fn, profiles or {})
    return TrainingDriver(strategy, invoker, pool, history,
                          CostMeter(trace=trace),
                          round_timeout_s=round_timeout_s, eval_every=0,
                          seed=seed, vectorized=True, mode=mode,
                          trace=trace)


def _digest(params) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _round_key(stats):
    return (stats.round_number, stats.selected, stats.successes, stats.late,
            stats.crashed, stats.duration_s, stats.eur, stats.cost)


def _run(task, parts, strategy_name, mode, n_rounds=3, **kw):
    trace = TraceRecorder()
    drv = _driver(task, parts, strategy_name, mode, trace=trace, **kw)
    params, res = drv.run(task.init_params(0), n_rounds)
    return _digest(params), [_round_key(r) for r in res.rounds], \
        trace.dumps().encode()


# ----------------------------------------------------------------------
# satellite: vectorized _batch_indices is draw-for-draw identical
# ----------------------------------------------------------------------
def _batch_indices_legacy(n, batch_size, epochs, rng):
    """The pre-PR-8 per-epoch/per-batch Python loop, verbatim."""
    idx_rows, mask_rows = [], []
    for _ in range(epochs):
        order = rng.permutation(n)
        for i in range(0, n, batch_size):
            chunk = order[i:i + batch_size]
            pad = batch_size - len(chunk)
            mask = np.ones(batch_size, dtype=np.float32)
            if pad:
                chunk = np.concatenate(
                    [chunk, np.zeros(pad, dtype=chunk.dtype)])
                mask[batch_size - pad:] = 0.0
            idx_rows.append(chunk)
            mask_rows.append(mask)
    return np.stack(idx_rows), np.stack(mask_rows)


@pytest.mark.parametrize("n,bs,epochs", [
    (10, 4, 3), (32, 32, 2), (7, 8, 1), (100, 16, 4), (1, 4, 2),
    (40, 16, 1), (33, 8, 5),
])
def test_batch_indices_vectorized_parity(n, bs, epochs):
    idx_a, mask_a = _batch_indices(n, bs, epochs, np.random.default_rng(7))
    idx_b, mask_b = _batch_indices_legacy(n, bs, epochs,
                                          np.random.default_rng(7))
    assert idx_a.dtype == idx_b.dtype
    assert np.array_equal(idx_a, idx_b)
    assert np.array_equal(mask_a, mask_b)


# ----------------------------------------------------------------------
# golden traces: toggling the pipeline changes nothing, any mode
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name", ["sync_fedavg_apodotiko",
                                  "semiasync_fedlesscan",
                                  "async_fedbuff_rotation"])
def test_golden_traces_pipeline_toggle(name, monkeypatch):
    golden = (GOLDEN_DIR / f"{name}.jsonl").read_bytes()
    monkeypatch.setenv("REPRO_DEVICE_PIPELINE", "1")
    on_trace, on_digest = run_scenario(name)
    monkeypatch.setenv("REPRO_DEVICE_PIPELINE", "0")
    off_trace, off_digest = run_scenario(name)
    assert on_trace == golden
    assert off_trace == golden
    assert on_digest == off_digest


# ----------------------------------------------------------------------
# real-task byte parity: enabled vs disabled, three modes, compression
# ----------------------------------------------------------------------
@pytest.mark.parametrize("strategy,mode,compress,server_opt", [
    ("fedavg", "sync", None, "sgd"),
    ("fedavg", "sync", None, "fedadam"),       # fused-kernel merge path
    ("fedlesscan", "semi-async", "topk", "sgd"),
    ("fedbuff", "async", None, "sgd"),
])
def test_pipeline_parity_real_task(setup, strategy, mode, compress,
                                   server_opt, monkeypatch):
    task, parts = setup
    monkeypatch.setenv("REPRO_DEVICE_PIPELINE", "1")
    on = _run(task, parts, strategy, mode, compress=compress,
              server_opt=server_opt)
    monkeypatch.setenv("REPRO_DEVICE_PIPELINE", "0")
    off = _run(task, parts, strategy, mode, compress=compress,
               server_opt=server_opt)
    assert on[0] == off[0], "final params diverged"
    assert on[1] == off[1], "round stats diverged"
    assert on[2] == off[2], "trace diverged"


# ----------------------------------------------------------------------
# lazy materialization + batched loss sync
# ----------------------------------------------------------------------
def test_device_batch_lazy_materialization(setup, monkeypatch):
    task, parts = setup
    monkeypatch.setenv("REPRO_DEVICE_PIPELINE", "1")
    pool = ClientPool(task, parts, None, seed=0)
    cids = ["c0", "c1", "c2"]
    gp = task.init_params(0)
    reset_transfer_stats()
    results = pool.batch_work_fn(cids, gp, 0)
    assert transfer_stats()["materialize_rows"] == 0, \
        "packaging must not materialize per-client trees"
    updates = [results[c][0] for c in cids]
    batch = updates[0].batch
    assert isinstance(batch, DeviceUpdateBatch)
    assert all(u.batch is batch for u in updates)

    # materializing one row == the legacy per-client slice, bit for bit
    ex = pool.executor
    datasets = [pool.clients[c].dataset for c in cids]
    seeds = [pool.client_seed(c, 0) for c in cids]
    legacy = ex.run_group(cids, datasets, gp, pool.proximal_mu, seeds)
    for i, cid in enumerate(cids):
        lazy_tree = updates[i].params         # triggers materialization
        for a, b in zip(jax.tree_util.tree_leaves(lazy_tree),
                        jax.tree_util.tree_leaves(legacy[cid][0])):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    assert transfer_stats()["materialize_rows"] == len(cids)

    # the whole loss vector crosses the host boundary exactly once
    reset_transfer_stats()
    b2 = ex.run_group_batch(cids, datasets, gp, pool.proximal_mu, seeds)
    for i, cid in enumerate(cids):
        assert b2.loss(i) == legacy[cid][1]
    assert transfer_stats()["loss_syncs"] == 1


def test_flat_update_matrix_gather_matches_ravel(setup, monkeypatch):
    task, parts = setup
    monkeypatch.setenv("REPRO_DEVICE_PIPELINE", "1")
    pool = ClientPool(task, parts, None, seed=0)
    cids = ["c0", "c1", "c2"]
    gp = task.init_params(0)
    results = pool.batch_work_fn(cids, gp, 0)
    updates = [results[c][0] for c in cids]
    mat, unravel = flat_update_matrix(updates)
    assert mat.shape[0] == len(cids)
    for i, u in enumerate(updates):
        ref = jax.flatten_util.ravel_pytree(u.params)[0]
        assert np.array_equal(np.asarray(mat[i]), np.asarray(ref))
    # gather returns a fresh array — mutating consumers (donation) can
    # never invalidate the batch matrix rows
    assert mat is not updates[0].batch.mat


# ----------------------------------------------------------------------
# compression on flat rows == compression on trees
# ----------------------------------------------------------------------
@pytest.mark.parametrize("scheme", ["topk", "int8"])
def test_encode_flat_matches_encode(scheme):
    rng = np.random.default_rng(3)
    gp = {"a": jnp.asarray(rng.normal(size=(9, 5)), jnp.float32),
          "b": jnp.asarray(rng.normal(size=(17,)), jnp.float32)}
    cfg = CompressionConfig(scheme=scheme, topk_ratio=0.2, chunk=16)
    tree_c, flat_c = UpdateCompressor(cfg), UpdateCompressor(cfg)
    for step in range(3):                     # residuals accumulate
        upd = jax.tree_util.tree_map(
            lambda l: l + jnp.asarray(rng.normal(size=l.shape) * 0.1,
                                      jnp.float32), gp)
        flat_u = jax.flatten_util.ravel_pytree(upd)[0]
        recon, pb, db = tree_c.encode("c0", upd, gp)
        row, pb2, db2 = flat_c.encode_flat("c0", flat_u, gp)
        assert (pb, db) == (pb2, db2)
        ref = jax.flatten_util.ravel_pytree(recon)[0]
        assert np.array_equal(np.asarray(row), np.asarray(ref)), \
            f"step {step}: flat reconstruction diverged"
    ra = np.asarray(tree_c._residuals["c0"])
    rb = np.asarray(flat_c._residuals["c0"])
    assert np.array_equal(ra, rb)


# ----------------------------------------------------------------------
# donation safety: retained global params survive donated merges
# ----------------------------------------------------------------------
def test_donation_safety_retained_global_params():
    rng = np.random.default_rng(0)
    gp = {"w": jnp.asarray(rng.normal(size=(1031,)), jnp.float32)}
    gp_before = np.asarray(gp["w"]).copy()
    updates = [ClientUpdate(f"c{i}",
                            {"w": jnp.asarray(rng.normal(size=(1031,)),
                                              jnp.float32)},
                            10, 0) for i in range(4)]
    coeffs = fedavg_coefficients(updates)
    merger = MergePipeline(ServerOptConfig(name="fedadam", lr=0.1))
    out1 = merger.merge(gp, updates, coeffs)
    # the strategy retains gp across the merge: donation must never take
    # the params buffer, so gp stays readable and bit-identical
    assert np.array_equal(np.asarray(gp["w"]), gp_before)
    out2 = merger.merge(gp, updates, coeffs)   # moments donated + rebuilt
    assert np.all(np.isfinite(np.asarray(out2["w"])))
    assert merger.steps == 2
    # the plain weighted sum with a donated matrix matches the reference
    agg = aggregate(updates, coeffs)
    ref = aggregate_reference(updates, coeffs)
    np.testing.assert_allclose(np.asarray(agg["w"]), np.asarray(ref["w"]),
                               rtol=1e-6, atol=1e-6)
    assert out1 is not None


# ----------------------------------------------------------------------
# checkpoint/resume with in-flight batch-backed updates + compression
# ----------------------------------------------------------------------
def test_resume_with_inflight_batch_updates(setup, tmp_path, monkeypatch):
    """A slow client's batch-backed update spans the checkpoint boundary:
    the engine snapshot materializes it lazily (invoker state_dict), the
    compressor residuals ride along, and the resumed run replays the
    tail byte-identically."""
    task, parts = setup
    monkeypatch.setenv("REPRO_DEVICE_PIPELINE", "1")
    profiles = {"c0": ClientProfile(slow_factor=8.0)}
    kw = dict(compress="topk", profiles=profiles, round_timeout_s=8.0)

    ref = _driver(task, parts, "fedlesscan", "semi-async", **kw)
    ref_params, ref_res = ref.run(task.init_params(0), 4)

    first = _driver(task, parts, "fedlesscan", "semi-async", **kw)
    ckpt = RoundCheckpointer(tmp_path / "ckpt")
    first.run(task.init_params(0), 2, checkpointer=ckpt, checkpoint_every=2)

    resumed = _driver(task, parts, "fedlesscan", "semi-async", **kw)
    params0, next_round = ckpt.restore(resumed, task.init_params(0))
    assert next_round == 2
    tail_params, tail_res = resumed.run(params0, 4, start_round=next_round)

    assert [_round_key(r) for r in tail_res.rounds] == \
        [_round_key(r) for r in ref_res.rounds[2:]]
    assert _digest(tail_params) == _digest(ref_params)


# ----------------------------------------------------------------------
# recompile-free rounds within one power-of-two bucket
# ----------------------------------------------------------------------
def test_recompile_counter_flat_within_bucket(setup, monkeypatch):
    task, parts = setup
    monkeypatch.setenv("REPRO_DEVICE_PIPELINE", "1")
    pool = ClientPool(task, parts, None, seed=0)
    ex = VectorizedExecutor(task)
    gp = task.init_params(0)
    ids = pool.client_ids
    # warm-up compiles the bucket-4 dispatch up front …
    ex.warmup(pool, ids[:4], gp)
    compiled = ex.compile_count
    assert compiled >= 1
    # … then 5 rounds with cohort sizes all in the 4-bucket: 0 new
    # compiles (equal shards ⇒ one group; 3 and 4 both pad to K=4)
    for rnd, size in enumerate([3, 4, 3, 4, 3], start=1):
        ex.run_clients(pool, ids[:size], gp, rnd)
        assert ex.compile_count == compiled, \
            f"round {rnd} (cohort {size}) recompiled"
    # a bucket jump (5 → K=8) is a legitimate new compile
    ex.run_clients(pool, ids[:5], gp, 9)
    assert ex.compile_count == compiled + 1


def test_client_update_batch_semantics():
    mat = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    unravel = lambda flat: {"w": flat}
    batch = DeviceUpdateBatch(mat, ["a", "b"], unravel,
                              losses=jnp.asarray([0.5, 0.25, 0.0, 0.0]))
    u = ClientUpdate("a", num_samples=10, round_number=1,
                     batch=batch, batch_row=0)
    assert np.array_equal(np.asarray(u.flat_params()), [0.0, 1.0, 2.0])
    assert np.array_equal(np.asarray(u.params["w"]), [0.0, 1.0, 2.0])
    # set_row invalidates the cached tree; assignment detaches the batch
    batch.set_row(0, jnp.asarray([9.0, 9.0, 9.0]))
    u2 = ClientUpdate("a2", batch=batch, batch_row=0)
    assert np.array_equal(np.asarray(u2.params["w"]), [9.0, 9.0, 9.0])
    u2.params = {"w": jnp.zeros(3)}
    assert u2.batch is None and u2.batch_row == -1
    with pytest.raises(ValueError):
        ClientUpdate("c")                     # neither params nor batch
    with pytest.raises(IndexError):
        batch.row(2)                          # padding rows unaddressable
    assert batch.loss(1) == 0.25
    assert pipeline_enabled() in (True, False)


# ------------------------------------------------- compile-cache placement
REPO_ROOT = Path(__file__).resolve().parents[1]
CACHE_SCRIPT = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache
    compile_cache.DEFAULT_CACHE_DIR = sys.argv[1]
    print(compile_cache.enable_compilation_cache(sys.argv[2] or None))
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(5.0)).block_until_ready()
""")


def test_compile_cache_default_is_in_checkout():
    from repro.launch.compile_cache import DEFAULT_CACHE_DIR
    assert Path(DEFAULT_CACHE_DIR) == REPO_ROOT / ".jax_cache"


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_entries_land_in_one_place(tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR, when set, wins over any path given in
    code; otherwise the entry point's default directory is used."""
    default, given, from_env = (tmp_path / "default", tmp_path / "given",
                                tmp_path / "env")
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(from_env)
    res = subprocess.run(
        [sys.executable, "-c", CACHE_SCRIPT, str(default),
         str(given) if env_set else ""],
        capture_output=True, text=True, timeout=300, cwd=str(REPO_ROOT),
        env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    want = from_env if env_set else default
    assert res.stdout.strip().splitlines()[-1] == str(want)
    assert any(want.iterdir())
    others = [d for d in (default, given, from_env) if d != want]
    assert not any(d.exists() for d in others)

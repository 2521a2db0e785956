"""Compressed client updates + mesh-sharded merge (kernels/compress.py,
core/compress.py, fed_agg shard_map path).

Covers the tentpole guarantees:

* int8 per-chunk quantization round-trips exactly on representable
  grids and matches the numpy oracle bit-for-bit;
* top-k keeps deterministic tie order (lowest index wins) and the
  Pallas mask decode equals the scatter decode;
* error feedback telescopes: cumulative decoded + current residual
  equals the cumulative injected delta (the EF-SGD invariant), as a
  deterministic check and as a hypothesis property when available;
* compressed runs reach convergence parity with dense in all three
  training modes while cutting wire bytes ≥ 10× at top-k@1%;
* the mesh-sharded merge matches the single-device kernel (in-process
  single-device fallback + a 2-forced-device subprocess);
* trace/billing byte-parity: dense runs emit byte-identical record
  shapes (no payload fields, no egress lines), compressed runs gain
  exactly the new fields;
* error-feedback residuals ride the v2 checkpoint array store.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import ClientUpdate
from repro.core.compress import CompressionConfig, UpdateCompressor
from repro.core.history import ClientHistoryDB
from repro.core.strategies import StrategyConfig, make_strategy
from repro.data import label_sorted_shards, make_image_classification
from repro.faas.cost import CostMeter, PriceBook, egress_cost
from repro.faas.invoker import MockInvoker
from repro.faas.platform import FaaSConfig, SimulatedFaaSPlatform
from repro.faas.trace import TraceRecorder
from repro.fl.client import ClientPool
from repro.fl.controller import TrainingDriver
from repro.fl.tasks import ClassificationTask, TaskConfig
from repro.kernels import ops
from repro.kernels.ref import int8_decode_ref, int8_encode_ref, topk_ref

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------- kernels
def test_int8_roundtrip_exact_on_representable_grid():
    """Integer multiples of a power-of-two scale survive the quantizer
    exactly: scale = absmax/127 is itself a power of two, so q·scale
    reproduces every input bit-for-bit."""
    rng = np.random.default_rng(0)
    scale = 2.0 ** -3
    x = (rng.integers(-127, 128, size=600).astype(np.float32) * scale)
    x[0] = 127 * scale                     # pin absmax to the grid edge
    q, s = ops.int8_encode(jnp.asarray(x), chunk=256)
    out = ops.int8_decode(q, s, x.size)
    np.testing.assert_array_equal(np.asarray(out), x)


def test_int8_matches_numpy_oracle():
    rng = np.random.default_rng(1)
    for n, chunk in ((1000, 256), (64, 16), (257, 256), (5, 8)):
        x = rng.normal(size=n).astype(np.float32) * rng.uniform(0.01, 10)
        q, s = ops.int8_encode(jnp.asarray(x), chunk=chunk)
        q_ref, s_ref = int8_encode_ref(x, chunk=chunk)
        np.testing.assert_array_equal(np.asarray(q), q_ref)
        np.testing.assert_array_equal(np.asarray(s), s_ref)
        out = ops.int8_decode(q, s, n)
        np.testing.assert_array_equal(np.asarray(out),
                                      int8_decode_ref(q_ref, s_ref, n))


def test_int8_zero_chunk_is_safe():
    x = np.zeros(512, np.float32)
    q, s = ops.int8_encode(jnp.asarray(x), chunk=256)
    assert not np.any(np.asarray(q))
    np.testing.assert_array_equal(np.asarray(ops.int8_decode(q, s, 512)), x)


def test_topk_tie_stability_lowest_index_wins():
    """20 equal-magnitude entries, k=5: the kept set is exactly the five
    lowest indices — deterministic across runs and identical between the
    mask-kernel decode and the scatter decode."""
    x = jnp.asarray(np.tile([1.0, -1.0], 10).astype(np.float32))
    idx, vals, decoded = ops.topk_encode(x, 5)
    np.testing.assert_array_equal(np.sort(np.asarray(idx)), np.arange(5))
    want = np.zeros(20, np.float32)
    want[:5] = np.asarray(x)[:5]
    np.testing.assert_array_equal(np.asarray(decoded), want)
    np.testing.assert_array_equal(
        np.asarray(ops.topk_decode(idx, vals, 20)), want)


def test_topk_matches_numpy_oracle():
    rng = np.random.default_rng(2)
    for n, k in ((1000, 10), (4096, 41), (100, 100), (50, 80)):
        x = rng.normal(size=n).astype(np.float32)
        idx, vals, decoded = ops.topk_encode(jnp.asarray(x), k)
        _, _, ref = topk_ref(jnp.asarray(x), k)
        np.testing.assert_array_equal(np.asarray(decoded), np.asarray(ref))
        np.testing.assert_array_equal(
            np.asarray(ops.topk_decode(idx, vals, n)), np.asarray(ref))


# ------------------------------------------------------- error feedback
def _ef_telescopes(deltas, scheme, **cfg_kw):
    """EF invariant: Σ decoded_i + residual_N == Σ delta_i."""
    comp = UpdateCompressor(CompressionConfig(scheme=scheme,
                                              error_feedback=True, **cfg_kw))
    g = {"w": jnp.zeros(deltas[0].size, jnp.float32)}
    total_delta = np.zeros(deltas[0].size, np.float64)
    total_decoded = np.zeros(deltas[0].size, np.float64)
    for d in deltas:
        u = {"w": jnp.asarray(d)}
        recon, payload, dense = comp.encode("c0", u, g)
        assert payload is not None and dense == d.size * 4
        total_delta += d.astype(np.float64)
        total_decoded += np.asarray(recon["w"], np.float64)
    residual = np.asarray(comp._residuals["c0"], np.float64)
    np.testing.assert_allclose(total_decoded + residual, total_delta,
                               rtol=1e-4, atol=1e-5)


def test_error_feedback_telescopes_deterministic():
    rng = np.random.default_rng(3)
    deltas = [rng.normal(size=300).astype(np.float32) for _ in range(5)]
    _ef_telescopes(deltas, "topk", topk_ratio=0.05)
    _ef_telescopes(deltas, "int8", chunk=64)


def test_error_feedback_accumulation_property():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.integers(0, 2**32 - 1), min_size=2, max_size=6),
           st.sampled_from(["topk", "int8"]))
    def prop(seeds, scheme):
        deltas = [np.random.default_rng(s).normal(size=128)
                  .astype(np.float32) for s in seeds]
        kw = ({"topk_ratio": 0.1} if scheme == "topk" else {"chunk": 32})
        _ef_telescopes(deltas, scheme, **kw)

    prop()


def test_error_feedback_changes_second_encode():
    """With EF the dropped mass feeds back: encoding the same update
    twice yields different reconstructions; without EF it is a pure
    function of the delta."""
    rng = np.random.default_rng(4)
    g = {"w": jnp.zeros(200, jnp.float32)}
    u = {"w": jnp.asarray(rng.normal(size=200), jnp.float32)}
    for ef, expect_same in ((True, False), (False, True)):
        comp = UpdateCompressor(CompressionConfig(
            scheme="topk", topk_ratio=0.05, error_feedback=ef))
        r1, _, _ = comp.encode("a", u, g)
        r2, _, _ = comp.encode("a", u, g)
        assert bool(jnp.array_equal(r1["w"], r2["w"])) == expect_same


def test_repro_compress_env_kill_switch(monkeypatch):
    monkeypatch.setenv("REPRO_COMPRESS", "0")
    comp = UpdateCompressor(CompressionConfig(scheme="topk"))
    u = {"w": jnp.ones(10)}
    recon, payload, dense = comp.encode("a", u, {"w": jnp.zeros(10)})
    assert payload is None and dense is None
    assert recon is u


# ------------------------------------------------- end-to-end parity
IMG, CLASSES, N_CLIENTS = 14, 3, 8


@pytest.fixture(scope="module")
def fl_setup():
    from repro.data.synthetic import ArrayDataset
    from repro.models.small import make_cnn
    full = make_image_classification(460, image_size=IMG,
                                     n_classes=CLASSES, seed=0)
    train = ArrayDataset(full.x[:380], full.y[:380])
    test = ArrayDataset(full.x[380:], full.y[380:])
    parts = label_sorted_shards(train, N_CLIENTS, 2, seed=0)
    # local SGD keeps client deltas heavy-tailed, which is the regime
    # top-k sparsification is built for (Adam whitens the delta spectrum
    # and makes a 1% keep-rate uninformative at this tiny scale)
    task = ClassificationTask(
        make_cnn(IMG, 1, CLASSES, 8, "compress_test_cnn"),
        TaskConfig(epochs=2, batch_size=32, optimizer="sgd",
                   learning_rate=0.05, per_sample_time_s=0.01))
    return task, parts, test


def _run_fl(fl_setup, strategy_name, compressor=None, trace=None,
            rounds=10, seed=0):
    task, parts, test = fl_setup
    history = ClientHistoryDB()
    history.ensure(parts.keys())
    strategy = make_strategy(
        strategy_name,
        StrategyConfig(clients_per_round=N_CLIENTS, max_rounds=rounds),
        history, seed=seed)
    pool = ClientPool(task, parts, None, seed=seed, compressor=compressor)
    platform = SimulatedFaaSPlatform(
        FaaSConfig(cold_start_median_s=2.0, cold_start_sigma=0.3,
                   perf_variation=(0.9, 1.1), failure_rate=0.0,
                   network_jitter_s=0.4),
        seed=seed, recorder=trace)
    invoker = MockInvoker(platform, pool.work_fn, {})
    driver = TrainingDriver(strategy, invoker, pool, history,
                            CostMeter(trace=trace), round_timeout_s=90.0,
                            eval_every=0, seed=seed, trace=trace)
    params, result = driver.run(task.init_params(seed), rounds)
    _, loss = task.evaluate(params, test)
    return loss, result, driver


@pytest.mark.parametrize("strategy_name", ["fedavg", "fedlesscan",
                                           "fedbuff"])
def test_compressed_vs_dense_convergence_parity(fl_setup, strategy_name):
    """Top-k@1% with error feedback reaches the dense final loss (within
    tolerance) in every training mode — sync, semi-async, and
    barrier-free — while cutting wire bytes ≥ 10×."""
    dense_loss, _, _ = _run_fl(fl_setup, strategy_name)
    comp = UpdateCompressor(CompressionConfig(scheme="topk",
                                              topk_ratio=0.01))
    comp_loss, result, driver = _run_fl(fl_setup, strategy_name,
                                        compressor=comp)
    assert comp_loss <= dense_loss + 0.5, (
        f"{strategy_name}: compressed loss {comp_loss:.4f} vs dense "
        f"{dense_loss:.4f}")
    # ≥10× reduction at top-k@1% (analytically 50×: 8 bytes/entry kept
    # vs 4 bytes/param dense)
    res = next(iter(comp._residuals.values()))
    P = int(res.shape[0])
    k = max(1, int(round(P * 0.01)))
    assert P * 4 >= 10 * k * 8
    assert driver.cost.total > 0


def test_compressed_update_carries_wire_size(fl_setup):
    task, parts, _ = fl_setup
    comp = UpdateCompressor(CompressionConfig(scheme="int8", chunk=256))
    pool = ClientPool(task, parts, None, seed=0, compressor=comp)
    cid = pool.client_ids[0]
    g = task.init_params(0)
    update, work_s = pool.work_fn(cid, g, 0)
    P = sum(int(np.prod(np.shape(l)))
            for l in jax.tree_util.tree_leaves(g))
    assert update.dense_bytes == P * 4
    assert update.payload_bytes == P + (-(-P // 256)) * 4
    assert update.payload_bytes < update.dense_bytes
    # the record round-trip preserves the byte fields
    rec = json.loads(json.dumps({
        "client_id": update.client_id, "num_samples": update.num_samples,
        "round_number": update.round_number,
        "payload_bytes": update.payload_bytes,
        "dense_bytes": update.dense_bytes}))
    assert rec["payload_bytes"] == update.payload_bytes


# --------------------------------------------------- trace byte-parity
def test_dense_trace_shape_unchanged_compressed_gains_fields(fl_setup):
    dense_trace = TraceRecorder()
    _run_fl(fl_setup, "fedavg", trace=dense_trace, rounds=2)
    comp_trace = TraceRecorder()
    comp = UpdateCompressor(CompressionConfig(scheme="topk",
                                              topk_ratio=0.01))
    _run_fl(fl_setup, "fedavg", compressor=comp, trace=comp_trace,
            rounds=2)

    dense_recs = dense_trace.records
    comp_recs = comp_trace.records
    # dense: aggregation records keep the exact legacy key set, attempt
    # records carry no payload field, and there are no egress lines
    for r in dense_recs:
        if r["type"] == "aggregation":
            assert set(r) == {"type", "time", "round", "merged",
                              "strategy", "mode"}
        assert "payload_bytes" not in r or r["type"] != "attempt"
        if r["type"] == "billing":
            assert r["kind"] != "egress"
    # compressed: every successful attempt carries the wire size, every
    # aggregation carries the round's payload total + achieved ratio,
    # and egress billing lines appear
    agg = [r for r in comp_recs if r["type"] == "aggregation"]
    assert agg and all("payload_bytes" in r and "compression_ratio" in r
                       for r in agg)
    assert all(r["compression_ratio"] > 10 for r in agg)
    att = [r for r in comp_recs
           if r["type"] == "attempt" and r.get("status") == "ok"]
    assert att and all("payload_bytes" in r for r in att)
    egress = [r for r in comp_recs
              if r["type"] == "billing" and r["kind"] == "egress"]
    assert egress
    total_egress = sum(r["cost"] for r in egress)
    assert total_egress > 0


def test_egress_cost_math():
    assert egress_cost(2**30) == pytest.approx(0.12)
    assert egress_cost(0) == 0.0
    meter = CostMeter(prices=PriceBook())
    assert meter.charge_egress(None) == 0.0
    assert meter.invocations == 0          # dense no-op leaves no record
    c = meter.charge_egress(2**20, client_id="a", round_number=3)
    assert c == pytest.approx(0.12 / 1024)
    assert meter.by_client["a"] == pytest.approx(c)
    assert meter.rounds[3] == pytest.approx(c)


def test_transfer_time_extends_billable_duration(fl_setup):
    """A compressed update's upload rides the invocation's billable
    window: with a tiny simulated bandwidth the same seed's attempts get
    strictly longer; dense runs never see a transfer term."""
    task, parts, _ = fl_setup

    def run(compressor, bw):
        history = ClientHistoryDB()
        history.ensure(parts.keys())
        strategy = make_strategy(
            "fedavg", StrategyConfig(clients_per_round=4, max_rounds=2),
            history, seed=0)
        pool = ClientPool(task, parts, None, seed=0, compressor=compressor)
        platform = SimulatedFaaSPlatform(
            FaaSConfig(failure_rate=0.0, upload_bandwidth_bps=bw),
            seed=0)
        driver = TrainingDriver(strategy,
                                MockInvoker(platform, pool.work_fn, {}),
                                pool, history, CostMeter(),
                                round_timeout_s=600.0, eval_every=0,
                                seed=0)
        _, result = driver.run(task.init_params(0), 1)
        return result.rounds[0].duration_s

    dense_slow_bw = run(None, 1e3)
    dense_fast_bw = run(None, 1e12)
    assert dense_slow_bw == dense_fast_bw    # no payload → bw never read
    comp = lambda: UpdateCompressor(CompressionConfig(scheme="topk",
                                                      topk_ratio=0.01))
    comp_slow = run(comp(), 1e4)
    comp_fast = run(comp(), 1e12)
    assert comp_slow > comp_fast


# ------------------------------------------------------ sharded merge
def test_sharded_merge_single_device_fallback():
    """mesh.size == 1 falls back to the single-device kernel exactly."""
    from repro.launch.mesh import make_host_mesh
    rng = np.random.default_rng(5)
    upd = jnp.asarray(rng.normal(size=(4, 777)), jnp.float32)
    coeffs = jnp.asarray(rng.uniform(0.1, 0.4, size=4), jnp.float32)
    mesh = make_host_mesh()
    got = ops.fed_agg_sharded(upd, coeffs, mesh)
    want = ops.fed_agg(upd, coeffs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(data=2)
    assert int(mesh.size) == 2
    rng = np.random.default_rng(0)
    K, P = 5, 1003                       # P not divisible by the mesh
    upd = jnp.asarray(rng.normal(size=(K, P)), jnp.float32)
    coeffs = jnp.asarray(rng.uniform(0.05, 0.4, size=K), jnp.float32)
    params = jnp.asarray(rng.normal(size=P), jnp.float32)
    m = jnp.zeros(P, jnp.float32)
    v = jnp.zeros(P, jnp.float32)
    got = ops.fed_agg_sharded(upd, coeffs, mesh)
    want = ops.fed_agg(upd, coeffs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    for opt in ("sgd", "fedavgm", "fedadam", "fedyogi", "fedadagrad"):
        gs = ops.fed_agg_apply_sharded(
            upd, coeffs, params, m, v, 0.3, 0.8, 0.9, 0.95, 1e-3,
            opt=opt, mesh=mesh)
        g1 = ops.fed_agg_apply(
            upd, coeffs, params, m, v, 0.3, 0.8, 0.9, 0.95, 1e-3,
            opt=opt)
        for a, b in zip(gs, g1):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)
    print("SHARDED-OK")
""")


def test_sharded_merge_two_device_subprocess():
    res = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         cwd=str(REPO_ROOT))
    assert "SHARDED-OK" in res.stdout, res.stdout + res.stderr


def test_merge_pipeline_mesh_dispatch_matches_default(fl_setup):
    """A single-device mesh on the MergePipeline changes nothing — the
    sharded dispatch is bitwise-inert until devices exist."""
    from repro.core.merge import MergePipeline, ServerOptConfig
    from repro.launch.mesh import make_host_mesh
    rng = np.random.default_rng(6)
    like = {"w": jnp.zeros((3, 5)), "b": jnp.zeros(4)}
    g = {k: jnp.asarray(rng.normal(size=np.shape(v)), jnp.float32)
         for k, v in like.items()}
    updates = [ClientUpdate(f"c{i}",
                            {k: jnp.asarray(rng.normal(size=np.shape(v)),
                                            jnp.float32)
                             for k, v in like.items()}, 10, 0)
               for i in range(3)]
    coeffs = rng.uniform(0.1, 0.5, size=3)
    cfg = ServerOptConfig(name="fedadam", lr=0.2)
    plain = MergePipeline(cfg).merge(dict(g), updates, coeffs, mix=0.7)
    meshed = MergePipeline(cfg, mesh=make_host_mesh()).merge(
        dict(g), updates, coeffs, mix=0.7)
    for k in like:
        np.testing.assert_array_equal(np.asarray(plain[k]),
                                      np.asarray(meshed[k]))


# -------------------------------------------------------- checkpointing
def test_compressor_state_roundtrips_through_array_store(tmp_path):
    rng = np.random.default_rng(7)
    like = {"w": jnp.zeros((4, 3)), "b": jnp.zeros(5)}
    g = {k: jnp.zeros(np.shape(v), jnp.float32) for k, v in like.items()}
    comp = UpdateCompressor(CompressionConfig(scheme="topk",
                                              topk_ratio=0.1))
    for cid in ("c1", "c0"):
        u = {k: jnp.asarray(rng.normal(size=np.shape(v)), jnp.float32)
             for k, v in like.items()}
        comp.encode(cid, u, g)
    arrays = {}
    state = comp.state_dict(arrays)
    assert state["clients"] == ["c0", "c1"]
    assert set(arrays) == {"compress/residual/c0", "compress/residual/c1"}
    # every residual tree shares the model-params structure (the v2
    # checkpoint contract) and stays fp32
    for tree in arrays.values():
        assert set(tree) == set(like)
        assert all(np.asarray(l).dtype == np.float32
                   for l in jax.tree_util.tree_leaves(tree))
    fresh = UpdateCompressor(CompressionConfig(scheme="topk",
                                               topk_ratio=0.1))
    fresh.load_state_dict(state, arrays)
    for cid in ("c0", "c1"):
        np.testing.assert_array_equal(np.asarray(fresh._residuals[cid]),
                                      np.asarray(comp._residuals[cid]))
    mismatched = UpdateCompressor(CompressionConfig(scheme="int8"))
    with pytest.raises(ValueError, match="scheme"):
        mismatched.load_state_dict(state, arrays)


def test_driver_checkpoint_carries_compressor_only_when_active(fl_setup):
    _, _, dense_driver = _run_fl(fl_setup, "fedavg", rounds=1)
    state = dense_driver.checkpoint_state({})
    assert "compressor" not in state

    comp = UpdateCompressor(CompressionConfig(scheme="topk",
                                              topk_ratio=0.01))
    _, _, driver = _run_fl(fl_setup, "fedavg", compressor=comp, rounds=1)
    arrays = {}
    state = driver.checkpoint_state(arrays)
    assert state["compressor"]["scheme"] == "topk"
    assert any(k.startswith("compress/residual/") for k in arrays)


def test_checkpoint_resume_preserves_compressed_run(fl_setup, tmp_path):
    """Interrupt/resume with compression on replays the uninterrupted
    run exactly: residuals restore from the array store, so the resumed
    encodes (and therefore the merged models) match bit-for-bit."""
    from repro.fl.checkpointing import RoundCheckpointer
    task, _, test = fl_setup

    def run(resume_dir=None, save_dir=None, rounds=4):
        comp = UpdateCompressor(CompressionConfig(scheme="topk",
                                                  topk_ratio=0.01))
        loss, result, driver = None, None, None
        history = ClientHistoryDB()
        parts = fl_setup[1]
        history.ensure(parts.keys())
        strategy = make_strategy(
            "fedavg",
            StrategyConfig(clients_per_round=N_CLIENTS, max_rounds=rounds),
            history, seed=0)
        pool = ClientPool(task, parts, None, seed=0, compressor=comp)
        platform = SimulatedFaaSPlatform(
            FaaSConfig(cold_start_median_s=2.0, cold_start_sigma=0.3,
                       perf_variation=(0.9, 1.1), failure_rate=0.0,
                       network_jitter_s=0.4), seed=0)
        driver = TrainingDriver(strategy,
                                MockInvoker(platform, pool.work_fn, {}),
                                pool, history, CostMeter(),
                                round_timeout_s=90.0, eval_every=0, seed=0)
        params = task.init_params(0)
        start = 0
        ck = None
        if resume_dir is not None:
            params, start = RoundCheckpointer(resume_dir).restore(
                driver, params)
        if save_dir is not None:
            ck = RoundCheckpointer(save_dir)
        params, _ = driver.run(params, rounds, start_round=start,
                               checkpointer=ck,
                               checkpoint_every=2 if ck else 0)
        return params

    ckpt = tmp_path / "ck"
    clean = run(save_dir=str(ckpt))
    resumed = run(resume_dir=str(ckpt))
    flat_c = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(clean)])
    flat_r = np.concatenate([np.asarray(l).ravel() for l in
                             jax.tree_util.tree_leaves(resumed)])
    np.testing.assert_array_equal(flat_c, flat_r)


# ------------------------------------------------------- tier-2 (slow)
@pytest.mark.slow
def test_gemma_scale_compression_sweep(tmp_path):
    """gemma3-1b-scale codec cells: ≥10× at top-k@1% holds at the 1B
    parameter count, and the bench's extrapolated figures land in
    results/BENCH_compression.json (run with -m slow / --model gemma)."""
    res = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_compression",
         "--model", "gemma", "--gemma-shards", "1"],
        capture_output=True, text=True, timeout=3600,
        cwd=str(pathlib.Path(__file__).resolve().parents[1]),
        env={**os.environ, "PYTHONPATH": "src"})
    assert res.returncode == 0, res.stdout + res.stderr
    repo = pathlib.Path(__file__).resolve().parents[1]
    grid = json.loads((repo / "results"
                       / "BENCH_compression.json").read_text())
    cells = grid["gemma3-1b"]["cells"]
    assert cells["topk@1%"]["compression_ratio"] >= 10
    assert cells["topk@1%"]["param_count"] > 5e8

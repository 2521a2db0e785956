"""Delta-based merge pipeline + pluggable server optimizers (core/merge.py).

Covers the three tentpole guarantees:

* identity (``sgd`` lr=1, no momentum) reproduces the pre-pipeline
  merges **byte-identically** in every strategy family;
* the adaptive families (FedAvgM / FedAdagrad / FedAdam / FedYogi) match
  an independent per-element scalar reference, and the fused Pallas
  kernel path matches the `tree_map` reference path to fp32 tolerance
  (``REPRO_AGG_KERNEL=0`` semantics);
* interrupt/resume replays byte-identically with non-trivial optimizer
  moments in flight (moments snapshot into the v2 array store).

Plus the unified empty-cohort / zero-update behaviour per training mode.
"""
import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ClientHistoryDB, ClientUpdate, MergePipeline,
                        SERVER_OPTS, ServerOptConfig, StrategyConfig,
                        fedavg_aggregate, make_strategy)
from repro.core.aggregation import aggregate
from repro.faas import CostMeter, FaaSConfig, MockInvoker, SimulatedFaaSPlatform
from repro.faas.platform import ClientProfile
from repro.faas.trace import TraceRecorder
from repro.fl.checkpointing import RoundCheckpointer
from repro.fl.controller import TrainingDriver

IDS = [f"c{i}" for i in range(8)]


def _work_fn(cid, params, rnd):
    w = params["w"] + 0.1 * (rnd + 1)
    return ClientUpdate(cid, {"w": w}, 10, rnd), 10.0


class _StubPool:
    def __init__(self, client_ids):
        self._ids = list(client_ids)
        self.clients = {}

    @property
    def client_ids(self):
        return self._ids


def _driver(strategy_name="fedlesscan", seed=0, profiles=None, trace=None,
            round_timeout_s=60.0, clients_per_round=3, ids=None, **strat_kw):
    ids = IDS if ids is None else ids
    history = ClientHistoryDB()
    history.ensure(ids)
    strategy = make_strategy(
        strategy_name,
        StrategyConfig(clients_per_round=clients_per_round, max_rounds=10,
                       **strat_kw),
        history, seed=seed)
    platform = SimulatedFaaSPlatform(
        FaaSConfig(cold_start_median_s=2.0, cold_start_sigma=0.3,
                   perf_variation=(0.9, 1.1), failure_rate=0.0,
                   network_jitter_s=0.4),
        seed=seed, recorder=trace)
    invoker = MockInvoker(platform, _work_fn, profiles or {})
    return TrainingDriver(strategy, invoker, _StubPool(ids), history,
                          CostMeter(trace=trace),
                          round_timeout_s=round_timeout_s,
                          eval_every=0, seed=seed, trace=trace)


def _rand_updates(rng, tree_like, k=4):
    def one():
        return {key: jnp.asarray(rng.normal(size=np.shape(val)),
                                 jnp.float32)
                for key, val in tree_like.items()}
    return [ClientUpdate(f"c{i}", one(), 10 + i, 0) for i in range(k)]


def _ravel(tree):
    return np.concatenate([np.asarray(tree[k], np.float64).ravel()
                           for k in sorted(tree)])


# ---------------------------------------------------------------- scalar ref
def _scalar_merge(cfg: ServerOptConfig, g, mats, coeffs, mix, m, v):
    """Independent per-element reference: plain Python floats, no jax."""
    out = list(g)
    for j in range(len(g)):
        s = sum(c * mat[j] for c, mat in zip(coeffs, mats))
        delta = mix * (s - g[j])
        if cfg.name in ("sgd", "fedavgm"):
            m[j] = cfg.momentum * m[j] + delta
            step = m[j]
        else:
            m[j] = cfg.b1 * m[j] + (1.0 - cfg.b1) * delta
            dsq = delta * delta
            if cfg.name == "fedadagrad":
                v[j] = v[j] + dsq
            elif cfg.name == "fedadam":
                v[j] = cfg.b2 * v[j] + (1.0 - cfg.b2) * dsq
            else:
                v[j] = v[j] - (1.0 - cfg.b2) * dsq * math.copysign(
                    1.0, v[j] - dsq) * (0.0 if v[j] == dsq else 1.0)
            step = m[j] / (math.sqrt(v[j]) + cfg.eps)
        out[j] = g[j] + cfg.lr * step
    return out, m, v


@pytest.mark.parametrize("opt", ["fedavgm", "fedadagrad", "fedadam",
                                 "fedyogi"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_server_opt_matches_scalar_reference(opt, use_kernel):
    """Randomized-pytree parity of each family against the per-element
    scalar reference, on both the kernel and the tree_map path."""
    rng = np.random.default_rng(7)
    like = {"b": jnp.zeros(3), "w": jnp.zeros((2, 4))}
    cfg = ServerOptConfig(name=opt, lr=0.3, momentum=0.9, b2=0.95)
    pipe = MergePipeline(cfg, use_kernel=use_kernel)
    g_tree = {k: jnp.asarray(rng.normal(size=np.shape(v)), jnp.float32)
              for k, v in like.items()}
    g = list(_ravel(g_tree))
    m = [0.0] * len(g)
    v = [0.0] * len(g)
    for _ in range(4):                      # several steps: moments live
        updates = _rand_updates(rng, like)
        coeffs = rng.uniform(0.05, 0.5, size=len(updates))
        g_tree = pipe.merge(g_tree, updates, coeffs, mix=0.8)
        mats = [list(_ravel(u.params)) for u in updates]
        g, m, v = _scalar_merge(cfg.normalized(), g, mats,
                                list(coeffs), 0.8, m, v)
        np.testing.assert_allclose(_ravel(g_tree), g, rtol=2e-4, atol=2e-5)
    assert pipe.steps == 4
    assert pipe.last_update_norm > 0.0


@pytest.mark.parametrize("opt", ["fedavgm", "fedadagrad", "fedadam",
                                 "fedyogi", "sgd"])
def test_kernel_and_reference_paths_agree(opt):
    """The fused fed_agg_apply kernel and the tree_map twin produce the
    same trajectory (params, moments, ‖Δ‖₂) to fp32 tolerance."""
    rng = np.random.default_rng(3)
    like = {"w": jnp.zeros((5, 7)), "b": jnp.zeros(11)}
    cfg = ServerOptConfig(name=opt, lr=0.5, momentum=0.8)
    kern = MergePipeline(cfg, use_kernel=True)
    tree = MergePipeline(cfg, use_kernel=False)
    gk = gt = {k: jnp.asarray(rng.normal(size=np.shape(v)), jnp.float32)
               for k, v in like.items()}
    for _ in range(3):
        updates = _rand_updates(rng, like)
        coeffs = rng.uniform(0.1, 0.4, size=len(updates))
        gk = kern.merge(gk, updates, coeffs, mix=0.9)
        gt = tree.merge(gt, updates, coeffs, mix=0.9)
        np.testing.assert_allclose(_ravel(gk), _ravel(gt),
                                   rtol=1e-4, atol=1e-5)
        assert kern.last_update_norm == pytest.approx(
            tree.last_update_norm, rel=1e-4)
    np.testing.assert_allclose(_ravel(kern._m), _ravel(tree._m),
                               rtol=1e-4, atol=1e-5)


def test_env_gate_reverts_to_reference_path(monkeypatch):
    """REPRO_AGG_KERNEL=0 (use_kernel unset) routes the optimizer merge
    through the tree_map path — same result as use_kernel=False."""
    rng = np.random.default_rng(5)
    like = {"w": jnp.zeros(6)}
    g = {"w": jnp.asarray(rng.normal(size=6), jnp.float32)}
    updates = _rand_updates(rng, like, k=3)
    coeffs = np.ones(3) / 3
    monkeypatch.setenv("REPRO_AGG_KERNEL", "0")
    auto = MergePipeline(ServerOptConfig(name="fedadam"))
    ref = MergePipeline(ServerOptConfig(name="fedadam"), use_kernel=False)
    out_a = auto.merge(g, updates, coeffs)
    out_r = ref.merge(g, updates, coeffs)
    assert np.array_equal(_ravel(out_a), _ravel(out_r))


# ------------------------------------------------------------ identity path
def test_identity_is_byte_identical_to_legacy_merges():
    h = ClientHistoryDB()
    rng = np.random.default_rng(1)
    ups = [ClientUpdate(f"c{i}",
                        {"w": jnp.asarray(rng.normal(size=9), jnp.float32)},
                        7 + i, 0) for i in range(4)]
    g = {"w": jnp.asarray(rng.normal(size=9), jnp.float32)}

    fedavg = make_strategy("fedavg", StrategyConfig(), h)
    assert fedavg.merger.is_identity
    got = fedavg.aggregate(ups, 0, global_params=g)
    want = fedavg_aggregate(ups)
    assert np.array_equal(np.asarray(got["w"]), np.asarray(want["w"]))

    fedasync = make_strategy("fedasync", StrategyConfig(), h)
    got = fedasync.on_client_finish(ups[0], 1.0, 2, 5, global_params=g)
    alpha = 0.6 * (3 + 1) ** -0.5
    anchor = ClientUpdate("__g__", g, 0, 5)
    want = aggregate([anchor, ups[0]], np.array([1 - alpha, alpha]))
    assert np.array_equal(np.asarray(got["w"]), np.asarray(want["w"]))

    # fedlesscan's staleness path: same-round + stale mix, legacy Eq. 3
    from repro.core import staleness_aggregate
    stale_mix = [ClientUpdate(u.client_id, u.params, u.num_samples, rn)
                 for u, rn in zip(ups, (3, 3, 2, 2))]
    fls = make_strategy("fedlesscan", StrategyConfig(), h)
    got = fls.aggregate(stale_mix, 3, now=0.0, global_params=g)
    want = staleness_aggregate(stale_mix, 3, tau=2)
    assert np.array_equal(np.asarray(got["w"]), np.asarray(want["w"]))

    # fedbuff's buffered flush: legacy (1−η)·global + η·weighted average
    fedbuff = make_strategy("fedbuff", StrategyConfig(buffer_k=2), h)
    assert fedbuff.on_client_finish(ups[0], 1.0, 4, 5,
                                    global_params=g) is None
    got = fedbuff.on_client_finish(ups[1], 2.0, 5, 5, global_params=g)
    eta = 0.7
    weights = np.array([ups[0].num_samples * (5 - 4 + 1) ** -0.5,
                        ups[1].num_samples * 1.0], dtype=np.float64)
    legacy = np.concatenate(([1.0 - eta], eta * weights / weights.sum()))
    want = aggregate([anchor, ups[0], ups[1]], legacy)
    assert np.array_equal(np.asarray(got["w"]), np.asarray(want["w"]))


def test_fedavgm_defaults_momentum_and_validates_name():
    assert ServerOptConfig(name="fedavgm").normalized().momentum == 0.9
    assert ServerOptConfig(name="fedavgm",
                           momentum=0.5).normalized().momentum == 0.5
    assert not ServerOptConfig(name="sgd", lr=0.5).is_identity
    assert ServerOptConfig().is_identity
    with pytest.raises(ValueError, match="unknown server optimizer"):
        MergePipeline(ServerOptConfig(name="adamw"))
    assert set(SERVER_OPTS) == {"sgd", "fedavgm", "fedadagrad",
                                "fedadam", "fedyogi"}


def test_moments_stay_fp32_for_low_precision_params(tmp_path):
    """bf16 model params must not quantize the fp32 moment buffers — on
    the kernel path (moments unravel through an f32 view, not the
    params-dtype unravel) or through a checkpoint round-trip (the array
    store restores server_opt/* entries as fp32)."""
    rng = np.random.default_rng(2)
    g = {"w": jnp.asarray(rng.normal(size=8), jnp.bfloat16)}
    updates = [ClientUpdate(f"c{i}",
                            {"w": jnp.asarray(rng.normal(size=8),
                                              jnp.bfloat16)}, 10, 0)
               for i in range(3)]
    pipe = MergePipeline(ServerOptConfig(name="fedadam"), use_kernel=True)
    out = pipe.merge(g, updates, np.ones(3) / 3)
    assert out["w"].dtype == jnp.bfloat16            # params keep dtype
    assert pipe._m["w"].dtype == jnp.float32         # moments stay fp32
    # the bf16-quantized copy differs — proves no round-trip happened
    exact = np.asarray(pipe._m["w"], np.float32)
    assert not np.array_equal(exact,
                              np.asarray(exact.astype(jnp.bfloat16),
                                         np.float32))

    # checkpoint round-trip through the npz array store keeps fp32 bits
    from repro.fl.checkpointing import (_atomic_write_npz, _flat_entries,
                                        _unflatten_like)
    entries = _flat_entries("extra|server_opt/m", pipe._m)
    path = tmp_path / "m.npz"
    _atomic_write_npz(path, entries)
    data = np.load(path)
    restored = _unflatten_like(data, "extra|server_opt/m", g,
                               force_dtype=np.float32)
    assert np.array_equal(np.asarray(restored["w"], np.float32), exact)


def test_opt_path_requires_global_params():
    rng = np.random.default_rng(0)
    ups = _rand_updates(rng, {"w": jnp.zeros(4)}, k=2)
    pipe = MergePipeline(ServerOptConfig(name="fedadam"))
    with pytest.raises(ValueError, match="needs the current global"):
        pipe.merge(None, ups, np.ones(2) / 2)


# ------------------------------------------------- empty-cohort unification
ALL_CRASH = {cid: ClientProfile(crash=True) for cid in IDS}


@pytest.mark.parametrize("strategy_name,mode",
                         [("fedavg", "sync"), ("fedlesscan", "semi-async"),
                          ("fedbuff", "async")])
def test_empty_cohort_keeps_params_unchanged(strategy_name, mode):
    """Every training mode: a cohort that delivers nothing leaves the
    global model unchanged and (in barrier modes) emits the zero-delta
    aggregation record."""
    trace = TraceRecorder()
    d = _driver(strategy_name, profiles=dict(ALL_CRASH), trace=trace,
                server_opt="fedadam")
    assert d.mode == mode
    w0 = jnp.arange(4, dtype=jnp.float32)
    params, res = d.run({"w": w0}, 2)
    assert np.array_equal(np.asarray(params["w"]), np.asarray(w0))
    assert d.strategy.merger.steps == 0
    aggs = trace.select("aggregation")
    if mode != "async":                    # async: no merge event fired
        assert aggs and all(a["merged"] == 0 for a in aggs)
        assert all(a["server_opt"] == "fedadam" for a in aggs)
        assert all(a["update_norm"] == 0.0 for a in aggs)


def test_direct_empty_aggregate_per_strategy():
    h = ClientHistoryDB()
    g = {"w": jnp.ones(3)}
    for name in ("fedavg", "fedprox", "fedlesscan", "safa",
                 "fedasync", "fedbuff"):
        strat = make_strategy(name, StrategyConfig(), h)
        assert strat.aggregate([], 0, global_params=g) is g
        assert strat.aggregate([], 0) is None      # legacy callers
        assert strat.last_aggregate_count == 0


def test_legacy_aggregate_override_still_runs():
    """Pre-pipeline Strategy subclasses (aggregate without the
    global_params kwarg) keep working: the driver detects the old
    signature and calls it the old way."""
    from repro.core import FedAvg

    class OldStyle(FedAvg):
        def aggregate(self, updates, round_number, now=None):
            self.last_aggregate_count = len(updates)
            return fedavg_aggregate(list(updates)) if updates else None

    history = ClientHistoryDB()
    history.ensure(IDS)
    strategy = OldStyle(StrategyConfig(clients_per_round=3, max_rounds=10),
                        history)
    platform = SimulatedFaaSPlatform(FaaSConfig(), seed=0)
    d = TrainingDriver(strategy, MockInvoker(platform, _work_fn, {}),
                       _StubPool(IDS), history, CostMeter(),
                       round_timeout_s=60.0, eval_every=0, seed=0)
    params, res = d.run({"w": jnp.zeros(4)}, 2)
    assert len(res.rounds) == 2
    assert res.rounds[-1].aggregated_updates == 3


# ----------------------------------------------------- traces + checkpoints
def test_aggregation_records_carry_server_opt_metadata():
    trace = TraceRecorder()
    d = _driver("fedlesscan", trace=trace, server_opt="fedyogi",
                server_opt_lr=0.5)
    d.run({"w": jnp.zeros(4)}, 2)
    aggs = trace.select("aggregation")
    assert len(aggs) == 2
    for a in aggs:
        assert a["server_opt"] == "fedyogi"
        assert a["update_norm"] > 0.0
    assert [a["server_steps"] for a in aggs] == [1, 2]


def test_identity_traces_unchanged_by_pipeline():
    """The default server opt adds no fields — aggregation records keep
    the exact pre-pipeline shape (byte-compat for legacy traces)."""
    trace = TraceRecorder()
    d = _driver("fedavg", trace=trace)
    d.run({"w": jnp.zeros(4)}, 1)
    (agg,) = trace.select("aggregation")
    assert set(agg) == {"type", "time", "round", "merged", "strategy",
                        "mode"}


def _lines(recorder):
    return [json.dumps(r, sort_keys=True) for r in recorder.records]


SPAN_PROFILES = {cid: ClientProfile(slow_factor=8.0)
                 for cid in ("c0", "c1", "c2")}


def test_fedadam_resume_is_byte_identical_with_moments_in_flight(tmp_path):
    """Interrupt/resume in semi-async mode with fedadam: the checkpoint
    snapshots non-zero optimizer moments, and the resumed run replays the
    remaining timeline byte-identically (params + JSONL trace, which now
    includes update_norm diagnostics)."""
    kw = dict(profiles=dict(SPAN_PROFILES), server_opt="fedadam",
              server_opt_lr=0.7)
    ref_trace = TraceRecorder()
    ref = _driver("fedlesscan", trace=ref_trace, **kw)
    ref_params, _ = ref.run({"w": jnp.zeros(4)}, 6)

    t1 = TraceRecorder()
    first = _driver("fedlesscan", trace=t1, **kw)
    ckpt = RoundCheckpointer(tmp_path / "ckpt")
    first.run({"w": jnp.zeros(4)}, 2, checkpointer=ckpt, checkpoint_every=2)

    # the snapshot carries real moments: fedadam stepped twice by now
    state = json.loads((tmp_path / "ckpt" / "round_000002.json").read_text())
    merger_state = state["strategy_state"]["merger"]
    assert merger_state == {"name": "fedadam", "steps": 2,
                            "has_m": True, "has_v": True}
    assert {"server_opt/m", "server_opt/v"} <= set(state["array_keys"])

    t2 = TraceRecorder()
    resumed = _driver("fedlesscan", trace=t2, **kw)
    params0, next_round = ckpt.restore(resumed, {"w": jnp.zeros(4)})
    assert next_round == 2
    assert resumed.strategy.merger.steps == 2
    assert resumed.strategy.merger._m is not None
    tail_params, _ = resumed.run(params0, 6, start_round=next_round)

    assert np.array_equal(np.asarray(tail_params["w"]),
                          np.asarray(ref_params["w"]))
    assert _lines(t1) + _lines(t2) == _lines(ref_trace)


def test_async_fedbuff_resume_with_moments(tmp_path):
    """Barrier-free resume with a non-identity server opt: event-horizon
    snapshot mid-run, moments restored, byte-identical trace tail."""
    kw = dict(profiles={"c0": ClientProfile(slow_factor=8.0)},
              server_opt="fedyogi", server_opt_lr=0.4)
    ck = RoundCheckpointer(tmp_path / "ck", keep=50)
    ref_trace = TraceRecorder()
    ref = _driver("fedbuff", trace=ref_trace, **kw)
    ref_params, _ = ref.run({"w": jnp.zeros(4)}, 4,
                            checkpointer=ck, checkpoint_every=15.0)
    tags = ck.rounds()
    assert len(tags) >= 2
    tag = tags[len(tags) // 2]
    state = json.loads((tmp_path / "ck" / f"round_{tag:06d}.json")
                       .read_text())
    offset = state["trace_offset"]
    assert state["strategy_state"]["merger"]["steps"] > 0

    t2 = TraceRecorder()
    resumed = _driver("fedbuff", trace=t2, **kw)
    params0, _ = ck.restore(resumed, {"w": jnp.zeros(4)}, round_number=tag)
    tail_params, _ = resumed.run(params0, 4)
    assert np.array_equal(np.asarray(tail_params["w"]),
                          np.asarray(ref_params["w"]))
    assert _lines(t2) == _lines(ref_trace)[offset:]


def test_moment_free_checkpoint_migrates_to_fresh_optimizer(tmp_path):
    """A checkpoint written before the merge pipeline (no `merger` state)
    restores with a fresh optimizer: moments re-accumulate from the
    resume point instead of failing."""
    d = _driver("fedlesscan", server_opt="fedadam")
    ckpt = RoundCheckpointer(tmp_path / "ckpt")
    params, _ = d.run({"w": jnp.zeros(4)}, 2,
                      checkpointer=ckpt, checkpoint_every=2)
    spath = tmp_path / "ckpt" / "round_000002.json"
    state = json.loads(spath.read_text())
    del state["strategy_state"]["merger"]        # moment-free snapshot
    state["array_keys"] = [k for k in state["array_keys"]
                           if not k.startswith("server_opt/")]
    spath.write_text(json.dumps(state))

    resumed = _driver("fedlesscan", server_opt="fedadam")
    params0, next_round = ckpt.restore(resumed, {"w": jnp.zeros(4)})
    assert next_round == 2
    assert resumed.strategy.merger.steps == 0
    assert resumed.strategy.merger._m is None
    resumed.run(params0, 3, start_round=next_round)   # keeps running
    assert resumed.strategy.merger.steps == 1


def test_restore_rejects_server_opt_mismatch(tmp_path):
    d = _driver("fedlesscan", server_opt="fedadam")
    ckpt = RoundCheckpointer(tmp_path / "ckpt")
    params, _ = d.run({"w": jnp.zeros(4)}, 2,
                      checkpointer=ckpt, checkpoint_every=2)
    other = _driver("fedlesscan", server_opt="fedyogi")
    with pytest.raises(ValueError, match="server"):
        ckpt.restore(other, {"w": jnp.zeros(4)})


def test_experiment_surface_threads_server_opt(tmp_path):
    """ExperimentConfig.server_opt* reaches the strategy's pipeline and
    the exported trace."""
    from repro.data import label_sorted_shards, make_image_classification
    from repro.data.synthetic import ArrayDataset
    from repro.fl.experiment import (ExperimentConfig, ScenarioConfig,
                                     run_experiment)
    from repro.fl.tasks import ClassificationTask, TaskConfig
    from repro.models.small import make_cnn

    full = make_image_classification(200, image_size=14, n_classes=3, seed=0)
    train = ArrayDataset(full.x[:160], full.y[:160])
    parts = label_sorted_shards(train, 6, 2, seed=0)
    task = ClassificationTask(
        make_cnn(14, 1, 3, 16, "srvopt_cnn"),
        TaskConfig(epochs=1, batch_size=32, per_sample_time_s=0.05))
    trace_path = tmp_path / "trace.jsonl"
    cfg = ExperimentConfig(
        strategy="fedavg", n_rounds=2, clients_per_round=3, eval_every=0,
        seed=0, server_opt="fedadam", server_opt_lr=0.1,
        trace_path=str(trace_path),
        scenario=ScenarioConfig(round_timeout_s=60.0, seed=0))
    res = run_experiment(task, parts, None, cfg)
    assert len(res.rounds) == 2
    from repro.faas.trace import load_jsonl
    aggs = [r for r in load_jsonl(trace_path) if r["type"] == "aggregation"]
    assert aggs and all(a["server_opt"] == "fedadam" for a in aggs)


def test_kernel_errors_propagate_without_fallback(monkeypatch):
    """A kernel lowering or compile error surfaces to the caller: neither
    the optimizer merge nor the identity weighted sum drops to the
    tree_map twin behind its back."""
    import repro.kernels as kernels

    def refused(*args, **kwargs):
        raise ValueError("Mosaic refused the block shape")

    monkeypatch.setattr(kernels, "fed_agg_apply", refused)
    monkeypatch.setattr(kernels, "fed_agg", refused)
    g = {"w": jnp.zeros(4)}
    ups = [ClientUpdate("a", {"w": jnp.ones(4)}, 1, 0)]
    with pytest.raises(ValueError, match="Mosaic"):
        MergePipeline(ServerOptConfig(name="fedadam"),
                      use_kernel=True).merge(g, ups, [1.0])
    with pytest.raises(ValueError, match="Mosaic"):
        aggregate(ups, np.array([1.0]), use_kernel=True)

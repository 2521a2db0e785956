"""Tests for the unified scheduling subsystem (fl/scheduler.py).

Covers the Scheduler protocol and the shipped policies (random, full,
fedlesscan, apodotiko, adaptive, rotation), the Strategy.select
compatibility shim, the driver integration in barrier and barrier-free
modes (scheduling trace records, feedback hooks), and scheduler
overrides through ExperimentConfig.
"""
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ClientHistoryDB, ClientUpdate, StrategyConfig,
                        make_strategy, select_clients, select_random)
from repro.faas import (CostMeter, FaaSConfig, MockInvoker,
                        SimulatedFaaSPlatform, TraceRecorder)
from repro.fl.controller import TrainingDriver
from repro.fl.scheduler import (SCHEDULERS, AdaptiveScheduler,
                                ApodotikoScheduler, RandomScheduler,
                                RotationScheduler, make_scheduler)

IDS = [f"c{i}" for i in range(8)]


def _stats(eur, selected=6, late=0, crashed=0):
    return SimpleNamespace(eur=eur, selected=["x"] * selected,
                           late=["x"] * late, crashed=["x"] * crashed)


# ---------------------------------------------------------------- factory
def test_factory_registry_and_errors():
    assert set(SCHEDULERS) == {"random", "full", "fedlesscan", "apodotiko",
                               "adaptive", "rotation"}
    with pytest.raises(ValueError, match="unknown scheduler"):
        make_scheduler("greedy", 4)
    with pytest.raises(ValueError, match="history"):
        make_scheduler("fedlesscan", 4)


def test_random_scheduler_matches_select_random():
    sched = RandomScheduler(4, seed=7)
    want = select_random(IDS, 4, np.random.default_rng(7))
    assert sched.propose(IDS, 4, 0.0, 0) == want


# ---------------------------------------------------------------- shim
def test_strategy_select_shim_preserves_behaviour():
    """Strategy.select delegates to its scheduler and reproduces the
    pre-scheduler selection stream exactly (same rng, same draws)."""
    history = ClientHistoryDB()
    history.ensure(IDS)
    cfg = StrategyConfig(clients_per_round=3, max_rounds=10)
    fedavg = make_strategy("fedavg", cfg, history, seed=3)
    assert fedavg.select(IDS, 0) == select_random(
        IDS, 3, np.random.default_rng(3))

    for i in range(5):
        history.mark_success(f"c{i}", 0)
        history.client_report(f"c{i}", 0, 10.0 + i)
    fls = make_strategy("fedlesscan", cfg, history, seed=3)
    want = select_clients(history, IDS, 2, 10, 3,
                          np.random.default_rng(3), ema_alpha=cfg.ema_alpha)
    assert fls.select(IDS, 2) == want.selected
    assert fls.last_plan is not None          # plan still surfaced
    assert fls.last_plan.selected == want.selected

    safa = make_strategy("safa", cfg, history, seed=3)
    assert safa.select(IDS, 0) == list(IDS)


# ---------------------------------------------------------------- rotation
def test_rotation_deterministic_cycle_and_eligibility():
    sched = RotationScheduler(3, IDS, timeout_s=10.0)
    assert sched.propose(IDS, 3, 0.0, 0) == ["c0", "c1", "c2"]
    # in-flight exclusion: the driver passes only eligible clients
    assert sched.propose([c for c in IDS if c not in {"c3", "c4"}],
                         2, 0.0, 0) == ["c5", "c6"]


def test_rotation_backoff_doubles_and_resets():
    sched = RotationScheduler(1, ["a", "b"], timeout_s=10.0)
    sched.notify_miss("a", now=0.0)           # cooldown until 10
    assert sched.propose(["a", "b"], 1, 5.0, 0) == ["b"]
    sched.notify_miss("a", now=20.0)          # streak 2: until 20 + 20
    assert sched.propose(["a"], 1, 30.0, 0) == ["a"]   # fallback probe
    assert sched.propose(["a", "b"], 1, 30.0, 0) == ["b"]
    sched.notify_finish("a", now=50.0)        # arrival clears the backoff
    assert sched._cooldown_until.get("a") is None
    assert sched._fail_streak["a"] == 0


# ---------------------------------------------------------------- apodotiko
def test_apodotiko_explores_rookies_then_avoids_stragglers():
    sched = ApodotikoScheduler(2, seed=0)
    first = sched.propose(IDS, 8, 0.0, 0)
    assert sorted(first) == sorted(IDS)       # all rookies explored
    # feedback: c0/c1 reliable and fast, c7 crashes every time
    for rnd in range(12):
        sched.notify_finish("c0", rnd, duration_s=5.0)
        sched.notify_finish("c1", rnd, duration_s=6.0)
        sched.notify_miss("c7", rnd)
        for cid in IDS[2:7]:
            sched.notify_finish(cid, rnd, duration_s=20.0)
    picks = [cid for rnd in range(10, 40)
             for cid in sched.propose(IDS, 2, 0.0, rnd)]
    assert picks.count("c7") < picks.count("c0")
    assert picks.count("c7") < picks.count("c1")


def test_apodotiko_deterministic_and_staleness_boosts_ignored():
    a = ApodotikoScheduler(3, seed=5)
    b = ApodotikoScheduler(3, seed=5)
    for rnd in range(3):
        assert a.propose(IDS, 3, 0.0, rnd) == b.propose(IDS, 3, 0.0, rnd)
    # staleness: a long-ignored reliable client outscores an equally
    # reliable recently-picked one
    sched = ApodotikoScheduler(1, seed=0)
    for cid in ("c0", "c1"):
        sched.notify_finish(cid, 0.0, duration_s=10.0)
    sched._last_selected["c0"] = 9
    sched._last_selected["c1"] = 0
    scores = sched._scores(["c0", "c1"], 10)
    assert scores[1] > scores[0]


def test_apodotiko_late_arrival_counts_one_observation():
    """A late-but-alive invocation is reported twice by the driver
    (notify_miss at the deadline, notify_finish(late=True) on arrival)
    but must count as ONE resolved invocation — otherwise productive
    stragglers' success rates are deflated twice."""
    sched = ApodotikoScheduler(2, seed=0)
    sched.notify_miss("c0", 30.0, crashed=False)      # deadline
    sched.notify_finish("c0", 45.0, duration_s=40.0, late=True)
    assert sched._observations["c0"] == 1
    assert sched._successes.get("c0", 0) == 0
    assert sched._duration_ema["c0"] == 40.0          # data still recorded
    # 1 on-time + 1 late -> success rate 1/2, not 1/3
    sched.notify_finish("c0", 60.0, duration_s=10.0)
    assert sched._successes["c0"] / sched._observations["c0"] == 0.5


def test_apodotiko_state_roundtrip():
    a = ApodotikoScheduler(2, seed=1)
    a.propose(IDS, 2, 0.0, 0)
    a.notify_finish("c0", 1.0, duration_s=4.0, cold=True)
    a.notify_miss("c3", 1.0)
    b = ApodotikoScheduler(2, seed=99)
    b.load_state_dict(a.state_dict())
    for rnd in range(1, 4):
        assert a.propose(IDS, 2, 0.0, rnd) == b.propose(IDS, 2, 0.0, rnd)


# ---------------------------------------------------------------- adaptive
def test_adaptive_cohort_grows_and_shrinks_with_eur():
    sched = AdaptiveScheduler(6, seed=0, min_cohort=2, max_cohort=10)
    assert sched.cohort_size(0, []) == 6
    for _ in range(3):
        sched.cohort_size(1, [_stats(1.0)] * 3)
    assert sched.cohort_size(4, [_stats(1.0)] * 3) > 6      # healthy: grow
    for _ in range(12):
        sched.cohort_size(5, [_stats(0.3, late=2, crashed=2)] * 3)
    assert sched.cohort_size(9, [_stats(0.3, late=2, crashed=2)] * 3) == 2
    assert sched._size >= sched.min_cohort


def test_trailing_cache_holds_its_window():
    """The trailing-metrics memo is keyed on object identity, so it must
    hold its window: a freed stats object's id can otherwise be taken by
    a new one, which then replays the stale value."""
    import gc
    import weakref

    from repro.fl.metrics import TrailingMetricsCache

    class Stats:                         # weak-referenceable RoundStats
        def __init__(self, eur):
            self.eur, self.selected = eur, ["x"] * 6
            self.late, self.crashed = [], []

    cache = TrailingMetricsCache(3)
    s = Stats(1.0)
    assert cache.compute([s] * 3) == (1.0, 0.0)
    ref = weakref.ref(s)
    del s
    gc.collect()
    assert ref() is not None
    assert cache.compute([Stats(0.3)] * 3)[0] == pytest.approx(0.3)


def test_adaptive_delegates_selection_to_inner():
    inner = RandomScheduler(6, seed=4)
    sched = AdaptiveScheduler(6, inner=inner)
    want = select_random(IDS, 4, np.random.default_rng(4))
    assert sched.propose(IDS, 4, 0.0, 0) == want


# ---------------------------------------------------------------- driver
def _work_fn(cid, params, rnd):
    return ClientUpdate(cid, {"w": jnp.full((4,), 1.0)}, 10, rnd), 10.0


class _StubPool:
    def __init__(self, client_ids):
        self._ids = list(client_ids)
        self.clients = {}

    @property
    def client_ids(self):
        return self._ids


def _driver(client_ids, strategy_name, profiles=None, cohort=3,
            round_timeout_s=30.0, seed=0, trace=None, scheduler=None,
            **strat_kw):
    history = ClientHistoryDB()
    history.ensure(client_ids)
    strategy = make_strategy(
        strategy_name,
        StrategyConfig(clients_per_round=cohort, max_rounds=20, **strat_kw),
        history, seed=seed)
    platform = SimulatedFaaSPlatform(
        FaaSConfig(cold_start_median_s=2.0, cold_start_sigma=0.0,
                   perf_variation=(1.0, 1.0), failure_rate=0.0,
                   network_jitter_s=0.0),
        seed=seed, recorder=trace)
    invoker = MockInvoker(platform, _work_fn, profiles or {})
    return TrainingDriver(strategy, invoker, _StubPool(client_ids), history,
                          CostMeter(trace=trace),
                          round_timeout_s=round_timeout_s, eval_every=0,
                          trace=trace, scheduler=scheduler)


def test_driver_emits_scheduling_records_sync():
    trace = TraceRecorder()
    d = _driver(IDS, "fedlesscan", cohort=3, trace=trace)
    d.run({"w": jnp.zeros(4)}, 3)
    recs = trace.select("scheduling")
    assert len(recs) == 3
    for rnd, rec in enumerate(recs):
        assert rec["round"] == rnd
        assert rec["scheduler"] == "fedlesscan"
        assert rec["mode"] == "semi-async"
        assert len(rec["selected"]) == 3
        assert rec["pool_size"] == len(IDS)


def test_driver_emits_scheduling_records_async():
    trace = TraceRecorder()
    d = _driver(IDS, "fedasync", cohort=3, trace=trace)
    d.run({"w": jnp.zeros(4)}, 2)
    recs = trace.select("scheduling")
    # initial cohort + one refill per delivered update
    assert recs[0]["scheduler"] == "rotation"
    assert recs[0]["want"] == 3 and len(recs[0]["selected"]) == 3
    assert len(recs) >= 1 + 6
    # every selected client was eligible (never in flight twice)
    for rec in recs:
        assert len(rec["selected"]) <= rec["pool_size"]


def test_legacy_select_override_still_drives_cohorts():
    """A pre-scheduler Strategy subclass overriding `select` directly is
    wrapped in StrategySelectScheduler — its policy picks the cohorts."""
    from repro.core.strategies import FedAvg

    class FirstK(FedAvg):
        name = "first-k"

        def select(self, client_ids, round_number):
            return list(client_ids)[:self.config.clients_per_round]

    history = ClientHistoryDB()
    history.ensure(IDS)
    strategy = FirstK(StrategyConfig(clients_per_round=3, max_rounds=20),
                      history, seed=0)
    platform = SimulatedFaaSPlatform(
        FaaSConfig(cold_start_median_s=2.0, cold_start_sigma=0.0,
                   perf_variation=(1.0, 1.0), failure_rate=0.0,
                   network_jitter_s=0.0), seed=0)
    d = TrainingDriver(strategy, MockInvoker(platform, _work_fn, {}),
                       _StubPool(IDS), history, CostMeter(),
                       round_timeout_s=30.0, eval_every=0)
    assert d.scheduler.name == "strategy-select"
    _, res = d.run({"w": jnp.zeros(4)}, 2)
    assert all(r.selected == IDS[:3] for r in res.rounds)


def test_driver_accepts_scheduler_override_in_barrier_mode():
    trace = TraceRecorder()
    sched = ApodotikoScheduler(3, seed=0)
    d = _driver(IDS, "fedavg", cohort=3, trace=trace, scheduler=sched)
    _, res = d.run({"w": jnp.zeros(4)}, 4)
    assert len(res.rounds) == 4
    assert all(r["scheduler"] == "apodotiko"
               for r in trace.select("scheduling"))
    # feedback reached the scheduler: every finishing client observed
    assert sum(sched._observations.values()) > 0


def test_driver_adaptive_scheduler_resizes_cohorts():
    sched = AdaptiveScheduler(4, seed=0, min_cohort=2, max_cohort=6,
                              window=2)
    d = _driver(IDS, "fedavg", cohort=4, scheduler=sched)
    _, res = d.run({"w": jnp.zeros(4)}, 5)
    sizes = [len(r.selected) for r in res.rounds]
    assert sizes[0] == 4
    assert max(sizes) > 4                    # healthy pool → cohort grew


def test_experiment_config_scheduler_override_and_trace(tmp_path):
    from repro.data import label_sorted_shards, make_image_classification
    from repro.data.synthetic import ArrayDataset
    from repro.fl.experiment import (ExperimentConfig, ScenarioConfig,
                                     run_experiment)
    from repro.fl.tasks import ClassificationTask, TaskConfig
    from repro.models.small import make_cnn

    full = make_image_classification(400, image_size=14, n_classes=3, seed=0)
    train = ArrayDataset(full.x[:300], full.y[:300])
    parts = label_sorted_shards(train, 8, 2, seed=0)
    task = ClassificationTask(
        make_cnn(14, 1, 3, 16),
        TaskConfig(epochs=1, batch_size=32, per_sample_time_s=0.05))
    trace = tmp_path / "trace.jsonl"
    cfg = ExperimentConfig(
        strategy="fedlesscan", scheduler="apodotiko", n_rounds=3,
        clients_per_round=4, eval_every=0, seed=0, trace_path=str(trace),
        scenario=ScenarioConfig(round_timeout_s=30.0, seed=0))
    res = run_experiment(task, parts, None, cfg)
    assert len(res.rounds) == 3
    from repro.faas import load_jsonl
    scheds = [r for r in load_jsonl(trace) if r["type"] == "scheduling"]
    assert len(scheds) == 3
    assert all(r["scheduler"] == "apodotiko" for r in scheds)

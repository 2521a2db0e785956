"""Cohort-sharded multi-device executor + overlapped dispatch (PR 10).

The contract under test: splitting the vectorized executor's cohort (K)
dim over a ``("clients",)`` mesh changes *where* local training runs but
not what it computes (≤1e-5 vs single-device; a size-1 mesh is the
identical code path), and deferring the executor launch to the round's
first INVOKE_START (``REPRO_OVERLAP_DISPATCH``) leaves every golden
trace byte-identical — virtual time never observes the wall clock.
Plus the riding satellites: mesh-keyed jit caches / per-mesh compile
accounting, the lazy once-only ``work_provider`` hook on the event
engine.
"""
import hashlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from fleet_parity_common import GOLDEN_DIR, run_scenario

from repro.core import ClientHistoryDB, ClientUpdate, StrategyConfig, make_strategy
from repro.core.compress import CompressionConfig, UpdateCompressor
from repro.data import make_image_classification
from repro.data.synthetic import ArrayDataset
from repro.faas import CostMeter, FaaSConfig, MockInvoker, SimulatedFaaSPlatform
from repro.faas.events import EventQueue
from repro.faas.invoker import InvocationEngine
from repro.faas.trace import TraceRecorder
from repro.fl.client import ClientPool
from repro.fl.controller import TrainingDriver
from repro.fl.executor import VectorizedExecutor, _bucket
from repro.fl.tasks import ClassificationTask, TaskConfig
from repro.launch.mesh import make_clients_mesh
from repro.models.small import make_cnn


# ----------------------------------------------------------------------
# shared real-task fixture (same shape as test_round_pipeline's)
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


def _driver(task, parts, strategy_name, mode, seed=0, trace=None):
    history = ClientHistoryDB()
    history.ensure(parts.keys())
    strategy = make_strategy(
        strategy_name,
        StrategyConfig(clients_per_round=4, max_rounds=10, buffer_k=3),
        history, seed=seed)
    pool = ClientPool(task, parts, None, proximal_mu=strategy.proximal_mu(),
                      seed=seed)
    platform = SimulatedFaaSPlatform(
        FaaSConfig(cold_start_median_s=2.0, cold_start_sigma=0.3,
                   perf_variation=(0.9, 1.1), failure_rate=0.0,
                   network_jitter_s=0.4),
        seed=seed, recorder=trace)
    invoker = MockInvoker(platform, pool.work_fn, {})
    drv = TrainingDriver(strategy, invoker, pool, history,
                         CostMeter(trace=trace),
                         round_timeout_s=30.0, eval_every=0,
                         seed=seed, vectorized=True, mode=mode,
                         trace=trace)
    return drv, pool


def _digest(params) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(params):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def _run(task, parts, strategy_name, mode, n_rounds=2):
    trace = TraceRecorder()
    drv, pool = _driver(task, parts, strategy_name, mode, trace=trace)
    # the executor is cached on the task across drivers: pin defaults
    pool.executor.configure_mesh(None)
    params, _res = drv.run(task.init_params(0), n_rounds)
    return _digest(params), trace.dumps().encode()


# ----------------------------------------------------------------------
# bucket math: mesh-divisible padding
# ----------------------------------------------------------------------
@pytest.mark.parametrize("k,mult,want", [
    (1, 1, 1), (2, 1, 2), (3, 1, 4), (5, 1, 8), (16, 1, 16),
    (1, 2, 2), (3, 2, 4), (3, 8, 8), (5, 8, 8), (9, 8, 16),
    (16, 8, 16), (17, 8, 32), (6, 3, 9),
])
def test_bucket_rounds_to_mesh_multiple(k, mult, want):
    b = _bucket(k, mult)
    assert b == want
    assert b >= k and b % mult == 0


# ----------------------------------------------------------------------
# single-device mesh is the identical code path
# ----------------------------------------------------------------------
def test_single_device_mesh_is_inert(setup):
    task, parts = setup
    pool = ClientPool(task, parts, None, proximal_mu=0.0, seed=0)
    cids = [f"c{i}" for i in range(3)]
    datasets = [pool.clients[c].dataset for c in cids]
    seeds = [pool.client_seed(c, 0) for c in cids]
    params = task.init_params(0)

    plain = VectorizedExecutor(task)
    # on this host make_clients_mesh clamps the ask to the devices that
    # exist; a size-1 result must normalize away entirely
    meshed = VectorizedExecutor(task, mesh=make_clients_mesh(1))
    assert meshed.mesh is None and meshed._mesh_key() is None

    a = plain.run_group(cids, datasets, params, 0.0, seeds)
    b = meshed.run_group(cids, datasets, params, 0.0, seeds)
    for cid in cids:
        pa, la = a[cid]
        pb, lb = b[cid]
        assert la == lb
        for x, y in zip(jax.tree_util.tree_leaves(pa),
                        jax.tree_util.tree_leaves(pb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_configure_mesh_size_one_keeps_compile_key(setup):
    """configure_mesh with a degenerate mesh lands on the same (None)
    compile-accounting key — no phantom recompiles."""
    task, parts = setup
    pool = ClientPool(task, parts, None, proximal_mu=0.0, seed=0)
    ex = VectorizedExecutor(task)
    cids = [f"c{i}" for i in range(2)]
    datasets = [pool.clients[c].dataset for c in cids]
    seeds = [pool.client_seed(c, 0) for c in cids]
    ex.run_group(cids, datasets, task.init_params(0), 0.0, seeds)
    before = ex.compile_count
    assert before == 1
    ex.configure_mesh(make_clients_mesh(1))
    ex.run_group(cids, datasets, task.init_params(0), 0.0, seeds)
    assert ex.compile_count == before
    assert ex.compile_count_total == before


# ----------------------------------------------------------------------
# overlapped dispatch: byte parity on the gate, goldens included
# ----------------------------------------------------------------------
def test_overlap_gate_byte_parity_real_training(setup, monkeypatch):
    task, parts = setup
    runs = {}
    for flag in ("1", "0"):
        monkeypatch.setenv("REPRO_OVERLAP_DISPATCH", flag)
        runs[flag] = _run(task, parts, "fedavg", "sync")
    assert runs["1"][0] == runs["0"][0]      # final params digest
    assert runs["1"][1] == runs["0"][1]      # full trace bytes


@pytest.mark.parametrize("name", ["sync_fedavg_apodotiko",
                                  "semiasync_fedlesscan",
                                  "async_fedbuff_rotation"])
def test_golden_traces_overlap_toggle(name, monkeypatch):
    golden = (GOLDEN_DIR / f"{name}.jsonl").read_bytes()
    monkeypatch.setenv("REPRO_OVERLAP_DISPATCH", "1")
    on_trace, on_digest = run_scenario(name)
    monkeypatch.setenv("REPRO_OVERLAP_DISPATCH", "0")
    off_trace, off_digest = run_scenario(name)
    assert on_trace == golden
    assert off_trace == golden
    assert on_digest == off_digest


# ----------------------------------------------------------------------
# engine: the deferred work_provider hook
# ----------------------------------------------------------------------
def test_work_provider_lazy_and_consumed_once():
    provider_calls = []
    wf_calls = []

    def wf(cid, params, rnd):
        wf_calls.append(cid)
        return ClientUpdate(cid, {"w": jnp.zeros(3)}, 5, rnd), 4.0

    cids = ["a", "b", "c"]
    provided = {cid: (ClientUpdate(cid, {"w": jnp.ones(3)}, 5, 0), 4.0)
                for cid in cids}

    def provider():
        provider_calls.append(1)
        return provided

    platform = SimulatedFaaSPlatform(FaaSConfig(failure_rate=0.0), seed=0)
    engine = InvocationEngine(MockInvoker(platform, wf, {}))
    queue = EventQueue()
    engine.open_round(queue, cids, {"w": jnp.zeros(3)}, 0, 0.0,
                      work_provider=provider)
    assert provider_calls == []              # lazy: nothing ran yet

    done = []
    while True:
        ev = queue.pop()
        if ev is None:
            break
        completion = engine.handle(queue, ev)
        if completion is not None:
            done.append(completion)
    assert provider_calls == [1]             # exactly one batch dispatch
    assert wf_calls == []                    # per-client path never ran
    assert {c.client_id for c in done} == set(cids)
    for c in done:
        assert c.update is provided[c.client_id][0]


def test_work_provider_none_falls_back_to_work_fn():
    wf_calls = []

    def wf(cid, params, rnd):
        wf_calls.append(cid)
        return ClientUpdate(cid, {"w": jnp.zeros(3)}, 5, rnd), 4.0

    platform = SimulatedFaaSPlatform(FaaSConfig(failure_rate=0.0), seed=0)
    engine = InvocationEngine(MockInvoker(platform, wf, {}))
    queue = EventQueue()
    engine.open_round(queue, ["a", "b"], {"w": jnp.zeros(3)}, 0, 0.0,
                      work_provider=lambda: None)
    while True:
        ev = queue.pop()
        if ev is None:
            break
        engine.handle(queue, ev)
    assert sorted(wf_calls) == ["a", "b"]


# ----------------------------------------------------------------------
# forced 2-device subprocess: sharded parity end to end
# ----------------------------------------------------------------------
MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    assert jax.device_count() == 2
    from repro.data import make_image_classification
    from repro.data.synthetic import ArrayDataset
    from repro.fl.client import ClientPool
    from repro.fl.executor import VectorizedExecutor
    from repro.fl.tasks import ClassificationTask, TaskConfig
    from repro.launch.mesh import make_clients_mesh
    from repro.models.small import make_cnn

    full = make_image_classification(160, image_size=14, n_classes=4,
                                     seed=0)
    x, y = np.asarray(full.x), np.asarray(full.y)
    parts = {f"c{i}": ArrayDataset(x[i * 20:(i + 1) * 20],
                                   y[i * 20:(i + 1) * 20])
             for i in range(8)}
    model = make_cnn(14, 1, 4, 8, "tiny")
    task = ClassificationTask(
        model, TaskConfig(epochs=1, batch_size=10, per_sample_time_s=0.05))
    pool = ClientPool(task, parts, None, proximal_mu=0.0, seed=0)
    params = task.init_params(0)
    cids = [f"c{i}" for i in range(4)]
    datasets = [pool.clients[c].dataset for c in cids]
    seeds = [pool.client_seed(c, 0) for c in cids]

    mesh = make_clients_mesh(2)
    assert int(mesh.size) == 2
    ex = VectorizedExecutor(task)

    # ---- executor-level parity: sharded vs single-device, 1e-5 -------
    single = ex.run_group(cids, datasets, params, 0.0, seeds)
    ex.configure_mesh(mesh)
    sharded = ex.run_group(cids, datasets, params, 0.0, seeds)
    for cid in cids:
        ps, ls = sharded[cid]
        p1, l1 = single[cid]
        assert abs(ls - l1) < 1e-5, (cid, ls, l1)
        for a, b in zip(jax.tree_util.tree_leaves(ps),
                        jax.tree_util.tree_leaves(p1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)

    # ---- per-mesh compile accounting + mesh-keyed jit cache ----------
    meshed_count = ex.compile_count
    assert meshed_count == 1
    ex.run_group(cids, datasets, params, 0.0, seeds)
    assert ex.compile_count == meshed_count          # flat per mesh
    ex.configure_mesh(None)
    assert ex.compile_count == 1                     # the no-mesh counter
    ex.run_group(cids, datasets, params, 0.0, seeds)
    assert ex.compile_count == 1                     # flat there too
    assert ex.compile_count_total == 2
    assert {k[1] for k in ex._jit_cache} == {None,
                                             tuple(mesh.shape.items())}
    # odd cohort: the bucket must round up to the device count
    odd = cids[:3]
    ex.configure_mesh(mesh)
    ex.run_group(odd, [pool.clients[c].dataset for c in odd], params, 0.0,
                 [pool.client_seed(c, 0) for c in odd])

    # ---- an unsharded merge kernel gets the row-sharded matrix on one
    # device: Mosaic refuses a kernel spread over a mesh outside shard_map
    from repro.kernels import ops
    mat = ex.run_group_batch(cids, datasets, params, 0.0, seeds).mat
    assert len(mat.sharding.device_set) == 2
    cf = jnp.full((mat.shape[0],), 0.25)
    seen, real = [], ops._fed_agg
    def spy(u, c, **kw):
        seen.append(len(u.sharding.device_set))
        return real(u, c, **kw)
    ops._fed_agg = spy
    got = ops.fed_agg(mat, cf)
    ops._fed_agg = real
    assert seen == [1], seen
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ops.fed_agg(jnp.asarray(np.asarray(mat)),
                                                cf)))

    # ---- driver-level parity across all three modes ------------------
    import hashlib
    from repro.core import ClientHistoryDB, StrategyConfig, make_strategy
    from repro.faas import (CostMeter, FaaSConfig, MockInvoker,
                            SimulatedFaaSPlatform)
    from repro.fl.controller import TrainingDriver

    def digest_leaves(tree):
        return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]

    def run(strategy_name, mode, mesh):
        history = ClientHistoryDB()
        history.ensure(parts.keys())
        strategy = make_strategy(
            strategy_name,
            StrategyConfig(clients_per_round=4, max_rounds=10, buffer_k=3),
            history, seed=0)
        p = ClientPool(task, parts, None,
                       proximal_mu=strategy.proximal_mu(), seed=0)
        p.executor.configure_mesh(mesh)
        platform = SimulatedFaaSPlatform(
            FaaSConfig(cold_start_median_s=2.0, cold_start_sigma=0.3,
                       perf_variation=(0.9, 1.1), failure_rate=0.0,
                       network_jitter_s=0.4),
            seed=0)
        invoker = MockInvoker(platform, p.work_fn, {})
        drv = TrainingDriver(strategy, invoker, p, history, CostMeter(),
                             round_timeout_s=30.0, eval_every=0, seed=0,
                             vectorized=True, mode=mode)
        out, _res = drv.run(task.init_params(0), 2)
        return digest_leaves(out)

    for strategy_name, mode in (("fedavg", "sync"),
                                ("fedlesscan", "semi-async"),
                                ("fedbuff", "async")):
        a = run(strategy_name, mode, mesh)
        b = run(strategy_name, mode, None)
        assert len(a) == len(b)
        for x, y in zip(a, b):
            np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5,
                                       err_msg=f"{strategy_name}/{mode}")
    print("EXECUTOR-SHARDED-OK")
""")


def test_sharded_executor_two_device_subprocess():
    res = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         cwd=str(GOLDEN_DIR.parent.parent))
    assert "EXECUTOR-SHARDED-OK" in res.stdout, res.stdout + res.stderr

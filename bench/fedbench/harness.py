"""One run of one cell: set-up, the measured window, the correctness
check, and with tracing the per-layer metrics.  `bench/run.py` is its
command line."""
from __future__ import annotations

import math
import os
import shutil
import statistics
import sys
import time
import traceback
from typing import Dict, List, Optional

import numpy as np

from .registry import BENCH, ROOT, Cell, _json

TRACE_DIR = ROOT / ".bench_out" / "trace"


class NoChip(SystemExit):
    """The run found no accelerator, or fewer chips than the cell asks
    for: it exits non-zero and prints no result."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs JAX lowers (each new program, compiled or read
    from the persistent cache) and those read from the cache."""

    def __init__(self):
        import jax.monitoring as mon
        self.lowered = 0
        self.cache_hits = 0

        def on_duration(name, *_a, **_k):
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lowered += 1

        def on_event(name, *_a, **_k):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        self._on = (on_duration, on_event)

    def close(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on[0])
        mon.unregister_event_listener(self._on[1])

    def snapshot(self):
        return self.lowered, self.cache_hits


def sub_seeds(seed: int):
    """Independent 32-bit seeds for data, weights, the study and the
    sampled rounds, from one seed of any size."""
    s = np.random.SeedSequence(int(seed)).generate_state(4)
    return [int(v) for v in s]


def percentile_line(times: List[float]) -> str:
    """Median and the highest whole percentile with ten samples or more
    beyond it."""
    n = len(times)
    if n == 0:
        return "no rounds"
    med = statistics.median(times)
    p = math.floor(100 * (1 - 10 / n)) if n > 10 else None
    if p is None or p <= 50:
        return f"median {med:.6f} s over {n} rounds"
    q = float(np.percentile(times, p))
    return f"median {med:.6f} s, p{p} {q:.6f} s, over {n} rounds"


def check_devices(chips: int, peaks: dict):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX found "
                     f"{devices[0].platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        raise NoChip(f"bench: no peaks for device kind {kind!r} in "
                     f"bench/peaks.json")
    return devices[:chips]


def build_task(cfg: dict):
    import importlib
    from repro.fl.tasks import ClassificationTask, TaskConfig
    mod, fn = cfg["program"]["factory"].split(":")
    model = getattr(importlib.import_module(mod), fn)(
        **cfg["program"]["kwargs"])
    local = cfg["local"]
    return ClassificationTask(model, TaskConfig(
        epochs=local["epochs"], batch_size=local["batch_size"],
        learning_rate=local["learning_rate"],
        optimizer=local["optimizer"],
        per_sample_time_s=cfg["per_sample_time_s"]))


def experiment_config(traffic: dict, seed: int):
    from repro.fl.experiment import ExperimentConfig, ScenarioConfig
    return ExperimentConfig(
        strategy=traffic["strategy"],
        n_rounds=traffic["rounds_per_study"],
        clients_per_round=traffic["cohort"], tau=traffic["tau"],
        eval_every=traffic["eval_every"], seed=seed, vectorized=True,
        server_opt=traffic["server_opt"],
        compress_scheme=traffic["compress_scheme"],
        executor_devices=traffic["executor_devices"],
        merge_devices=traffic["merge_devices"],
        scenario=ScenarioConfig(
            straggler_fraction=traffic["straggler_fraction"],
            slow_share=traffic["slow_share"],
            slow_factor=traffic["slow_factor"],
            round_timeout_s=traffic["round_timeout_s"], seed=seed))


def sample_rounds(traffic: dict, seed: int):
    """The round whose training and merge are checked (any round) and the
    evaluation that is checked."""
    n = traffic["rounds_per_study"]
    rng = np.random.default_rng(seed)
    evals = [r for r in range(n) if (r + 1) % traffic["eval_every"] == 0]
    return int(rng.integers(0, n)), int(rng.choice(evals))


def merged(base: dict, overrides: Optional[dict]) -> dict:
    """A copy of `base` with dotted keys of `overrides` set."""
    import copy
    out = copy.deepcopy(base)
    for key, value in (overrides or {}).items():
        *path, last = key.split(".")
        node = out
        for part in path:
            node = node[part]
        node[last] = value
    return out


class Bench:
    """A cell's program, probes and data.  One task (its compiled
    programs) serves every seed that `prepare` is given."""

    def __init__(self, cell: Cell, traffic_overrides=None,
                 config_overrides=None):
        import jax
        sys.path.insert(0, str(ROOT / "src"))
        from repro.fl.client import ClientPool

        from . import probes
        self.cell = cell
        self.cfg = merged(cell.config, config_overrides)
        self.traffic = merged(cell.traffic, traffic_overrides)
        self.n_rounds = self.traffic["rounds_per_study"]
        self.task = build_task(self.cfg)
        self._init = jax.jit(lambda k: cell.reference.init(k, self.cfg))
        self.cap = probes.Capture(0, 0)
        # the executor is cached on the task, so every study's pool uses
        # this one, probes and all
        self.executor = ClientPool(self.task, {}).executor
        self.undo = probes.install_probes(self.cap, self.task, self.executor)

    def prepare(self, seed: int) -> None:
        """Data, weights, study seed and sampled rounds from `seed`."""
        import jax
        from repro.data.synthetic import ArrayDataset
        data_seed, init_seed, study_seed, pick_seed = sub_seeds(seed)
        train_np, test_np = self.cell.generator.generate(
            self.cfg, self.traffic, data_seed)
        self.train = {f"client_{i}": ArrayDataset(x, y)
                      for i, (x, y) in enumerate(train_np)}
        self.test = {f"client_{i}": ArrayDataset(x, y)
                     for i, (x, y) in enumerate(test_np)}
        self.init = self._init(jax.random.PRNGKey(init_seed))
        self.study_seed = study_seed % 2 ** 31
        self.exp = experiment_config(self.traffic, self.study_seed)
        self.cap.round_t, self.cap.round_e = sample_rounds(self.traffic,
                                                           pick_seed)

    def checker(self, precision=None):
        from .checks import Checker
        return Checker(self.cell.reference, self.cfg, self.traffic,
                       self.train, self.test, self.study_seed, precision)

    def study(self, span=None):
        import jax
        from repro.fl.experiment import run_experiment
        self.cap.reset()
        if span is None:
            res = run_experiment(self.task, self.train, self.test, self.exp,
                                 initial_params=self.init)
        else:
            with jax.profiler.TraceAnnotation(span):
                res = run_experiment(self.task, self.train, self.test,
                                     self.exp, initial_params=self.init)
        jax.block_until_ready(res.final_params)
        self.cap.result = res
        return res

    def close(self) -> None:
        """Take every probe and span out of the program, last in first."""
        while self.undo:
            self.undo.pop()()


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, require_chip: bool = True,
             traffic_overrides: Optional[dict] = None,
             config_overrides: Optional[dict] = None,
             limits: Optional[dict] = None) -> dict:
    cell = Cell(workload)
    limits = limits or cell.limits
    peaks = _json(BENCH / "peaks.json")

    import jax
    if require_chip:
        devices = check_devices(cell.chips, peaks)
    else:
        devices = jax.devices()[:cell.chips]
    peak = peaks["devices"].get(devices[0].device_kind)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compilation_cache
    cache = enable_compilation_cache()
    counter = CompileCounter()
    bench = Bench(cell, traffic_overrides, config_overrides)
    try:
        return _measure(bench, counter, devices, peak, cache, seed, seconds,
                        trace, limits, t0)
    finally:
        bench.close()
        counter.close()


def _measure(bench, counter, devices, peak, cache, seed, seconds, trace,
             limits, t0) -> dict:
    import jax

    from . import checks, probes, xtrace
    cell = bench.cell
    bench.prepare(seed)
    cfg, traffic, cap = bench.cfg, bench.traffic, bench.cap
    task, executor = bench.task, bench.executor
    n_rounds = bench.n_rounds
    round_t, round_e = cap.round_t, cap.round_e
    study = bench.study

    study()                       # warm-up: the window's every shape
    compiles_setup = counter.snapshot()
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s; compile cache {cache}; programs "
        f"lowered {compiles_setup[0]}, read from the cache "
        f"{compiles_setup[1]}; "
        f"rounds checked: training and merge {round_t}, evaluation "
        f"{round_e}")

    metric_mods = {m["name"]: cell.metric_module(m["name"])
                   for m in cell.per_layer} if trace else {}
    if trace:
        env = probes.Env(task, executor, bench.undo)
        for mod in metric_mods.values():
            if hasattr(mod, "install"):
                mod.install(env)
        from repro.fl.controller import TrainingDriver
        bench.undo.append(probes.wrap_method(TrainingDriver, "run_round",
                                             span="bench.round"))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    studies, failed, error = [], 0, None
    starts_before = len(cap.round_starts)
    trained_before = len(cap.trained)
    study_marks = []
    w0 = time.perf_counter()
    window_span = (jax.profiler.TraceAnnotation("bench.window")
                   if trace else None)
    if window_span is not None:
        window_span.__enter__()
    while time.perf_counter() - w0 < seconds:
        first = len(cap.round_starts)
        try:
            res = study("bench.study" if trace else None)
        except Exception:                                # noqa: BLE001
            # the round in progress raised; the study's other rounds
            # are neither completed nor failed
            error = traceback.format_exc()
            failed += 1
            break
        studies.append(res)
        study_marks.append((first, time.perf_counter()))
        if window_span is not None:
            # the profiler traces the window's first study only: a whole
            # window of the LSTM cell's trace is hundreds of MB
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            window_span = None
            trained_traced = len(cap.trained)
    w1 = time.perf_counter()
    if window_span is not None:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    compiles_window = tuple(a - b for a, b in zip(counter.snapshot(),
                                                  compiles_setup))
    attempted = len(cap.round_starts) - starts_before
    completed = sum(len(r.rounds) for r in studies)
    for res in studies:
        finite = all(bool(jax.numpy.isfinite(leaf).all()) for leaf in
                     jax.tree_util.tree_leaves(res.final_params))
        if not finite:
            failed += len(res.rounds)
    window_s = w1 - w0
    round_s = window_s / completed if completed else float("nan")

    # per-round wall times: from each round's start to the next one's, the
    # last round of a study to the study's end (evaluation included)
    times = []
    for first, end in study_marks:
        starts = cap.round_starts[first:first + n_rounds] + [end]
        times += [b - a for a, b in zip(starts, starts[1:])]
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)

    log(f"window {window_s:.6f} s, {len(studies)} studies, {completed} "
        f"rounds completed, {attempted} started, {failed} failed")
    log(f"programs lowered in the window {compiles_window[0]}; executor "
        f"compile_count "
        f"{executor.compile_count}")
    log(f"round wall times: {percentile_line(times)}; max "
        f"{max(times) if times else float('nan'):.6f} s")
    log(f"peak_bytes_in_use {mem_peak} (fullest of {len(devices)} chips)")
    if studies:
        last = studies[-1]
        log(f"study (virtual time): mean EUR {last.mean_eur:.6f}, cost "
            f"${last.total_cost:.6f}, duration {last.total_duration_s:.1f} "
            f"s, accuracy curve {[(r, round(a, 6)) for r, a in last.accuracy_curve]}, "
            f"final accuracy {last.final_accuracy:.6f}")
    if error:
        log("a study raised:\n" + error)

    # ---- correctness: the last study against the reference -------------
    numbers: Dict[str, float] = {}
    if studies and not error:
        t_ref = time.perf_counter()
        chk = bench.checker()
        numbers = chk.numbers(cap)
        if chk.merge_error:
            log(f"merge records: {chk.merge_error}")
        log(f"reference check {time.perf_counter() - t_ref:.3f} s")
    # a number whose limit is null is printed, not compared (PERF.md)
    correct = (bool(numbers) and failed == 0
               and all(numbers[k] <= limits[k] for k in checks.NUMBERS
                       if limits[k] is not None))

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(mem_peak)}
    if not trace or not studies:
        metrics = {"round_s": {"value": round_s, "unit": "s/round"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        path = xtrace.find_xplane(str(TRACE_DIR))
        tr = xtrace.load(path, len(devices))
        ctx = ReadContext(tr, len(studies[0].rounds), len(devices), peak,
                          cfg, cell.reference, traffic,
                          cap.trained[trained_before:trained_traced],
                          studies[:1])
        metrics = {}
        for m in cell.per_layer:
            value = metric_mods[m["name"]].read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = sum(xtrace.busy_ns(tr, c) for c in range(tr.chips))
        device["busy_s"] = busy / tr.chips / 1e9
        device["window_s"] = tr.window_s
        result["breakdown"] = xtrace.breakdown(tr)
        log(f"trace {path}: {os.path.getsize(path)} bytes")
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": numbers.get(k), "limit": limits[k]}
                        for k in checks.NUMBERS}
    for k in checks.NUMBERS:
        log(f"check {k} {numbers.get(k)} limit {limits[k]}")
    return result


class ReadContext:
    """What a per-layer metric's reader gets: the reduced trace of the
    window's first study, the rounds it completed, the chips and their peaks, the
    configuration with its reference module (for analytic counts), the
    traffic, the clients trained per executor call, and the traced study."""

    def __init__(self, trace, rounds, chips, peak, config, reference,
                 traffic, trained, studies):
        self.trace, self.rounds, self.chips = trace, rounds, chips
        self.peak, self.config, self.reference = peak, config, reference
        self.traffic, self.trained, self.studies = traffic, trained, studies

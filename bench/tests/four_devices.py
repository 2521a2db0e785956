"""Whole runs of FEMNIST's cell on four virtual CPU devices, at a CPU
test's size, with the cohort and the merge sharded over the four
(`executor_devices` and `merge_devices` 4), one run per planted fault
named on the command line ("none" for a sound run); prints one JSON line
per run: {"fault", "correct", "device_count", "checks"}.  Run it in a process of its own, since the device count is
fixed when JAX starts:

    python3 bench/tests/four_devices.py none no_exchange frozen
"""
import json
import os
import sys
import time
from pathlib import Path

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      str(BENCH.parent / ".bench_out" / "jax_cache_cpu4"))

WORKLOAD = "femnist-fedlesscan-s30"
TRAFFIC = dict(population=16, train_per_client=23, test_per_client=10,
               cohort=8, rounds_per_study=5, executor_devices=4,
               merge_devices=4)
CONFIG = {"fc_width": 64, "program.kwargs.fc_width": 64}


def no_exchange(updates, coeffs, mesh, **kwargs):
    """The P-sharded merge with the exchange between chips left out: the
    chip that holds the first rows of the cohort sums those alone."""
    import jax.numpy as jnp
    rows = -(-updates.shape[0] // int(mesh.size))
    return jnp.einsum("k,kp->p", coeffs[:rows], updates[:rows])


def frozen_task(harness):
    """Every local step returns the params it was given: the program's
    task is built with a learning rate of 0."""
    orig = harness.build_task
    return lambda cfg: orig(harness.merged(cfg, {"local.learning_rate":
                                                 0.0}))


def main(faults) -> int:
    import jax
    import repro.kernels
    from fedbench import harness
    from fedbench.registry import Cell
    assert len(jax.devices()) == 4

    class FourChips(Cell):
        def __init__(self, workload):
            super().__init__(workload)
            self.chips = 4
    harness.Cell = FourChips
    for fault in faults:
        saved = {}
        if fault == "no_exchange":
            saved[(repro.kernels, "fed_agg_sharded")] = \
                repro.kernels.fed_agg_sharded
            repro.kernels.fed_agg_sharded = no_exchange
        elif fault == "frozen":
            saved[(harness, "build_task")] = harness.build_task
            harness.build_task = frozen_task(harness)
        elif fault != "none":
            raise SystemExit(f"no fault {fault!r}")
        try:
            res = harness.run_cell(WORKLOAD, 3, 0.1, False,
                                   t0=time.perf_counter(),
                                   require_chip=False,
                                   traffic_overrides=TRAFFIC,
                                   config_overrides=CONFIG)
        finally:
            for (obj, attr), value in saved.items():
                setattr(obj, attr, value)
        print(json.dumps({"fault": fault, "correct": res["correct"],
                          "device_count": res["device"]["count"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

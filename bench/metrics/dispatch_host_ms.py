"""dispatch_host_ms (staging and dispatch, VectorizedExecutor.run_clients:
host gather of the cohort's batches, np.stack, device_put, the enqueue of
the group-train and flatten programs, packaging of the updates): host
time inside the executor instance's `run_clients`, per round completed.
Moves round_s."""
from fedbench import xtrace

SPAN = "bench.dispatch_host"


def install(env):
    env.wrap(env.executor, "run_clients", SPAN)


def read(ctx):
    ns = xtrace.span_ns(ctx.trace, SPAN)
    if ns is None or not ctx.rounds:
        return None
    return ns / ctx.rounds / 1e6

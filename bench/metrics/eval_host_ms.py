"""eval_host_ms (evaluation, fl/controller._evaluate -> task.evaluate):
host time inside the task instance's `evaluate`, per round completed
(its per-batch host syncs included).  Moves round_s."""
from fedbench import xtrace

SPAN = "bench.eval_host"


def install(env):
    env.wrap(env.task, "evaluate", SPAN)


def read(ctx):
    ns = xtrace.span_ns(ctx.trace, SPAN)
    if ns is None or not ctx.rounds:
        return None
    return ns / ctx.rounds / 1e6

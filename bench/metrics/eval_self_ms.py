"""eval_self_ms (evaluation, fl/controller._evaluate -> task.evaluate):
host time inside the program's `fl.eval` spans less the time inside the
`fl.sync` spans within them, clipped to the traced window, per round
completed: evaluation's own host work, without the waits on the device
queue that eval_host_ms also holds.  Moves round_s."""
from fedbench import program_trace


def read(ctx):
    ns = program_trace.self_ns(program_trace.of(ctx), "fl.eval",
                               ["fl.sync"], ctx.trace.window)
    if ns is None or not ctx.rounds:
        return None
    return ns / ctx.rounds / 1e6

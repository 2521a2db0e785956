"""stage_host_ms (staging, fl/executor._train_group): host time inside
the program's `fl.stage` spans (host gather of the cohort's batches,
np.stack, the bucket pad, and the host-to-device put), the union
clipped to the traced window, per round completed.  Refines
dispatch_host_ms, which also holds the enqueue and the packaging.
Moves round_s."""
from fedbench import program_trace, xtrace


def read(ctx):
    pt = program_trace.of(ctx)
    ivs = program_trace.span_union(pt, ["fl.stage"], ctx.trace.window)
    if not ivs or not ctx.rounds:
        return None
    return xtrace.total(ivs) / ctx.rounds / 1e6

"""host_sync_ms (host syncs, device-to-host reads): host time inside the
program's `fl.sync` spans (evaluation's per-batch reads, the batched
loss fetch, the server optimizer's update norm, the overlap-off block),
the union clipped to the traced window, per round completed.  A sync
waits for every program queued before it.  Moves round_s."""
from fedbench import program_trace, xtrace


def read(ctx):
    ivs = program_trace.span_union(program_trace.of(ctx), ["fl.sync"],
                                   ctx.trace.window)
    if not ivs or not ctx.rounds:
        return None
    return xtrace.total(ivs) / ctx.rounds / 1e6

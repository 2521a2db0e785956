"""stage_idle_ms (device idle while the host stages): time in which the
chip ran no XLA module while the host was inside an `fl.stage` span,
the mean over the chips used, per round completed.  The part of
device_idle_pct that staging the cohort's data (ROADMAP S2) could
remove.  Moves round_s."""
from fedbench import program_trace, xtrace


def read(ctx):
    stage = program_trace.span_union(program_trace.of(ctx), ["fl.stage"],
                                     ctx.trace.window)
    if not stage or not ctx.rounds:
        return None
    ns = sum(xtrace.total(program_trace.intersect(
        xtrace.idle_gaps(ctx.trace, c), stage)) for c in range(ctx.chips))
    return ns / ctx.chips / ctx.rounds / 1e6

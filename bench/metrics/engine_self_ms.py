"""engine_self_ms (host engine, history, billing: fl/controller,
faas/): host time inside the program's `fl.round` spans less the time
inside the spans of the round's other layers within them (`fl.schedule`,
`fl.stage`, `fl.dispatch`, `fl.package`, `fl.merge`, `fl.sync`), clipped
to the traced window, per round completed: the event loop, history,
billing and trace records.  Moves round_s."""
from fedbench import program_trace

CHILDREN = ("fl.schedule", "fl.stage", "fl.dispatch", "fl.package",
            "fl.merge", "fl.sync")


def read(ctx):
    ns = program_trace.self_ns(program_trace.of(ctx), "fl.round", CHILDREN,
                               ctx.trace.window)
    if ns is None or not ctx.rounds:
        return None
    return ns / ctx.rounds / 1e6

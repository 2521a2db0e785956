"""schedule_host_ms (host engine and scheduler: fl/scheduler.py with
core/history.py and core/clustering.py): host time inside the scheduler's
`propose`, wrapped at class level on every scheduler class of
fl/scheduler.py that defines one, per round completed.  Moves round_s."""
from fedbench import xtrace

SPAN = "bench.schedule_host"


def install(env):
    from repro.fl import scheduler
    for cls in vars(scheduler).values():
        if (isinstance(cls, type) and issubclass(cls, scheduler.Scheduler)
                and "propose" in vars(cls)):
            env.wrap(cls, "propose", SPAN)


def read(ctx):
    ns = xtrace.span_ns(ctx.trace, SPAN)
    if ns is None or not ctx.rounds:
        return None
    return ns / ctx.rounds / 1e6

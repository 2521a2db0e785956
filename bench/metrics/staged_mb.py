"""staged_mb (staging, fl/executor._train_group): megabytes (1e6 bytes)
the program staged from host to device for cohort training, the summed
`bytes` stat of the `fl.stage` spans that start inside the traced
window (the padded x, y and mask tensors of the cohort bucket), per
round completed.  Moves round_s."""
from fedbench import program_trace


def read(ctx):
    a, b = ctx.trace.window
    stages = [s for s in program_trace.of(ctx).spans.get("fl.stage", [])
              if a <= s.start < b and "bytes" in s.stats]
    if not stages or not ctx.rounds:
        return None
    return sum(s.stats["bytes"] for s in stages) / ctx.rounds / 1e6

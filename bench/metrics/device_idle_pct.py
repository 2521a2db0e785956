"""device_idle_pct (device, TPU v5e): the share of the traced window in
which no XLA program ran on the chip, 100 x (1 - busy / window), busy
being the union of the chip's XLA module runs; the mean over the chips
used.  Moves round_s."""
from fedbench import xtrace


def read(ctx):
    w = ctx.trace.window[1] - ctx.trace.window[0]
    busy = sum(xtrace.busy_ns(ctx.trace, c) for c in range(ctx.chips))
    return 100.0 * (1.0 - busy / ctx.chips / w)

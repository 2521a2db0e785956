"""train_device_ms (cohort training, fl/executor's group-train program):
the summed device time of the group-train program's runs in the traced
window, per round completed, the mean over the chips used.  The program
is the jit of `VectorizedExecutor._group_fn`'s vmapped `one_client`.
Moves round_s."""
from fedbench import xtrace

MODULES = r"jit_one_client"


def read(ctx):
    per_chip = [sum(xtrace.module_ns(ctx.trace, c, MODULES))
                for c in range(ctx.chips)]
    if not any(per_chip) or not ctx.rounds:
        return None
    return sum(per_chip) / ctx.chips / ctx.rounds / 1e6

"""merge_roofline_pct (merge, core/merge.py -> kernels/fed_agg.py): the
least time of the merges' semantic work over the merge program's device
time, summed over the merges of the traced window.

Work per merge of K updates of P float32 values (fedbench/work.py):
2 K P operations and (K P + P + K) x 4 bytes, whatever tiling or padding
implements it.  Least time is the larger of operations / peak FLOP/s and
bytes / peak HBM bytes/s over the chips used (the identity merge is
memory-bound at any K).  Device time is that of the `fed_agg` program
(`kernels/fed_agg._fed_agg_impl`, pad and un-pad included; the row
gather before it is a program of its own and not counted), the mean over
the chips.  K of each merge is the round's `aggregated_updates`.
Moves round_s."""
from fedbench import work, xtrace

MODULES = r"jit__fed_agg_impl|jit_fed_agg_sharded.*"


def read(ctx):
    ks = [r.aggregated_updates for s in ctx.studies for r in s.rounds
          if r.aggregated_updates > 0]
    runs = [xtrace.module_ns(ctx.trace, c, MODULES)
            for c in range(ctx.chips)]
    if not ks or any(len(r) != len(ks) for r in runs):
        return None
    device_s = sum(map(sum, runs)) / ctx.chips / 1e9
    p = ctx.config["params"]
    least = sum(work.least_time(*work.merge_work(k, p),
                                ctx.chips * ctx.peak["bf16_flops"],
                                ctx.chips * ctx.peak["hbm_bytes_per_s"])[0]
                for k in ks)
    return 100.0 * least / device_s

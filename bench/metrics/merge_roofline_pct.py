"""merge_roofline_pct (merge, core/merge.py -> kernels/fed_agg.py): the
least time of the merges' semantic work over the merge programs' device
time, summed over the merges of the traced window.

Work per merge of K updates of P float32 values (fedbench/work.py):
2 K P operations and (K P + P + K) x 4 bytes, whatever tiling, padding or
sharding implements it.  Least time is the larger of operations / peak
FLOP/s and bytes / peak HBM bytes/s over the chips used (the identity
merge is memory-bound at any K).  K of each merge is the round's
`aggregated_updates`.  Device time is that of the merge's own programs,
the mean over the chips:

- one chip: the `fed_agg` program (`jit__fed_agg_impl`,
  `kernels/fed_agg._fed_agg_impl`, pad and un-pad included), one run
  per merge;
- several chips: what `kernels/fed_agg.fed_agg_sharded` runs, on every
  chip and in this order: the pad of P to a multiple of the chips
  (`jit__pad`); the four programs of its eager `shard_map` (`jit__unknown`:
  its inputs brought to the P-sharded layout, which moves the update rows
  between the chips, the per-chip `fed_agg`, its output put together);
  and the cut back to P, which JAX runs on a sharded array as a
  `jit_gather` and a `jit_broadcast_in_dim` of its result, after, where
  the chip holds it, the index's own two programs
  (`jit_convert_element_type`, `jit_broadcast_in_dim`).  JAX gives these
  names to other eager programs too (the row reads of a merge whose
  updates come from two rounds launch the same gather), so a chip's runs
  count only where they were launched inside an `fl.merge` span of the
  program (`fedbench/program_trace`), each paired with its launch on the
  host by run id (`xtrace.launched_runs`), and only as that sequence,
  once per span that merges updates (`k` > 0), and as the last of them
  launched there.  Where P is a multiple of the chips there is no pad
  and no cut back.

On either, the row gather before the merge (`jit__take`, or the row
reads and their stack) and the unravel after it are programs of their
own and are not counted.  Where a chip shows another number or order of
these runs, or the trace holds no launches, the reader reads nothing.
Moves round_s."""
from fedbench import program_trace, work, xtrace

MODULES = r"jit__fed_agg_impl"
PAD, SHARDED, PER_MERGE = "jit__pad", "jit__unknown", 4
INDEX = ["jit_convert_element_type", "jit_broadcast_in_dim"]
CUT = ["jit_gather", "jit_broadcast_in_dim"]
NAMES = {PAD, SHARDED, *INDEX, *CUT}


def sharded_merge_ns(runs, padded: bool):
    """Durations of the sharded merge's runs among one chip's runs of
    NAMES launched inside one `fl.merge` span (in the order they ran), or
    None where they are not the sequence the docstring names; without
    the pad and the cut back where P is a multiple of the chips."""
    names = [n for n, _, _ in runs]
    first = PAD if padded else SHARDED
    if first not in names or (PAD in names) != padded:
        return None
    i = names.index(first)
    seq = [PAD] * padded + [SHARDED] * PER_MERGE
    for tail in ((CUT, INDEX + CUT) if padded else ([],)):
        if names[i:] == seq + tail:
            return [e - s for _, s, e in runs[i:]]
    return None


def read(ctx):
    ks = [r.aggregated_updates for s in ctx.studies for r in s.rounds
          if r.aggregated_updates > 0]
    if not ks:
        return None
    p = ctx.config["params"]
    if ctx.chips == 1:
        runs = [xtrace.module_ns(ctx.trace, 0, MODULES)]
        if len(runs[0]) != len(ks):
            return None
    else:
        merges = [(m.start, m.end) for m in
                  program_trace.of(ctx).spans.get("fl.merge", [])
                  if m.stats.get("k", 0) > 0]
        if len(merges) != len(ks):
            return None
        runs = []
        for c in range(ctx.chips):
            mine = []
            for span in merges:
                got = xtrace.launched_runs(ctx.trace, c, NAMES, span)
                got = None if got is None else sharded_merge_ns(
                    got, p % ctx.chips != 0)
                if got is None:
                    return None
                mine += got
            runs.append(mine)
    device_s = sum(map(sum, runs)) / ctx.chips / 1e9
    least = sum(work.least_time(*work.merge_work(k, p),
                                ctx.chips * ctx.peak["bf16_flops"],
                                ctx.chips * ctx.peak["hbm_bytes_per_s"])[0]
                for k in ks)
    return 100.0 * least / device_s

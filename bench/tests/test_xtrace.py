"""The reduction from trace events to per-layer metrics, on events whose
answers are worked out by hand."""
import json
from types import SimpleNamespace

import pytest

from fedbench import program_trace, xtrace
from fedbench.program_trace import ProgramTrace, Span
from fedbench.registry import BENCH, load_module

MS = 1_000_000  # ns


def metric(name):
    return load_module(BENCH / "metrics" / f"{name}.py", f"m_{name}")


@pytest.fixture
def ctx():
    # one chip, a 1 s window holding two rounds
    modules = [[
        ("jit_one_client", 100 * MS, 400 * MS),      # 300 ms training
        ("jit__take", 400 * MS, 402 * MS),
        ("jit__fed_agg_impl", 402 * MS, 404 * MS),   # 2 ms merge
        ("jit_one_client", 500 * MS, 800 * MS),      # 300 ms training
        ("jit__fed_agg_impl", 801 * MS, 803 * MS),   # 2 ms merge
        ("jit__eval_batch_impl", 790 * MS, 900 * MS),  # overlaps training
        ("jit_one_client", 1100 * MS, 1200 * MS),    # after the window
    ]]
    spans = {
        "bench.window": [(0, 1000 * MS)],
        "bench.dispatch_host": [(80 * MS, 100 * MS), (480 * MS, 500 * MS)],
        "bench.eval_host": [(900 * MS, 950 * MS)],
        "bench.schedule_host": [(10 * MS, 11 * MS), (470 * MS, 471 * MS),
                                (470 * MS, 470.5 * MS)],   # nested call
        "bench.round": [(0, 450 * MS), (450 * MS, 900 * MS)],
    }
    trace = xtrace.Trace(1, modules, [[]], spans)
    cfg = json.loads((BENCH / "configs" / "femnist_cnn.json").read_text())
    ref = load_module(BENCH / cfg["reference"], "ref_femnist")
    rounds = [SimpleNamespace(aggregated_updates=14),
              SimpleNamespace(aggregated_updates=0),
              SimpleNamespace(aggregated_updates=12)]
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    return SimpleNamespace(
        trace=trace, rounds=2, chips=1, peak=peak, config=cfg,
        reference=ref, traffic={"train_per_client": 227},
        trained=[15, 14], studies=[SimpleNamespace(rounds=rounds)])


def test_busy_and_idle(ctx):
    # busy: 100-404 and 500-900 -> 704 ms of the 1000 ms window
    assert xtrace.busy_ns(ctx.trace, 0) == 704 * MS
    assert metric("device_idle_pct").read(ctx) == pytest.approx(29.6)
    gaps = xtrace.idle_gaps(ctx.trace, 0)
    assert gaps == [(0, 100 * MS), (404 * MS, 500 * MS),
                    (900 * MS, 1000 * MS)]


def test_idle_gaps_are_named_by_the_innermost_span(ctx):
    bd = xtrace.breakdown(ctx.trace)
    gaps = dict(bd["idle_gaps"])
    # 0-100 ms: round 1 (the dispatch span 80-100 is inside it, but the
    # gap's midpoint 50 ms is not); 404-500: midpoint 452 in round 2;
    # 900-1000: midpoint 950, in no span but the window
    assert gaps == {"bench.round": pytest.approx(0.196),
                    "bench.window": pytest.approx(0.1)}
    ops = dict(bd["device_ops"])
    assert ops["jit_one_client"] == pytest.approx(0.6)
    assert ops["jit__fed_agg_impl"] == pytest.approx(0.004)


def test_layer_times_per_round(ctx):
    assert metric("train_device_ms").read(ctx) == pytest.approx(300.0)
    assert metric("dispatch_host_ms").read(ctx) == pytest.approx(20.0)
    assert metric("eval_host_ms").read(ctx) == pytest.approx(25.0)
    # the nested call inside 470-471 ms counts once
    assert metric("schedule_host_ms").read(ctx) == pytest.approx(1.0)


def test_train_mfu(ctx):
    # 29 clients x 227 samples x 5 epochs x 103,271,424 FLOP over 1 s
    flops = 29 * 227 * 5 * 103_271_424
    assert metric("train_mfu").read(ctx) == pytest.approx(
        100 * flops / 197e12)


def test_merge_roofline(ctx):
    p = 6_603_710
    least = ((14 * p + p + 14) + (12 * p + p + 12)) * 4 / 819e9
    assert metric("merge_roofline_pct").read(ctx) == pytest.approx(
        100 * least / 0.004)


def test_readers_return_nothing_when_there_is_nothing_to_read(ctx):
    ctx.trace.modules = [[("jit__take", 0, MS)]]
    ctx.trace.spans = {"bench.window": [(0, 1000 * MS)]}
    for name in ("train_device_ms", "merge_roofline_pct",
                 "dispatch_host_ms", "eval_host_ms", "schedule_host_ms"):
        assert metric(name).read(ctx) is None


@pytest.fixture
def ctx4(ctx, monkeypatch):
    """The same window on four chips.  Each chip trains for 300 ms per
    round.  Each of the two merges runs, on every chip, as the sharded
    merge's pad, its eager shard_map's four `jit__unknown` programs and
    the cut back (a gather and a broadcast; on the first chip after the
    index's two programs), 2 ms in all (the index's 0.02 ms more), after
    the row gather (`jit__take`; in the second merge, whose updates come
    from two rounds, row reads by the same names as the cut back) and
    before the unravel.  The host is inside `fl.merge` at 395-404 ms and
    795-804 ms, and launches each program run in 400-404 ms or 800-804 ms
    1 ms before it runs; every other program 100 ms before."""
    def merge(at, first, rows):
        q = MS / 4
        out = [("jit__take", at, at + 2 * MS)] if rows == "take" else [
            (n, at + i * q, at + (i + 1) * q) for i, n in enumerate(
                ["jit_convert_element_type", "jit_broadcast_in_dim",
                 "jit_gather", "jit_broadcast_in_dim"] * 2)]
        t = at + 2 * MS
        out += [("jit__pad", t, t + 2 * q)]
        out += [("jit__unknown", t + (2 + i) * q, t + (3 + i) * q)
                for i in range(4)]
        t += 6 * q
        if first:
            out += [("jit_convert_element_type", t, t + MS / 100),
                    ("jit_broadcast_in_dim", t + MS / 100, t + MS / 50)]
            t += MS / 50
        out += [("jit_gather", t, t + q), ("jit_broadcast_in_dim", t + q,
                                            t + 2 * q)]
        return out + [("jit_split", t + 2 * q, t + 2 * q + 1000)]

    def chip(c):
        return ([("jit_one_client", 100 * MS, 400 * MS)]
                + merge(400 * MS, c == 0, "take")
                + [("jit_one_client", 500 * MS, 800 * MS)]
                + merge(800 * MS, c == 0, "reads"))
    ctx.trace = xtrace.Trace(4, [chip(c) for c in range(4)], [[]] * 4,
                             ctx.trace.spans)
    launch(ctx.trace)
    ctx.chips = 4
    merges = ProgramTrace({"fl.merge": [
        Span("fl.merge", 395 * MS, 404 * MS, {"k": 14}),
        Span("fl.merge", 795 * MS, 804 * MS, {"k": 12})]})
    monkeypatch.setattr(program_trace, "of", lambda ctx: merges)
    return ctx


def launch(trace, at=None):
    """Run ids (chip c's k-th run: 1000 c + k) and launches for the
    trace's runs, as the fixture above says; `at`: (chip, k) -> a launch
    time in ms instead."""
    trace.run_ids = [[1000 * c + k for k in range(len(m))]
                     for c, m in enumerate(trace.modules)]
    trace.launches = {}
    for c, m in enumerate(trace.modules):
        for k, (_, start, _) in enumerate(m):
            soon = any(a * MS <= start < (a + 4) * MS for a in (400, 800))
            trace.launches[1000 * c + k] = start - (MS if soon
                                                    else 100 * MS)
    for (c, k), ms in (at or {}).items():
        trace.launches[1000 * c + k] = ms * MS


def test_readers_on_four_chips(ctx4):
    assert metric("train_device_ms").read(ctx4) == pytest.approx(300.0)
    flops = 29 * 227 * 5 * 103_271_424
    assert metric("train_mfu").read(ctx4) == pytest.approx(
        100 * flops / (4 * 197e12))


def merge_pct_on_four_chips(index_ms=0.02):
    # the work of merges of 14 and 12 updates over four chips' peak
    # bandwidth, against 2 ms of merge programs per merge on each chip
    # and the index's programs on the first
    p = 6_603_710
    least = ((14 * p + p + 14) + (12 * p + p + 12)) * 4 / (4 * 819e9)
    return 100 * least / ((4 * 4 + 2 * index_ms) / 4 / 1000)


def test_merge_reader_on_four_chips(ctx4):
    # the pad, the four `jit__unknown` runs and the cut back launched
    # inside each `fl.merge` span; the row gather or the row reads
    # before them and the unravel after them are not counted
    assert metric("merge_roofline_pct").read(ctx4) == pytest.approx(
        merge_pct_on_four_chips())


def test_merge_reader_without_a_pad_where_p_divides(ctx4):
    # P a multiple of the chips: the shard_map's four programs alone
    ctx4.config = dict(ctx4.config, params=6_603_712)
    ctx4.trace.modules = [[r for r in m if r[0] not in (
        "jit__pad", "jit_gather", "jit_broadcast_in_dim",
        "jit_convert_element_type")] for m in ctx4.trace.modules]
    launch(ctx4.trace)
    p = 6_603_712
    least = ((14 * p + p + 14) + (12 * p + p + 12)) * 4 / (4 * 819e9)
    assert metric("merge_roofline_pct").read(ctx4) == pytest.approx(
        100 * least / 0.002)
    # a pad where P needs none is no merge of this kernel
    ctx4.config = dict(ctx4.config, params=6_603_710)
    assert metric("merge_roofline_pct").read(ctx4) is None


def with_stray(trace, launched_ms):
    """One more `jit__unknown` on every chip, running right after the
    first merge's programs, launched at `launched_ms`."""
    trace.modules = [m + [("jit__unknown", 404 * MS, 405 * MS)]
                     for m in trace.modules]
    launch(trace, {(c, len(m) - 1): launched_ms
                   for c, m in enumerate(trace.modules)})


def test_merge_reader_ignores_a_stray_program_outside_the_merge(ctx4):
    # launched at 420 ms, after the first merge span: not the merge's
    with_stray(ctx4.trace, 420)
    assert metric("merge_roofline_pct").read(ctx4) == pytest.approx(
        merge_pct_on_four_chips())


def test_merge_reader_reads_nothing_for_a_fifth_program_in_a_merge(ctx4):
    with_stray(ctx4.trace, 400)
    assert metric("merge_roofline_pct").read(ctx4) is None


def test_merge_reader_reads_nothing_without_launches(ctx4):
    ctx4.trace.launches = {}
    assert metric("merge_roofline_pct").read(ctx4) is None


@pytest.mark.parametrize("name", ["jit__pad", "jit__unknown", "jit_gather"])
def test_merge_reader_reads_nothing_when_a_chip_lacks_a_merge_program(
        ctx4, name):
    chip = ctx4.trace.modules[2]
    del chip[max(i for i, (n, _, _) in enumerate(chip) if n == name)]
    launch(ctx4.trace)
    assert metric("merge_roofline_pct").read(ctx4) is None

"""The reduction from trace events to per-layer metrics, on events whose
answers are worked out by hand."""
import json
from types import SimpleNamespace

import pytest

from fedbench import xtrace
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
def ctx4(ctx):
    """The same window on four chips.  Each chip trains for 300 ms per
    round; each of the two merges runs as four `jit__unknown` programs
    per chip (the P-sharded merge's eager shard_map), 1 ms in all."""
    def chip():
        q = MS / 4
        return ([("jit_one_client", 100 * MS, 400 * MS),
                 ("jit__take", 400 * MS, 402 * MS)]
                + [("jit__unknown", 402 * MS + i * q, 402 * MS + (i + 1) * q)
                   for i in range(4)]
                + [("jit_one_client", 500 * MS, 800 * MS)]
                + [("jit__unknown", 801 * MS + i * q, 801 * MS + (i + 1) * q)
                   for i in range(4)])
    ctx.trace = xtrace.Trace(4, [chip() for _ in range(4)], [[]] * 4,
                             ctx.trace.spans)
    ctx.chips = 4
    return ctx


def test_readers_on_four_chips(ctx4):
    assert metric("train_device_ms").read(ctx4) == pytest.approx(300.0)
    flops = 29 * 227 * 5 * 103_271_424
    assert metric("train_mfu").read(ctx4) == pytest.approx(
        100 * flops / (4 * 197e12))


def test_merge_reader_reads_nothing_on_several_chips(ctx4):
    # the sharded merge's programs are named `jit__unknown`, as any eager
    # program is: nothing tells them apart, so no reading
    assert metric("merge_roofline_pct").read(ctx4) is None
    ctx4.trace.modules = [m + [("jit__fed_agg_impl", 402 * MS, 404 * MS),
                               ("jit__fed_agg_impl", 801 * MS, 803 * MS)]
                          for m in ctx4.trace.modules]
    assert metric("merge_roofline_pct").read(ctx4) is None

"""The readers of the program's own spans (fedbench/program_trace.py and
the six metric files that use it), on events whose answers are worked
out by hand."""
from types import SimpleNamespace

import pytest

from fedbench import program_trace, xtrace
from fedbench.program_trace import ProgramTrace, Span
from fedbench.registry import BENCH, load_module

MS = 1_000_000  # ns

NEW = ("stage_host_ms", "staged_mb", "stage_idle_ms", "host_sync_ms",
       "eval_self_ms", "engine_self_ms")


def metric(name):
    return load_module(BENCH / "metrics" / f"{name}.py", f"pm_{name}")


def spans(name, ivs, **stats):
    return [Span(name, s * MS, e * MS, dict(stats)) for s, e in ivs]


@pytest.fixture
def ctx(monkeypatch):
    # one chip, a 1 s window holding two rounds; the group-train program
    # runs 100-400, 500-800 and 950-1100 ms (the last past the window),
    # so the chip idles 0-100, 400-500 and 800-950 ms
    modules = [[("jit_one_client", 100 * MS, 400 * MS),
                ("jit_one_client", 500 * MS, 800 * MS),
                ("jit__eval_batch_impl", 960 * MS, 970 * MS),
                ("jit_one_client", 950 * MS, 1100 * MS)]]
    trace = xtrace.Trace(1, modules, [[]], {"bench.window": [(0, 1000 * MS)]})
    sp = {
        "fl.round": spans("fl.round", [(0, 450), (450, 900)]),
        "fl.schedule": spans("fl.schedule", [(5, 10), (455, 460)]),
        # the first stage straddles the end of the idle gap at 100 ms;
        # the last one lies after the window
        "fl.stage": (spans("fl.stage", [(60, 120)], bytes=1_000_000)
                     + spans("fl.stage", [(480, 520)], bytes=2_000_000)
                     + spans("fl.stage", [(1100, 1200)], bytes=5_000_000)),
        "fl.dispatch": spans("fl.dispatch", [(120, 125), (520, 525)]),
        "fl.package": spans("fl.package", [(125, 130), (525, 530)]),
        "fl.merge": spans("fl.merge", [(440, 445), (890, 895)]),
        # one loss fetch inside round 0, two reads inside evaluation, one
        # after the window
        "fl.sync": spans("fl.sync", [(430, 435), (910, 920), (930, 950),
                                     (1500, 1510)]),
        "fl.eval": spans("fl.eval", [(900, 960)]),
    }
    pt = ProgramTrace(sp)
    monkeypatch.setattr(program_trace, "of", lambda ctx: pt)
    return SimpleNamespace(trace=trace, rounds=2, chips=1)


def test_staging(ctx):
    # stages 60 + 40 ms inside the window
    assert metric("stage_host_ms").read(ctx) == pytest.approx(50.0)
    # 3 MB in the window's stages over 2 rounds
    assert metric("staged_mb").read(ctx) == pytest.approx(1.5)
    # idle under a stage: 60-100 and 480-500 ms
    assert metric("stage_idle_ms").read(ctx) == pytest.approx(30.0)


def test_syncs_and_evaluation(ctx):
    # 5 + 10 + 20 ms of syncs inside the window
    assert metric("host_sync_ms").read(ctx) == pytest.approx(17.5)
    # 60 ms of evaluation less its two syncs (30 ms)
    assert metric("eval_self_ms").read(ctx) == pytest.approx(15.0)


def test_engine_self_time(ctx):
    # 900 ms of rounds less schedule 10, stage 100, dispatch 10,
    # package 10, merge 10 and the loss fetch 5 inside them
    assert metric("engine_self_ms").read(ctx) == pytest.approx(377.5)


def test_interval_intersection():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (30, 40), (45, 60)]
    assert program_trace.intersect(a, b) == [(5, 10), (20, 25), (45, 50)]
    assert program_trace.intersect(a, []) == []


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_on_a_program_without_spans(monkeypatch, name):
    # a program without fl.* spans, like the one before them
    monkeypatch.setattr(program_trace, "of", lambda ctx: ProgramTrace())
    trace = xtrace.Trace(1, [[("jit_one_client", 0, 900 * MS)]], [[]],
                         {"bench.window": [(0, 1000 * MS)]})
    assert metric(name).read(SimpleNamespace(trace=trace, rounds=2,
                                             chips=1)) is None


def test_load_reads_spans_and_their_stats(tmp_path):
    import jax
    program_trace.load.cache_clear()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("fl.round", round=4) as s:
            with jax.profiler.TraceAnnotation("fl.sync", what="eval",
                                              bytes=8):
                pass
            s.set_metadata(syncs=1)
        with jax.profiler.TraceAnnotation("bench.other"):
            pass
    pt = program_trace.load(xtrace.find_xplane(str(tmp_path)))
    assert set(pt.spans) == {"fl.round", "fl.sync"}
    (rnd,), (sync,) = pt.spans["fl.round"], pt.spans["fl.sync"]
    assert rnd.stats == {"round": 4, "syncs": 1}
    assert sync.stats == {"what": "eval", "bytes": 8}
    assert rnd.start <= sync.start and sync.end <= rnd.end

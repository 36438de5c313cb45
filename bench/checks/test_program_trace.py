"""The program's ``iemas.*`` spans in a trace (`trace/program.py`) and the
per-layer metrics that read them, against a small trace with known
answers; and the same metrics left out where a program wrote no such
span."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import loader

FIXTURES = Path(__file__).parent / "fixtures"
program = loader.module(loader.BENCH / "trace" / "program.py")
reduce = loader.module(loader.BENCH / "trace" / "reduce.py")

#: each new metric's answer on small_program_trace.pbtxt, by hand (ns -> ms)
EXPECTED = {
    # (40 + 70) ns of assembly over 2 batches in the window
    "fused.assemble_ms_per_batch": 55e-6,
    # (140 + 60) ns of settlement over 2 batches
    "fused.settle_ms_per_batch": 100e-6,
    # batch 1: 500 - (20 + 360 + 20); batch 2: 400 - 330
    "router.self_ms_per_batch": 85e-6,
    # rounds 3 and 5
    "fused.bid_rounds_per_batch": 4.0,
    # decode 1150..1380 holds 100 ns of operations; 2 steps
    "engine.decode_idle_ms_per_tok": 65e-6,
    # serve 900..1400 outside prefill 950..1110 and decode 1150..1380 is
    # 110 ns, of which 1110..1120 is busy; 1 serve
    "engine.prep_idle_ms_per_req": 100e-6,
    # promised 0 + 100 + 60 of 100 + 120 + 80 prompt tokens
    "router.affinity_promised_share": 100.0 * 160 / 300,
    # kept min(0, 0) + min(90, 100) + min(70, 60) of 160 promised
    "router.affinity_kept_share": 100.0 * 150 / 160,
}


def _ctx(fixture: str, tmp_path, monkeypatch):
    """The traced run's context, its trace written where the harness
    writes one."""
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        (FIXTURES / fixture).read_text())
    (tmp_path / "run.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(program, "TRACE_DIR", tmp_path)
    return SimpleNamespace(
        trace=reduce.Trace(ProfileData.from_serialized_xspace(raw)))


def test_spans_are_clipped_to_the_window(tmp_path, monkeypatch):
    found = program.spans(_ctx("small_program_trace.pbtxt", tmp_path,
                               monkeypatch))
    assert len(found) == 18          # the route_batch after the window left out
    assert [s.stats["batch"] for s in program.named(found, "route_batch")] \
        == [1, 2]
    device = program.named(found, "fused.device")
    assert [s.stats for s in device] == [
        {"rounds": 3, "warm": 1, "fallback": 0, "retraces": 0},
        {"rounds": 5, "warm": 1, "fallback": 1, "retraces": 1}]
    serve, = program.named(found, "engine.serve")
    assert (serve.start, serve.end) == (900.0, 1400.0)
    assert serve.stats["mode"] == "fresh" and serve.stats["session"] == "d1"


def test_helper_times(tmp_path, monkeypatch):
    ctx = _ctx("small_program_trace.pbtxt", tmp_path, monkeypatch)
    found = program.spans(ctx)
    assert program.wall_ns(found, "fused.device") == 160 + 180
    assert program.self_ns(found, "route_batch") == 100 + 70
    serve = program.named(found, "engine.serve")
    kids = [s for s in found if s.name in ("engine.prefill", "engine.decode")]
    assert program.minus(serve, kids) == [(900, 950), (1110, 1150),
                                          (1380, 1400)]
    assert program.idle_ns(ctx, [(900, 1400)]) == 500 - 120 - 100


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reader(name, tmp_path, monkeypatch):
    ctx = _ctx("small_program_trace.pbtxt", tmp_path, monkeypatch)
    assert loader.metric(name).read(ctx) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_silent_without_program_spans(name, tmp_path, monkeypatch):
    # a program that writes no iemas.* span: the metric is left out
    ctx = _ctx("small_trace.pbtxt", tmp_path, monkeypatch)
    assert loader.metric(name).read(ctx) is None
    assert loader.metric(name).read(SimpleNamespace(trace=None)) is None


def test_every_new_metric_is_declared():
    spec = loader.benchmark()
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in EXPECTED:
        assert declared[name]["workloads"] == ["qwen3-8b-x2.coqa",
                                               "qwen3-8b-x4.coqa"]


#: the engine idle readers on two_chip_trace.pbtxt, by hand (ns -> ms): idle
#: is time in which no chip runs, so the chip that is not serving (and the
#: one that serves nothing in the window) does not add idle time
ON_FOUR_CHIPS = {
    # decode 300..560 holds TPU:0's 250..550, decode 700..980 TPU:1's
    # 650..950: idle 10 + 30 ns over 4 steps
    "engine.decode_idle_ms_per_tok": 10e-6,
    # serves outside their decode: 200..300 (busy from 250), 560..600,
    # 600..700 (busy from 650), 980..1000: idle 50 + 40 + 50 + 20 ns over
    # 2 serves
    "engine.prep_idle_ms_per_req": 80e-6,
}


def test_idle_on_several_chips(tmp_path, monkeypatch):
    ctx = _ctx("two_chip_trace.pbtxt", tmp_path, monkeypatch)
    assert program.idle_ns(ctx, [(200, 600)]) == 100
    assert program.idle_ns(ctx, [(0, 1000)]) == 1000 - 100 - 300 - 300


@pytest.mark.parametrize("name", sorted(ON_FOUR_CHIPS))
def test_engine_idle_reader_on_several_chips(name, tmp_path, monkeypatch):
    ctx = _ctx("two_chip_trace.pbtxt", tmp_path, monkeypatch)
    assert loader.metric(name).read(ctx) == pytest.approx(ON_FOUR_CHIPS[name])

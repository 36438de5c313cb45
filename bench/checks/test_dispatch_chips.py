"""`loop.dispatch_chips_per_batch` against small traces with known answers:
route batches served on two and three chips, with two engine serves that
overlap on two host threads; a program that writes no dispatch span; and
no program spans at all."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import loader

FIXTURES = Path(__file__).parent / "fixtures"
NAME = "loop.dispatch_chips_per_batch"
program = loader.module(loader.BENCH / "trace" / "program.py")
reduce = loader.module(loader.BENCH / "trace" / "reduce.py")


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


@pytest.mark.parametrize("fixture, want", [
    # batches on 2 and 3 chips; the one after the window left out
    ("dispatch_trace.pbtxt", 2.5),
    # routing, engine and feedback spans, no dispatch span: a program
    # that does not write it
    ("small_program_trace.pbtxt", None),
    # no iemas.* span at all
    ("small_trace.pbtxt", None),
])
def test_reader(fixture, want, tmp_path, monkeypatch):
    got = loader.metric(NAME).read(_ctx(fixture, tmp_path, monkeypatch))
    assert got == (None if want is None else pytest.approx(want))


def test_overlapping_serves_on_two_threads_are_both_read(tmp_path,
                                                         monkeypatch):
    ctx = _ctx("dispatch_trace.pbtxt", tmp_path, monkeypatch)
    serves = program.named(program.spans(ctx), "engine.serve")
    assert [(s.start, s.end, s.stats["chip"]) for s in serves] == [
        (60, 450, 0), (80, 480, 2)]
    # the two chips' work overlaps: busy 100..450 of the first serve
    assert program.idle_ns(ctx, [(60, 450)]) == 40


def test_untraced_run_reads_nothing():
    assert loader.metric(NAME).read(SimpleNamespace(trace=None)) is None


def test_declared():
    m, = [m for m in loader.benchmark()["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "chips/batch", "better": "higher",
        "source": "program_counter", "layer": "serving loop",
        "moves": "req_per_s"}
    # later cells may be appended to the list
    assert {"qwen3-8b-x2.coqa", "qwen3-8b-x4.coqa",
            "deepseek-v2-lite-x2.coqa"} <= set(m["workloads"])

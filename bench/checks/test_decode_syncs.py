"""`engine.decode_syncs_per_tok` against small traces with known answers:
decode spans that count their read-backs, decode spans that do not (a
program without the counter), and no program spans at all."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import loader

FIXTURES = Path(__file__).parent / "fixtures"
NAME = "engine.decode_syncs_per_tok"
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
    # 1 + 1 read-backs over 4 + 3 steps; the span after the window left out
    ("decode_syncs_trace.pbtxt", 2 / 7),
    # decode spans with ``steps`` alone: a program that does not count syncs
    ("small_program_trace.pbtxt", None),
    # no iemas.* span at all
    ("small_trace.pbtxt", None),
])
def test_reader(fixture, want, tmp_path, monkeypatch):
    got = loader.metric(NAME).read(_ctx(fixture, tmp_path, monkeypatch))
    assert got == (None if want is None else pytest.approx(want))


def test_untraced_run_reads_nothing():
    assert loader.metric(NAME).read(SimpleNamespace(trace=None)) is None


def test_declared():
    m, = [m for m in loader.benchmark()["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "syncs/tok", "better": "lower",
                 "source": "program_counter", "layer": "engine",
                 "moves": "req_per_s", "workloads": ["qwen3-8b-x2.coqa"]}

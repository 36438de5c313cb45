"""The trace reduction against a small trace with known answers."""
from pathlib import Path

import pytest

import loader

FIXTURE = Path(__file__).parent / "fixtures" / "small_trace.pbtxt"


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    reduce = loader.module(loader.BENCH / "trace" / "reduce.py")
    text = FIXTURE.read_text()
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    return reduce.Trace(pd)


def test_window_and_busy_union(trace):
    # window 50..1000 ns; ops 100..300 (holding the kernel 150..200) and
    # 500..600 inside it, one op after it
    assert trace.window_s == pytest.approx(950e-9)
    assert trace.busy_s() == pytest.approx(300e-9)


def test_device_time_inside_host_spans(trace):
    assert trace.busy_in("bench.route") == pytest.approx(200e-9)
    assert trace.busy_in("bench.serve") == pytest.approx(100e-9)
    assert trace.busy_in("bench.feedback") == 0.0


def test_kernel_events_by_name(trace):
    # by the instruction's own name, not an operand that mentions it
    assert trace.kernel_events("auction_bid") == [(150.0, 200.0)]
    assert trace.kernel_events("no_such_kernel") == []


def test_breakdown(trace):
    ops = dict((n, v) for n, v in trace.top_ops())
    assert ops == pytest.approx({"program:jit_program": 200e-9,
                                 "program:jit_decode_step": 100e-9,
                                 "op:fusion": 300e-9,
                                 "op:auction_bid": 50e-9})
    gaps = trace.idle_gaps()
    assert [g[0] for g in gaps] == ["feedback", "routing", "loop"]
    assert [g[1] for g in gaps] == pytest.approx([400e-9, 200e-9, 50e-9])


def test_idle_share_reader(trace):
    from types import SimpleNamespace

    ctx = SimpleNamespace(trace=trace)
    share = loader.metric("device.idle_share").read(ctx)
    assert share == pytest.approx(100.0 * (1 - 300 / 950))

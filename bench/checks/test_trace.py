"""The trace reduction against small traces with known answers: one chip,
and a four-chip cell whose two serving chips serve one after the other
while a third holds an agent that serves nothing and a fourth shows no
plane."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import loader

FIXTURES = Path(__file__).parent / "fixtures"


def _load(name: str, chips: int = 0):
    from jax.profiler import ProfileData

    reduce = loader.module(loader.BENCH / "trace" / "reduce.py")
    text = (FIXTURES / name).read_text()
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    return reduce.Trace(pd, chips)


@pytest.fixture(scope="module")
def trace():
    return _load("small_trace.pbtxt")


@pytest.fixture(scope="module")
def two_chips():
    return _load("two_chip_trace.pbtxt", chips=4)


def test_window_and_busy_union(trace):
    # window 50..1000 ns; ops 100..300 (holding the kernel 150..200) and
    # 500..600 inside it, one op after it
    assert trace.window_s == pytest.approx(950e-9)
    assert trace.busy_s() == pytest.approx(300e-9)
    assert trace.chips == 1


def test_device_time_inside_host_spans(trace):
    assert trace.busy_in_sum("bench.route") == pytest.approx(200e-9)
    assert trace.busy_in_sum("bench.serve") == pytest.approx(100e-9)
    assert trace.busy_in_sum("bench.feedback") == 0.0


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
    ctx = SimpleNamespace(trace=trace)
    share = loader.metric("device.idle_share").read(ctx)
    assert share == pytest.approx(100.0 * (1 - 300 / 950))


def test_two_chips_busy(two_chips):
    # each chip's own union: TPU:0 100 + 300 ns, TPU:1 300 ns, TPU:2 none
    # in the window; averaged over the cell's four chips
    assert two_chips.window_s == pytest.approx(1000e-9)
    assert two_chips.chips == 4
    assert two_chips.busy_s() == pytest.approx(700e-9 / 4)
    assert two_chips.busy_by_device() == pytest.approx(
        {"/device:TPU:0": 400e-9, "/device:TPU:1": 300e-9,
         "/device:TPU:2": 0.0})
    # any chip busy: 50..150, 250..550, 650..950
    assert two_chips.busy_union().tolist() == [[50, 150], [250, 550],
                                               [650, 950]]
    # summed over the chips: the route program runs on TPU:0 alone, and
    # each serve on its own agent's chip
    assert two_chips.busy_in_sum("bench.route") == pytest.approx(100e-9)
    assert two_chips.busy_in_sum("bench.serve") == pytest.approx(600e-9)


def test_silent_chips_count():
    # without the cell's count, every chip the trace shows counts, also one
    # that ran nothing in the window; a chip with no plane only by the count
    three = _load("two_chip_trace.pbtxt")
    assert three.chips == 3
    assert three.busy_s() == pytest.approx(700e-9 / 3)
    # a count below what the trace shows does not hide a chip
    assert _load("two_chip_trace.pbtxt", chips=1).chips == 3


def _ctx(trace):
    """Two served requests, each counted as 1e5 operations and 1.5e5 bytes,
    on a device of 1e12 FLOP/s and 1e12 B/s."""
    return SimpleNamespace(
        trace=trace, model={}, served=[{"n_prompt": 8, "n_hit": 0,
                                        "n_gen": 2}] * 2,
        counts=SimpleNamespace(served_request=lambda *a: (1e5, 1.5e5)),
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12})


#: the changed readers by hand: (one chip as before this change, the
#: four-chip cell of two_chip_trace)
READINGS = {
    # 2e5 operations over the window times the cell's chips: 950 ns x 1
    # chip; 1000 ns x 4 chips (two of which serve nothing)
    "model.mfu": (100 * 2e5 / (950e-9 * 1e12), 100 * 2e5 / (4e-6 * 1e12)),
    # 3e5 bytes over device-seconds inside serve spans: 100 ns; 300 + 300
    # ns (the average over the two chips, 300 ns, would read double)
    "model.hbm_roofline": (100 * 3e5 / (100e-9 * 1e12),
                           100 * 3e5 / (600e-9 * 1e12)),
    # device-seconds inside the one route span: 200 ns; 100 ns on TPU:0
    # (the average would read half)
    "fused.device_ms_per_batch": (200e-6, 100e-6),
    # 1 - busy / window, busy averaged over the cell's chips: 300 / 950;
    # (400 + 300 + 0 + 0) / 4 / 1000 (the headline reading on four chips)
    "device.idle_share": (100 * (1 - 300 / 950), 100 * (1 - 175 / 1000)),
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_readers_per_chip(name, trace, two_chips):
    metric = loader.metric(name)
    one, two = READINGS[name]
    assert metric.read(_ctx(trace)) == pytest.approx(one)
    assert metric.read(_ctx(two_chips)) == pytest.approx(two)

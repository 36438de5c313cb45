"""The grouped expert kernel's per-layer metrics (`metrics/moe.*`) against
a small trace with known answers (``fixtures/moe_trace.pbtxt``), left out
where the program has no such kernel or its spans no MoE counters."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import loader

FIXTURES = Path(__file__).parent / "fixtures"
program = loader.module(loader.BENCH / "trace" / "program.py")
reduce = loader.module(loader.BENCH / "trace" / "reduce.py")
MODEL = loader.load_json(FIXTURES / "tiny-dsv2.json")
PEAKS = {"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 4e12}
NAMES = ("moe.expert_ffn_roofline", "moe.expert_ffn_share")


def _ctx(fixture: str, tmp_path, monkeypatch, text=None):
    """The traced run's context, its trace (the fixture, or ``text``)
    written where the harness writes one."""
    from jax.profiler import ProfileData

    raw = ProfileData.text_proto_to_serialized_xspace(
        text or (FIXTURES / fixture).read_text())
    (tmp_path / "run.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(program, "TRACE_DIR", tmp_path)
    return SimpleNamespace(
        trace=reduce.Trace(ProfileData.from_serialized_xspace(raw)),
        model=MODEL, peaks=PEAKS)


def test_share(tmp_path, monkeypatch):
    # moe_gmm inside the serves: 100 + 50 + 100 + 40 ns (the one at 1700
    # lies outside); device busy inside them: 350 + 240 ns
    ctx = _ctx("moe_trace.pbtxt", tmp_path, monkeypatch)
    got = loader.metric("moe.expert_ffn_share").read(ctx)
    assert got == pytest.approx(100.0 * 290 / 590)


def test_roofline(tmp_path, monkeypatch):
    # rows 30 + 12 + 18 + 6 and experts 10 + 10 + 9 + 6 over the spans, at
    # the fixture's widths (hidden 64, expert width 32): 2·3·64·32 per row,
    # the experts' weights and the rows in and out in bfloat16
    ops = 2 * 3 * 64 * 32 * 66
    nbytes = 2 * (35 * 3 * 64 * 32 + 2 * 66 * 64)
    least = max(ops / PEAKS["bf16_flops_per_s"],
                nbytes / PEAKS["hbm_bytes_per_s"])
    ctx = _ctx("moe_trace.pbtxt", tmp_path, monkeypatch)
    got = loader.metric("moe.expert_ffn_roofline").read(ctx)
    assert got == pytest.approx(100.0 * least / 290e-9)
    assert got < 100.0


@pytest.mark.parametrize("name", NAMES)
def test_silent_without_the_kernel(name, tmp_path, monkeypatch):
    # a dense model's trace: no moe_gmm operation, no MoE counters
    ctx = _ctx("small_program_trace.pbtxt", tmp_path, monkeypatch)
    assert loader.metric(name).read(ctx) is None
    assert loader.metric(name).read(SimpleNamespace(trace=None,
                                                    model=None)) is None


def test_roofline_silent_without_counters(tmp_path, monkeypatch):
    # the kernel ran, but the engine spans carry no MoE counters
    text = (FIXTURES / "moe_trace.pbtxt").read_text().replace(
        '"moe_rows"', '"rows"')
    ctx = _ctx(None, tmp_path, monkeypatch, text=text)
    assert loader.metric("moe.expert_ffn_roofline").read(ctx) is None


def test_declared():
    spec = loader.benchmark()
    declared = {m["name"]: m for m in spec["per_layer"]}
    for name in NAMES:
        m = declared[name]
        assert (m["source"], m["layer"], m["moves"], m["workloads"]) == (
            "device_trace", "expert FFN kernel", "req_per_s",
            ["deepseek-v2-lite-x2.coqa"])

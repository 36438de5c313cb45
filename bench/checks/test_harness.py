"""The harness end to end on the CPU at a tiny size: it refuses to report
off the chip, it finds a cell defined by added files alone, and ``correct``
comes out false when the timed path is broken underneath."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness
import loader

FIX = loader.BENCH / "checks" / "fixtures"


def _run_py(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not any('"metrics"' in ln for ln in lines)


def test_refuses_without_a_chip():
    cell = loader.benchmark()["workloads"][0]["name"]
    proc = _run_py(loader.ROOT, "--workload", cell, "--seed",
                   str(2**31 + 5), "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and _no_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_in_a_bare_checkout(tmp_path):
    shutil.copy(loader.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(loader.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = loader.benchmark()["workloads"][0]["name"]
    proc = _run_py(tmp_path, "--workload", cell, "--seed",
                   "1", "--seconds", "1", "--trace", "1")
    assert proc.returncode != 0 and _no_result(proc)


#: tiny cells: configuration, traffic and the devices the cell asks for
TINY = {"served": ("tiny-x2.json", "tiny-coqa.json", 1),
        "served4": ("tiny-x4.json", "tiny-coqa32.json", 4),
        "fleet": ("tiny-fleet.json", "tiny-open.json", 1)}


def _run(kind, hook=None, seed=2**31 + 77, trace=False, controls=None):
    config_file, traffic_file, chips = TINY[kind]
    config = loader.load_json(FIX / config_file)
    traffic = loader.load_json(FIX / traffic_file)
    cell = {"name": "tiny", "chips": chips}
    return harness.run_cell(loader.benchmark(), cell, config, traffic, seed,
                            2.0, trace, time.perf_counter(),
                            require_chip=False, hook=hook, controls=controls)


def test_cell_from_added_files(tmp_path):
    """A cell whose configuration and traffic exist only as new files."""
    spec = loader.benchmark()
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    shutil.copy(FIX / "tiny-fleet.json",
                tmp_path / "bench" / "configs" / "tiny-added.json")
    shutil.copy(FIX / "tiny-open.json",
                tmp_path / "bench" / "traffic" / "tiny_added.json")
    spec["configs"].append({"name": "tiny-added", "source": "x",
                            "file": "bench/configs/tiny-added.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "tiny-added.open",
                              "config": "tiny-added", "traffic": "tiny_added",
                              "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell, config, traffic = loader.cell(loader.benchmark(tmp_path),
                                        "tiny-added.open", root=tmp_path)
    res = harness.run_cell(spec, cell, config, traffic, 3, 2.0, False,
                           time.perf_counter(), require_chip=False)
    assert res["correct"] and res["window"]["served"] > 0
    assert set(res["metrics"]) == {"req_per_s", "route_ms_p95",
                                   "cost_per_req", "setup_s"}


@pytest.mark.parametrize("kind", ["served", "fleet"])
def test_sound_run_is_correct(kind):
    res = _run(kind, trace=kind == "served")
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("kind", ["served", "fleet"])
def test_controls_are_not_correct(kind):
    """Each control, in the program's place, is judged by the same
    comparison as the program (`harness.judge`) and comes out not correct.
    At this size the tiny configuration's own logit limit (0.03) holds:
    the program read 0 to 0.011 and the float8 control 0.074 to 0.18 on
    seven CPU runs."""
    from control_run import CONTROLS

    res = _run(kind, seed=2**31 + 91, controls=CONTROLS)
    assert res["correct"], res["checks"]
    ctl = res["control_checks"]
    assert set(ctl) == ({"bf16", "fp8"} if kind == "served" else {"bf16"})
    for name, c in ctl.items():
        assert c["correct"] is False, (name, c["checks"])


def test_judge():
    limits = {"a": 0.5, "b": 0}
    assert harness.judge({"a": 0.5, "b": 0.0}, limits)[1] is True
    assert harness.judge({"a": 0.6, "b": 0.0}, limits)[1] is False
    assert harness.judge({"b": 1e-9}, limits)[1] is False
    assert harness.judge({"c": 9.0}, limits) == ({}, False)


def _alter_tokens(system):
    """The engine's served tokens altered where they are produced."""
    for rt in system.cluster.agents.values():
        serve = rt.engine.serve

        def altered(*a, _serve=serve, **kw):
            res = _serve(*a, **kw)
            res.output_tokens = res.output_tokens.copy()
            res.output_tokens[1:] = (res.output_tokens[1:] + 1) % 511 + 1
            return res

        rt.engine.serve = altered


def _alter_answers(system):
    """The router's payments altered where route_batch produces them."""
    route = system.router.route_batch

    def altered(*a, **kw):
        out = route(*a, **kw)
        for d in out:
            if d.agent_id is not None:
                d.payment = d.payment * 1.01 + 1e-3
                break
        return out

    system.router.route_batch = altered


def _alter_ledger(system):
    """Phase 1's prefix ledger altered where Phase 4 writes it: each
    recorded prompt loses its last token."""
    ledger = system.router.ledger
    update = ledger.update

    def altered(agent_id, dialogue_id, prompt_tokens):
        update(agent_id, dialogue_id, prompt_tokens[:-1])

    ledger.update = altered


@pytest.mark.parametrize("kind,fault", [("served", _alter_tokens),
                                        ("served", _alter_answers),
                                        ("served", _alter_ledger),
                                        ("fleet", _alter_answers),
                                        ("fleet", _alter_ledger)])
def test_broken_path_is_not_correct(kind, fault):
    res = _run(kind, hook=fault)
    assert res["correct"] is False
    failed = [n for n, c in res["checks"].items() if c["value"] > c["limit"]]
    assert failed, res["checks"]


@pytest.mark.parametrize("kind", ["served4", "served"])
def test_agents_on_the_cells_own_devices(kind):
    """Agent i's weights and session caches sit on device i mod the cell's
    devices: four agents on four devices each on its own, and two agents
    of a one-device cell both on device 0, though the CPU shows four and
    the program's cluster first places its own weights round-robin."""
    import jax

    assert len(jax.devices()) >= 4, "bench/checks/conftest.py sets four"
    chips = TINY[kind][2]
    res = _run(kind)
    assert res["correct"], res["checks"]
    placement = res["window"]["placement"]
    assert len(placement) == (4 if kind == "served4" else 2)
    for i, where in enumerate(placement.values()):
        assert where["weights"] == [i % chips], placement
        assert where["sessions"] in ([], [i % chips]), placement
    # 32 dialogues in flight outgrow one agent's 12 slots and spread
    held = sum(bool(w["sessions"]) for w in placement.values())
    assert held >= (2 if kind == "served4" else 1), placement
    # what each agent served in the window adds up to the window's count
    by_agent = res["window"]["by_agent"]
    assert list(by_agent) == list(placement)
    assert sum(a["served"] for a in by_agent.values()) \
        == res["window"]["served"]
    for a in by_agent.values():
        assert (a["kv_hit_share"] is None) == (a["served"] == 0)


def _compile_loop_in_window(system):
    """The first serve inside the window asks for more tokens than the
    warm pass did, so the greedy-loop program compiles its next output
    size there."""
    forced = []
    for rt in system.cluster.agents.values():
        serve = rt.engine.serve

        def more_tokens(*a, _serve=serve, **kw):
            if system.rec.active and not forced:
                forced.append(True)
                kw["max_new_tokens"] = 9      # output bucket 16, not 8
            return _serve(*a, **kw)

        rt.engine.serve = more_tokens


def test_loop_compile_in_window_is_counted():
    res = _run("served", hook=_compile_loop_in_window)
    assert res["window"]["engine_compiles"] >= 1
    assert any(e["where"] == "engine" and e["in_window"]
               for e in res["window"]["compile_events"])

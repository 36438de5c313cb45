"""Read a cell's compared numbers for the program and for its controls, on
the chip, at the cell's own size and load, over several seeds in one process.

    python3 bench/checks/control_run.py --workload <cell> --seeds 11,12,13 \
        --seconds 10 [--trace 1]

For each seed the cell runs as the benchmark runs it (set-up, warm pass,
window), then checks what the window produced twice: as the program made it,
and with the reference put in the program's place one precision step below
the configuration's (routing: bfloat16 inputs for the float32 router;
engine: float8 e4m3 projections for the bfloat16 model; Phase-1 affinity
in bfloat16). Each control is judged by the harness's own comparison
(`harness.judge`) against the configuration's limits, as the program is.
One JSON line per seed: ``{"seed", "correct", "program": {...}, "controls":
{<control>: {"correct", "numbers"}}, "metrics", "device", "window"}``.
With ``--trace 1`` the window is traced as in a benchmark run and
``metrics`` are the cell's per-layer metrics, so one set-up gives both
readings. Exits non-zero unless the program reads correct and every
control reads not correct on every seed. The limits in the configuration files are set between these
readings (see PERF.md). The benchmark's own runs never run the controls.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import harness  # noqa: E402
import loader  # noqa: E402

CONTROLS = {"routing": ("bf16",), "engine": ("fp8",)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = loader.benchmark()
    cell, config, traffic = loader.cell(spec, args.workload)
    separated = True
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(spec, cell, config, traffic, seed,
                               args.seconds, bool(args.trace),
                               time.perf_counter(),
                               controls=CONTROLS)
        ctl = res["control_checks"]
        separated &= res["correct"] and not any(c["correct"]
                                                for c in ctl.values())
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program": {k: v["value"] for k, v in res["checks"].items()},
            "controls": {name: {"correct": c["correct"],
                                "numbers": {k: v["value"] for k, v
                                            in c["checks"].items()}}
                         for name, c in ctl.items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "device": res["device"], "breakdown": res.get("breakdown"),
            "window": res["window"]}), flush=True)
    print(f"control_run: program correct and every control not correct on "
          f"every seed: {bool(separated)}", file=sys.stderr)
    return 0 if separated else 1


if __name__ == "__main__":
    sys.exit(main())

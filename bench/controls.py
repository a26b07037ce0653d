"""Readings of a cell's compared numbers for its limits: the program's,
from whole runs, and the control's, the plain reference computed in
bfloat16 in the program's place on the same inputs.

    python3 bench/controls.py --workload <cell> --seeds 11,12,13 --seconds 10

One process runs every seed in turn, on the chip (it takes the same gate
as ``run.py``), and prints one JSON line per seed:
``{"seed", "correct", "program": {number: value}, "control": {...}}``.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as bench_run  # noqa: E402
from harness import device, registry  # noqa: E402
from harness.record import Recorder  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)
    bench = registry.spec()
    cell = registry.workload(bench, args.workload)
    try:
        devs = device.gate(cell["chips"])
    except device.NoChip as e:
        print(f"controls: {e}", file=sys.stderr)
        return 2
    device.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        config = registry.config(bench, cell["config"])
        traffic = registry.traffic(cell["traffic"])
        bench_run.apply_overrides(config, traffic, args.set)
        ctx = bench_run.Ctx(cell=cell, config=config, traffic=traffic,
                            seed=seed, seconds=args.seconds,
                            limits=registry.measured_limits(cell["name"]),
                            rec=Recorder(),
                            peaks=device.peaks(devs[0].device_kind))
        line = bench_run.run_cell(ctx, devs, False)
        print(json.dumps({
            "seed": seed, "correct": line["correct"],
            "program": {k: v["value"] for k, v in line["checks"].items()},
            "control": ctx.loop.control()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

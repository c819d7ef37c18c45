"""Readings that the limits in ``limits/<cell>.json`` are set from; the
benchmark's own runs do not run this.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--control-seeds 3]
                             [--fault <name>]

For each seed, in one process (the steps compile once): set-up as in a
run, a short window at the cell's own load (enough rounds to finish as
many requests as a run compares, or just set-up's first steps when
training), then the comparison with the plain reference. The first
``--control-seeds`` seeds also read the control: the reference in the
program's place at int8, one step below the bfloat16 the configuration
states. ``--fault`` plants one of ``faults.py``'s faults in the
program's step instead. One JSON line per seed; for training it also
holds each step's loss of the program, the reference and the control.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import faults, harness  # noqa: E402
from bench.drivers import program  # noqa: E402


def readings(cell, seed: int, control: bool) -> dict:
    drv = harness.driver(cell.traffic["kind"]).Driver(cell, seed)
    t0 = time.perf_counter()
    drv.setup()
    if cell.traffic["kind"] == "serve":
        drv.window(rounds=math.ceil(cell.traffic["check"]["requests"] / drv.B))
    drv.release()
    t1 = time.perf_counter()
    numbers = drv.check(control=control)
    losses = {"losses": drv.losses} if hasattr(drv, "losses") else {}
    return {"seed": seed, **numbers, **losses, "program_s": t1 - t0,
            "check_s": time.perf_counter() - t1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.fault:
        kind = cell.traffic["kind"]
        program.steps = lambda: faults.steps_with(kind, args.fault)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": cell.name, "fault": args.fault,
                          **readings(cell, seed, i < args.control_seeds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

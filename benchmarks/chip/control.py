#!/usr/bin/env python3
"""Read a cell's compared numbers for the program and for its control.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 11,12,13 --seconds <s> [--variant program|control]

Runs the cell once per seed in this one process, with the program (the
lower readings a limit is set from) or with the cell's control put in the
program's place (the upper readings), and prints one JSON line per seed
with every compared number.  The benchmark's own runs never run the
control.  Run it on the chip, at the cell's own size.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--variant", choices=("program", "control"),
                    default="control")
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = harness.run_cell(args.workload, seed, args.seconds, False,
                                 variant=args.variant)
        except harness.NoChip as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "variant": args.variant,
                          "seed": seed, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

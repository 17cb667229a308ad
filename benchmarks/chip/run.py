#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  Set-up (JAX start-up, data made on the device from ``--seed``,
warm-up of every shape the cell uses) is timed as ``setup_s``; then whole
units of the cell's traffic run for ``--seconds``.  With ``--trace 0`` the
result line carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.  After the
window the program's state is freed and a sample of its answers is
compared with the cell's plain reference: each compared number goes to
standard error beside its limit, and into the result line under
``checks``.  The last line of standard output is the result, one JSON
object.  With no TPU, or fewer chips than the cell needs, the command
exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(HERE.parents[1] / "src"))

import harness  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    harness.enable_compile_cache()
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace))
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

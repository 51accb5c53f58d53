"""Command line of the benchmark (see `bench/run.py`)."""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one benchmark cell on the chip and print its "
                    "result as the last line of standard output.")
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the device and report per-layer metrics")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: judge the int8 control's tokens in the "
                         "program's place (reads not correct); the "
                         "program's own readings go to stderr")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from .driver import Fault, run_cell
    from .spec import Bench

    try:
        return run_cell(Bench(ROOT), args.workload, args.seed, args.seconds,
                        trace=bool(args.trace), control=bool(args.control))
    except Fault as e:
        print(f"bench: {e}", file=sys.stderr)
        return 4

#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` at the root of the checkout, finds the cell's
configuration (``bench/configs/``), traffic mix (``bench/mixes/``) and
per-layer metric readers (``bench/metrics/``) by name, and runs it on the
machine it is started on.  Earlier lines on standard error give the run's
notes and each number compared with its limit; the last line of standard
output is one JSON object.  Without an accelerator, or with fewer chips
than the cell asks for, it exits with 3 and prints no result.

``--control bfloat16`` puts the reference computed in bfloat16 in place of
the served answers: the comparison must then come out not correct.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bfloat16",), default=None)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    from bench.cell import Cell, NoAccelerator, execute

    cell = Cell.find(args.workload, ROOT)
    try:
        result = execute(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START, control=args.control)
    except NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains: one set-up, then the
cell's traffic at each rate in turn, each for ``--seconds``.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds 20 --rates 4,8,16

Prints one line per rate: the offered and completed read rates, the read
latency's median and 95th percentile, freshness, the generator's lateness,
and how far the latency grew from the first third of the window to the
last (a backlog that grows through the window).  The state carries over
from one rate to the next, as it does in a long-lived service.
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
    ap.add_argument("--rates", required=True,
                    help="comma-separated operation rates per second")
    args = ap.parse_args(argv)

    import numpy as np

    from bench.cell import Cell, NoAccelerator, Run, quantile, \
        require_accelerator
    from bench.traffic import Traffic

    cell = Cell.find(args.workload, ROOT)
    if cell.mix["loop"] != "open":
        ap.error(f"{args.workload} is not an open-loop cell")
    try:
        require_accelerator(cell.workload["chips"])
    except NoAccelerator as e:
        print(f"sweep: {e}; nothing was run", file=sys.stderr)
        return 3
    run = Run(cell, args.seed, args.seconds, False, t_start=T_START)
    run.setup()
    print(json.dumps({"setup_s": run.setup_s}), flush=True)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        run.mix = dict(cell.mix, rate_per_s=rate)
        run.traffic = Traffic(run.mix, run.config, args.seed + k + 1)
        run.reads, run.lateness, run.notes, run.compiles = [], [], {}, 0
        for w in run.writes:
            w.in_window = False
        run._open_loop()
        e2e = run.end_to_end()
        win = [r for r in run.reads if r.in_window and r.ok]
        lat = np.array([r.completed - r.due for r in win])
        third = max(len(lat) // 3, 1)
        print(json.dumps({
            "rate_per_s": rate,
            "reads_offered_per_s": rate * cell.mix["ops"][0]["share"],
            **{k2: v for k2, v in e2e.items() if k2 != "setup_s"},
            "lateness_ms": run.notes.get("lateness_ms"),
            "p50_first_third_ms": quantile(lat[:third], 0.5) * 1e3,
            "p50_last_third_ms": quantile(lat[-third:], 0.5) * 1e3,
            "writes": run.notes.get("writes"),
            "compiles_in_window": run.compiles,
            "executor_retraces": run.notes.get("executor_retraces"),
            "failed": sum(not r.ok for r in run.reads if r.in_window),
        }), flush=True)
    run.service.stop(drain=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

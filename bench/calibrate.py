"""Readings that set a serving cell's ``correct`` limits, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,...,12 --control 3

For each seed, in one process: a run of the cell as the benchmark makes it
(the window at the cell's own load, then the comparison with the float32
reference), printing the program's readings and its ``checks``. For the
first ``--control`` seeds the runner's ``control`` puts the control
(:mod:`bench.reference.dense` at ``CONTROL``, one step below each stated
precision) in the program's place on the same sample and judges it by the
same checks: its ``correct`` has to come out false. The benchmark's own runs
never run the control. Prints one JSON line per seed; the limits and
readings are kept in ``PERF.md``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(gaps) -> dict:
    import numpy as np

    return {"max_gap": float(np.nanmax(gaps)),
            "mean_gap": float(np.nanmean(gaps)),
            "flip_share": float(np.nanmean(gaps > 0)),
            "tokens": int(np.isfinite(gaps).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from bench.run import enable_compile_cache

    cell = harness.load_cell(args.workload)
    harness.device_info(cell.spec["chips"])
    enable_compile_cache()
    log = lambda msg: print(msg, file=sys.stderr, flush=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        res = cell.runner.run(cell, seed=seed, seconds=args.seconds,
                              trace=False, t_start=time.perf_counter(),
                              log=log)
        out = {"seed": seed, "correct": res.correct, "checks": res.checks,
               "attempted": res.attempted, "e2e": res.e2e,
               "memory_peak_bytes": res.memory_peak_bytes,
               "program": readings(res.gaps)}
        if i < args.control:
            checks, correct = cell.runner.control(cell, res, seed)
            out["control"] = {"correct": correct, "checks": checks}
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

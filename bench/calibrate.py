#!/usr/bin/env python3
"""Read the numbers that a cell's limits are set from, on the chip.

    python3 bench/calibrate.py --workload granite.chat --seconds 20 \\
        --seeds 11 12 13 --control-seeds 11 12 13

For each seed, one process runs the cell as a run does (set-up, a window
of ``--seconds`` at the cell's own load, the same sample) and prints the
program's compared number; for each control seed it also prints the
control's: the plain reference in the program's place at the precision
below the configuration's (serving: the float8 forward's own top token,
judged by the float32 reference; prefix sums: the float64 sum of the
input rounded to bfloat16). One JSON line per seed. The limit goes above
the program's largest reading and below the control's smallest.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as brun  # noqa: E402  (puts src/ on the path)
from bench import spec  # noqa: E402
from bench.reference import prefix_sum  # noqa: E402


def serve_readings(driver, state, run, control: bool) -> dict:
    conf = state.conf
    picked = driver.sample(state, run.seed, conf["check"]["tokens"])
    driver.free_program(state)
    out = {}
    for name, ctl in (("program", False), ("control", True)):
        if ctl and not control:
            continue
        g = np.concatenate(driver.gaps(conf, run.seed, picked, control=ctl))
        out[name] = {"max_logit_gap": float(g.max()),
                     "tokens": int(g.size),
                     "share_nonzero": float(np.mean(g > 0)),
                     "p99": float(np.quantile(g, 0.99))}
    return out


def scan_readings(driver, state, run, control: bool) -> dict:
    x = np.asarray(state.x)
    y = np.asarray(state.last)
    state.x = state.last = state.kept = state.compiled = None
    out = {"program": {"cumsum_rel_err": prefix_sum.rel_err(x, y)}}
    if control:
        out["control"] = {"cumsum_rel_err": prefix_sum.rel_err(
            x, prefix_sum.control_answer(x))}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, spec.benchmark())
    try:
        _, peaks = brun.prepare(cell)
    except brun.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return brun.NO_CHIP
    driver = cell.driver()
    read = (serve_readings if cell.traffic["driver"] == "serve_open_loop"
            else scan_readings)
    for seed in args.seeds:
        run = brun.Run(cell=cell, seed=seed, seconds=args.seconds,
                       trace=False, peaks=peaks)
        state = driver.setup(run)
        driver.measure(state, run, brun.Tracer(False))
        r = read(driver, state, run, seed in args.control_seeds)
        print(json.dumps({"workload": cell.name, "seed": seed, **r}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

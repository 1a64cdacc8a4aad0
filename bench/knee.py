#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest arrival rate it sustains.

    python3 bench/knee.py --workload granite.chat --seed 7 --seconds 30 \\
        --rates 0.8 1.2 1.6 2.0

Runs the cell's mix at each rate for ``--seconds`` in one process (on the
chip; it exits 3 without one) and prints one JSON line per rate: requests
due, those answered in the window, tokens/s, TTFT median and 95th
percentile, and the waiting queue at the close. The highest rate whose
queue does not grow through the window is the knee; the cell's mix runs
at about four fifths of it. Not part of a run: a benchmark PR that moves
a serving mix's rate runs it once and records the sweep in PERF.md.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run as brun  # noqa: E402  (puts src/ on the path)
from bench import spec, stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload, spec.benchmark())
    try:
        _, peaks = brun.prepare(cell)
    except brun.NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return brun.NO_CHIP
    driver = cell.driver()
    for rate in args.rates:
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   rate_per_s=rate))
        run = brun.Run(cell=c, seed=args.seed, seconds=args.seconds,
                       trace=False, peaks=peaks)
        state = driver.setup(run)
        driver.measure(state, run, brun.Tracer(False))
        ttft, _, tokens = driver.latencies(state)
        waiting = len(state.eng.waiting)
        answered = sum(1 for r in state.live.values()
                       if r.output and r.output.times[0] <= state.close)
        print(json.dumps({
            "rate_per_s": rate, "due": len(state.reqs),
            "first_token_in_window": answered,
            "waiting_at_close": waiting,
            "tokens_per_s": tokens / args.seconds,
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "compiles_in_window": state.compiles_in_window}), flush=True)
        driver.free_program(state)
    return 0


if __name__ == "__main__":
    sys.exit(main())

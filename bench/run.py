#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared for
``correct`` with its limit. The same checks end standard error.

The run needs the chips the cell asks for. With no accelerator, too few
chips, or a device missing from ``bench/peaks.json`` it exits 3 and
prints no result; it never falls back to the CPU. Set-up (process start
to the first timed call: imports, inputs and weights made from the seed,
compilation or the persistent compile cache, warm-up of every shape the
window uses) is ``setup_s``; nothing compiles inside the window.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec, trace_reduce  # noqa: E402

#: Exit code when the machine lacks the cell's chips.
NO_CHIP = 3
#: Where compiled programs persist between runs of this checkout: a fixed
#: path, because the path is part of what an entry is found by.
CACHE_DIR = os.path.join(_ROOT, ".jax_cache")


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    """What a driver is given: the cell, the run's arguments and the
    device's peaks."""

    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    peaks: dict
    t0: float = _T0


class Tracer:
    """Starts and stops the profiler around the part of the window the
    driver chooses; ``--trace 0`` makes both calls no-ops."""

    def __init__(self, on: bool):
        self.on = on
        self.dir: Optional[str] = None
        self._mark: Optional[Any] = None
        self.begun: Optional[float] = None

    def begin(self) -> None:
        if not self.on or self.dir is not None:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host spans only
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._mark.__enter__()
        self.begun = time.perf_counter()

    def end(self) -> None:
        if self._mark is None:
            return
        import jax
        self._mark.__exit__(None, None, None)
        self._mark = None
        jax.profiler.stop_trace()

    def close(self) -> None:
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


def device_record(chips: int, peaks_table: dict) -> Tuple[dict, dict]:
    """The device as JAX reports it, and its peaks. Raises ``NoChip``."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform == "cpu":
        raise NoChip("JAX found no accelerator (platform 'cpu'); this "
                     "benchmark runs only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if d.device_kind not in peaks_table["devices"]:
        raise NoChip(f"no peaks for device kind {d.device_kind!r} in "
                     f"bench/peaks.json")
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devs)}, peaks_table["devices"][d.device_kind])


def enable_compile_cache(path: str = CACHE_DIR) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile, so warm set-up is steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def prepare(cell: spec.Cell) -> Tuple[dict, dict]:
    """Check for the cell's chips and turn the compile cache on; returns
    the device record and its peaks. Raises ``NoChip``."""
    import repro.core.scan  # noqa: F401  (the program under test, first)
    found = device_record(cell.chips, spec.load_json(
        os.path.join(spec.BENCH_DIR, "peaks.json")))
    enable_compile_cache()
    return found


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def layer_metrics(run: Run, reduced, facts: dict) -> Dict[str, dict]:
    """Each per-layer metric of the cell, from its reader; a reader that
    finds nothing returns None and the metric is left out."""
    ctx = types.SimpleNamespace(trace=reduced, facts=facts, peaks=run.peaks,
                                run=run)
    out = {}
    for m in run.cell.per_layer:
        value = spec.reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def breakdown(reduced) -> dict:
    ops = sorted(reduced.op_ns().items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(reduced.gaps().items(), key=lambda kv: -kv[1][0])[:10]
    return {"device_ops": [[k, v / 1e9] for k, v in ops],
            "idle_gaps": [[f"{k} x{n}", v / 1e9] for k, (v, n) in gaps]}


def run_cell(run: Run, device: dict) -> dict:
    """Set up, measure, reduce the trace, check; return the result."""
    driver = run.cell.driver()
    tracer = Tracer(run.trace)
    try:
        state = driver.setup(run)
        driver.measure(state, run, tracer)
        tracer.end()
        device = dict(device, memory_peak_bytes=memory_peak_bytes(
            run.cell.chips))
        metrics: Dict[str, dict] = {}
        extra: Dict[str, Any] = {}
        facts = driver.facts(state)
        if run.trace:
            reduced = trace_reduce.read_xplane(tracer.dir,
                                               driver.SPAN_NAMES)
            metrics = layer_metrics(run, reduced, facts)
            device["busy_s"] = reduced.busy_ns() / 1e9
            device["window_s"] = reduced.window_ns / 1e9
            extra["breakdown"] = breakdown(reduced)
            del reduced
        else:
            values = dict(driver.end_to_end(state))
            values["setup_s"] = facts["setup_s"]
            for m in run.cell.end_to_end:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
        attempted, failed = facts["attempted"], facts["failed"]
        checks = driver.check(state, run)      # frees the program's state
    finally:
        tracer.end()
        tracer.close()
    gc.collect()
    correct = bool(checks) and all(v <= lim for _, v, lim in checks) \
        and not facts.get("incorrect")
    return {"correct": correct, "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device,
            **extra,
            "checks": {n: {"value": v, "limit": lim}
                       for n, v, lim in checks}}


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILS"
        print(f"check {name}: {c['value']!r} <= limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    cell = spec.cell(args.workload, spec.benchmark())
    try:
        device, peaks = prepare(cell)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return NO_CHIP
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), peaks=peaks)
    print_result(run_cell(run, device))
    return 0


if __name__ == "__main__":
    sys.exit(main())

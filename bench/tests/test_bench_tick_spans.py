"""The trace reduction on a tick that carries the serve engine's phase
spans (``serve.step`` and its phases, which ``obs/trace.py`` puts on the
profiler's clock), on a small trace made in the test."""

import types

import pytest

from bench import spec, trace_reduce

MS = 1_000_000  # ns


def _tick_trace(program_spans):
    """One engine tick in a 200 ms window, then a wait. The benchmark's
    ``serve.tick`` holds 0-100 ms; with ``program_spans`` the engine's
    ``serve.step`` holds 1-99 ms and its phases: admit 1-20 (a prefill
    5-15 running 6-14 on the device), decode 20-25 and sync 25-60 (the
    decode program runs 22-58), sample 60-70 (an op 61-63), commit 70-95
    (two token writes, 72-73 and 80-81), and 95-99 in no phase."""
    ops = [("%fusion.9 = f", 6 * MS, 8 * MS),
           ("%while.1 = f", 22 * MS, 36 * MS),
           ("%argmax.2 = f", 61 * MS, 2 * MS),
           ("%dynamic-update-slice.3 = f", 72 * MS, 1 * MS),
           ("%dynamic-update-slice.3 = f", 80 * MS, 1 * MS)]
    modules = [("jit_fn(1)", 6 * MS, 8 * MS),
               ("jit_step(2)", 22 * MS, 36 * MS),
               ("jit_argmax(3)", 61 * MS, 2 * MS),
               ("jit__set(4)", 72 * MS, 1 * MS),
               ("jit__set(4)", 80 * MS, 1 * MS)]
    spans = [(trace_reduce.WINDOW_SPAN, 0, 200 * MS),
             ("serve.tick", 0, 100 * MS), ("gen.wait", 100 * MS, 100 * MS)]
    if program_spans:
        spans += [("serve.step", 1 * MS, 98 * MS),
                  ("serve.admit", 1 * MS, 19 * MS),
                  ("serve.prefill", 5 * MS, 10 * MS),
                  ("serve.decode", 20 * MS, 5 * MS),
                  ("serve.sync", 25 * MS, 35 * MS),
                  ("serve.sample", 60 * MS, 10 * MS),
                  ("serve.commit", 70 * MS, 25 * MS)]
    return trace_reduce.reduce_events([(ops, modules)], spans)


def test_idle_gaps_fall_in_the_engine_phase_spans():
    gaps = _tick_trace(program_spans=True).gaps()
    # idle ms: 0-6 and 14-22 in admit, 58-61 in sync, 63-72 in sample,
    # 73-80 between the token writes in commit; 81-200 ends in the wait
    assert gaps == {"serve.admit": (14 * MS, 2), "serve.sync": (3 * MS, 1),
                    "serve.sample": (9 * MS, 1), "serve.commit": (7 * MS, 1),
                    "gen.wait": (119 * MS, 1)}
    assert _tick_trace(program_spans=False).gaps() == {
        "serve.tick": (33 * MS, 5), "gen.wait": (119 * MS, 1)}


def test_existing_serve_readers_ignore_the_program_spans():
    names = ("serve.host_ms_per_tick", "serve.decode_ms",
             "serve.prefill_ms_per_ktok", "device_idle_pct.serve")

    def read(reduced):
        ctx = types.SimpleNamespace(
            trace=reduced, peaks={},
            facts={"modules": {"decode": "jit_step", "prefill": "jit_fn"},
                   "prefill_lengths": [512]})
        return {n: spec.reader(n).read(ctx) for n in names}

    with_spans = read(_tick_trace(program_spans=True))
    assert with_spans == read(_tick_trace(program_spans=False))
    assert with_spans == pytest.approx({
        "serve.host_ms_per_tick": 52.0, "serve.decode_ms": 36.0,
        "serve.prefill_ms_per_ktok": 16.0, "device_idle_pct.serve": 76.0})

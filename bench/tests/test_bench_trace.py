"""The trace reduction and the tails, on small traces made in the test."""

import types

import numpy as np
import pytest

from bench import stats, trace_reduce
from bench.drivers import serve_open_loop

MS = 1_000_000  # ns


def _trace():
    """One chip, 100 ms window. Program B runs 10-40 ms: a while op
    holding two body ops, then a kernel. Program A runs 60-70 ms. Host
    spans: a tick over 0-50 ms, a wait over 50-100 ms."""
    ops = [
        ("%while.4 = (s32[]) while(...)", 10 * MS, 20 * MS),
        ("%fusion.1 = f32[8] fusion(...)", 10 * MS, 8 * MS),
        ("%fusion.2 = f32[8] fusion(...)", 20 * MS, 8 * MS),
        ("%scan_sum_apply.1 = f32[8] custom-call(...)", 30 * MS, 10 * MS),
        ("%copy.3 = f32[8] copy(...)", 60 * MS, 10 * MS),
    ]
    # names that sort against time order
    modules = [("jit_b(123)", 10 * MS, 30 * MS), ("jit_a(456)", 60 * MS,
                                                   10 * MS)]
    spans = [(trace_reduce.WINDOW_SPAN, 0, 100 * MS),
             ("serve.tick", 0, 50 * MS), ("gen.wait", 50 * MS, 50 * MS)]
    return trace_reduce.reduce_events([(ops, modules)], spans)


def test_busy_is_the_union_of_device_intervals():
    r = _trace()
    assert r.window_ns == 100 * MS
    # 10-40 (the while op covers its body) and 60-70
    assert r.busy_ns() == 40 * MS
    assert r.busy_ns(0, 50 * MS) == 30 * MS
    assert r.busy_ns(35 * MS, 65 * MS) == 10 * MS
    assert 100 * (1 - r.busy_ns() / r.window_ns) == pytest.approx(60.0)


def test_op_time_is_own_time_by_program():
    ops = _trace().op_ns()
    assert ops == {"jit_b/while.4": 4 * MS, "jit_b/fusion.1": 8 * MS,
                   "jit_b/fusion.2": 8 * MS,
                   "jit_b/scan_sum_apply.1": 10 * MS,
                   "jit_a/copy.3": 10 * MS}
    assert sum(ops.values()) == _trace().busy_ns()


def test_gaps_are_labelled_by_the_open_host_span():
    gaps = _trace().gaps()
    # idle 0-10 in the tick; 40-60 is one gap whose midpoint (50) lies in
    # the wait, as does 70-100
    assert gaps == {"serve.tick": (10 * MS, 1), "gen.wait": (50 * MS, 2)}


def test_module_runs_and_spans_are_found_by_name():
    r = _trace()
    assert [m[2] for m in r.module_events("jit_a")] == [10 * MS]
    assert len(r.span_events("serve.tick")) == 1
    assert r.span_events(trace_reduce.WINDOW_SPAN) == []


def test_window_clips_events():
    r = trace_reduce.reduce_events(
        [([("%f.1 = f", 0, 30 * MS)], [])],
        [(trace_reduce.WINDOW_SPAN, 10 * MS, 10 * MS)])
    assert r.busy_ns() == 10 * MS


def test_a_cpu_trace_gives_no_device_number(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("scan.call"):
        jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no TPU plane"):
        trace_reduce.read_xplane(str(tmp_path), ["scan.call"])


def _served(stall_s):
    """100 requests due 0.1 s apart, each answered 50 ms after arrival
    and then a token every 20 ms for 10 tokens; the requests that arrive
    during a stall (3.0-4.0 s, one in ten) wait ``stall_s`` longer for
    their first token and each of their gaps grows by a tenth of it."""
    t0 = 100.0
    reqs, live = [], {}
    for i in range(100):
        arrival = 0.1 * i
        extra = stall_s if 3.0 <= arrival < 4.0 else 0.0
        times = [t0 + arrival + 0.05 + extra + (0.02 + extra / 10) * k
                 for k in range(10)]
        out = serve_open_loop.Stamped()
        out.times = times
        reqs.append(types.SimpleNamespace(index=i, arrival_s=arrival))
        live[i] = types.SimpleNamespace(output=out,
                                        finish_reason="length_budget")
    return types.SimpleNamespace(reqs=reqs, live=live, t_first=t0,
                                 close=t0 + 20.0)


def test_a_stall_in_the_window_moves_the_tails():
    calm = serve_open_loop.end_to_end(_served(0.0))
    stalled = serve_open_loop.end_to_end(_served(1.0))
    assert calm["itl_p95_ms"] == pytest.approx(20.0)
    assert stalled["itl_p95_ms"] > 100.0
    ttft = [serve_open_loop.latencies(_served(s))[0] for s in (0.0, 1.0)]
    assert stats.percentile(ttft[0], 95) == pytest.approx(0.05)
    assert stats.percentile(ttft[1], 95) > 1.0


def test_percentile_uses_every_sample():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(np.percentile(xs, 95))
    assert stats.percentile(xs + [10_000], 100) == 10_000
    assert stats.spread([1.0, 1.0, 1.0, 1.0]) == 0.0

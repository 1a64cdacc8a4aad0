"""Observability layer tests (ISSUE 7).

The contract under test:

  * the tracer's export is valid Chrome ``trace_event`` JSON with
    properly nested spans, and the DISABLED tracer is a true no-op —
    serve outputs are bitwise identical with tracing on or off;
  * streaming histograms land within one log-bucket (~9%) of numpy
    percentiles without storing samples;
  * the policy ``explain_*`` surface returns the documented branch at
    each boundary, agrees with ``choose_*``, and emits decision events;
  * ``EngineStats`` attached to a metrics registry stays write-through
    identical to the dataclass under a seeded chaos run, and
    ``summary()`` prints every monotonic counter ``as_dict`` carries;
  * ``time_fn``'s ``TimingStats`` is a float that remembers the run,
    ``Table.to_records()`` serializes it, and ``tools/bench_gate.py``
    passes a self-diff, fails an injected 2x slowdown, and validates
    the committed ``BENCH_*.json`` baselines;
  * the scan engine emits a ``kernel.launch`` event per compilation.
"""

import collections
import copy
import dataclasses
import glob
import json
import math
import os
import sys
import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # benchmarks/ + tools/ live at the repo root
    sys.path.insert(0, _REPO)

from benchmarks.common import Table, TimingStats, time_fn  # noqa: E402
from tools import bench_gate  # noqa: E402

from repro import configs  # noqa: E402
from repro.core.scan import policy  # noqa: E402
from repro.obs import Registry, trace  # noqa: E402
from repro.obs.metrics import Histogram  # noqa: E402
from repro.serve import (Engine, EngineConfig, FaultInjector,  # noqa: E402
                         Request)
from repro.train.step import init_params  # noqa: E402


@pytest.fixture
def tracer():
    """A live tracer, guaranteed disabled again afterwards."""
    t = trace.enable()
    t.clear()
    yield t
    trace.disable()


@pytest.fixture(scope="module")
def small_model():
    cfg = dataclasses.replace(configs.get_smoke_config("stablelm-12b"),
                              dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve(cfg, params, injector=None, metrics=None, n=4, seed=7):
    rng = np.random.default_rng(seed)
    eng = Engine(params, cfg, EngineConfig(
        max_slots=2, max_len=48, max_new_tokens=5, eos_id=-1,
        temperature=0.0), injector=injector, metrics=metrics)
    for rid in range(n):
        eng.submit(Request(rid=rid, prompt=rng.integers(
            2, 500, size=int(rng.integers(3, 9))).astype(np.int32)))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        done = eng.run_to_completion()
    eng.audit()
    return eng, {r.rid: list(r.output) for r in done}


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_schema(tracer, tmp_path):
    with trace.span("outer", depth=0):
        with trace.span("inner", depth=1):
            trace.instant("marker", k="v")
        trace.counter("queue", depth=3)
    path = tmp_path / "t.json"
    doc = trace.export(str(path))

    # File round-trips as JSON and matches the in-memory doc.
    assert json.loads(path.read_text()) == doc
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "inner", "marker", "queue"}

    # Chrome trace_event invariants per phase.
    for e in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        assert isinstance(e["ts"], float) and e["ts"] >= 0
    assert by_name["outer"]["ph"] == "X" and by_name["inner"]["ph"] == "X"
    assert by_name["marker"]["ph"] == "i" and by_name["marker"]["s"] == "t"
    assert by_name["queue"]["ph"] == "C"
    assert by_name["queue"]["args"] == {"depth": 3}

    # Nesting = containment on the same track: inner within outer,
    # marker within inner.
    outer, inner, marker = (by_name[k] for k in ("outer", "inner", "marker"))
    assert outer["tid"] == inner["tid"] == threading.get_ident() % 1_000_000
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["ts"] <= marker["ts"] <= inner["ts"] + inner["dur"]
    assert inner["args"] == {"depth": 1}


def test_span_records_even_when_body_raises(tracer):
    with pytest.raises(RuntimeError):
        with trace.span("doomed"):
            raise RuntimeError("boom")
    assert [e["name"] for e in tracer.events()] == ["doomed"]


def test_ring_buffer_bounds_memory():
    t = trace.enable(capacity=8)
    try:
        for i in range(50):
            trace.instant("e", i=i)
        evs = t.events()
        assert len(evs) == 8
        assert [e["args"]["i"] for e in evs] == list(range(42, 50))
    finally:
        trace.disable()


def test_disabled_tracer_is_noop():
    trace.disable()
    assert not trace.enabled()
    # No allocation path: the shared no-op span comes back identically.
    s1, s2 = trace.span("a", x=1), trace.span("b")
    assert s1 is s2
    trace.instant("a")
    trace.counter("a", v=1)
    assert trace.export()["traceEvents"] == []


def test_jsonable_coerces_exotic_args(tracer):
    trace.instant("e", arr=np.int64(3), tup=(1, "a"), d={"k": np.float32(2)})
    args = tracer.events()[0]["args"]
    assert json.loads(json.dumps(args)) == args  # JSON-safe
    assert args["tup"] == [1, "a"]


def test_serve_outputs_bitwise_identical_with_tracing(small_model):
    cfg, params = small_model
    trace.disable()
    _, base = _serve(cfg, params)
    t = trace.enable()
    try:
        _, traced = _serve(cfg, params)
        assert t.events(), "tracing on but nothing recorded"
    finally:
        trace.disable()
    assert traced == base  # token-for-token identical histories


def _host_spans(trace_dir, prefix):
    """``name -> [(start_ns, end_ns)]`` of the host events of the one
    ``.xplane.pb`` under ``trace_dir`` whose names start with ``prefix``,
    and each event's stats."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans, stats = collections.defaultdict(list), {}
    with warnings.catch_warnings():
        # jaxlib's stats type warns when first built
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
                        stats[e.name] = dict(e.stats)
    return spans, stats


def test_spans_reach_a_profiler_session_with_scalar_args(tracer, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.span("probe.outer", tick=3, rids=[1, 2], label="x"):
            with trace.span("probe.inner"):
                pass
        trace.instant("probe.instant")
    finally:
        jax.profiler.stop_trace()
    spans, stats = _host_spans(str(tmp_path), "probe.")
    # exact names; the list arg stays in the ring buffer only
    assert set(spans) == {"probe.outer", "probe.inner"}
    assert stats["probe.outer"] == {"tick": 3, "label": "x"}
    (a, b), = spans["probe.outer"]
    (c, d), = spans["probe.inner"]
    assert a <= c <= d <= b
    ring = {e["name"]: e for e in tracer.events()}
    assert set(ring) == {"probe.outer", "probe.inner", "probe.instant"}
    assert ring["probe.outer"]["args"]["rids"] == [1, 2]


def _two_ticks(cfg, params):
    rng = np.random.default_rng(3)
    eng = Engine(params, cfg, EngineConfig(
        max_slots=2, max_len=48, max_new_tokens=5, eos_id=-1,
        temperature=0.0))
    reqs = [Request(rid=rid, prompt=rng.integers(2, 500, size=6)
                    .astype(np.int32)) for rid in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.step()
    return [list(r.output) for r in reqs]


def test_profiler_session_captures_the_tick_phases(small_model, tmp_path):
    """With the ring tracer off, a ``jax.profiler`` session alone records
    every phase span of a tick, inside its ``serve.step``."""
    cfg, params = small_model
    trace.disable()
    base = _two_ticks(cfg, params)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = _two_ticks(cfg, params)
    finally:
        jax.profiler.stop_trace()
    assert traced == base  # bitwise identical token histories
    spans, _ = _host_spans(str(tmp_path), "serve.")
    steps = sorted(spans["serve.step"])
    assert len(steps) == 2 and "serve.tick" not in spans
    for name in ("serve.admit", "serve.decode", "serve.sync",
                 "serve.sample", "serve.commit"):
        assert len(spans[name]) == 2, name
        for (a, b), (s0, s1) in zip(sorted(spans[name]), steps):
            assert s0 <= a <= b <= s1, name
    assert spans["serve.prefill"]
    for a, b in spans["serve.prefill"]:
        assert any(s0 <= a <= b <= s1 for s0, s1 in spans["serve.admit"])


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_histogram_percentiles_track_numpy():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-3.0, sigma=1.2, size=5000)
    h = Histogram()
    for s in samples:
        h.record(float(s))
    for q in (50.0, 90.0, 99.0):
        want = float(np.percentile(samples, q))
        got = h.percentile(q)
        assert abs(got - want) / want < 0.10, (q, got, want)
    s = h.summary()
    assert s["count"] == 5000
    assert s["min"] == pytest.approx(samples.min())
    assert s["max"] == pytest.approx(samples.max())
    assert s["mean"] == pytest.approx(samples.mean())


def test_histogram_edge_cases():
    h = Histogram()
    assert np.isnan(h.percentile(50))
    h.record(0.0)  # non-positive lands in the underflow bucket
    h.record(2.5)
    assert h.count == 2 and h.min == 0.0 and h.max == 2.5
    assert h.percentile(0) <= h.percentile(100) == 2.5


def test_histogram_non_positive_observations_never_reach_log():
    """Regression wall: ``record`` must route v <= 0 to the underflow
    bucket BEFORE the log-bucket index — ``math.log`` on zero/negative
    raises. Latency histograms do see exact zeros (clock granularity)
    and negatives (wall-clock steps backward under NTP slew)."""
    h = Histogram()
    for v in (0.0, -1.0, -1e-9, -math.inf):
        h.record(v)               # must not raise
    assert h.count == 4
    assert h._underflow == 4
    assert h._buckets == {}       # nothing indexed into the log buckets
    # summary/percentiles stay finite-path (no NaN from the log)
    assert h.percentile(50.0) == h.min == -math.inf
    h2 = Histogram()
    h2.record(-2.0)
    h2.record(0.0)
    h2.record(1.0)
    h2.record(4.0)
    assert h2._underflow == 2 and h2.count == 4
    s = h2.summary()
    assert s["count"] == 4 and s["min"] == -2.0 and s["max"] == 4.0
    assert s["mean"] == pytest.approx(0.75)
    # underflow mass pins the low percentiles at/below zero, the
    # positive mass keeps the high ones in the log buckets
    assert h2.percentile(0.0) <= 0.0
    assert 0.0 < h2.percentile(99.0) <= 4.0
    # monotone in q even across the underflow/bucket seam
    qs = [h2.percentile(q) for q in (0, 25, 50, 75, 100)]
    assert qs == sorted(qs)


def test_registry_snapshot_and_reset():
    reg = Registry()
    reg.counter("c").inc(3)
    reg.gauge("g").set(1.5)
    reg.histogram("h").record(0.25)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["gauges"]["g"] == 1.5
    assert snap["histograms"]["h"]["count"] == 1
    assert json.loads(json.dumps(snap)) == snap
    assert reg.names() == ["c", "g", "h"]
    reg.reset()
    assert reg.names() == []


# ---------------------------------------------------------------------------
# policy explain surface
# ---------------------------------------------------------------------------

def test_explain_schedule_branches_and_boundaries():
    cores = 8
    # batch >= cores: rows fill the machine.
    d = policy.explain_schedule(cores, 1 << 20, cores=cores)
    assert d.value == "carry" and "fill every core" in d.reason
    # Exactly at the flip: batch one short of cores, plenty of chunks.
    d = policy.explain_schedule(cores - 1, 1 << 20, cores=cores)
    assert d.value == "carry"  # spare = 8//7 = 1 < 2: nothing to feed
    d = policy.explain_schedule(1, 1 << 20, cores=cores)
    assert d.value == "decoupled" and "spread the row" in d.reason
    assert d.inputs["spare"] == cores
    # Short row: chunks < spare cores.
    d = policy.explain_schedule(1, 1024, cores=cores, block_elems=2048)
    assert d.value == "carry" and "nothing to spread" in d.reason
    # explain == choose, everywhere on a small grid.
    for b in (1, 2, 7, 8, 64):
        for n in (512, 1 << 14, 1 << 22):
            assert (policy.explain_schedule(b, n).value
                    == policy.choose_schedule(b, n))


def test_explain_attention_schedule_branches():
    cores = 8
    # Decode shape: one row, long chain -> split-KV via the idle-core rule.
    d = policy.explain_attention_schedule(1, 4096, cores=cores)
    assert d.value == "decoupled" and "cores idle" in d.reason
    # Saturated rows + short chain -> carry.
    d = policy.explain_attention_schedule(64, 4096, cores=cores)
    assert d.value == "carry"
    # Long-context rule: chain >= SPLIT_KV_CHUNKS, rows below the cap.
    kv = policy.SPLIT_KV_CHUNKS * 128
    d = policy.explain_attention_schedule(16, kv, cores=cores)
    assert d.value == "decoupled" and "dominates" in d.reason
    # One chunk short of the threshold: carry again.
    d = policy.explain_attention_schedule(16, kv - 128, cores=cores)
    assert d.value == "carry"
    # Rows at the saturation cap: splitting returns nothing.
    d = policy.explain_attention_schedule(
        cores * policy.SPLIT_KV_ROW_CAP, kv, cores=cores)
    assert d.value == "carry"
    for rows in (1, 8, 64, 128):
        for kv_len in (512, 1 << 15, 1 << 20):
            assert (policy.explain_attention_schedule(rows, kv_len).value
                    == policy.choose_attention_schedule(rows, kv_len))


def test_policy_decisions_emit_trace_events(tracer):
    policy.explain_schedule(1, 1 << 20)
    policy.explain_attention_schedule(1, 4096)
    policy.choose(1 << 22)
    names = [e["name"] for e in tracer.events()]
    assert "policy.schedule" in names
    assert "policy.attention_schedule" in names
    assert "policy.choose" in names
    ev = next(e for e in tracer.events() if e["name"] == "policy.schedule")
    assert ev["args"]["value"] == "decoupled"
    assert ev["args"]["batch"] == 1 and "reason" in ev["args"]


def test_choice_carries_inputs_without_breaking_equality():
    a = policy.choose(1 << 22)
    b = copy.copy(a)
    object.__setattr__(b, "inputs", {})
    assert a == b  # inputs excluded from comparison
    assert a.inputs["n"] == 1 << 22 and "schedule" not in a.inputs


# ---------------------------------------------------------------------------
# EngineStats <-> registry mirroring
# ---------------------------------------------------------------------------

def test_engine_stats_summary_prints_every_counter(small_model):
    from repro.serve.stats import EngineStats
    st = EngineStats()
    # Drive every int counter to a distinct nonzero value so a dropped
    # field cannot hide behind a zero.
    for i, (k, v) in enumerate(st.as_dict().items()):
        if isinstance(v, int) and k != "total_finished":
            setattr(st, k, i + 2)
    st.record_finish("eos")
    text = st.summary()
    missing = [k for k, v in st.as_dict().items()
               if isinstance(v, int) and k not in (
                   "total_finished", "queue_depth")  # gauge, not monotonic
               and str(getattr(st, k, v)) not in text]
    # Name-level check: the once-dropped counters must appear by name.
    for name in ("prefill_retries", "nonfinite", "slow_ticks",
                 "prefill_evictions"):
        assert name in text, f"summary() dropped {name}"
    assert not missing, f"summary() lost counters: {missing}"


def test_engine_stats_registry_parity_under_chaos(small_model):
    cfg, params = small_model
    reg = Registry()
    inj = FaultInjector.from_seed(3, ticks=40, p_error=0.15, p_nan=0.15,
                                  p_stall=0.05, stall_s=0.002,
                                  poison_rids=[2])
    eng, _ = _serve(cfg, params, injector=inj, metrics=reg, n=4)
    st = eng.stats.as_dict()
    snap = reg.snapshot()
    # Something actually happened under chaos.
    assert st["step_retries"] > 0 or st["degradations"] > 0
    # Every int counter mirrors into a gauge, value-identical.
    for k, v in st.items():
        if isinstance(v, int):
            assert snap["gauges"][f"serve.stats.{k}"] == v, k
    # Finishes mirror into per-reason counters.
    for reason, nn in st["finished"].items():
        assert snap["counters"][f"serve.finished.{reason}"] == nn
    # The engine also feeds the tick-latency histogram.
    assert snap["histograms"]["serve.tick_s"]["count"] == st["ticks"]


def test_engine_without_registry_has_no_mirror(small_model):
    cfg, params = small_model
    eng, _ = _serve(cfg, params, n=2)
    assert getattr(eng.stats, "_registry", None) is None


# ---------------------------------------------------------------------------
# bench trajectory: TimingStats, Table.to_records, the gate
# ---------------------------------------------------------------------------

def test_timing_stats_is_a_float_with_memory():
    t = TimingStats([0.3, 0.1, 0.2])
    assert float(t) == pytest.approx(0.2)      # median
    assert (t.t_min, t.t_max, t.iters) == (0.1, 0.3, 3)
    ms = t * 1e3
    assert isinstance(ms, TimingStats)
    assert float(ms) == pytest.approx(200.0)
    assert ms.t_max == pytest.approx(300.0)
    assert isinstance(1e3 * t, TimingStats)
    half = t / 2
    assert isinstance(half, TimingStats)
    assert half.t_min == pytest.approx(0.05)
    # Degrades to plain float when stats stop being meaningful.
    assert not isinstance(t * t, TimingStats)
    assert not isinstance(5.0 / t, TimingStats)
    assert t.to_dict() == {"p50": float(t), "min": 0.1, "max": 0.3,
                           "iters": 3}
    assert f"{t:.4g}" == "0.2"                 # table formatter path


def test_time_fn_returns_timing_stats():
    t = time_fn(lambda: jnp.ones(4), iters=3, warmup=1)
    assert isinstance(t, TimingStats)
    assert t.iters == 3 and 0 < t.t_min <= float(t) <= t.t_max


def test_table_to_records_round_trips():
    t = Table("demo", ["name", "n", "Belem/s", "ms"])
    t.add("row", np.int64(4), np.float64(1.5), TimingStats([1.0, 2.0, 3.0]))
    rec = t.to_records()
    assert json.loads(json.dumps(rec)) == rec
    assert rec["columns"] == ["name", "n", "Belem/s", "ms"]
    name, n, tput, ms = rec["rows"][0]
    assert (name, n, tput) == ("row", 4, 1.5)
    assert ms == {"p50": 2.0, "min": 1.0, "max": 3.0, "iters": 3}


def _doc(rows, columns=("name", "Belem/s", "ms"), suite="engine"):
    return {"schema": bench_gate.SCHEMA, "suites": {suite: [{
        "title": "t", "columns": list(columns), "rows": rows}]}}


def test_bench_gate_passes_self_and_fails_2x_slowdown():
    base = _doc([["sum", 2.0, {"p50": 0.1, "min": 0.09, "max": 0.2,
                               "iters": 3}]])
    assert bench_gate.gate(copy.deepcopy(base), {"engine": base},
                           out=lambda *_: None) == []
    slow = copy.deepcopy(base)
    slow["suites"]["engine"][0]["rows"][0][2]["p50"] = 0.2  # 2x > 1.75x
    fails = bench_gate.gate(slow, {"engine": base}, out=lambda *_: None)
    assert len(fails) == 1 and "ms" in fails[0]
    # Generous tolerance swallows it; getting FASTER never fails.
    assert bench_gate.gate(slow, {"engine": base}, time_tol=3.0,
                           out=lambda *_: None) == []
    fast = copy.deepcopy(base)
    fast["suites"]["engine"][0]["rows"][0][2]["p50"] = 0.01
    assert bench_gate.gate(fast, {"engine": base},
                           out=lambda *_: None) == []


def test_bench_gate_rules_by_cell_kind():
    base = _doc([["sum", 2.0, {"p50": 0.1, "min": 0.1, "max": 0.1,
                               "iters": 1}]])
    # Throughput is inverted: collapsing Belem/s fails, rising doesn't.
    slow_tput = copy.deepcopy(base)
    slow_tput["suites"]["engine"][0]["rows"][0][1] = 0.5
    assert bench_gate.gate(slow_tput, {"engine": base},
                           out=lambda *_: None)
    fast_tput = copy.deepcopy(base)
    fast_tput["suites"]["engine"][0]["rows"][0][1] = 8.0
    assert not bench_gate.gate(fast_tput, {"engine": base},
                               out=lambda *_: None)
    # String drift (parity cell flipping to DIVERGED) fails.
    diverged = copy.deepcopy(base)
    diverged["suites"]["engine"][0]["rows"][0][0] = "DIVERGED"
    assert bench_gate.gate(diverged, {"engine": base},
                           out=lambda *_: None)
    # Structural drift: a lost row fails.
    short = copy.deepcopy(base)
    short["suites"]["engine"][0]["rows"] = []
    assert bench_gate.gate(short, {"engine": base}, out=lambda *_: None)
    # Disjoint suites gate nothing (reported, not failed).
    assert not bench_gate.gate(base, {"other": base}, out=lambda *_: None)


def test_bench_gate_schema_checker():
    good = _doc([["sum", 2.0, 0.1]])
    assert bench_gate.check_schema(good) == []
    assert bench_gate.check_schema({"schema": "nope", "suites": {}})
    ragged = _doc([["sum", 2.0]])  # row shorter than columns
    assert any("shape" in e for e in bench_gate.check_schema(ragged))


def test_committed_baselines_are_valid():
    paths = glob.glob(os.path.join(_REPO, "BENCH_*.json"))
    assert {os.path.basename(p) for p in paths} >= {
        "BENCH_engine.json", "BENCH_attention.json", "BENCH_serve.json"}
    for path in paths:
        doc = json.load(open(path))
        assert bench_gate.check_schema(doc, path) == []
        suite = os.path.basename(path)[len("BENCH_"):-len(".json")]
        assert suite in doc["suites"]
        assert doc["environment"]["backend"]  # provenance recorded
        # A baseline must gate cleanly against itself.
        assert bench_gate.gate(copy.deepcopy(doc), {suite: doc},
                               out=lambda *_: None) == []


# ---------------------------------------------------------------------------
# kernel launch events
# ---------------------------------------------------------------------------

def test_kernel_launch_event_per_compilation(tracer):
    from repro.kernels.scan_blocked import ops as sb_ops
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 4096)),
                    jnp.float32)
    sb_ops.cumsum(x, interpret=True, schedule="decoupled", block_n=1024)
    evs = [e for e in tracer.events() if e["name"] == "kernel.launch"]
    assert evs, "no kernel.launch event for a fresh scan"
    args = evs[0]["args"]
    assert args["monoid"] == "sum" and args["schedule"] == "decoupled"
    # Launch grid: rows x 4 sequence chunks (4096 / block_n=1024).
    assert args["grid"][-1] == 4 and len(args["grid"]) == 2
    # Decoupled reads the data twice (reduce pass + rescan pass).
    assert args["hbm_read_bytes_est"] == 2 * args["hbm_write_bytes_est"]
    assert args["vmem_block_bytes_est"] > 0

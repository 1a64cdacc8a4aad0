"""Open-loop serving: requests sent on the mix's schedule to one
``repro.serve.Engine``, whatever the engine's backlog.

One thread does everything, as the engine's own loop does: between ticks
it submits every request whose scheduled arrival has passed, then runs
one ``Engine.step``; with nothing to do it sleeps until the next arrival.
The host time of each output token is taken when the engine appends it
to the request's output, i.e. when the host holds it.

  itl_p95_ms    95th percentile of every gap between consecutive output
                tokens of a request, over all requests, inside the window
  tokens_per_s  output tokens produced inside the window / its seconds

After the window closes no request is sent; the engine runs on until
every due request has its first token (at most a minute).

The configuration file gives the model (``model``: the program's
``ModelConfig`` fields), the engine settings (``engine``), the plain reference
(``reference``: a module of ``bench/reference``), the compiled programs
that hold decode and prefill (``modules``) and the limits of the check.
"""

from __future__ import annotations

import gc
import importlib
import time
import types

import numpy as np

from bench import generator, stats

SPAN_NAMES = ("serve.tick", "serve.submit", "gen.wait")
#: How long a due request may take past the window's close.
GRACE_S = 60.0
#: Finish reasons of a request that answered.
ANSWERED = ("length_budget", "eos")


class Stamped(list):
    """A request's output list that notes when each token was appended."""

    def __init__(self):
        super().__init__()
        self.times: list = []

    def append(self, tok):
        super().append(tok)
        self.times.append(time.perf_counter())

    def extend(self, toks):
        toks = list(toks)
        super().extend(toks)
        self.times += [time.perf_counter()] * len(toks)


def reference(conf: dict):
    return importlib.import_module(f"bench.reference.{conf['reference']}")


def model_config(conf: dict):
    from repro.models.config import ModelConfig
    m = dict(conf["model"])
    m["layer_pattern"] = tuple(m["layer_pattern"])
    return ModelConfig(**m)


def make_weights(conf: dict, seed: int):
    """The seeded weights in the serving dtype, made on the device in one
    jitted call."""
    import jax
    import jax.numpy as jnp
    ref, m = reference(conf), conf["model"]
    dt = jnp.dtype(m["dtype"])
    gains = conf["init"]["gains"]
    return jax.jit(lambda k: ref.init_weights(k, m, dt, gains))(
        generator.jax_key(seed))


def _check_layout(params, mcfg) -> None:
    """The weights must have exactly the tree, shapes and dtypes that the
    program's own initialiser gives the model."""
    import jax
    from repro.train.step import init_params
    want = jax.eval_shape(lambda k: init_params(k, mcfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("seeded weights do not match the program's layout")


class _Compiles:
    def __init__(self):
        import jax
        from jax._src import dispatch
        self.n = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def listen(name, duration, **kw):
            if name == event:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def setup(run):
    from repro.serve import Engine, EngineConfig, Request
    conf, mix = run.cell.config, run.cell.traffic
    mcfg = model_config(conf)
    params = make_weights(conf, run.seed)
    _check_layout(params, mcfg)
    ecfg = EngineConfig(**conf["engine"], seed=run.seed)
    eng = Engine(params, mcfg, ecfg)
    reqs = generator.schedule(mix, run.seed, run.seconds, mcfg.vocab_size)
    # Warm-up: one request per prompt length the mix uses, two tokens
    # each, so every prefill shape and the decode step are compiled (or
    # loaded) and run once before the window.
    rng = np.random.default_rng(run.seed)
    lengths = sorted({r.prompt.size for r in reqs})
    for j, n in enumerate(lengths):
        eng.submit(Request(rid=-1 - j, max_new_tokens=2,
                           prompt=rng.integers(0, mcfg.vocab_size, n)
                           .astype(np.int32)))
    eng.run_to_completion()
    return types.SimpleNamespace(
        conf=conf, mix=mix, mcfg=mcfg, params=params, eng=eng, reqs=reqs,
        live={}, t0=run.t0, t_first=None, close=None,
        lag=[], compiles=_Compiles(), compiles_in_window=0,
        ticks=[], trace_span=None, drained_at=None)


def _busy(eng) -> bool:
    return bool(eng.waiting) or any(r is not None for r in eng.slot_req)


def measure(state, run, tracer):
    import jax
    from repro.serve import Request
    annotate = jax.profiler.TraceAnnotation
    eng, reqs = state.eng, state.reqs
    trace_s = float(state.mix.get("trace_seconds", 3.0))
    c0 = state.compiles.n
    t0 = state.t_first = time.perf_counter()
    close = state.close = t0 + run.seconds
    trace_at = close - min(trace_s, run.seconds) if run.trace else None
    i, n = 0, len(reqs)

    def submit_due(now):
        nonlocal i
        with annotate("serve.submit"):
            while i < n and t0 + reqs[i].arrival_s <= now:
                r = reqs[i]
                req = Request(rid=r.index, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens)
                eng.submit(req)
                req.output = Stamped()
                state.live[r.index] = req
                state.lag.append(now - (t0 + r.arrival_s))
                i += 1

    while True:
        now = time.perf_counter()
        if trace_at is not None and now >= trace_at:
            tracer.begin()
            trace_at = None
        if now >= close:
            break
        if i < n and t0 + reqs[i].arrival_s <= now:
            submit_due(now)
        if _busy(eng):
            t = time.perf_counter()
            with annotate("serve.tick"):
                eng.step()
            state.ticks.append((t - t0, time.perf_counter() - t))
        else:
            nxt = t0 + reqs[i].arrival_s if i < n else close
            if trace_at is not None:
                nxt = min(nxt, trace_at)
            with annotate("gen.wait"):
                time.sleep(max(0.0, min(nxt, close) - now))
    state.compiles_in_window = state.compiles.n - c0
    submit_due(close)              # due in the window, not yet sent
    if run.trace:
        state.trace_span = (tracer.begun, time.perf_counter())
        tracer.end()
    deadline = close + GRACE_S
    while (time.perf_counter() < deadline and _busy(eng)
           and any(not r.output for r in state.live.values())):
        eng.step()
    state.drained_at = time.perf_counter()


def latencies(state):
    """TTFT of every due request, every inter-token gap inside the window,
    and the tokens produced inside it."""
    close, t0 = state.close, state.t_first
    miss = close + GRACE_S
    ttft, itl, tokens = [], [], 0
    for r in state.reqs:
        req = state.live.get(r.index)
        times = req.output.times if req is not None else []
        answered = req is not None and req.finish_reason not in (
            "error", "rejected", "cancelled", "deadline", "cache_full")
        ttft.append((times[0] if times and answered else miss)
                    - (t0 + r.arrival_s))
        inside = [t for t in times if t <= close]
        tokens += len(inside)
        itl += [b - a for a, b in zip(inside, inside[1:])]
    return ttft, itl, tokens


def end_to_end(state):
    _, itl, tokens = latencies(state)
    elapsed = state.close - state.t_first
    return {"itl_p95_ms": 1e3 * stats.percentile(itl, 95),
            "tokens_per_s": tokens / elapsed}


def facts(state):
    import sys
    failed = 0
    incorrect = False
    for r in state.reqs:
        req = state.live.get(r.index)
        if (req is None or not req.output
                or req.finish_reason not in ANSWERED + (None,)):
            failed += 1
        if req is not None and req.finish_reason == "error":
            incorrect = True
    lag = sorted(state.lag) or [0.0]
    slow = sorted(state.ticks, key=lambda t: -t[1])[:3]
    ttft = latencies(state)[0]
    print(f"bench: {len(state.reqs)} requests due, {failed} failed; "
          f"TTFT p50 {1e3 * stats.percentile(ttft, 50):.1f} ms, p95 "
          f"{1e3 * stats.percentile(ttft, 95):.1f} ms; "
          f"generator late by p50 {stats.percentile(lag, 50):.4f} s, "
          f"max {lag[-1]:.4f} s; {len(state.ticks)} ticks, the slowest "
          f"(start s, seconds) {[(round(a, 2), round(d, 4)) for a, d in slow]}"
          f"; {state.compiles_in_window} compiles in the window; engine "
          f"recoveries {state.eng.stats.recoveries()}; drained "
          f"{state.drained_at - state.close:.1f} s past the close",
          file=sys.stderr, flush=True)
    out = {"setup_s": state.t_first - state.t0,
           "attempted": len(state.reqs), "failed": failed,
           "incorrect": incorrect, "model": state.conf["model"],
           "modules": state.conf["modules"],
           "compiles_in_window": state.compiles_in_window}
    out.update(window_s=state.close - state.t_first,
               **_work(state, state.t_first, state.close, "window_"))
    if state.trace_span is not None:
        a, b = state.trace_span
        out.update(trace_s=b - a, **_work(state, a, b))
    return out


def _work(state, a: float, b: float, prefix: str = "") -> dict:
    """The prompt length of every first token and the keys of every later
    token that the host received between ``a`` and ``b``."""
    prefill, decode = [], []
    for r in state.reqs:
        req = state.live.get(r.index)
        for k, t in enumerate(req.output.times if req else []):
            if a <= t <= b:
                if k == 0:
                    prefill.append(r.prompt.size)
                else:
                    decode.append(r.prompt.size + k)
    return {prefix + "prefill_lengths": prefill,
            prefix + "decode_keys": decode}


def sample(state, seed: int, tokens: int):
    """Requests the window finished, drawn from the seed: the longest
    (prompt and output) first, then others until ``tokens`` served tokens."""
    done = [(r.prompt, np.asarray(list(state.live[r.index].output), np.int32))
            for r in state.reqs
            if r.index in state.live
            and state.live[r.index].finish_reason in ANSWERED]
    if not done:
        return []
    order = np.random.default_rng(seed).permutation(len(done))
    longest = max(range(len(done)),
                  key=lambda j: done[j][0].size + done[j][1].size)
    picked, count = [], 0
    for j in [longest] + [int(j) for j in order if j != longest]:
        if count >= tokens:
            break
        picked.append(done[j])
        count += done[j][1].size
    return picked


def free_program(state) -> None:
    state.eng = state.params = None
    state.live = {k: None for k in state.live}
    gc.collect()


def gaps(conf, seed, picked, control=False):
    """Per sampled request, the reference gap of each served token (or of
    the control's choice)."""
    import jax.numpy as jnp
    ref = reference(conf)
    m = conf["model"]
    w = make_weights(conf, seed)
    pad = conf["check"]["pad_to"]
    out = [ref.served_gaps(w, m, p, o, pad, control=control)
           for p, o in picked]
    del w
    return out


def check(state, run):
    conf = state.conf
    picked = sample(state, run.seed, conf["check"]["tokens"])
    free_program(state)
    if not picked:                 # nothing finished: nothing checks out
        return [("max_logit_gap", 1e30, conf["limits"]["max_logit_gap"])]
    t = time.perf_counter()
    g = gaps(conf, run.seed, picked)
    import sys
    print(f"bench: checked {len(picked)} requests, "
          f"{sum(x.size for x in g)} served tokens against the reference "
          f"in {time.perf_counter() - t:.1f} s", file=sys.stderr, flush=True)
    return [("max_logit_gap", float(max(x.max() for x in g)),
             conf["limits"]["max_logit_gap"])]

"""Back-to-back calls of the public prefix sum, one caller.

The input is made on the device from the seed at the mix's shape; the
program under test is ``jax.jit(repro.core.scan.cumsum)`` as a user
calls it, policy-selected. Each call's result is ready before the next
call is issued. ``rows_per_s`` is every element scanned in the window
over the window's seconds.

Mix keys: ``shape`` (its product is the configuration's ``elements``),
``trace_calls`` (how many calls a ``--trace 1`` run profiles, after the
window).
"""

from __future__ import annotations

import math
import re
import time
import types

import numpy as np

from bench import flops, generator
from bench.reference import prefix_sum

SPAN_NAMES = ("scan.call",)

_KERNEL = re.compile(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"")


def kernel_ops(hlo_text: str) -> list:
    """Names of the Pallas kernels (``tpu_custom_call``) in a compiled
    program's text."""
    return sorted(set(_KERNEL.findall(hlo_text)))


def entry():
    """The program under test."""
    from repro.core import scan
    return scan.cumsum


def setup(run):
    import jax
    import jax.numpy as jnp
    conf, mix = run.cell.config, run.cell.traffic
    shape = tuple(int(s) for s in mix["shape"])
    if math.prod(shape) != int(conf["elements"]):
        raise ValueError(f"mix shape {shape} does not hold the "
                         f"configuration's {conf['elements']} elements")
    dtype = jnp.dtype(conf["dtype"])
    lo, hi = conf["values"]["low"], conf["values"]["high"]
    x = jax.jit(lambda k: jax.random.uniform(k, shape, dtype, lo, hi))(
        generator.jax_key(run.seed))
    compiled = jax.jit(entry()).lower(x).compile()
    jax.block_until_ready(compiled(x))            # first run, outside
    rng = np.random.default_rng(run.seed)
    return types.SimpleNamespace(
        x=x, compiled=compiled, kernels=kernel_ops(compiled.as_text()),
        shape=shape, itemsize=dtype.itemsize, keep_at=int(rng.integers(3)),
        kept=None, last=None, calls=0, elapsed=0.0, t0=run.t0,
        t_first=None, traced_calls=0, call_s=[])


def _call(state):
    import jax
    with jax.profiler.TraceAnnotation("scan.call"):
        y = state.compiled(state.x)
        y.block_until_ready()
    return y


def measure(state, run, tracer):
    state.t_first = now = time.perf_counter()
    end = state.t_first + run.seconds
    while True:
        y = _call(state)
        if state.calls == state.keep_at:
            state.kept = y
        state.calls += 1
        last, now = now, time.perf_counter()
        state.call_s.append(now - last)
        if now >= end:
            break
    state.elapsed = now - state.t_first
    state.last = y
    if run.trace:
        tracer.begin()
        for _ in range(int(run.cell.traffic.get("trace_calls", 1))):
            _call(state)
            state.traced_calls += 1
        tracer.end()


def end_to_end(state):
    rows = state.calls * math.prod(state.shape)
    return {"rows_per_s": rows / state.elapsed / 1e6}


def facts(state):
    import sys
    slow = max(range(len(state.call_s)), key=state.call_s.__getitem__)
    print(f"bench: {state.calls} calls, seconds a call: median "
          f"{sorted(state.call_s)[len(state.call_s) // 2]:.4f}, slowest "
          f"{state.call_s[slow]:.4f} (call {slow})", file=sys.stderr,
          flush=True)
    return {"setup_s": state.t_first - state.t0,
            "attempted": state.calls, "failed": 0,
            "calls_traced": state.traced_calls, "kernels": state.kernels,
            "bytes_per_call": flops.scan_bytes(state.shape, state.itemsize)}


def check(state, run):
    """Every call returns the same answer (the kept call's against the
    last, bitwise), and the last one matches the float64 reference."""
    import jax.numpy as jnp
    same = bool(jnp.array_equal(state.kept, state.last))
    x, y = np.asarray(state.x), np.asarray(state.last)
    state.x = state.kept = state.last = state.compiled = None
    limit = run.cell.config["limits"]["cumsum_rel_err"]
    return [("cumsum_rel_err", prefix_sum.rel_err(x, y), limit),
            ("calls_differ", 0.0 if same else 1.0, 0.0)]

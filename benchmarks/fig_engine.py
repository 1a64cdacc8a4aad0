"""Scan-engine sweep: schedule × monoid, one table.

The engine's promise is that each grid organization is written once and
runs over every registered monoid. This sweep drives the twelve
(schedule, monoid) cells that run in interpret mode through the family
``ops`` wrappers (the fused schedule runs only compiled on a TPU),
checks the cross-schedule parity invariant on the fly — BIT-parity for
carry/decoupled (shared in-tile network), tolerance for the
tree's different association on float data (``atol<=2e-4``; integral
monoids stay bitwise) — and reports wall-clock plus what
``policy.choose_schedule`` would pick for the shape, so the policy
rule can be eyeballed against measurement on real hardware (on
the CPU container the kernels run in interpret mode and wall-clock
mostly reflects algorithmic structure).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from benchmarks.common import Table, time_fn, throughput
from repro.core.scan import policy
from repro.kernels.compact import ops as kc_ops
from repro.kernels.scan_blocked import ops as sb_ops
from repro.kernels.segscan import ops as seg_ops
from repro.kernels.ssm_scan import ops as ssm_ops

SCHEDULES = ("carry", "decoupled", "tree")
TREE_ATOL = 2e-4


def _parity(baseline, leaves, schedule: str) -> str:
    same = all(bool(jnp.all(a == b)) for a, b in zip(baseline, leaves))
    if same:
        return "bitwise"
    if schedule == "tree" and all(
            np.allclose(np.asarray(a, np.float64), np.asarray(b, np.float64),
                        rtol=TREE_ATOL, atol=TREE_ATOL)
            for a, b in zip(baseline, leaves)):
        return f"atol<={TREE_ATOL:g}"
    return "DIVERGED"


def _cases(smoke: bool):
    rng = np.random.default_rng(0)
    if smoke:
        B, N = 1, 1 << 13
        T, D = 1 << 10, 128
    else:
        B, N = 1, 1 << 20
        T, D = 1 << 17, 256
    x = jnp.asarray(rng.standard_normal((B, N)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, N)), jnp.float32)
    f = jnp.asarray(rng.random((B, N)) < 0.01, jnp.int32)
    a = jnp.asarray(rng.uniform(0.8, 1.0, (1, T, D)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((1, T, D)), jnp.float32)
    m = jnp.asarray(rng.random((B, N)) < 0.5, jnp.int32)
    bn = 1 << 11
    return [
        ("sum", B * N, B, N,
         lambda s: functools.partial(sb_ops.cumsum, x, interpret=True,
                                     schedule=s, block_n=bn)),
        ("segmented", B * N, B, N,
         lambda s: functools.partial(seg_ops.segmented_cumsum, v, f,
                                     interpret=True, schedule=s,
                                     block_n=bn)),
        ("affine", T * D, 1, T,
         lambda s: functools.partial(ssm_ops.ssm_scan, a, b, interpret=True,
                                     schedule=s)),
        ("mask", B * N, B, N,
         lambda s: functools.partial(kc_ops.mask_compact, m, interpret=True,
                                     schedule=s, block_n=bn)),
    ]


def run(smoke: bool = False) -> Table:
    t = Table("Scan engine: schedule x monoid (kernel interpret mode)",
              ["monoid", "schedule", "policy", "parity", "Belem/s", "ms"])
    for name, elems, batch, n, make in _cases(smoke):
        chosen = policy.choose_schedule(batch, n)
        baseline = None
        for schedule in SCHEDULES:
            fn = make(schedule)
            out = fn()
            leaves = out if isinstance(out, tuple) else (out,)
            if baseline is None:
                baseline = leaves
                parity = "ref"
            else:
                parity = _parity(baseline, leaves, schedule)
            sec = time_fn(fn, iters=3, warmup=1)
            mark = " <- policy" if schedule == chosen else ""
            t.add(name, schedule + mark,
                  chosen if schedule == "carry" else "",
                  parity, throughput(elems, sec), sec * 1e3)
    return t


if __name__ == "__main__":
    run().show()

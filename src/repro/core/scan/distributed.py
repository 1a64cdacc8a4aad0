"""Distributed scans — the paper's §2 multithreaded algorithms across chips.

The paper's threads become mesh devices; its two-pass organizations become
``shard_map`` programs; its `sums` array exchange becomes a collective. The
mapping is exact:

  paper thread t_m            →  device with mesh index m along `axis_name`
  pass 1 local scan/reduce    →  per-shard scan/fold (no communication)
  `sums` buffer + barrier     →  all-gather / permute of per-shard totals
  pass 2 increment/scan       →  per-shard combine with the exclusive offset

Three carry-exchange schedules are provided (the paper's §2.2.1 discusses
barrier cost; on a TPU mesh the analogous choice is which collective):

  * ``all_gather``  — one all-gather of totals; every device folds its own
    exclusive prefix. One collective, O(m) payload per device. Best for
    small carries (scalars — plain cumsum).
  * ``hillis_permute`` — log2(m) ``ppermute`` rounds (Hillis–Steele over
    the device axis). O(log m) latency, O(1) payload per round. Best for
    LARGE carries (SSM matrix states under sequence parallelism), where
    all-gathering m full matrices would dominate.
  * ``ring`` — m-1 chained ``ppermute``s: the adjacent-only-synchronization
    StreamScan variant the paper cites ([35]). Exposes maximal overlap of
    the carry chain with local compute to the XLA scheduler.

``variant`` selects the paper's Fig 1a (1: scan-then-increment) vs Fig 1b
(2: accumulate-then-scan). Variant 2 performs no writes in pass 1 — the
bandwidth observation that makes SIMD2-P the paper's most robust algorithm
(Observation 3) — and is the default.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.scan import assoc
from repro.core.scan import blocked
from repro.core.scan import horizontal
from repro.core.scan import reference

Pytree = Any


def _exclusive_offset_all_gather(total, monoid, axis_name, m):
    """All-gather per-device totals; fold my exclusive prefix locally."""
    totals = jax.lax.all_gather(total, axis_name, axis=0)  # (m, ...)
    excl = reference.scan_ref(totals, monoid, axis=0, exclusive=True)
    my = jax.lax.axis_index(axis_name)
    return jax.tree.map(
        lambda e: jax.lax.dynamic_index_in_dim(e, my, 0, keepdims=False), excl
    )


def _exclusive_offset_hillis(total, monoid, axis_name, m):
    """Log-step doubling scan over the device axis via ppermute."""
    my = jax.lax.axis_index(axis_name)
    val = total  # running inclusive fold of a trailing window
    k = 1
    while k < m:
        perm = [(i, i + k) for i in range(m - k)]
        recv = jax.tree.map(
            lambda v: jax.lax.ppermute(v, axis_name, perm), val
        )
        val = jax.tree.map(
            lambda r, v, c: jnp.where(my >= k, c, v),
            recv,
            val,
            monoid.combine(recv, val),
        )
        k *= 2
    # val is the inclusive scan of totals; shift by one device for exclusive.
    perm = [(i, i + 1) for i in range(m - 1)]
    recv = jax.tree.map(lambda v: jax.lax.ppermute(v, axis_name, perm), val)
    ident = monoid.identity_like(total)
    return jax.tree.map(
        lambda r, i: jnp.where(my == 0, i, r), recv, ident
    )


def _exclusive_offset_ring(total, monoid, axis_name, m):
    """m-1 chained permutes: adjacent-only synchronization (StreamScan)."""
    my = jax.lax.axis_index(axis_name)
    ident = monoid.identity_like(total)
    offset = ident
    perm = [(i, i + 1) for i in range(m - 1)]
    for _ in range(m - 1):
        send = monoid.combine(offset, total)
        recv = jax.tree.map(
            lambda s: jax.lax.ppermute(s, axis_name, perm), send
        )
        offset = jax.tree.map(
            lambda r, i: jnp.where(my == 0, i, r), recv, ident
        )
    return offset


_EXCHANGES = {
    "all_gather": _exclusive_offset_all_gather,
    "hillis_permute": _exclusive_offset_hillis,
    "ring": _exclusive_offset_ring,
}


def _local_scan(xs, monoid, algorithm, block_size):
    if algorithm == "blocked":
        return blocked.scan_blocked(xs, monoid, axis=0, block_size=block_size)
    if algorithm == "horizontal":
        return horizontal.scan_horizontal(xs, monoid, axis=0)
    if algorithm == "ref":
        return reference.scan_ref(xs, monoid, axis=0)
    raise ValueError(f"unknown local algorithm {algorithm!r}")


def scan_sharded(
    elems: Pytree,
    op: "str | assoc.Monoid" = "sum",
    *,
    mesh: Mesh,
    axis_name: str,
    spec: P,
    scan_axis: int = 0,
    variant: int = 2,
    carry_exchange: str = "all_gather",
    local_algorithm: str = "blocked",
    block_size: int = 4096,
    exclusive: bool = False,
) -> Pytree:
    """Global scan of an array sharded along ``axis_name``.

    Args:
      elems: pytree of arrays, all sharded with ``spec``; the scanned axis
        must be the one mapped to ``axis_name``.
      spec: the PartitionSpec of ``elems`` (in == out).
      variant: 1 = Fig 1a (scan first), 2 = Fig 1b (accumulate first).
      carry_exchange: collective schedule for the `sums` array (see module
        docstring).
      local_algorithm: per-shard algorithm; "blocked" = the paper's
        cache-friendly partitioning *within* each device.
    """
    if variant not in (1, 2):
        raise ValueError("variant must be 1 or 2")
    monoid = assoc.get(op)
    m = mesh.shape[axis_name]
    exchange = _EXCHANGES[carry_exchange]

    def local_fn(xs):
        xs0 = jax.tree.map(lambda x: jnp.moveaxis(x, scan_axis, 0), xs)
        if variant == 1:
            # Pass 1: full local prefix sums (writes), totals as byproduct.
            local = _local_scan(xs0, monoid, local_algorithm, block_size)
            total = jax.tree.map(lambda x: x[-1], local)
            offset = exchange(total, monoid, axis_name, m)
            # Pass 2: increment by the exclusive device-prefix.
            out = monoid.combine(
                jax.tree.map(lambda o: o[None], offset), local
            )
            out = jax.tree.map(
                lambda o, l: jnp.broadcast_to(o, l.shape), out, local
            )
        else:
            # Pass 1: fold only — no writes (the bandwidth saver).
            total = monoid.fold(xs0, axis=0)
            offset = exchange(total, monoid, axis_name, m)
            # Pass 2: local scan fused with the offset.
            local = _local_scan(xs0, monoid, local_algorithm, block_size)
            out = monoid.combine(
                jax.tree.map(lambda o: o[None], offset), local
            )
            out = jax.tree.map(
                lambda o, l: jnp.broadcast_to(o, l.shape), out, local
            )
        if exclusive:
            # Local shift with the offset itself entering at position 0.
            out = jax.tree.map(
                lambda o, off: jnp.concatenate(
                    [jnp.broadcast_to(off[None], o[:1].shape), o[:-1]], axis=0
                ),
                out,
                offset,
            )
        return jax.tree.map(lambda x: jnp.moveaxis(x, 0, scan_axis), out)

    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(spec,), out_specs=spec)
    return fn(elems)


def make_sharded_cumsum(
    mesh: Mesh,
    axis_name: str,
    spec: P,
    **kw,
) -> "functools.partial":
    """Convenience: jit-ready global cumsum over a sharded axis."""
    return functools.partial(
        scan_sharded, mesh=mesh, axis_name=axis_name, spec=spec, **kw
    )

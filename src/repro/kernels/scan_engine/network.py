"""Monoid-generic in-tile scan networks.

Each function scans the VALUES of one VMEM tile (never a ref) with
``spec.combine``, the earlier operand on the left, filling vacated slots
with the monoid identity (``spec.fills``). A layout picks the network
its tiles need (``layout.scan_tile`` / ``layout.shift_one``), so the
schedule bodies never name one:

  tile_scan       along one axis of a tile: the Hillis–Steele log
                  network, split into lane rows and row totals when the
                  axis is a long lane axis (Rows, Channels).
  flat_scan       across a whole (bv, 8, 128) stack of vregs in
                  row-major order: lanes, then the eight sublane rows,
                  then the vregs in order (Flat).
  tree_scan       Blelloch's balanced tree, exclusive (the tree
                  schedule's network).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.scan.assoc import KernelSpec

LANES = 128
SUBLANES = 8


def _shift(x, k, axis, fill):
    """Shift ``x`` by ``k`` along ``axis`` — right for ``k > 0``, left
    for ``k < 0`` — filling the vacated slots with identity."""
    n = x.shape[axis]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (k, 0) if k > 0 else (0, -k)
    sl = [slice(None)] * x.ndim
    sl[axis] = slice(0, n) if k > 0 else slice(-k, n - k)
    return jnp.pad(x, pad, constant_values=fill)[tuple(sl)]


def shift_one(spec: KernelSpec, leaves, axis):
    """Exclusive shift: one step right, identity-filled (all leaves)."""
    return tuple(
        _shift(x, 1, axis, f) for x, f in zip(leaves, spec.fills))


def log_scan(spec: KernelSpec, leaves, axis):
    """Hillis–Steele log-step inclusive scan of monoid leaves (§3.1)."""
    n = leaves[0].shape[axis]
    k = 1
    while k < n:
        shifted = tuple(
            _shift(x, k, axis, f) for x, f in zip(leaves, spec.fills))
        leaves = spec.combine(shifted, leaves)
        k *= 2
    return leaves


def tile_scan(spec: KernelSpec, leaves, axis):
    """In-tile inclusive scan; two-level lane/sublane split on lane axes.

    When the scan axis is the (128-wide) lane axis and divisible, run the
    paper's Fig. 3 scheme lifted to the monoid: scan within each lane row,
    exclusive-scan the row totals along sublanes, broadcast-combine —
    "scan the vector in register, broadcast the last element".
    """
    x0 = leaves[0]
    n = x0.shape[axis]
    last = x0.ndim - 1
    if axis == last and n > LANES and n % LANES == 0:
        r = n // LANES
        ts = tuple(
            x.reshape(x.shape[:-1] + (r, LANES)) for x in leaves)
        ts = log_scan(spec, ts, axis=ts[0].ndim - 1)
        tot = tuple(t[..., LANES - 1] for t in ts)      # per-row totals
        off = log_scan(spec, tot, axis=tot[0].ndim - 1)
        off = shift_one(spec, off, axis=off[0].ndim - 1)  # exclusive
        ts = spec.combine(tuple(o[..., None] for o in off), ts)
        return tuple(t.reshape(x.shape) for t, x in zip(ts, leaves))
    return log_scan(spec, leaves, axis)


def _positions():
    """Lane and sublane-row index of every slot of one vreg, as
    (1, 8, 128) planes that broadcast over a stack of vregs."""
    shape = (1, SUBLANES, LANES)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 2),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _rolled(spec, leaves, k, axis, pos):
    """Each leaf rotated ``k`` slots along a vreg ``axis``, the slots
    that wrapped round (``pos < k``) set to the identity."""
    return tuple(jnp.where(pos >= k, pltpu.roll(x, k, axis), f)
                 for x, f in zip(leaves, spec.fills))


def flat_scan(spec: KernelSpec, leaves):
    """Inclusive scan of (bv, 8, 128) tiles in row-major order.

    Element ``i`` of the tile sits in vreg ``i // 1024``, sublane row
    ``i // 128 % 8``, lane ``i % 128``. The paper's Fig. 3, "scan the
    vector, broadcast the last element", on whole vregs:

      1. a log-step scan of each sublane row across its 128 lanes
         (rotate, mask the wrapped lanes, combine);
      2. each row's total, broadcast across its lanes and log-scanned
         down the vreg's eight rows;
      3. a carry through the tile's vregs in order, one combine each;
      4. vreg offset, then row offset, combined into every lane.

    Every rotate stays inside one vreg. On a v5e the carry of step 3,
    one add a vreg, ran faster than a log-step scan across the vregs or
    of their totals gathered eight to a vreg, and lowered and compiled
    in under a second at 256 vregs a tile.
    """
    shape = leaves[0].shape
    lane, row = _positions()
    k = 1
    while k < LANES:
        leaves = spec.combine(_rolled(spec, leaves, k, 2, lane), leaves)
        k *= 2
    rows = tuple(jnp.broadcast_to(x[:, :, LANES - 1:], shape)
                 for x in leaves)
    k = 1
    while k < SUBLANES:
        rows = spec.combine(_rolled(spec, rows, k, 1, row), rows)
        k *= 2
    before = _rolled(spec, rows, 1, 1, row)   # rows earlier in the vreg
    acc = tuple(jnp.full((1, 1, LANES), f, x.dtype)
                for x, f in zip(leaves, spec.fills))
    offs = []
    for v in range(shape[0]):                 # vregs earlier in the tile
        offs.append(acc)
        acc = spec.combine(
            acc, tuple(r[v:v + 1, SUBLANES - 1:] for r in rows))
    vregs = tuple(jnp.concatenate(o, axis=0) for o in zip(*offs))
    return spec.combine(spec.combine(vregs, before), leaves)


def flat_shift_one(spec: KernelSpec, leaves):
    """``shift_one`` in the row-major order of ``flat_scan``'s tiles:
    lane 0 of a row takes lane 127 of the row before it, across vregs
    too; the tile's first slot takes the identity."""
    lane, row = _positions()
    out = []
    for x, f in zip(leaves, spec.fills):
        in_row = pltpu.roll(x, 1, 2)        # lane 0: its own row's last
        from_row = pltpu.roll(in_row, 1, 1)  # lane 0: the row before's
        first = jnp.full((1,) + x.shape[1:], f, x.dtype)
        from_vreg = (first if x.shape[0] == 1  # row 0: the vreg before's
                     else jnp.concatenate([first, from_row[:-1]], axis=0))
        out.append(jnp.where(lane > 0, in_row,
                             jnp.where(row > 0, from_row, from_vreg)))
    return tuple(out)


def _pad_to(x, m, axis, fill):
    """Pad ``x`` up to length ``m`` along ``axis`` with the identity."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, m - x.shape[axis])
    return jnp.pad(x, pad, constant_values=fill)


def tree_scan(spec: KernelSpec, leaves, axis):
    """In-tile EXCLUSIVE scan in the §3.3 balanced tree's association.

    Blelloch's up-sweep/down-sweep written in place over the whole tile,
    so the compiler sees only shifts, an iota and selects — no reshape
    of the lane axis. Level ``s`` (1, 2, 4, …) of the up-sweep combines
    each right child with its left sibling ``s`` slots earlier
    (``combine(left, right)`` — left argument earlier, preserving
    non-commutative order); the down-sweep seeds the root with the
    identity and walks back down, handing each left child its parent's
    prefix and each right child ``combine(parent, old_left)``, the
    convention the ``core.scan.tree`` oracle pins. The tree's
    association is the recursive pairwise one; the masked passes are
    full-width, log2(n) of them each way, all inside VMEM — each a
    combine plus selects over the whole tile, so the sweep does
    O(n log n) work, not the O(n) of a compacting tree.

    Pads the scan axis to a power of two with the monoid identity (the
    padded tail contributes identity to every prefix and to the root
    total, so the slice-back is exact). Returns ``(exclusive_scan,
    total)`` where the total keeps a size-1 scan axis (the shape
    ``layout.take_last`` produces, so the inter-block carry chain is
    shared with the carry schedule verbatim) — the inclusive form is one
    ``combine(exclusive, elems)`` away, which the tree body fuses into
    its carry application.
    """
    n = leaves[0].shape[axis]
    m = 1
    while m < n:
        m *= 2
    if m != n:
        leaves = tuple(
            _pad_to(x, m, axis, f) for x, f in zip(leaves, spec.fills))
    pos = jax.lax.broadcasted_iota(jnp.int32, leaves[0].shape, axis)

    def select(mask, new, old):
        return tuple(jnp.where(mask, a, b) for a, b in zip(new, old))

    def shifted(xs, k):
        return tuple(_shift(x, k, axis, f) for x, f in zip(xs, spec.fills))

    s = 1
    while s < m:                                   # up-sweep
        right = (pos & (2 * s - 1)) == 2 * s - 1
        leaves = select(right, spec.combine(shifted(leaves, s), leaves),
                        leaves)
        s *= 2
    total = tuple(jax.lax.slice_in_dim(x, m - 1, m, axis=axis)
                  for x in leaves)
    root = pos == m - 1
    xs = select(root, tuple(jnp.full_like(x, f)
                            for x, f in zip(leaves, spec.fills)), leaves)
    s = m // 2
    while s >= 1:                                  # down-sweep
        phase = pos & (2 * s - 1)
        parent = shifted(xs, -s)                   # right sibling's slot
        right = select(phase == 2 * s - 1,
                       spec.combine(xs, shifted(xs, s)), xs)
        xs = select(phase == s - 1, parent, right)
        s //= 2
    if m != n:
        xs = tuple(jax.lax.slice_in_dim(x, 0, n, axis=axis) for x in xs)
    return xs, total

"""Blocked prefix sum: the SUM registration of the Pallas scan engine.

This family is nothing but the sum monoid run through the monoid-generic
engine (``repro.kernels.scan_engine``) — the hand rolled carry/decoupled
kernel bodies that used to live here are the engine's schedules now,
written once for every monoid. The layout follows from the shape: fewer
rows than a vreg's eight sublanes (one long column above all) run on
Flat, whole (8, 128) vregs of one row per tile, under the carry and
decoupled schedules; eight rows or more, and an explicit ``tree`` or
``fused``, run on Rows.

The public wrapper handles arbitrary ranks/axes, padding to block
multiples, dtype policy and interpret-mode fallback on CPU.
``in_place=True`` donates the input buffer — the paper's in-place variant
(§4.2.3) expressed as XLA buffer donation.

Four grid schedules (see ``core/scan/policy`` module doc):
  * ``schedule="carry"``     — grid-carried total, sequence sequential;
  * ``schedule="decoupled"`` — reduce-then-scan, two launches;
  * ``schedule="fused"``     — reduce-then-scan, single launch chained
    through cross-chunk semaphores (compiled TPU only, small grids);
  * ``schedule="tree"``      — carry's grid, work-efficient Blelloch
    sweep inside each tile (§3.3);
  * ``schedule="auto"``      — the policy's batch-vs-cores rule decides.

``cumsum`` is differentiable via a ``jax.custom_vjp`` whose backward is
ITSELF an engine scan — the adjoint of a prefix sum is a suffix sum, so
the gradient runs the same kernel on the flipped cotangent (one more
``kernel.launch`` with the same schedule), never falling back to
differentiate-through-the-network.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.scan import assoc, policy
from repro.kernels import scan_engine
from repro.kernels.scan_engine import monoids
from repro.kernels.scan_engine.layouts import SUBLANES, VREG
from repro.kernels.scan_engine import resolve_schedule  # back-compat export

SCHEDULES = scan_engine.RESOLVABLE
ROWS_BLOCK_N = 2048  # the Rows chunk length when the caller names none


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(
    jax.jit,
    static_argnames=("axis", "exclusive", "block_b", "block_n", "interpret",
                     "schedule"),
)
def _cumsum_impl(x, axis, exclusive, block_b, block_n, interpret, schedule):
    x = jnp.moveaxis(x, axis, -1)
    lead = x.shape[:-1]
    n = x.shape[-1]
    b = 1
    for d in lead:
        b *= d
    x2 = x.reshape(b, n)

    if _flat(b, schedule):
        bn = _flat_block(n, x.dtype, block_n)
        x2 = jnp.pad(x2, ((0, 0), (0, (-n) % bn)))
        layout = scan_engine.Flat(b, x2.shape[1], bn)
        out, = scan_engine.scan(
            (x2.reshape(layout.shape),), monoids.SUM, layout,
            schedule=schedule, exclusive=exclusive, interpret=interpret)
        out = out.reshape(b, -1)
    else:
        block_n = ROWS_BLOCK_N if block_n is None else block_n
        bb = min(block_b, b) if b % min(block_b, b) == 0 else 1
        pad_b = (-b) % bb
        bn = min(block_n, _round_up(n, 128))
        pad_n = (-n) % bn
        x2 = jnp.pad(x2, ((0, pad_b), (0, pad_n)))
        layout = scan_engine.Rows(x2.shape[0], x2.shape[1], bb, bn)
        out, = scan_engine.scan(
            (x2,), monoids.SUM, layout, schedule=schedule,
            exclusive=exclusive, interpret=interpret)
    out = out[:b, :n].reshape(lead + (n,))
    return jnp.moveaxis(out, -1, axis)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _flat(b: int, schedule: str) -> bool:
    """Fewer rows than a vreg has sublanes run on the Flat layout under
    the schedules the policy picks (``auto`` resolves to one of them);
    the rest, and an explicit ``tree`` or ``fused``, on Rows."""
    return b < SUBLANES and schedule in ("carry", "decoupled", "auto")


def _flat_block(n: int, dtype, block_n: "int | None") -> int:
    """The Flat chunk length: ``block_n`` rounded up to whole vregs if
    the caller names one; else an eighth of the policy's VMEM block
    budget, so the tile's input and output, double-buffered, take half
    of it and the network's temporaries the rest (2^18 32-bit elements,
    1 MiB). Never longer than the row, rounded up to whole vregs."""
    if block_n is None:
        block_n = policy.VMEM_BLOCK_BUDGET // (
            8 * assoc.accum_dtype(dtype).itemsize)
    return min(_round_up(block_n, VREG), _round_up(n, VREG))


# Gradient-as-a-scan: d(prefix sum)/dx is a SUFFIX sum of the cotangent
# with the same exclusivity — flip, run the identical engine kernel,
# flip back. All the static knobs ride as nondiff args so the backward
# reuses the forward's jitted ``_cumsum_impl`` (and therefore emits its
# own ``kernel.launch`` trace event when compiled).
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5, 6))
def _cumsum_vjp(x, axis, exclusive, block_b, block_n, interpret, schedule):
    return _cumsum_impl(x, axis, exclusive, block_b, block_n, interpret,
                        schedule)


def _cumsum_fwd(x, axis, exclusive, block_b, block_n, interpret, schedule):
    out = _cumsum_impl(x, axis, exclusive, block_b, block_n, interpret,
                       schedule)
    return out, None


def _cumsum_bwd(axis, exclusive, block_b, block_n, interpret, schedule,
                _residual, g):
    # Inclusive: dx_j = Σ_{i>=j} g_i; exclusive: dx_j = Σ_{i>j} g_i —
    # both are the same-flavor prefix sum of the reversed cotangent.
    rev = _cumsum_impl(jnp.flip(g, axis), axis, exclusive, block_b,
                       block_n, interpret, schedule)
    return (jnp.flip(rev, axis),)


_cumsum_vjp.defvjp(_cumsum_fwd, _cumsum_bwd)


def cumsum(
    x: jax.Array,
    axis: int = -1,
    exclusive: bool = False,
    block_b: int = 8,
    block_n: "int | None" = None,
    interpret: "bool | None" = None,
    schedule: str = "auto",
) -> jax.Array:
    """Kernel-backed prefix sum along ``axis`` (any rank).

    ``interpret=None`` auto-selects: compiled on TPU, interpret elsewhere.
    ``schedule`` picks the grid organization
    (carry|decoupled|fused|tree|auto). ``block_n`` is the chunk length;
    ``None`` lets the layout choose (2048 on Rows; on Flat, see
    ``_flat_block``). Differentiable: the custom VJP runs the backward
    as another engine scan (see module doc).
    """
    if interpret is None:
        interpret = not _on_tpu()
    if x.size == 0:
        # Zero-length scan axis (or an empty batch): the scan of nothing
        # is nothing — and the padding arithmetic below would divide by
        # a zero block.
        return x
    n = x.shape[axis]
    batch = max(x.size // max(n, 1), 1)
    if _flat(batch, schedule):   # the chunk _cumsum_impl will tile with
        bn = _flat_block(n, x.dtype, block_n)
    else:
        bn = min(ROWS_BLOCK_N if block_n is None else block_n,
                 _round_up(n, 128))
    schedule = resolve_schedule(schedule, batch, n, bn)
    return _cumsum_vjp(x, axis, exclusive, block_b, block_n, interpret,
                       schedule)


# ---------------------------------------------------------------------------
# Back-compat kernel entry points (PR-1 signatures; 2D, pre-padded)
# ---------------------------------------------------------------------------


def _scan_2d(x, block_b, block_n, exclusive, interpret, schedule):
    if x.ndim != 2:
        raise ValueError(f"kernel expects 2D input, got {x.shape}")
    layout = scan_engine.Rows(x.shape[0], x.shape[1], block_b, block_n)
    out, = scan_engine.scan(
        (x,), monoids.SUM, layout, schedule=schedule, exclusive=exclusive,
        interpret=interpret)
    return out


def scan_blocked_kernel(x, *, block_b=8, block_n=2048, exclusive=False,
                        interpret=False):
    """Carry-schedule prefix sum of a pre-padded 2D (B, N) array."""
    return _scan_2d(x, block_b, block_n, exclusive, interpret, "carry")


def scan_blocked_decoupled(x, *, block_b=8, block_n=2048, exclusive=False,
                           interpret=False):
    """Decoupled-schedule prefix sum of a pre-padded 2D (B, N) array."""
    return _scan_2d(x, block_b, block_n, exclusive, interpret, "decoupled")

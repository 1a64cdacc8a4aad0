"""The four grid organizations, each written ONCE over a KernelSpec.

This is the paper's central claim — prefix-scan performance is decided by
how the sub-procedures are ORGANIZED, not by the binary operator — turned
into code structure. Every schedule below is monoid-generic: it calls
only the ``KernelSpec`` interface (``combine`` / ``fills`` / ``emit``,
see ``repro.core.scan.assoc``) plus a ``Layout`` for geometry, so sum,
segmented, affine-SSM and compact-mask all run the SAME bodies:

  carry      the paper's single-pass accumulate (SIMD-P) partitioned over
             VMEM tiles: sequential grid along the scanned axis, the
             inter-block state in VMEM scratch. HBM: read n + write n.
  decoupled  the paper's reduce-then-scan (SIMD2-P, Observation 3): a
             fully parallel totals pass, a tiny sequential combine chain
             over chunk totals, a fully parallel apply pass. HBM: read 2n
             + write n — the price of spreading ONE row across cores.
  fused      decoupled in a single launch: every chunk computes its local
             scan once, then chains its prefix to its successor through
             cross-chunk semaphores (Merrill-style chained scan). HBM:
             read n + write n with decoupled's parallelism. Runs only
             compiled on a TPU, on grids that fit the chip's semaphore
             memory; elsewhere it raises. The policy never picks it.
  tree       the paper's balanced tree (§3.3, Observation 5; Blelloch's
             up-sweep/down-sweep): the carry schedule's grid and
             inter-block carry, but the IN-TILE network replaced by
             Blelloch's pairwise up-sweep (each right child combined
             with its left sibling, doubling the stride) and down-sweep
             (parent prefixes fan back out, ``combine(parent,
             old_left)``), written in place as full-width masked passes
             — 2·log2(b) of them per b-element tile, so not
             work-efficient as written: about twice the log network's
             combines. The policy never picks it. HBM: read n + write n.

Bit-identity across schedules holds by construction for every monoid:
carry/decoupled/fused run the identical in-tile scan network, and the
decoupled/fused combine chains apply ``combine`` in exactly the carry
chain's order (``combine`` is pointwise along the scan axis, so
combining a carry into a block and then taking the last column equals
combining it into the last column directly). The tree schedule computes
the same monoid products through a DIFFERENT association (the balanced
tree), so it is bitwise identical to the others exactly when ``combine``
is associative in machine arithmetic — integer monoids, logical monoids,
floats on exactly-representable data — and agrees to rounding error
otherwise. The parity wall in ``tests/test_scan_engine.py`` pins both
regimes.

CARRIED-PAYLOAD monoids (``spec.transform`` set — flash attention's
softmax pair with its weighted-value accumulator) run the same two
organizations as a FOLD over blocks: each grid block along the scanned
axis contributes ONE macro element built by the spec's input transform
from raw operand tiles, and outputs are emitted once from the final
carried state via ``spec.finalize``:

  carry      ``_fold_carry_body`` — the single-pass accumulate again:
             sequential KV grid, payload carry in VMEM scratch, finalize
             fused into the last block's writeback. This IS the classic
             flash-attention forward, recovered from the generic engine.
  decoupled  ``_fold_totals_body`` — split-KV / flash-decoding: KV chunks
             fully parallel, each running the same accumulate over its
             sub-blocks and publishing one payload element to the chain
             buffers; a tiny jnp combine chain + finalize stitches the
             chunks. (No fused form: a fold has no per-element writeback
             to chain a prefix into, so "fused" raises.)

Folds are not bitwise-invariant across schedules — the chunk chain
re-associates the payload rescaling — but agree to float tolerance, and
each matches the reference oracles to the usual kernel tolerances.

Both fold forms honor the layout's optional KV-extent bounds
(``layout.fold_active``): grid cells whose mask is provably all-dead
skip the transform-and-combine entirely, leaving the carry untouched —
bitwise identical to folding in the identity element the masked
transform would have produced, at none of the cost. Causal prefill
runs ~half its cells this way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.scan import policy
from repro.core.scan.assoc import KernelSpec
from repro.kernels.scan_engine.network import tree_scan
from repro.obs import trace

SCHEDULES = ("carry", "decoupled", "fused", "tree")
RESOLVABLE = SCHEDULES + ("auto",)


def resolve_schedule(schedule: str, batch: int, n: int,
                     block_elems: int) -> str:
    """'auto' -> the policy's four-way rule; else validate.

    Shared by every family's ops wrapper. ``block_elems`` is the chunk
    length the kernel will ACTUALLY tile the scanned axis with — the
    policy's chunks-per-core test is only meaningful against the real
    grid.
    """
    if schedule not in RESOLVABLE:
        raise ValueError(
            f"unknown schedule {schedule!r}; one of {RESOLVABLE}")
    if schedule == "auto":
        return policy.choose_schedule(batch, n, block_elems=block_elems)
    return schedule


# ---------------------------------------------------------------------------
# Chunk-total chain
# ---------------------------------------------------------------------------


def exclusive_chain(spec: KernelSpec, totals, axis: int = 1):
    """Sequential exclusive monoid scan of chunk totals along ``axis``.

    Left-to-right ``lax.scan`` applying ``combine`` in EXACTLY the carry
    schedule's association order — this is what makes the decoupled and
    fused organizations bit-identical to the carry chain.
    """
    init = tuple(
        jnp.full_like(jax.lax.index_in_dim(t, 0, axis, keepdims=False), f)
        for t, f in zip(totals, spec.fills))

    def step(carry, t):
        return spec.combine(carry, t), carry

    moved = tuple(jnp.moveaxis(t, axis, 0) for t in totals)
    _, offs = jax.lax.scan(step, init, moved)
    return tuple(jnp.moveaxis(o, 0, axis) for o in offs)


# ---------------------------------------------------------------------------
# Shared kernel-body pieces
# ---------------------------------------------------------------------------


def _scan_block(spec, layout, data_refs, elem_dts):
    raw = tuple(layout.read(r) for r in data_refs)
    elems = tuple(r.astype(dt) for r, dt in zip(raw, elem_dts))
    scanned = layout.scan_tile(spec, elems)
    return elems, scanned


def _emit(spec, layout, out_refs, elems, combined):
    if spec.emit is not None:
        outs = spec.emit(elems, combined)
    else:
        outs = tuple(combined[i] for i in spec.out_leaves)
    for r, o in zip(out_refs, outs):
        layout.write(r, o)


def _dtypes(spec, operands):
    in_dts = tuple(jnp.dtype(o.dtype) for o in operands)
    return spec.elem_dtypes(in_dts), spec.out_dtypes(in_dts)


# ---------------------------------------------------------------------------
# Schedule 1: carry (single-pass accumulate, grid-carried total)
# ---------------------------------------------------------------------------


def _carry_body(*refs, spec, layout, elem_dts, n_out, exclusive, n_tot):
    n_elem = spec.n_leaves
    n_ops = len(refs) - n_out - n_tot - n_elem
    data_refs = refs[:n_ops]
    out_refs = refs[n_ops:n_ops + n_out]
    tot_refs = refs[n_ops + n_out:n_ops + n_out + n_tot]
    carry_refs = refs[n_ops + n_out + n_tot:]
    j = pl.program_id(layout.seq_grid_axis)

    @pl.when(j == 0)
    def _reset():
        # New row/stripe: reset the running state to the monoid identity.
        for r, f in zip(carry_refs, spec.fills):
            r[...] = jnp.full(r.shape, f, r.dtype)

    elems, scanned = _scan_block(spec, layout, data_refs, elem_dts)
    carry = tuple(layout.read_carry(r) for r in carry_refs)
    sel = layout.shift_one(spec, scanned) if exclusive else scanned
    combined = spec.combine(carry, sel)       # carry is the EARLIER operand
    _emit(spec, layout, out_refs, elems, combined)
    new_carry = spec.combine(
        carry, tuple(layout.take_last(s) for s in scanned))
    for r, c in zip(carry_refs, new_carry):
        layout.write_carry(r, c)
    # Optional running chunk-totals chain (combined through chunk j) —
    # bit-identical to the decoupled chain by the argument above.
    for r, c in zip(tot_refs, new_carry):
        layout.write_chain(r, c)


def scan_carry(operands, spec, layout, *, exclusive=False, interpret=False,
               return_totals=False):
    elem_dts, out_dts = _dtypes(spec, operands)
    n_tot = spec.n_leaves if return_totals else 0
    body = functools.partial(
        _carry_body, spec=spec, layout=layout, elem_dts=elem_dts,
        n_out=len(out_dts), exclusive=exclusive, n_tot=n_tot)
    outs = pl.pallas_call(
        body,
        grid=layout.grid,
        in_specs=layout.op_specs(len(operands)),
        out_specs=[layout.out_spec()] * len(out_dts)
        + [layout.chain_spec_for(i) for i in range(n_tot)],
        out_shape=[jax.ShapeDtypeStruct(layout.shape, dt) for dt in out_dts]
        + [jax.ShapeDtypeStruct(layout.chain_shape_for(i), dt)
           for i, dt in enumerate(elem_dts[:n_tot])],
        scratch_shapes=[layout.carry_scratch(dt, i)
                        for i, dt in enumerate(elem_dts)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=layout.semantics("arbitrary")),
        interpret=interpret,
        name=f"scan_{spec.name}_carry",
    )(*operands)
    if return_totals:
        return tuple(outs[:len(out_dts)]), tuple(outs[len(out_dts):])
    return tuple(outs)


# ---------------------------------------------------------------------------
# Schedule 2: decoupled (reduce-then-scan, two launches)
# ---------------------------------------------------------------------------


def _totals_body(*refs, spec, layout, elem_dts):
    n_elem = spec.n_leaves
    n_ops = len(refs) - n_elem
    data_refs = refs[:n_ops]
    tot_refs = refs[n_ops:]
    _, scanned = _scan_block(spec, layout, data_refs, elem_dts)
    for r, s in zip(tot_refs, scanned):
        layout.write_chain(r, layout.take_last(s))


def _apply_body(*refs, spec, layout, elem_dts, n_out, exclusive):
    n_elem = spec.n_leaves
    n_ops = len(refs) - n_out - n_elem
    data_refs = refs[:n_ops]
    off_refs = refs[n_ops:n_ops + n_elem]
    out_refs = refs[n_ops + n_elem:]
    elems, scanned = _scan_block(spec, layout, data_refs, elem_dts)
    carry = tuple(layout.read_chain(r) for r in off_refs)
    sel = layout.shift_one(spec, scanned) if exclusive else scanned
    combined = spec.combine(carry, sel)
    _emit(spec, layout, out_refs, elems, combined)


def scan_decoupled(operands, spec, layout, *, exclusive=False,
                   interpret=False, return_totals=False):
    elem_dts, out_dts = _dtypes(spec, operands)
    par = pltpu.CompilerParams(
        dimension_semantics=layout.semantics("parallel"))

    totals = pl.pallas_call(
        functools.partial(
            _totals_body, spec=spec, layout=layout, elem_dts=elem_dts),
        grid=layout.grid,
        in_specs=layout.op_specs(len(operands)),
        out_specs=[layout.chain_spec_for(i) for i in range(spec.n_leaves)],
        out_shape=[jax.ShapeDtypeStruct(layout.chain_shape_for(i), dt)
                   for i, dt in enumerate(elem_dts)],
        compiler_params=par,
        interpret=interpret,
        name=f"scan_{spec.name}_totals",
    )(*operands)

    offsets = exclusive_chain(spec, tuple(totals))

    outs = tuple(pl.pallas_call(
        functools.partial(
            _apply_body, spec=spec, layout=layout, elem_dts=elem_dts,
            n_out=len(out_dts), exclusive=exclusive),
        grid=layout.grid,
        in_specs=layout.op_specs(len(operands))
        + [layout.chain_spec_for(i) for i in range(spec.n_leaves)],
        out_specs=[layout.out_spec()] * len(out_dts),
        out_shape=[jax.ShapeDtypeStruct(layout.shape, dt) for dt in out_dts],
        compiler_params=par,
        interpret=interpret,
        name=f"scan_{spec.name}_apply",
    )(*operands, *offsets))
    if return_totals:
        # Running (inclusive) chunk totals — exactly the carry schedule's
        # per-chunk carries: exclusive offset ⊕ local total, O(B·chunks).
        running = spec.combine(offsets, tuple(totals))
        return outs, running
    return outs


# ---------------------------------------------------------------------------
# Schedule 3: fused (single-launch decoupled, cross-chunk semaphores)
# ---------------------------------------------------------------------------


# The native chain holds one regular semaphore per grid cell, and the
# chip's semaphore memory is small. This is the TPU v5e figure: compiled
# against a described v5e, a 384-cell grid fits its 2 KiB and a 448-cell
# grid is refused. Another TPU generation needs its own compile check
# (and, if it differs, a cap keyed by ``device_kind``).
FUSED_MAX_CELLS = 256


def _fused_body(*refs, spec, layout, elem_dts, n_out, exclusive):
    # refs: data ops | outs | HBM chain bufs | 2×staging | 3 semaphores
    n_elem = spec.n_leaves
    n_ops = len(refs) - n_out - 3 * n_elem - 3
    data_refs = refs[:n_ops]
    out_refs = refs[n_ops:n_ops + n_out]
    pref_refs = refs[n_ops + n_out:n_ops + n_out + n_elem]  # HBM chain bufs
    scratch = refs[n_ops + n_out + n_elem:]
    stage_in = scratch[:n_elem]           # VMEM landing for pred prefix
    stage_out = scratch[n_elem:2 * n_elem]  # VMEM staging for own prefix
    sems, dsem_in, dsem_out = scratch[2 * n_elem:2 * n_elem + 3]

    j = pl.program_id(layout.seq_grid_axis)
    nseq = layout.num_seq_blocks
    elems, scanned = _scan_block(spec, layout, data_refs, elem_dts)
    total = tuple(layout.take_last(s) for s in scanned)

    @pl.when(j > 0)
    def _await_predecessor():
        # Predecessor signals only after its prefix DMA has landed in HBM.
        pltpu.semaphore_wait(layout.sem_at(sems, j - 1), 1)
        for p, s in zip(pref_refs, stage_in):
            cp = pltpu.make_async_copy(layout.chain_at(p, j - 1), s,
                                       dsem_in)
            cp.start()
            cp.wait()

    prefix = tuple(
        jnp.where(j > 0, layout.read_chain(s),
                  jnp.full_like(layout.read_chain(s), f))
        for s, f in zip(stage_in, spec.fills))

    @pl.when(j < nseq - 1)
    def _publish():
        # Publish combine(prefix_in, total) for the successor, then signal.
        new_prefix = spec.combine(prefix, total)
        for s, p, v in zip(stage_out, pref_refs, new_prefix):
            layout.write_chain(s, v)
            cp = pltpu.make_async_copy(s, layout.chain_at(p, j), dsem_out)
            cp.start()
            cp.wait()
        pltpu.semaphore_signal(layout.sem_at(sems, j), 1)

    sel = layout.shift_one(spec, scanned) if exclusive else scanned
    combined = spec.combine(prefix, sel)
    _emit(spec, layout, out_refs, elems, combined)


def scan_fused(operands, spec, layout, *, exclusive=False, interpret=False,
               return_totals=False):
    """Single-launch decoupled: chunk prefixes chained through semaphores.

    Each grid instance scans its chunk once, waits for its predecessor's
    published prefix, combines, republishes, and fuses the prefix into
    its own writeback — read n + write n total. The chain runs only
    compiled on a TPU, and only for grids of at most
    ``FUSED_MAX_CELLS`` cells; anything else raises rather than running
    another schedule under this one's name. The policy never picks it.
    """
    if interpret:
        raise ValueError(
            "schedule='fused' runs only compiled on a TPU: its "
            "cross-chunk semaphore chain has no interpret-mode form")
    if return_totals:
        raise ValueError(
            "schedule='fused' publishes chunk prefixes, not running "
            "totals; use carry, decoupled or tree for return_totals")
    cells = 1
    for g in layout.grid:
        cells *= g
    if cells > FUSED_MAX_CELLS:
        raise ValueError(
            f"schedule='fused' needs one semaphore per grid cell; "
            f"{cells} cells exceed the chip's {FUSED_MAX_CELLS}")
    elem_dts, out_dts = _dtypes(spec, operands)
    n_elem = spec.n_leaves
    grid = layout.grid
    outs = pl.pallas_call(
        functools.partial(
            _fused_body, spec=spec, layout=layout, elem_dts=elem_dts,
            n_out=len(out_dts), exclusive=exclusive),
        grid=grid,
        in_specs=layout.op_specs(len(operands)),
        out_specs=[layout.out_spec()] * len(out_dts)
        + [pl.BlockSpec(memory_space=pl.ANY)]
        * n_elem,
        out_shape=[jax.ShapeDtypeStruct(layout.shape, dt) for dt in out_dts]
        + [jax.ShapeDtypeStruct(layout.chain_shape, dt) for dt in elem_dts],
        scratch_shapes=(
            [pltpu.VMEM(layout.chain_block, dt) for dt in elem_dts]
            + [pltpu.VMEM(layout.chain_block, dt) for dt in elem_dts]
            + [pltpu.SemaphoreType.REGULAR(tuple(grid)),
               pltpu.SemaphoreType.DMA(()),
               pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=layout.semantics("parallel")),
        interpret=interpret,
        name=f"scan_{spec.name}_fused",
    )(*operands)
    return tuple(outs[:len(out_dts)])  # drop the HBM chain buffers


# ---------------------------------------------------------------------------
# Schedule 4: tree (Blelloch sweep inside each tile)
# ---------------------------------------------------------------------------


def _tree_body(*refs, spec, layout, elem_dts, n_out, exclusive, n_tot):
    n_elem = spec.n_leaves
    n_ops = len(refs) - n_out - n_tot - n_elem
    data_refs = refs[:n_ops]
    out_refs = refs[n_ops:n_ops + n_out]
    tot_refs = refs[n_ops + n_out:n_ops + n_out + n_tot]
    carry_refs = refs[n_ops + n_out + n_tot:]
    j = pl.program_id(layout.seq_grid_axis)

    @pl.when(j == 0)
    def _reset():
        for r, f in zip(carry_refs, spec.fills):
            r[...] = jnp.full(r.shape, f, r.dtype)

    raw = tuple(layout.read(r) for r in data_refs)
    elems = tuple(r.astype(dt) for r, dt in zip(raw, elem_dts))
    excl, total = tree_scan(spec, elems, layout.scan_axis)
    carry = tuple(layout.read_carry(r) for r in carry_refs)
    # The down-sweep hands us the exclusive scan for free; inclusive is
    # one extra pointwise combine with the raw elements.
    sel = excl if exclusive else spec.combine(excl, elems)
    combined = spec.combine(carry, sel)       # carry is the EARLIER operand
    _emit(spec, layout, out_refs, elems, combined)
    # ``total`` already carries a size-1 scan axis — the same shape
    # ``layout.take_last`` yields — so the carry chain is carry's verbatim.
    new_carry = spec.combine(carry, total)
    for r, c in zip(carry_refs, new_carry):
        layout.write_carry(r, c)
    for r, c in zip(tot_refs, new_carry):
        layout.write_chain(r, c)


def scan_tree(operands, spec, layout, *, exclusive=False, interpret=False,
              return_totals=False):
    """Carry's grid with the Blelloch tree as the in-tile network.

    The §3.3 organization: the tile's combines follow the balanced tree
    (see ``tree_scan``), at the cost of 2·log2(n) masked strided passes
    inside VMEM. The inter-block carry
    chain, exclusive handling, and optional chunk-totals chain all match
    ``scan_carry`` exactly, so the schedules differ only in how each
    tile internally associates ``combine``.
    """
    elem_dts, out_dts = _dtypes(spec, operands)
    n_tot = spec.n_leaves if return_totals else 0
    body = functools.partial(
        _tree_body, spec=spec, layout=layout, elem_dts=elem_dts,
        n_out=len(out_dts), exclusive=exclusive, n_tot=n_tot)
    outs = pl.pallas_call(
        body,
        grid=layout.grid,
        in_specs=layout.op_specs(len(operands)),
        out_specs=[layout.out_spec()] * len(out_dts)
        + [layout.chain_spec_for(i) for i in range(n_tot)],
        out_shape=[jax.ShapeDtypeStruct(layout.shape, dt) for dt in out_dts]
        + [jax.ShapeDtypeStruct(layout.chain_shape_for(i), dt)
           for i, dt in enumerate(elem_dts[:n_tot])],
        scratch_shapes=[layout.carry_scratch(dt, i)
                        for i, dt in enumerate(elem_dts)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=layout.semantics("arbitrary")),
        interpret=interpret,
        name=f"scan_{spec.name}_tree",
    )(*operands)
    if return_totals:
        return tuple(outs[:len(out_dts)]), tuple(outs[len(out_dts):])
    return tuple(outs)


# ---------------------------------------------------------------------------
# Carried-payload fold schedules (spec.transform monoids)
# ---------------------------------------------------------------------------


def fold_chain(spec: KernelSpec, totals, axis: int = 1):
    """Sequential INCLUSIVE fold of chunk elements along ``axis``.

    Left-to-right ``lax.scan`` seeded with the monoid identity — the
    same association order as the fold-carry chain, so the decoupled
    fold re-associates only at chunk boundaries.
    """
    init = tuple(
        jnp.full_like(jax.lax.index_in_dim(t, 0, axis, keepdims=False), f)
        for t, f in zip(totals, spec.fills))

    def step(carry, t):
        return spec.combine(carry, t), None

    moved = tuple(jnp.moveaxis(t, axis, 0) for t in totals)
    final, _ = jax.lax.scan(step, init, moved)
    return final


def _fold_step(spec, layout, data_refs, carry_refs, elem_dts, ids):
    """One fold accumulate — transform, combine, carry writeback —
    gated on the layout's KV-extent liveness when bounds are on.

    Returns the traced ``active`` predicate (``None`` without bounds):
    a skipped cell leaves the carry untouched, which is bitwise equal to
    folding in the monoid identity its fully-masked transform would have
    produced.
    """
    active = layout.fold_active(ids)

    def step():
        ops = tuple(layout.read_op(r) for r in data_refs)
        elem = spec.transform(ops, ids)
        elem = tuple(e.astype(dt) for e, dt in zip(elem, elem_dts))
        carry = tuple(r[...] for r in carry_refs)
        new_carry = spec.combine(carry, elem)  # carry is EARLIER operand
        for r, c in zip(carry_refs, new_carry):
            r[...] = c.astype(r.dtype)

    if active is None:
        step()
    else:
        pl.when(active)(step)
    return active


def _fold_carry_body(*refs, spec, layout, elem_dts, n_ops, n_out, count):
    data_refs = refs[:n_ops]
    out_refs = refs[n_ops:n_ops + n_out]
    cnt_refs = refs[n_ops + n_out:n_ops + n_out + count]
    scratch = refs[n_ops + n_out + count:]
    carry_refs = scratch[:spec.n_leaves]
    cnt_scratch = scratch[spec.n_leaves:]
    j = pl.program_id(layout.seq_grid_axis)

    @pl.when(j == 0)
    def _reset():
        for r, f in zip(carry_refs, spec.fills):
            r[...] = jnp.full(r.shape, f, r.dtype)
        for r in cnt_scratch:
            r[...] = jnp.zeros(r.shape, r.dtype)

    active = _fold_step(spec, layout, data_refs, carry_refs, elem_dts,
                        layout.block_ids())
    for r in cnt_scratch:
        r[0, 0] += (1 if active is None
                    else active.astype(jnp.int32))

    @pl.when(j == layout.num_seq_blocks - 1)
    def _finalize():
        cur = tuple(r[...] for r in carry_refs)
        for r, o in zip(out_refs, spec.finalize(cur)):
            layout.write(r, o)
        for r, c in zip(cnt_refs, cnt_scratch):
            r[0, 0] = c[...]


def fold_carry(operands, spec, layout, *, interpret=False,
               count_cells=False):
    """Single-pass accumulate of a carried-payload monoid (flash fwd).

    ``count_cells=True`` appends an int32 ``layout.count_shape`` output
    counting the fold cells that actually executed per grid row — the
    instrumentation behind the causal-bound "launches ~half the cells"
    assertion.
    """
    elem_dts, out_dts = _dtypes(spec, operands)
    count = 1 if count_cells else 0
    body = functools.partial(
        _fold_carry_body, spec=spec, layout=layout, elem_dts=elem_dts,
        n_ops=len(operands), n_out=len(out_dts), count=count)
    outs = pl.pallas_call(
        body,
        grid=layout.grid,
        in_specs=layout.op_specs(len(operands)),
        out_specs=[layout.out_spec_for(i) for i in range(len(out_dts))]
        + [layout.count_spec()] * count,
        out_shape=[jax.ShapeDtypeStruct(layout.out_shape_for(i), dt)
                   for i, dt in enumerate(out_dts)]
        + [jax.ShapeDtypeStruct(layout.count_shape, jnp.int32)] * count,
        scratch_shapes=[layout.carry_scratch(dt, i)
                        for i, dt in enumerate(elem_dts)]
        + [pltpu.VMEM((1, 1), jnp.int32)] * count,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=layout.semantics("arbitrary")),
        interpret=interpret,
        name=f"scan_{spec.name}_fold_carry",
    )(*operands)
    if count_cells:
        return tuple(outs[:-1]), outs[-1][..., 0, 0]
    return tuple(outs)


def _fold_totals_body(*refs, spec, layout, elem_dts, n_ops):
    n_elem = spec.n_leaves
    data_refs = refs[:n_ops]
    chain_refs = refs[n_ops:n_ops + n_elem]
    carry_refs = refs[n_ops + n_elem:]
    s = pl.program_id(len(layout.split_grid) - 1)

    @pl.when(s == 0)
    def _reset():
        for r, f in zip(carry_refs, spec.fills):
            r[...] = jnp.full(r.shape, f, r.dtype)

    _fold_step(spec, layout, data_refs, carry_refs, elem_dts,
               layout.split_block_ids())

    @pl.when(s == layout.blocks_per_chunk - 1)
    def _publish():
        cur = tuple(r[...] for r in carry_refs)
        for r, c in zip(chain_refs, cur):
            layout.write_chain(r, c)


def fold_decoupled(operands, spec, layout, *, interpret=False):
    """Split-KV fold: parallel chunk accumulates + tiny combine chain.

    The flash-decoding organization: launch 1 runs the fold-carry body
    over each of ``layout.splits`` KV chunks in parallel, publishing one
    payload element per chunk; the chunks are then stitched by a
    sequential jnp combine (same association as the carry chain at chunk
    granularity) and finalized — read ``n`` once plus
    O(rows · splits · payload) chain traffic, with the scanned axis
    spread across cores.
    """
    elem_dts, out_dts = _dtypes(spec, operands)
    totals = pl.pallas_call(
        functools.partial(
            _fold_totals_body, spec=spec, layout=layout, elem_dts=elem_dts,
            n_ops=len(operands)),
        grid=layout.split_grid,
        in_specs=layout.split_op_specs(len(operands)),
        out_specs=[layout.split_chain_spec_for(i)
                   for i in range(spec.n_leaves)],
        out_shape=[jax.ShapeDtypeStruct(layout.chain_shape_for(i), dt)
                   for i, dt in enumerate(elem_dts)],
        scratch_shapes=[layout.carry_scratch(dt, i)
                        for i, dt in enumerate(elem_dts)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=layout.split_semantics()),
        interpret=interpret,
        name=f"scan_{spec.name}_fold_totals",
    )(*operands)

    final = fold_chain(spec, tuple(totals))
    outs = spec.finalize(final)
    return tuple(
        layout.unchain_out(o).astype(dt) for o, dt in zip(outs, out_dts))


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def _launch_event(operands, spec: KernelSpec, layout, schedule: str) -> None:
    """Record a ``kernel.launch`` trace event: monoid, schedule, the
    layout that tiles the operands (``"rows"``, ``"flat"``, ...), grid
    shape, a VMEM working-set estimate (one grid cell's operand blocks),
    and the schedule's slow-memory traffic estimate (read/write bytes —
    the quantity the roofline memory term and ``benchmarks.common
    .hlo_bytes`` measure, so trace events correlate with bench rows).

    Fires at TRACE time for jitted callers — once per compilation, which
    is exactly when the launch geometry is decided — and costs one
    attribute check when tracing is disabled. Uses only static shape /
    dtype metadata, so it is safe under jax tracing.
    """
    if not trace.enabled():
        return
    is_fold = spec.transform is not None
    fold_split = is_fold and schedule not in ("carry", "tree")
    grid = layout.split_grid if fold_split else layout.grid

    def nbytes(shape, dtype):
        n = 1
        for s in shape:
            n *= int(s)
        return n * jnp.dtype(dtype).itemsize

    in_bytes = sum(nbytes(o.shape, o.dtype) for o in operands)
    try:
        specs = (layout.split_op_specs(len(operands)) if fold_split
                 else layout.op_specs(len(operands)))
        vmem_est = sum(
            nbytes(bs.block_shape, o.dtype)
            for bs, o in zip(specs, operands)
            if getattr(bs, "block_shape", None) is not None)
    except Exception:           # noqa: BLE001 — estimate only, never fatal
        vmem_est = 0
    _, out_dts = _dtypes(spec, operands)
    if is_fold:
        out_bytes = sum(nbytes(layout.out_shape_for(i), dt)
                        for i, dt in enumerate(out_dts))
    else:
        out_bytes = sum(nbytes(layout.shape, dt) for dt in out_dts)
    # The module-doc traffic model: decoupled's totals pass re-reads the
    # data; carry/fused read it once.
    reads = 2 * in_bytes if (schedule == "decoupled" and not is_fold) \
        else in_bytes
    trace.instant(
        "kernel.launch", monoid=spec.name, schedule=schedule,
        layout=type(layout).__name__.lower(), fold=is_fold, grid=list(grid),
        vmem_block_bytes_est=vmem_est,
        hbm_read_bytes_est=reads, hbm_write_bytes_est=out_bytes)


def scan(operands, spec: KernelSpec, layout, *, schedule: str = "carry",
         exclusive: bool = False, interpret: bool = False,
         return_totals: bool = False, count_cells: bool = False):
    """Run ``spec``'s monoid scan over ``operands`` under one schedule.

    Returns a tuple of output arrays (most registrations emit one).
    ``return_totals=True`` additionally returns the running chunk-totals
    chain (one ``layout.chain_shape`` array per element leaf, combined
    through chunk ``j``) so callers can derive row aggregates in
    O(B·chunks) instead of re-reducing the data — not supported for
    carried-payload (transform) monoids, whose outputs already ARE the
    fold.

    Carried-payload monoids (``spec.transform``) run the fold forms of
    the schedules; ``fused`` raises there (a fold has no per-element
    writeback to chain a prefix into) and ``tree`` maps to the carry fold
    (a fold consumes one macro element per grid block — there is no
    in-block element axis for the tree sweep to reorganize).
    ``count_cells=True`` (carry fold only) additionally returns the
    executed-cell counts — the causal-bound instrumentation.
    """
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown schedule {schedule!r}; one of {SCHEDULES}")
    if exclusive and not spec.supports_exclusive:
        raise ValueError(
            f"monoid {spec.name!r} does not support exclusive mode")
    if count_cells and (spec.transform is None or schedule != "carry"):
        raise ValueError(
            "count_cells instruments the carry fold only")
    _launch_event(operands, spec, layout, schedule)
    if spec.transform is not None:
        if return_totals:
            raise ValueError(
                "return_totals is meaningless for carried-payload "
                "monoids: the output IS the fold")
        if schedule == "fused":
            raise ValueError(
                "carried-payload folds have no fused form; use carry or "
                "decoupled")
        if schedule in ("carry", "tree"):
            return fold_carry(tuple(operands), spec, layout,
                              interpret=interpret, count_cells=count_cells)
        return fold_decoupled(tuple(operands), spec, layout,
                              interpret=interpret)
    fn = {"carry": scan_carry, "decoupled": scan_decoupled,
          "fused": scan_fused, "tree": scan_tree}[schedule]
    res = fn(tuple(operands), spec, layout, exclusive=exclusive,
             interpret=interpret, return_totals=return_totals)
    if return_totals:
        outs, totals = res
        return outs, tuple(layout.unchain(t) for t in totals)
    return res

"""Grid/tile layouts the monoid-generic schedules are written against.

A layout answers every SHAPE question a schedule has — grid geometry,
block specs, carry/chunk-total shapes, how to read a tile out of a ref,
which in-tile network (``network.py``) scans it — so the schedule
bodies in ``schedules.py`` contain no per-family geometry. Five layouts
cover the five kernel families:

  Rows      (R, N) leaves scanned along the last axis in (bb, bn) VMEM
            tiles; rows are the paper's threads. Used by the sum,
            segmented and compact-mask registrations.
  Flat      fewer than eight (R, N) leaves — one long column is the
            common case — each viewed as an (N/128, 128) plane and
            scanned in lane-dense (bn/1024, 8, 128) tiles of whole
            vregs, one chunk total per tile. Used by the sum
            registration (``scan_blocked.cumsum``) for the carry and
            decoupled schedules.
  Channels  (B, T, D) leaves scanned along the TIME axis in (1, bt, bd)
            tiles; channels ride the 128-lane axis as independent lanes
            (the paper's §3.2 vertical SIMD — natural on TPU, not a
            gather penalty). Used by the affine/SSM registration.
  KVBlocks  attention geometry for carried-payload (transform) monoids:
            q (BH, Tq, d) against k/v (BHkv, Tk, d), folded along KV
            blocks. Operands have DIFFERENT index maps (GQA maps q head
            ``h`` to kv head ``h // group`` — free addressing, paper
            Obs. 5), monoid leaves are per-q-block payload carries with
            per-leaf trailing dims (``leaf_dims``), and outputs are the
            fold. Used by the flash-attention registration (forward and
            the backward dq fold).
  QBlocks   the TRANSPOSED attention fold for the backward dk/dv: one
            grid row per (kv head, KV block), folded along the
            (group × q-block) axis — every q head addressing this KV
            head is part of the fold, so GQA head summation is the fold
            itself.

Attention layouts optionally carry ``kv_bounds`` — the per-q-block KV
extent (causal, window, kv_len): fold schedules skip grid cells whose
mask is provably all-dead. With the zeroed-probability convention
(``assoc.softmax_pair_kernel_spec``) a skipped cell's element is the
monoid identity, so the bound is bitwise-invisible while causal prefill
runs ~half the cells.

All layouts put the scanned axis LAST in the grid, expose ``chunk``
axis 1 in their chunk-total arrays, and keep the scan axis at size 1 in
carry slices so monoid ``combine`` broadcasts carries against tiles.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.scan_engine import network
from repro.kernels.scan_engine.network import LANES, SUBLANES

VREG = SUBLANES * LANES   # 32-bit elements in one vreg


def _check_divisible(shape, block, what):
    for s, b in zip(shape, block):
        if s % b:
            raise ValueError(
                f"{what} shape {shape} not divisible by block {block}")


class _UniformLeaves:
    """Shared per-leaf plumbing for layouts whose monoid leaves all share
    the data tile geometry (Rows, Channels). The schedules only speak the
    per-leaf/per-operand dialect so carried-payload layouts (KVBlocks)
    can differ; uniform layouts delegate to their single spec."""

    def op_specs(self, n_ops):
        return [self.data_spec()] * n_ops

    def out_spec(self):
        return self.data_spec()

    def out_spec_for(self, i):
        return self.out_spec()

    def out_shape_for(self, i):
        return self.shape

    def chain_spec_for(self, leaf):
        return self.chain_spec()

    def chain_shape_for(self, leaf):
        return self.chain_shape

    # -- in-tile network ------------------------------------------------
    def scan_tile(self, spec, leaves):
        return network.tile_scan(spec, leaves, self.scan_axis)

    def shift_one(self, spec, leaves):
        return network.shift_one(spec, leaves, self.scan_axis)


@dataclasses.dataclass(frozen=True)
class Rows(_UniformLeaves):
    """2D (rows, n) leaves, scan along axis 1, blocks (bb, bn)."""

    rows: int
    n: int
    bb: int
    bn: int

    def __post_init__(self):
        _check_divisible((self.rows, self.n), (self.bb, self.bn), "Rows")

    # -- grid geometry --------------------------------------------------
    @property
    def shape(self):
        return (self.rows, self.n)

    @property
    def grid(self):
        return (self.rows // self.bb, self.n // self.bn)

    @property
    def num_seq_blocks(self):
        return self.n // self.bn

    @property
    def seq_grid_axis(self):
        return len(self.grid) - 1

    scan_axis = 1  # within the (bb, bn) tile

    def semantics(self, seq_kind: str):
        return ("parallel",) * (len(self.grid) - 1) + (seq_kind,)

    # -- block specs ----------------------------------------------------
    def data_spec(self):
        return pl.BlockSpec((self.bb, self.bn), lambda i, j: (i, j))

    # Chunk totals are kept as (row blocks, chunks, bb, LANES): every
    # grid cell's (bb, 1) carry column, replicated across one lane
    # tile, is a whole trailing (sublane, lane) plane of its own — a
    # block shape the TPU compiler accepts for any bb and any chunk
    # count, and one a DMA may slice. The price is 128x the bytes of a
    # lane-dense (rows, chunks) chain, paid per (bb, bn) chunk: at
    # bb=1, bn=2048 a 2^28-element row would hold 64 MiB of totals and
    # 64 MiB of offsets. Rows of fewer than eight therefore run on Flat,
    # whose chunks are a hundred times longer.
    def chain_spec(self):
        return pl.BlockSpec(self.chain_block, lambda i, j: (i, j, 0, 0))

    @property
    def chain_shape(self):
        return (self.rows // self.bb, self.num_seq_blocks, self.bb, LANES)

    @property
    def chain_block(self):
        return (1, 1, self.bb, LANES)

    def unchain(self, x):
        """Chain array -> (rows, chunks), chunk ``j`` in column ``j``."""
        nb, nc = x.shape[:2]
        return x[..., 0].transpose(0, 2, 1).reshape(nb * self.bb, nc)

    def carry_scratch(self, dtype, leaf=0):
        return pltpu.VMEM((self.bb, 1), dtype)

    # -- in-kernel views ------------------------------------------------
    def read(self, ref):
        return ref[...]

    def write(self, ref, val):
        ref[...] = val.astype(ref.dtype)

    def read_carry(self, ref):
        return ref[...]

    def write_carry(self, ref, val):
        ref[...] = val.astype(ref.dtype)

    def read_chain(self, ref):
        return ref[0, 0][:, :1]

    def write_chain(self, ref, val):
        ref[0, 0] = jnp.broadcast_to(val, self.chain_block[2:]).astype(
            ref.dtype)

    def take_last(self, x):
        return x[:, -1:]

    # -- fused-schedule addressing (whole-array HBM refs) ---------------
    def chain_at(self, ref, seq_index):
        """Slice one chunk's (1, 1, bb, LANES) block of the chain buffer for
        this instance's row block."""
        return ref.at[pl.ds(pl.program_id(0), 1), pl.ds(seq_index, 1)]

    def sem_at(self, sem, seq_index):
        return sem.at[pl.program_id(0), seq_index]


@dataclasses.dataclass(frozen=True)
class Flat(_UniformLeaves):
    """(rows, n) leaves, fewer rows than a vreg has sublanes, each row
    scanned as an (n/128, 128) plane in blocks of ``bn`` elements.

    Operands and outputs have the shape ``(rows, n/1024, 8, 128)``, the
    bytes of the (rows, n) array exactly (a row-major (8, 128) tile is
    1,024 consecutive elements), so a column reaches the kernel through
    a bitcast. A block is ``(1, bn/1024, 8, 128)``: whole vregs of one
    row, every sublane used, where a (1, bn) Rows block fills one
    sublane in eight. The in-tile network is ``network.flat_scan``;
    carries are (1, LANES) rows holding one value in every lane.

    Chunk totals are ``(rows, chunks, 1, LANES)``: one (1, LANES) plane
    per chunk, a block shape the compiler accepts, 512 bytes a chunk.
    """

    rows: int
    n: int
    bn: int

    def __post_init__(self):
        _check_divisible((self.n, self.bn), (self.bn, VREG), "Flat")

    @property
    def shape(self):
        return (self.rows, self.n // VREG, SUBLANES, LANES)

    @property
    def grid(self):
        return (self.rows, self.n // self.bn)

    @property
    def num_seq_blocks(self):
        return self.n // self.bn

    @property
    def seq_grid_axis(self):
        return len(self.grid) - 1

    def semantics(self, seq_kind: str):
        return ("parallel", seq_kind)

    def data_spec(self):
        return pl.BlockSpec((1, self.bn // VREG, SUBLANES, LANES),
                            lambda i, j: (i, j, 0, 0))

    def chain_spec(self):
        return pl.BlockSpec(self.chain_block, lambda i, j: (i, j, 0, 0))

    @property
    def chain_shape(self):
        return (self.rows, self.num_seq_blocks, 1, LANES)

    @property
    def chain_block(self):
        return (1, 1, 1, LANES)

    def unchain(self, x):
        """Chain array -> (rows, chunks)."""
        return x[:, :, 0, 0]

    def carry_scratch(self, dtype, leaf=0):
        return pltpu.VMEM((1, LANES), dtype)

    def read(self, ref):
        return ref[0]  # (bn/1024, 8, 128)

    def write(self, ref, val):
        ref[0] = val.astype(ref.dtype)

    def read_carry(self, ref):
        return ref[...]

    def write_carry(self, ref, val):
        ref[...] = val.astype(ref.dtype)

    def read_chain(self, ref):
        return ref[0, 0]

    def write_chain(self, ref, val):
        ref[0, 0] = jnp.broadcast_to(val, (1, LANES)).astype(ref.dtype)

    def take_last(self, x):
        last = x[x.shape[0] - 1:, SUBLANES - 1:, LANES - 1:]
        return jnp.broadcast_to(last.reshape(1, 1), (1, LANES))

    def scan_tile(self, spec, leaves):
        return network.flat_scan(spec, leaves)

    def shift_one(self, spec, leaves):
        return network.flat_shift_one(spec, leaves)


@dataclasses.dataclass(frozen=True)
class Channels(_UniformLeaves):
    """3D (B, T, D) leaves, scan along axis 1 (time), blocks (1, bt, bd).

    In-kernel tiles are (bt, bd) with time on the SUBLANE axis and
    channels on lanes; carries are (1, bd) — one state per channel lane.
    """

    b: int
    t: int
    d: int
    bt: int
    bd: int

    def __post_init__(self):
        _check_divisible((self.t, self.d), (self.bt, self.bd), "Channels")

    @property
    def shape(self):
        return (self.b, self.t, self.d)

    @property
    def grid(self):
        return (self.b, self.d // self.bd, self.t // self.bt)

    @property
    def num_seq_blocks(self):
        return self.t // self.bt

    @property
    def seq_grid_axis(self):
        return len(self.grid) - 1

    scan_axis = 0  # within the (bt, bd) tile

    def semantics(self, seq_kind: str):
        return ("parallel",) * (len(self.grid) - 1) + (seq_kind,)

    def data_spec(self):
        return pl.BlockSpec((1, self.bt, self.bd), lambda i, d, t: (i, t, d))

    # Chunk totals are kept as (B, chunks, 1, D): the (1, bd) carry row
    # is a whole (sublane, lane-block) plane, as the compiler requires.
    def chain_spec(self):
        return pl.BlockSpec(self.chain_block,
                            lambda i, d, t: (i, t, 0, d))

    @property
    def chain_shape(self):
        return (self.b, self.num_seq_blocks, 1, self.d)

    @property
    def chain_block(self):
        return (1, 1, 1, self.bd)

    def unchain(self, x):
        """Chain array -> (B, chunks, D)."""
        return x[:, :, 0]

    def carry_scratch(self, dtype, leaf=0):
        return pltpu.VMEM((1, self.bd), dtype)

    def read(self, ref):
        return ref[0]  # (bt, bd)

    def write(self, ref, val):
        ref[0] = val.astype(ref.dtype)

    def read_carry(self, ref):
        return ref[...]  # (1, bd): broadcasts over the (bt, bd) tile

    def write_carry(self, ref, val):
        ref[...] = val.astype(ref.dtype)

    def read_chain(self, ref):
        return ref[0, 0]  # (1, bd)

    def write_chain(self, ref, val):
        ref[0, 0] = val.astype(ref.dtype)

    def take_last(self, x):
        return x[-1:, :]

    def chain_at(self, ref, seq_index):
        i, d = pl.program_id(0), pl.program_id(1)
        return ref.at[pl.ds(i, 1), pl.ds(seq_index, 1), :,
                      pl.ds(d * self.bd, self.bd)]

    def sem_at(self, sem, seq_index):
        return sem.at[pl.program_id(0), pl.program_id(1), seq_index]


def _block_map_lookup(table):
    """Scalar lookup ``j -> table[j]`` usable inside a Pallas index map.

    Index maps may not capture ARRAY constants, so the table is encoded
    arithmetically over python-int literals (a one-hot dot product on
    the traced block id). O(len(table)) scalar ops per grid cell — cheap
    at block granularity; a scalar-prefetch table
    (``PrefetchScalarGridSpec``) is the TPU-native upgrade path.
    """
    table = tuple(int(x) for x in table)

    def look(j):
        out = jnp.int32(0)
        for idx, phys in enumerate(table):
            out = out + jnp.int32(phys) * (j == idx).astype(jnp.int32)
        return out

    return look


def block_live(qi, kj, *, bq, bk, causal, window, kv_len):
    """Whether the (q-block ``qi``, kv-block ``kj``) mask has ANY live
    entry — the per-q-block KV extent in predicate form.

    Conservative in the safe direction: a False is a proof that every
    (row, col) pair in the cell is masked (each conjunct is a necessary
    condition for liveness over the block's row/col ranges), so skipping
    the cell is exact; a rare True on a fully-masked cell merely folds
    in the monoid identity. Works on python ints (analytic cell counts)
    and traced program ids (in-kernel skip) alike.
    """
    live = True
    if kv_len is not None:
        live = kj * bk < kv_len
    if causal:
        live = live & (kj * bk <= (qi + 1) * bq - 1)
    if window is not None:
        live = live & ((kj + 1) * bk - 1 > qi * bq - window)
    return live


def _active_cell_count(nq, nk, *, bq, bk, bounds):
    causal, window, kv_len = bounds
    return sum(
        bool(block_live(qi, kj, bq=bq, bk=bk, causal=causal,
                        window=window, kv_len=kv_len))
        for qi in range(nq) for kj in range(nk))


@dataclasses.dataclass(frozen=True)
class _AttnFold:
    """Shared plumbing for the attention fold layouts (KVBlocks/QBlocks).

    Both transposes share the field set, operand addressing kinds,
    split-grid derivation, and the KV-extent liveness wiring; concrete
    classes supply only the grid orientation — which axis is the fold,
    the per-operand/output index maps, and the chain/carry geometry.

    ``op_kinds`` names each operand's addressing — ``"q"`` (q-major
    (bh, tq, d) tiles), ``"kv"`` (kv-major (bh_kv, tk, d) tiles with the
    GQA ``h // group`` association), ``"qstat"`` (q-major per-row
    statistics, trailing dim 1) — so the backward folds can feed
    ``(q, k, v, do, m, l, delta)`` through the same layouts.
    ``out_dims`` gives per-output trailing dims (stats outputs are
    dim-1); ``kv_bounds = (causal, window, kv_len)`` enables the
    per-q-block KV extent (``fold_active``).
    """

    bh: int              # flattened B·H_q query rows
    bh_kv: int           # flattened B·H_kv rows; bh == bh_kv * group
    tq: int
    tk: int
    d: int
    bq: int
    bk: int
    group: int = 1
    splits: int = 1      # fold-axis chunks for the decoupled schedule
    leaf_dims: "tuple | None" = None   # per-leaf trailing dims
    op_kinds: tuple = ("q", "kv", "kv")
    out_dims: "tuple | None" = None    # per-output trailing dims; all d
    kv_bounds: "tuple | None" = None   # (causal, window, kv_len) extent
    # Page indirection (serve/paging.py): logical KV block j reads
    # physical block kv_block_map[j] — block-granular gather folded into
    # the operand INDEX MAPS, so a paged pool feeds the fold with no
    # materialized contiguous copy. None = identity addressing.
    kv_block_map: "tuple | None" = None

    def __post_init__(self):
        name = type(self).__name__
        _check_divisible((self.tq, self.tk), (self.bq, self.bk), name)
        if self.bh != self.bh_kv * self.group:
            raise ValueError(
                f"bh={self.bh} != bh_kv={self.bh_kv} * group={self.group}")
        if self.kv_block_map is not None and len(self.kv_block_map) != self.nk:
            raise ValueError(
                f"kv_block_map has {len(self.kv_block_map)} entries for "
                f"{self.nk} logical KV blocks")
        if self.num_seq_blocks % self.splits:
            raise ValueError(
                f"splits={self.splits} must divide {self.num_seq_blocks} "
                f"{name} fold blocks")
        bad = set(self.op_kinds) - {"q", "kv", "qstat"}
        if bad:
            raise ValueError(f"unknown op kinds {sorted(bad)}")

    # -- geometry --------------------------------------------------------
    @property
    def nq(self):
        return self.tq // self.bq

    @property
    def nk(self):
        return self.tk // self.bk

    @property
    def blocks_per_chunk(self):
        return self.num_seq_blocks // self.splits

    @property
    def seq_grid_axis(self):
        return len(self.grid) - 1

    @property
    def split_grid(self):
        return self.grid[:-1] + (self.splits, self.blocks_per_chunk)

    def semantics(self, seq_kind: str):
        return ("parallel",) * (len(self.grid) - 1) + (seq_kind,)

    def split_semantics(self):
        # chunks parallel, sub-blocks within a chunk sequential
        return ("parallel",) * 3 + ("arbitrary",)

    def out_dim(self, i: int) -> int:
        return self.d if self.out_dims is None else self.out_dims[i]

    # -- block specs -----------------------------------------------------
    def _check_ops(self, n_ops):
        if n_ops != len(self.op_kinds):
            raise ValueError(
                f"{type(self).__name__} expects {len(self.op_kinds)} "
                f"operands ({self.op_kinds}), got {n_ops}")

    def op_specs(self, n_ops):
        self._check_ops(n_ops)
        return [self._op_spec(kind, split=False) for kind in self.op_kinds]

    def split_op_specs(self, n_ops):
        self._check_ops(n_ops)
        return [self._op_spec(kind, split=True) for kind in self.op_kinds]

    # -- causal-aware KV extent ------------------------------------------
    def fold_active(self, ids):
        """Liveness of the grid cell at semantic ids ``(h, qi, kj)`` —
        ``None`` when no bounds are configured (always run)."""
        if self.kv_bounds is None:
            return None
        causal, window, kv_len = self.kv_bounds
        if not causal and window is None and kv_len is None:
            # No live constraint: block_live would fold to the python
            # constant True, which the schedules' pl.when/counter can't
            # consume — report "no bound" instead.
            return None
        _, qi, kj = ids
        return block_live(qi, kj, bq=self.bq, bk=self.bk, causal=causal,
                          window=window, kv_len=kv_len)

    def _live_plane_cells(self) -> int:
        """Live cells of the (q-block, kv-block) plane under bounds."""
        if self.kv_bounds is None:
            return self.nq * self.nk
        return _active_cell_count(self.nq, self.nk, bq=self.bq,
                                  bk=self.bk, bounds=self.kv_bounds)

    # -- in-kernel views -------------------------------------------------
    def read_op(self, ref):
        return ref[0]

    def write(self, ref, val):
        ref[0] = val.astype(ref.dtype)

    def write_chain(self, ref, val):
        ref[0, 0] = val.astype(ref.dtype)


@dataclasses.dataclass(frozen=True)
class KVBlocks(_AttnFold):
    """Attention fold geometry for carried-payload (transform) monoids.

    q ``(bh, tq, d)`` attends k/v ``(bh_kv, tk, d)``; the scanned axis is
    the KV-block axis and the monoid leaves are per-q-block PAYLOAD
    carries — ``(bq, leaf_dims[i])`` tiles (flash attention: the
    ``(m, l)`` pair at dim 1 plus the weighted-value accumulator at dim
    ``d``) — so carries, chain buffers and scratch are per-leaf shaped,
    unlike the uniform-leaf layouts above.

    Two grids serve the two fold schedules:

      carry      ``(bh, nq, nk)``, KV axis sequential ("arbitrary"):
                 the single-pass accumulate — q·kᵀ folded into the VMEM
                 payload carry block by block, output written once at
                 the last KV block.
      decoupled  ``(bh, nq, splits, nk/splits)``: the split-KV /
                 flash-decoding organization. KV chunks are fully
                 parallel; WITHIN a chunk the sub-block axis is the same
                 sequential accumulate, publishing one payload triple
                 per chunk to the chain buffers; a tiny jnp combine
                 chain + finalize stitches chunks back together.

    ``group`` maps q head ``h`` to kv head ``h // group`` in the k/v
    index maps (GQA as free addressing, paper Obs. 5). Used by the
    flash forward AND the backward dq fold (see ``_AttnFold`` for the
    operand-kind / out-dims / KV-bounds machinery).
    """

    @property
    def shape(self):
        return (self.bh, self.tq, self.d)

    @property
    def num_seq_blocks(self):
        return self.nk          # the fold walks KV blocks

    @property
    def grid(self):
        return (self.bh, self.nq, self.nk)

    def leaf_dim(self, leaf: int) -> int:
        dims = self.leaf_dims if self.leaf_dims is not None \
            else (1, 1, self.d)
        return dims[leaf]

    def _op_spec(self, kind, split: bool):
        g, bpc = self.group, self.blocks_per_chunk
        if kind == "q" or kind == "qstat":
            dim = self.d if kind == "q" else 1
            if split:
                return pl.BlockSpec((1, self.bq, dim),
                                    lambda h, i, c, s: (h, i, 0))
            return pl.BlockSpec((1, self.bq, dim),
                                lambda h, i, j: (h, i, 0))
        if self.kv_block_map is not None:
            # Paged addressing: the logical fold position routes through
            # the block map; the grid walk (and with it kv_bounds /
            # fold_active, keyed on LOGICAL ids) is unchanged.
            m = _block_map_lookup(self.kv_block_map)
            if split:
                return pl.BlockSpec((1, self.bk, self.d),
                                    lambda h, i, c, s, g=g, bpc=bpc, m=m:
                                    (h // g, m(c * bpc + s), 0))
            return pl.BlockSpec((1, self.bk, self.d),
                                lambda h, i, j, g=g, m=m: (h // g, m(j), 0))
        if split:
            return pl.BlockSpec((1, self.bk, self.d),
                                lambda h, i, c, s, g=g, bpc=bpc:
                                (h // g, c * bpc + s, 0))
        return pl.BlockSpec((1, self.bk, self.d),
                            lambda h, i, j, g=g: (h // g, j, 0))

    def out_spec_for(self, i: int):
        # independent of the KV axis: the block persists in VMEM across
        # the sequential axis and is written once, at the last KV block
        dim = self.out_dim(i)
        return pl.BlockSpec((1, self.bq, dim), lambda h, qi, j: (h, qi, 0))

    def out_shape_for(self, i: int):
        return (self.bh, self.tq, self.out_dim(i))

    def chain_shape_for(self, leaf: int):
        return (self.bh * self.nq, self.splits, self.bq,
                self.leaf_dim(leaf))

    def split_chain_spec_for(self, leaf: int):
        nq = self.nq
        return pl.BlockSpec(
            (1, 1, self.bq, self.leaf_dim(leaf)),
            lambda h, i, c, s, nq=nq: (h * nq + i, c, 0, 0))

    def carry_scratch(self, dtype, leaf=0):
        return pltpu.VMEM((self.bq, self.leaf_dim(leaf)), dtype)

    def active_cells(self) -> int:
        """Analytic count of live grid cells under ``kv_bounds`` (full
        grid when bounds are off) — per flattened head row."""
        return self._live_plane_cells()

    # -- cell-count instrumentation (carry fold) -------------------------
    @property
    def count_shape(self):
        # trailing (1, 1) plane per row: a block the compiler accepts
        return (self.bh, self.nq, 1, 1)

    def count_spec(self):
        return pl.BlockSpec((1, 1, 1, 1), lambda h, qi, j: (h, qi, 0, 0))

    # -- in-kernel views -------------------------------------------------
    def block_ids(self):
        return (pl.program_id(0), pl.program_id(1), pl.program_id(2))

    def split_block_ids(self):
        bpc = self.blocks_per_chunk
        return (pl.program_id(0), pl.program_id(1),
                pl.program_id(2) * bpc + pl.program_id(3))

    def unchain_out(self, x):
        """(bh·nq, bq, dim) fold/finalize result -> (bh, tq, dim)."""
        return x.reshape(self.bh, self.tq, x.shape[-1])


@dataclasses.dataclass(frozen=True)
class QBlocks(_AttnFold):
    """Transposed attention fold geometry: the backward dk/dv layout.

    One grid row per (kv head, KV block); the scanned axis walks the
    (group × q-block) product — every q head that addresses this KV head
    under GQA plus every q block, so the head summation IS the fold.
    Monoid leaves are per-KV-block accumulators of shape
    ``(bk, leaf_dims[i])`` (flash backward: the dk and dv tiles), and
    outputs land kv-major at ``(bh_kv, tk, out_dim)``.

    Operand addressing mirrors :class:`KVBlocks` with the roles
    transposed: kv-kind operands ride the grid row, q-kind operands are
    indexed from the fold position ``f`` as
    ``(h_kv·group + f // nq, f % nq)``. ``kv_bounds`` applies the same
    per-(q-block, kv-block) liveness predicate — for a causal grid the
    fold skips the q blocks above the diagonal.
    """

    op_kinds: tuple = ("q", "kv", "kv", "q", "qstat", "qstat", "qstat")

    @property
    def num_seq_blocks(self):
        return self.group * self.nq    # the fold walks (group, q) blocks

    @property
    def grid(self):
        return (self.bh_kv, self.nk, self.num_seq_blocks)

    def leaf_dim(self, leaf: int) -> int:
        return self.d if self.leaf_dims is None else self.leaf_dims[leaf]

    def _op_spec(self, kind, split: bool):
        g, nq, bpc = self.group, self.nq, self.blocks_per_chunk
        if kind == "q" or kind == "qstat":
            dim = self.d if kind == "q" else 1
            if split:
                return pl.BlockSpec(
                    (1, self.bq, dim),
                    lambda h, j, c, s, g=g, nq=nq, bpc=bpc:
                    (h * g + (c * bpc + s) // nq, (c * bpc + s) % nq, 0))
            return pl.BlockSpec(
                (1, self.bq, dim),
                lambda h, j, f, g=g, nq=nq: (h * g + f // nq, f % nq, 0))
        if split:
            return pl.BlockSpec((1, self.bk, self.d),
                                lambda h, j, c, s: (h, j, 0))
        return pl.BlockSpec((1, self.bk, self.d),
                            lambda h, j, f: (h, j, 0))

    def out_spec_for(self, i: int):
        # independent of the fold axis: persists in VMEM, written once
        dim = self.out_dim(i)
        return pl.BlockSpec((1, self.bk, dim), lambda h, j, f: (h, j, 0))

    def out_shape_for(self, i: int):
        return (self.bh_kv, self.tk, self.out_dim(i))

    def chain_shape_for(self, leaf: int):
        return (self.bh_kv * self.nk, self.splits, self.bk,
                self.leaf_dim(leaf))

    def split_chain_spec_for(self, leaf: int):
        nk = self.nk
        return pl.BlockSpec(
            (1, 1, self.bk, self.leaf_dim(leaf)),
            lambda h, j, c, s, nk=nk: (h * nk + j, c, 0, 0))

    def carry_scratch(self, dtype, leaf=0):
        return pltpu.VMEM((self.bk, self.leaf_dim(leaf)), dtype)

    def active_cells(self) -> int:
        """Live fold cells per flattened kv-head row (every q head of
        the group walks the same (qi, kj) liveness plane)."""
        return self.group * self._live_plane_cells()

    # -- cell-count instrumentation (carry fold) -------------------------
    @property
    def count_shape(self):
        return (self.bh_kv, self.nk, 1, 1)

    def count_spec(self):
        return pl.BlockSpec((1, 1, 1, 1), lambda h, j, f: (h, j, 0, 0))

    # -- in-kernel views -------------------------------------------------
    def block_ids(self):
        h, j, f = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        return (h * self.group + f // self.nq, f % self.nq, j)

    def split_block_ids(self):
        h, j = pl.program_id(0), pl.program_id(1)
        f = pl.program_id(2) * self.blocks_per_chunk + pl.program_id(3)
        return (h * self.group + f // self.nq, f % self.nq, j)

    def unchain_out(self, x):
        """(bh_kv·nk, bk, dim) fold/finalize result -> (bh_kv, tk, dim)."""
        return x.reshape(self.bh_kv, self.tk, x.shape[-1])

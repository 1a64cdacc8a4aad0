"""Monoid-generic Pallas scan engine: each schedule written once.

The paper's finding is that prefix-sum performance is decided by how the
computation's sub-procedures are ORGANIZED — single-pass accumulate,
reduce-then-scan, scan-then-propagate, and their partitioned variants —
not by the binary operator being scanned. This package is that split as
architecture:

  organization (written ONCE)             operator (a registration)
  --------------------------------------  --------------------------------
  schedules.scan_carry      — the paper's  assoc.SUM_KERNEL        (cumsum)
    single-pass accumulate (SIMD-P) over   assoc.SEGMENTED_SUM_KERNEL
    VMEM partitions                          (segmented scans / MoE ranks)
  schedules.scan_decoupled  — reduce-then- assoc.AFFINE_KERNEL
    scan (SIMD2-P, Observation 3), two       (SSM/xLSTM recurrences)
    launches                               assoc.mask_kernel_spec
  schedules.scan_fused      — reduce-then-   (stream compaction, fused
    scan in ONE launch, chunk prefixes       predicate select)
    chained through cross-chunk            assoc.softmax_pair_kernel_spec
    semaphores (Merrill-style); compiled     (flash attention: carried
    TPU only, never picked by the policy     payload + input transform)
  schedules.scan_tree       — work-efficient
    balanced tree (§3.3, Observation 5):
    Blelloch up-sweep/down-sweep inside
    each VMEM tile, carry's grid between
    tiles
  schedules.fold_carry /    — the same two
    schedules.fold_decoupled organizations
    as a FOLD for carried-payload monoids
    (spec.transform builds each block's
    element from raw operand tiles;
    decoupled == split-KV flash-decoding)

(The paper's remaining organization, scan-then-propagate / SIMD1-P, is
the same dataflow as reduce-then-scan with the pass-1 scans kept; its
extra intermediate traffic loses under Observation 3, so the engine does
not ship it as a schedule — ``core.scan.blocked.scan_two_pass`` keeps it
available as a library oracle.)

Geometry lives in ``layouts`` (Rows for 2D batch×sequence, Flat for
fewer than eight long rows, Channels for SSM batch×time×channel tiles,
KVBlocks for the attention fold), in-tile networks in ``network``;
``core/scan/policy.choose_schedule`` arbitrates the three-way schedule
choice (``choose_attention_schedule`` the two-way fold variant). The
five kernel families under
``repro.kernels.{scan_blocked,segscan,ssm_scan,compact,flash_attention}``
are thin back-compat wrappers over this engine — adding a new schedule
(or a new monoid) is a one-file change.
"""

from repro.kernels.scan_engine import monoids
from repro.kernels.scan_engine.layouts import (Channels, Flat, KVBlocks,
                                               QBlocks, Rows, block_live)
from repro.kernels.scan_engine.network import tile_scan, tree_scan
from repro.kernels.scan_engine.schedules import (RESOLVABLE, SCHEDULES,
                                                 exclusive_chain, fold_carry,
                                                 fold_chain, fold_decoupled,
                                                 resolve_schedule, scan,
                                                 scan_carry, scan_decoupled,
                                                 scan_fused, scan_tree)

__all__ = [
    "Channels", "Flat", "KVBlocks", "QBlocks", "RESOLVABLE", "Rows",
    "SCHEDULES", "block_live", "exclusive_chain", "fold_carry", "fold_chain",
    "fold_decoupled", "monoids",
    "resolve_schedule", "scan", "scan_carry", "scan_decoupled",
    "scan_fused", "scan_tree", "tile_scan", "tree_scan",
]

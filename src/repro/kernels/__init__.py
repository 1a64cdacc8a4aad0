"""Pallas TPU kernels for the performance-critical compute layers.

All four scan families are registrations of ONE monoid-generic engine
(``scan_engine``): each grid organization is written once against a
kernel-side monoid spec (``core/scan/assoc.KernelSpec``), and a family
is just a spec + a layout + a back-compat ``ops`` wrapper. Four grid
schedules per family (`schedule=` knob on each ``ops`` wrapper,
arbitrated by ``core/scan/policy.choose_schedule``):

  carry      — the paper's §2.2 partitioned single pass: sequential grid
               along the scanned axis, VMEM scratch carry, both logical
               passes fused while the block is VMEM-resident. Parallelism
               across rows only.
  decoupled  — the paper's SIMD2-P reduce-then-scan (Observation 3): a
               fully parallel totals pass, a tiny exclusive combine, and
               a fully parallel scan+offset pass — the scanned axis
               itself spreads across cores (B=1, huge-N serve shapes).
  fused      — the same reduce-then-scan in a SINGLE launch: chunk
               prefixes chained through cross-chunk semaphores, erasing
               decoupled's second data read. Compiled on a TPU only, for
               grids that fit its semaphore memory; never picked by the
               policy.
  tree       — carry's grid with Blelloch's balanced tree in each tile.

  scan_engine      — the schedules (written once) + layouts + registry
  scan_blocked     — prefix sum            (sum monoid registration)
  segscan          — segmented prefix sum  ((value, flag) registration)
  ssm_scan         — affine-monoid scan    (SSM/xLSTM recurrences)
  compact          — stream compaction     (mask monoid, fused select)
  flash_attention  — online-softmax monoid scan over KV blocks
"""

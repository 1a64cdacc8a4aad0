"""Algorithm selection policy — the paper's §5 observations, codified.

The paper closes with five observations about which algorithm to use when.
This module turns them into an executable policy so `repro.core.scan.api`
can pick a sensible default, and so the choice is documented in one place:

  Obs 1  Dilation factors are fragile → we never auto-pick dilated variants;
         equal partitions + partitioning (whose one tunable, the block size,
         follows from cache/VMEM geometry) are the default.
  Obs 2  Partition only when bandwidth-bound → tiny inputs that fit in
         VMEM/cache skip the blocked machinery.
  Obs 3  SIMD2-P (accumulate-first + partitioning) is the most robust
         multithreaded organization → variant=2 is the distributed default.
  Obs 4  In/out-of-place interacts with structure → exposed as buffer
         donation in the jitted wrappers, not an algorithm change.
  Obs 5  Tree/vertical lose on memory access → never auto-picked; they
         remain available for study and as oracles.

Kernel SCHEDULE rule (Obs 2/3 applied to the Pallas grid): the kernel-
backed scans run one of FOUR grid organizations, two of them picked by
``choose_schedule`` (also surfaced as ``Choice.schedule``) and executed
by the monoid-generic engine in ``repro.kernels.scan_engine``:

  'carry'      grid-carried total: ("parallel", "arbitrary") — one fused
               HBM pass (read n + write n), but the sequence axis is a
               sequential carry chain, so parallelism == batch rows. The
               winner whenever ``batch >= cores`` keeps every core busy
               (the paper's SIMD-P single-pass organization).
  'decoupled'  reduce-then-scan in two launches: a fully parallel pass 1b
               emits per-chunk totals only, a tiny exclusive scan combines
               them, and a fully parallel pass 2 redoes the in-chunk scan
               with the chunk offset fused into the writeback — both grids
               are ("parallel", "parallel"), so a LONG row spreads across
               cores at the price of reading the data twice
               (read 2n + write n; the paper's SIMD2-P, Observation 3).
  'fused'      the same reduce-then-scan organization in ONE launch: each
               chunk scans once and chains its prefix to its successor
               through cross-chunk semaphores. Never picked here: it
               needs one semaphore per grid cell, and the chip's
               semaphore memory holds a few hundred, far short of the
               chunk counts of the long rows it was meant for.
  'tree'       carry's grid with Blelloch's up-sweep/down-sweep as the
               in-tile network (the paper's §3.3 balanced tree). Never
               picked here: written for the chip it is 2·log2(b)
               full-width masked passes per b-element tile, each a
               combine plus selects — about twice the work of the
               Hillis–Steele network carry runs — and no chip cell has
               shown it winning. Same HBM traffic as carry (read n +
               write n).

  The flip: carry-chain when ``batch >= cores`` (enough rows to fill the
  machine; cheapest traffic); a parallel-sequence schedule (decoupled)
  when a long row would otherwise serialize — ``batch < cores`` AND the
  row spans multiple blocks AND there are at least ``cores // batch``
  chunks to spread. Serve-engine decode and SSM prefill (B=1, N ≥ 2^22)
  land on decoupled; training shapes (B ≥ 8) keep the carry chain.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from repro.obs import trace


# TPU v5e geometry (targets; the container CPU only validates semantics).
VMEM_BYTES = 64 * 1024 * 1024  # per-core VMEM class budget we plan against
VMEM_BLOCK_BUDGET = VMEM_BYTES // 8  # working set ≤ 1/8 VMEM: in+out+slack
L2_HALF_FLOATS = 128 * 1024  # the paper's best CPU partition: ½ L2 in elems

# Cores one kernel launch can spread over (the paper's thread count): the
# v5e chip exposes a handful of Mosaic-parallelizable cores per launch
# class; 8 also matches the paper's CPU thread sweet spot (Fig. 7).
NUM_CORES = 8

@dataclasses.dataclass(frozen=True)
class Choice:
    algorithm: str  # 'horizontal' | 'blocked' | 'two_pass' | 'kernel'
    block_size: int
    variant: int  # two-pass organization (1 = scan-first, 2 = reduce-first)
    carry_exchange: str  # distributed sums exchange
    reason: str
    schedule: str = "carry"  # grid org: 'carry'|'decoupled'
    # The inputs the choice was made from (the explain surface) — filled
    # by ``choose``; excluded from equality so cached/reconstructed
    # Choices with the same outcome still compare equal.
    inputs: Dict = dataclasses.field(default_factory=dict, compare=False)


@dataclasses.dataclass(frozen=True)
class Decision:
    """A policy decision plus why: the answer to "why did this run
    split-KV?". ``inputs`` echoes every argument the rule consumed."""

    what: str        # which rule decided ('schedule' | 'attention_schedule')
    value: str       # the decision itself
    reason: str      # human-readable rationale
    inputs: Dict = dataclasses.field(default_factory=dict, compare=False)

    def emit(self) -> "Decision":
        """Record the decision as a trace instant event (no-op when
        tracing is disabled) and return self."""
        trace.instant(f"policy.{self.what}", value=self.value,
                      reason=self.reason, **self.inputs)
        return self


def explain_schedule(
    batch: int,
    n: int,
    cores: int = NUM_CORES,
    block_elems: int = 2048,
) -> Decision:
    """``choose_schedule`` with its working shown: the decision, the
    branch of the rule that fired, and the inputs — emitted as a
    ``policy.schedule`` trace event."""
    batch = max(int(batch), 1)
    chunks = -(-n // max(block_elems, 1))
    spare = cores // batch  # cores idle under the carry chain
    inputs = dict(batch=batch, n=n, cores=cores, block_elems=block_elems,
                  chunks=chunks, spare=spare)
    if batch >= cores:
        return Decision(
            "schedule", "carry",
            f"batch {batch} >= cores {cores}: rows alone fill every core; "
            f"carry chain has the cheapest HBM traffic", inputs).emit()
    # A parallel-sequence schedule costs a second read; only worth it
    # when the idle cores can actually be fed — at least ``spare`` chunks
    # per row (a row inside one block has nothing to parallelize).
    if spare >= 2 and chunks >= spare:
        return Decision(
            "schedule", "decoupled",
            f"batch {batch} < cores {cores} with {chunks} chunks >= "
            f"{spare} spare cores: spread the row (two-launch "
            f"decoupled)", inputs).emit()
    return Decision(
        "schedule", "carry",
        f"batch {batch} < cores {cores} but only {chunks} chunk(s) for "
        f"{spare} spare core(s): nothing to spread, keep the carry chain",
        inputs).emit()


def choose_schedule(
    batch: int,
    n: int,
    cores: int = NUM_CORES,
    block_elems: int = 2048,
) -> str:
    """Kernel grid organization for a (batch, n) scan — see module doc.

    ``block_elems`` must be the chunk length the kernel will actually
    tile with — the chunks-per-spare-core test is meaningless against
    any other block size. ``explain_schedule`` returns the same decision
    with its rationale.
    """
    return explain_schedule(batch, n, cores, block_elems).value


# Attention (carried-payload fold) thresholds. SPLIT_KV_CHUNKS is the KV
# chain length past which the fold's serial latency dominates a row's
# cost and the split-KV form pays for its chain traffic — 256 chunks is
# 32k context at the default 128-wide KV block, the serve long-context
# class. SPLIT_KV_ROW_CAP bounds it to decode/scoring shapes (few query
# rows): when (head, q-block) rows already oversubscribe every core by
# this factor, splitting KV buys no throughput and only adds traffic.
SPLIT_KV_CHUNKS = 256
SPLIT_KV_ROW_CAP = 8


def explain_attention_schedule(
    batch_rows: int,
    kv_len: int,
    cores: int = NUM_CORES,
    block_elems: int = 128,
    split_kv_chunks: int = SPLIT_KV_CHUNKS,
    split_kv_row_cap: int = SPLIT_KV_ROW_CAP,
) -> Decision:
    """``choose_attention_schedule`` with its working shown — emitted as
    a ``policy.attention_schedule`` trace event."""
    batch_rows = max(int(batch_rows), 1)
    chunks = -(-kv_len // max(block_elems, 1))
    spare = cores // batch_rows
    inputs = dict(batch_rows=batch_rows, kv_len=kv_len, cores=cores,
                  block_elems=block_elems, chunks=chunks, spare=spare,
                  split_kv_chunks=split_kv_chunks,
                  split_kv_row_cap=split_kv_row_cap)
    if batch_rows < cores and spare >= 2 and chunks >= spare:
        return Decision(
            "attention_schedule", "decoupled",
            f"{batch_rows} fold row(s) leave {spare} cores idle and the "
            f"KV chain has {chunks} chunks to spread: split-KV "
            f"(flash-decoding)", inputs).emit()
    if chunks >= split_kv_chunks and batch_rows < cores * split_kv_row_cap:
        return Decision(
            "attention_schedule", "decoupled",
            f"KV chain of {chunks} chunks >= {split_kv_chunks} dominates "
            f"a row's latency and {batch_rows} rows < "
            f"{cores * split_kv_row_cap} saturation cap: split-KV",
            inputs).emit()
    return Decision(
        "attention_schedule", "carry",
        f"{batch_rows} rows fill the machine (or the {chunks}-chunk KV "
        f"chain is short): classic flash carry accumulate", inputs).emit()


def choose_attention_schedule(
    batch_rows: int,
    kv_len: int,
    cores: int = NUM_CORES,
    block_elems: int = 128,
    split_kv_chunks: int = SPLIT_KV_CHUNKS,
    split_kv_row_cap: int = SPLIT_KV_ROW_CAP,
) -> str:
    """Grid organization for the attention fold (softmax pair + payload).

    Two-way (attention has no fused form — the output is the fold, so
    there is no per-element writeback to chain a prefix into):

      carry      the flash forward: (head, q-block) rows parallel, KV
                 blocks a sequential accumulate. Right whenever the rows
                 fill the machine and the KV chain is short — training
                 and ordinary prefill shapes.
      decoupled  split-KV / flash-decoding: KV chunks parallel, partial
                 (m, l, acc) payloads combined in a tiny second step.
                 Chosen when rows leave cores idle (decode: one q block,
                 ``batch_rows == B·H``), or when the KV chain is long
                 (the 32k/500k-context prefill and padded-cache scoring
                 class) while rows stay within ``SPLIT_KV_ROW_CAP·cores``
                 — fully saturated rows keep the carry form, where
                 splitting adds chain traffic and returns nothing.

    ``batch_rows`` is the number of independent fold chains the carry
    grid already parallelizes (B·H_q·q_blocks); ``block_elems`` the KV
    chunk length actually tiled. ``explain_attention_schedule`` returns
    the same decision with its rationale.
    """
    return explain_attention_schedule(
        batch_rows, kv_len, cores, block_elems, split_kv_chunks,
        split_kv_row_cap).value


def explain_cache_layout(
    max_slots: int,
    max_len: int,
    page_size: int,
    num_pages: "int | None" = None,
    expected_len: "int | None" = None,
) -> Decision:
    """Serve KV-cache layout rule (``contiguous`` | ``paged``) with its
    working shown — emitted as a ``policy.cache_layout`` trace event.

    The contiguous layout reserves ``max_slots · max_len`` K/V slots up
    front; the paged layout (serve/paging.py) reserves ``num_pages ·
    page_size`` and assigns pages on demand, so memory follows ACTUAL
    sequence length. Decide paged when the page budget is below the
    worst case (contiguous could not even allocate the pool) or when the
    expected length leaves most of a contiguous slot dead; otherwise
    the indirection buys nothing and contiguous keeps the simpler
    (gather-free) addressing.
    """
    worst = max_slots * max_len
    budget = worst if num_pages is None else num_pages * page_size
    inputs = dict(max_slots=max_slots, max_len=max_len,
                  page_size=page_size, num_pages=num_pages,
                  expected_len=expected_len, worst_tokens=worst,
                  budget_tokens=budget)
    if budget < worst:
        return Decision(
            "cache_layout", "paged",
            f"page budget {budget} tokens < worst case {worst}: only "
            f"on-demand pages can host {max_slots} slots; admission "
            f"backpressure replaces up-front reservation", inputs).emit()
    if expected_len is not None and 2 * expected_len <= max_len:
        return Decision(
            "cache_layout", "paged",
            f"expected length {expected_len} <= max_len {max_len}/2: a "
            f"contiguous slot would be mostly dead reservation",
            inputs).emit()
    return Decision(
        "cache_layout", "contiguous",
        f"budget {budget} covers the worst case {worst} and lengths run "
        f"near max_len: page indirection buys nothing", inputs).emit()


def choose_cache_layout(
    max_slots: int,
    max_len: int,
    page_size: int,
    num_pages: "int | None" = None,
    expected_len: "int | None" = None,
) -> str:
    """Serve cache layout for ``EngineConfig.cache_layout="auto"`` —
    see ``explain_cache_layout`` for the rule and rationale."""
    return explain_cache_layout(
        max_slots, max_len, page_size, num_pages, expected_len).value


def explain_defrag(
    fragmentation: float,
    free_pages: int,
    longest_free_run: int,
    *,
    threshold: float = 0.5,
) -> Decision:
    """Auto-defrag rule (``defrag`` | ``skip``) with its working shown —
    emitted as a ``policy.defrag`` trace event.

    Driven by the ``serve.pages.fragmentation`` gauge (1 - largest free
    run / free pages) and the free-run length. Page-granular allocation
    never NEEDS contiguity, so this is a locality/observability policy:
    compacting live pages to the front keeps pool writes clustered and
    the gauge honest, and it is free of correctness risk (the gathered
    view is invariant under page renaming). Skip when the pool is full
    (fragmentation pins to 1.0 but compaction cannot create space —
    only request completion can) and when the free space is already one
    healthy extent.
    """
    inputs = dict(fragmentation=round(float(fragmentation), 4),
                  free_pages=int(free_pages),
                  longest_free_run=int(longest_free_run),
                  threshold=threshold)
    if free_pages == 0:
        return Decision(
            "defrag", "skip",
            "no free pages: compaction cannot create space, only "
            "request completion can", inputs).emit()
    if fragmentation < threshold:
        return Decision(
            "defrag", "skip",
            f"fragmentation {fragmentation:.2f} < threshold {threshold}: "
            f"largest free run {longest_free_run}/{free_pages} pages is "
            f"healthy", inputs).emit()
    return Decision(
        "defrag", "defrag",
        f"fragmentation {fragmentation:.2f} >= threshold {threshold}: "
        f"free space shattered into runs <= {longest_free_run} of "
        f"{free_pages} pages — compact live pages to the front",
        inputs).emit()


def choose_defrag(
    fragmentation: float,
    free_pages: int,
    longest_free_run: int,
    *,
    threshold: float = 0.5,
) -> bool:
    """True when the engine tick should run ``Engine.defrag()`` — see
    ``explain_defrag`` for the rule and rationale."""
    return explain_defrag(fragmentation, free_pages, longest_free_run,
                          threshold=threshold).value == "defrag"


def choose(
    n: int,
    itemsize: int = 4,
    n_devices: int = 1,
    bandwidth_abundant: bool = False,
    carry_bytes: int = 4,
    kernel_available: bool = True,
    batch: int = NUM_CORES,
    cores: int = NUM_CORES,
) -> Choice:
    """Pick a scan algorithm for ``n`` elements of ``itemsize`` bytes.

    ``batch`` is the number of independent rows scanned together (defaults
    to "plenty" so shape-oblivious callers keep the carry-chain default);
    it only affects ``Choice.schedule``. Every call emits a
    ``policy.choose`` trace event carrying the inputs and reason.
    """
    bytes_total = n * itemsize
    block = max(1024, min(VMEM_BLOCK_BUDGET // max(itemsize, 1), n))
    schedule = choose_schedule(batch, n, cores)
    inputs = dict(n=n, itemsize=itemsize, n_devices=n_devices,
                  bandwidth_abundant=bandwidth_abundant,
                  carry_bytes=carry_bytes,
                  kernel_available=kernel_available, batch=batch,
                  cores=cores, bytes_total=bytes_total)

    def _emit(choice: Choice) -> Choice:
        Decision("choose", choice.algorithm, choice.reason,
                 dict(inputs, schedule=choice.schedule,
                      block_size=choice.block_size)).emit()
        return choice

    if bytes_total <= VMEM_BLOCK_BUDGET:
        # Fits in fast memory: one horizontal pass, no partitioning (Obs 2).
        return _emit(Choice(
            "horizontal", n, 2, "all_gather",
            "input fits in VMEM; in-register log-step scan only",
            inputs=inputs,
        ))

    if bandwidth_abundant:
        # The KNL/HBM finding: when bandwidth is abundant, partitioning's
        # overhead is pure cost (Obs 2) — plain two-pass, reduce-first.
        return _emit(Choice(
            "two_pass", block, 2, "all_gather",
            "bandwidth abundant: skip partitioning (paper Fig 13)",
            schedule, inputs=inputs,
        ))

    algo = "kernel" if kernel_available else "blocked"
    # Large carries (e.g. SSM matrix states) across many devices favor the
    # log-step permute exchange over all-gather.
    exchange = "all_gather"
    if n_devices > 1 and carry_bytes * n_devices > 1 << 20:
        exchange = "hillis_permute"
    reason = "bandwidth-bound: cache/VMEM partitioning, reduce-first (SIMD2-P)"
    if schedule == "decoupled":
        reason += "; decoupled grid (batch < cores, long row)"
    return _emit(Choice(algo, block, 2, exchange, reason, schedule,
                        inputs=inputs))

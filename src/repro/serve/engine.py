"""Serving engine: continuous batching over a fixed slot pool, hardened.

vLLM-style scheduling reduced to its JAX-native core: a fixed decode batch
of ``max_slots`` sequences; finished sequences free their slot; waiting
requests are admitted by prefilling into the freed slot. Slot bookkeeping
(free-slot compaction) routes through ``repro.relational.compact`` — an
exclusive prefix sum over the free bitmap packs the free slot ids to the
front, the paper's stream-compaction use case running the engine.

The decode step is ONE jitted call for the whole pool (padded, masked);
prefill is a second jitted call per admitted request batch. ``cache_len``
is threaded as a per-row (B,) vector so each slot gets its own RoPE
positions and masking extent — a row's output never depends on who else
occupies the pool, which is what lets the chaos wall demand bitwise
identity for undisturbed requests.

Request lifecycle (this file's contract — see README "Serving under
failure"): every submitted request terminates with exactly ONE
``finish_reason`` from :data:`repro.serve.stats.FINISH_REASONS`; none is
lost or duplicated. The hardening layers:

  * admission control — bounded waiting queue with a reject-vs-block
    policy; prompts that cannot fit (``S + budget > max_len`` under
    ``strict_admission``) are failed fast as ``rejected`` instead of
    silently corrupting the cache;
  * deadlines — per-request tick TTLs finish overdue requests with
    ``deadline``; host-side :meth:`Engine.cancel` finishes ``cancelled``;
  * step-failure recovery — bookkeeping is only committed after a
    successful tick; exceptions from the jitted step are retried with
    backoff, then the active set is bisected with probe calls to
    quarantine the poison request (finished ``error``) so one bad
    sequence never takes down the pool;
  * numeric degradation ladder — non-finite logits on any ACTIVE row
    roll the tick back and re-run it once on the safe route (dense
    attention, ``chunked`` reference scan); persistent non-finite ticks
    are skipped (trainer NaN-guard parity) and eventually quarantined.

By default the decode cache is NOT donated (``donate_cache=False``): the
pre-tick cache stays alive so a rolled-back tick is a no-op. Donation
(``donate_cache=True``) restores the zero-copy fast path but narrows
recovery — when the pre-tick buffers are gone the engine adopts the
written cache and skips the advance, which self-heals attention caches
(next tick overwrites the same positions) but is documented lossy for
recurrent (ssm/xlstm) state.

Fault injection (``serve/faults.py``) hooks the two jitted entry points;
the safe route is deliberately un-wrapped so the ladder escapes the
injector the way a real fallback kernel escapes a broken primary one.

Cache layouts (``EngineConfig.cache_layout``): the default
``"contiguous"`` layout reserves one padded ``max_len`` KV row per slot;
``"paged"`` replaces the rows with a shared page pool plus per-slot page
tables (``serve/paging.py``) so HBM scales with ACTUAL sequence length
— the same cache-memory budget admits strictly more concurrent
sequences. Under paging ``finish_reason="cache_full"`` means the
ALLOCATOR is exhausted (pool empty), and admission applies backpressure
(the request waits) instead of reserving worst-case rows up front.
Decode under either layout is bitwise identical at equal configs.
``prefill_chunk`` additionally stages long prompts one chunk per tick
so resident decodes interleave instead of stalling behind a monolithic
prefill.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scan import policy as scan_policy
from repro.models.config import ModelConfig
from repro.obs import trace
from repro.obs.metrics import Registry
from repro.relational import compact as rel_compact
from repro.serve import paging
from repro.serve.faults import StepContext
from repro.serve.sampling import sample_logits
from repro.serve.stats import FINISH_REASONS, EngineStats
from repro.serve.steps import (bucket_len, bucketable, init_cache_for,
                               init_paged_cache_for,
                               make_bucketed_prefill_fn,
                               make_chunked_prefill_fn, make_paged_serve_step,
                               make_prefill_fn, make_serve_step)

Pytree = Any


class AdmissionError(RuntimeError):
    """Raised by ``submit(..., strict=True)`` when a request is rejected."""


class EngineStepError(RuntimeError):
    """A decode step failed unrecoverably (ambient / non-isolatable)."""


class EngineDeadlineError(TimeoutError):
    """``run_to_completion`` exhausted ``max_ticks`` under ``strict``."""


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 8
    max_len: int = 512
    max_new_tokens: int = 64
    temperature: float = 0.0       # greedy default
    top_p: float = 1.0
    eos_id: int = 1
    seed: int = 0
    # Prefill attention route: ``attn_impl="flash"`` runs prompt attention
    # on the engine-backed flash fold, ``attn_schedule`` its grid
    # organization (carry | decoupled | auto — policy decides; the long-KV
    # class lands on the split-KV decoupled form).
    attn_impl: Optional[str] = None
    attn_schedule: str = "auto"
    # SSM decode route ("auto" | "chunked" | "kernel"); the degradation
    # ladder's safe route always pins "chunked".
    ssm_impl: str = "auto"

    # -- admission ------------------------------------------------------
    max_waiting: Optional[int] = None   # bound on the waiting queue
    admission_policy: str = "reject"    # "reject" | "block" on full queue
    strict_admission: bool = True       # reject S + budget > max_len
    # -- deadlines ------------------------------------------------------
    deadline_ticks: Optional[int] = None  # default per-request tick TTL
    strict_deadlines: bool = False        # run_to_completion raises
    # -- failure recovery ----------------------------------------------
    max_step_retries: int = 2
    retry_backoff_s: float = 0.0
    # -- numeric ladder -------------------------------------------------
    degrade_on_nonfinite: bool = True
    max_consecutive_nan_ticks: int = 3
    # -- cache / compile hygiene ----------------------------------------
    donate_cache: bool = False          # True = fast path, narrower recovery
    bucket_prompts: bool = True         # pad prompts to pow2 buckets
    max_prefill_variants: int = 8       # LRU cap on jitted prefill shapes
    slow_tick_s: Optional[float] = None  # wall-clock SLO; over -> slow_ticks
    # -- paged KV cache (serve/paging.py) -------------------------------
    cache_layout: str = "contiguous"    # "contiguous" | "paged" | "auto"
    page_size: int = 16                 # tokens per KV page
    num_pages: Optional[int] = None     # pool size; None = worst case + null
    prefill_chunk: Optional[int] = None  # stage long prompts N tokens/tick
    # -- copy-on-write prefix sharing (paged only) ----------------------
    share_prefixes: bool = False        # map common prompt prefixes via COW
    prefix_cache_pages: int = 32        # LRU entry cap on the registry
    # -- auto-defrag policy (paged only) --------------------------------
    auto_defrag: bool = True            # policy.choose_defrag on the tick
    defrag_threshold: float = 0.5       # fragmentation gauge trigger
    defrag_cooldown: int = 8            # min ticks between auto defrags

    def __post_init__(self):
        if self.admission_policy not in ("reject", "block"):
            raise ValueError(
                f"admission_policy must be 'reject' or 'block', "
                f"got {self.admission_policy!r}")
        if self.cache_layout not in ("contiguous", "paged", "auto"):
            raise ValueError(
                f"cache_layout must be 'contiguous', 'paged' or 'auto', "
                f"got {self.cache_layout!r}")
        if self.page_size < 1:
            raise ValueError(f"page_size={self.page_size} < 1")
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk={self.prefill_chunk} < 1")
        if self.prefix_cache_pages < 1:
            raise ValueError(
                f"prefix_cache_pages={self.prefix_cache_pages} < 1")
        if self.defrag_cooldown < 1:
            raise ValueError(f"defrag_cooldown={self.defrag_cooldown} < 1")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                    # (S,) int32
    max_new_tokens: Optional[int] = None
    deadline_ticks: Optional[int] = None  # overrides EngineConfig TTL
    # filled by the engine:
    output: Optional[list] = None
    finish_reason: Optional[str] = None   # one of FINISH_REASONS when done
    error: Optional[str] = None           # detail for error/rejected
    submit_tick: int = -1
    finish_tick: int = -1
    degraded: bool = False                # served (partly) on the safe route

    @property
    def done(self) -> bool:
        return self.finish_reason is not None


# Step fns are memoized globally: ``ModelConfig`` is a frozen (hashable)
# dataclass, so engines sharing an architecture share ONE jitted step and
# its traced executables instead of recompiling per Engine (the chaos
# wall builds a dozen engines over the same tiny model).
_STEP_JIT: Dict[tuple, Any] = {}


def _jit_step(cfg: ModelConfig, ssm_impl: Optional[str], donate: bool,
              paged: bool = False):
    key = (cfg, ssm_impl, donate, paged)
    if key not in _STEP_JIT:
        fn = (make_paged_serve_step(cfg, ssm_impl=ssm_impl) if paged
              else make_serve_step(cfg, ssm_impl=ssm_impl))
        _STEP_JIT[key] = (jax.jit(fn, donate_argnums=(2,)) if donate
                          else jax.jit(fn))
    return _STEP_JIT[key]


class Engine:
    def __init__(self, params: Pytree, cfg: ModelConfig, ecfg: EngineConfig,
                 injector: Any = None,
                 metrics: Optional[Registry] = None):
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        self.injector = injector
        # ``metrics`` mirrors EngineStats into an obs registry (one
        # surface for dashboards + chaos invariants); None = stats only.
        self.stats = EngineStats().attach(metrics)
        self.metrics = metrics
        self.key = jax.random.PRNGKey(ecfg.seed)

        # Cache layout: "auto" asks the policy layer (budget below the
        # worst case, or typical lengths far under max_len -> paged).
        layout = ecfg.cache_layout
        if layout == "auto":
            layout = scan_policy.choose_cache_layout(
                ecfg.max_slots, ecfg.max_len, ecfg.page_size,
                num_pages=ecfg.num_pages)
        self.cache_layout = layout
        self._paged = layout == "paged"

        ssm_primary = None if ecfg.ssm_impl == "auto" else ecfg.ssm_impl
        self._step = _jit_step(cfg, ssm_primary, donate=ecfg.donate_cache,
                               paged=self._paged)
        self._step_nodonate = _jit_step(cfg, ssm_primary, donate=False,
                                        paged=self._paged)
        # The SAFE route: dense attention (decode is dense already) and
        # the jnp reference scan for SSM layers; never injector-wrapped.
        self._step_safe = _jit_step(cfg, "chunked", donate=False,
                                    paged=self._paged)
        self._wstep = (injector.wrap_step(self._step) if injector
                       else self._step)
        self._wstep_probe = (injector.wrap_step(self._step_nodonate)
                             if injector else self._step_nodonate)
        # Whether the safe route changes numerics vs the primary one.
        has_recurrent = any(k in ("mamba", "mlstm", "slstm")
                            for k in cfg.layer_pattern)
        self._prefill_safe_differs = ecfg.attn_impl is not None or (
            has_recurrent and ecfg.ssm_impl == "kernel")
        self._decode_safe_differs = (
            has_recurrent and ecfg.ssm_impl == "kernel")

        self._bucketed = (ecfg.bucket_prompts and bucketable(cfg))
        self._prefill_cache: "OrderedDict[tuple, Any]" = OrderedDict()
        self._tick = 0
        self._nan_streak = 0

        # Chunked prefill shares bucketing's gate (pads in the staging
        # cache must be inert: pure global-attention stacks only).
        self._chunk_ok = ecfg.prefill_chunk is not None and bucketable(cfg)
        self._chunk_job: Optional[dict] = None

        B, L = ecfg.max_slots, ecfg.max_len
        if self._paged:
            # Geometry/layer-support problems (incl. sliding-window ring
            # extents vs page_size) fail HERE with the offending layer
            # named, not mid-jit-trace.
            paging.validate_paged_support(cfg, L, ecfg.page_size)
            self._paged_names = paging.paged_layer_names(cfg)
            self._local_names = frozenset(
                n for n in self._paged_names if n.endswith("_local"))
            self._ring_pages = (
                min(int(cfg.sliding_window), L) // ecfg.page_size
                if self._local_names else 0)
            n_pages = (ecfg.num_pages if ecfg.num_pages is not None
                       else B * (L // ecfg.page_size) + 1)
            self.allocator: Optional[paging.PageAllocator] = \
                paging.PageAllocator(n_pages, ecfg.page_size,
                                     stats=self.stats, metrics=metrics)
            self.ptable: Optional[paging.PageTable] = \
                paging.PageTable(B, L // ecfg.page_size)
            self.cache = init_paged_cache_for(cfg, B, L, ecfg.page_size,
                                              n_pages)
        else:
            self._paged_names = ()
            self._local_names = frozenset()
            self.allocator = None
            self.ptable = None
            self.cache = init_cache_for(cfg, B, L)
        # Copy-on-write prefix sharing: the registry maps prompt-prefix
        # chunks to live physical pages. Gated on ``bucketable`` (pure
        # global-attention stacks) for the same reason bucketing and
        # chunked prefill are: the suffix-only prefill stages through a
        # contiguous cache whose pads must be inert, and a local ring
        # that has wrapped is no longer prefix-pristine.
        self.registry: Optional[paging.PrefixRegistry] = None
        if self._paged and ecfg.share_prefixes:
            if not bucketable(cfg):
                raise ValueError(
                    "share_prefixes requires a pure global-attention "
                    f"decoder (bucketable); pattern {cfg.layer_pattern!r} "
                    "is not")
            self.registry = paging.PrefixRegistry(
                self.allocator, ecfg.page_size,
                capacity=ecfg.prefix_cache_pages)
        self._last_defrag = -(10 ** 9)
        self.tokens = jnp.zeros((B, 1), jnp.int32)
        self.lengths = np.zeros(B, np.int64)          # per-slot position
        self.budgets = np.zeros(B, np.int64)          # remaining new tokens
        self.slot_req: list[Optional[Request]] = [None] * B
        self.waiting: list[Request] = []
        self.finished: list[Request] = []

    # -- slot bookkeeping (scan-based compaction) -----------------------
    def _free_slots(self) -> np.ndarray:
        # A staging chunked-prefill job holds its destination slot so
        # admission cannot hand it out before the job finalizes.
        held = self._chunk_job["slot"] if self._chunk_job is not None else -1
        free = np.array([r is None and i != held
                         for i, r in enumerate(self.slot_req)], np.int32)
        # Stream compaction over the free bitmap (paper §1: "new offsets
        # during a partitioning step"): ONE mask scan inside
        # filter_compact packs the free slot ids to the front. The
        # per-slot ranks are part of the bookkeeping contract (see
        # test_free_slot_compaction_ranks); the host cumsum avoids a
        # second device scan for them.
        slots, count = rel_compact.filter_compact(
            jnp.arange(free.size, dtype=jnp.int32),
            jnp.asarray(free, bool))
        ranks = np.cumsum(free) - free
        return np.asarray(slots)[: int(count)], ranks

    # -- lifecycle ------------------------------------------------------
    def _finish(self, req: Request, reason: str,
                error: Optional[str] = None) -> None:
        """The ONLY way a request terminates: exactly one finish reason."""
        assert req.finish_reason is None, (
            f"request {req.rid} finished twice: "
            f"{req.finish_reason!r} then {reason!r}")
        assert reason in FINISH_REASONS
        req.finish_reason = reason
        req.error = error
        req.finish_tick = self._tick
        self.stats.record_finish(reason)
        self.finished.append(req)
        trace.instant("serve.request.finish", rid=req.rid, reason=reason,
                      tick=self._tick, tokens=len(req.output or ()),
                      degraded=req.degraded, error=error)

    def _budget_of(self, req: Request) -> int:
        return (req.max_new_tokens if req.max_new_tokens is not None
                else self.ecfg.max_new_tokens)

    # -- admission ------------------------------------------------------
    def submit(self, req: Request, strict: bool = False) -> bool:
        """Queue a request. Returns False (or raises under ``strict``)
        when admission control rejects it — the request is then already
        finished with ``finish_reason="rejected"``."""
        self.stats.submitted += 1
        req.output = []
        req.submit_tick = self._tick
        trace.instant("serve.request.submit", rid=req.rid,
                      prompt_len=int(np.asarray(req.prompt).size),
                      tick=self._tick)
        reason = self._validate(req)
        if reason is None and self.ecfg.max_waiting is not None:
            if self.ecfg.admission_policy == "block":
                # Drive the engine until the queue drains below the bound
                # (single-threaded stand-in for a blocking producer).
                guard = 0
                while (len(self.waiting) >= self.ecfg.max_waiting
                       and guard < 100_000):
                    if self.step() == 0 and not self.waiting:
                        break
                    guard += 1
            if len(self.waiting) >= self.ecfg.max_waiting:
                reason = (f"waiting queue full "
                          f"({len(self.waiting)}/{self.ecfg.max_waiting})")
        if reason is not None:
            self._finish(req, "rejected", error=reason)
            if strict:
                raise AdmissionError(f"request {req.rid}: {reason}")
            return False
        self.waiting.append(req)
        self.stats.observe_queue(len(self.waiting))
        return True

    def _validate(self, req: Request) -> Optional[str]:
        S = int(np.asarray(req.prompt).shape[0])
        budget = self._budget_of(req)
        if S < 1:
            return "empty prompt"
        if budget < 1:
            return f"max_new_tokens={budget} < 1"
        if S + 1 > self.ecfg.max_len:
            return (f"prompt length {S} cannot fit max_len="
                    f"{self.ecfg.max_len}")
        if self.ecfg.strict_admission and S + budget > self.ecfg.max_len:
            return (f"prompt {S} + budget {budget} > max_len="
                    f"{self.ecfg.max_len} cannot complete")
        return None

    def cancel(self, rid: int) -> bool:
        """Host-side cancel: finishes the request with ``cancelled``."""
        for i, req in enumerate(self.waiting):
            if req.rid == rid:
                self.waiting.pop(i)
                self._finish(req, "cancelled")
                self.stats.observe_queue(len(self.waiting))
                return True
        if (self._chunk_job is not None
                and self._chunk_job["req"].rid == rid):
            req = self._chunk_job["req"]
            self._chunk_job = None
            self._finish(req, "cancelled")
            return True
        for slot, req in enumerate(self.slot_req):
            if req is not None and req.rid == rid:
                self._release(slot)
                self._finish(req, "cancelled")
                return True
        return False

    def _release(self, slot: int) -> None:
        if self._paged and int(self.ptable.counts[slot]):
            # Host bookkeeping only; the device page table is refreshed
            # once per tick (in _ensure_pages) before the decode step
            # reads it, so a freed-then-reallocated page is never
            # reachable through a stale table row.
            self.allocator.release(self.ptable.release(slot))
        self.slot_req[slot] = None
        self.lengths[slot] = 0
        self.budgets[slot] = 0

    def _admit(self) -> None:
        self._advance_chunk_job()
        free_idx, _ = self._free_slots()
        free_list = [int(i) for i in free_idx]
        while self.waiting and free_list:
            req = self.waiting[0]
            S = int(np.asarray(req.prompt).shape[0])
            shared = (self.registry.match(np.asarray(req.prompt))
                      if self.registry is not None else [])
            if self._paged:
                need = paging.pages_for(S, self.ecfg.page_size) - len(shared)
                if need > self.allocator.free_count:
                    # Allocator exhausted: admission BACKPRESSURE. The
                    # request stays queued (FIFO order preserved) until
                    # decode finishes free pages — the paged analogue of
                    # waiting for a free slot, replacing the contiguous
                    # layout's up-front worst-case reservation.
                    trace.instant("serve.admit.backpressure", rid=req.rid,
                                  want=need,
                                  free=self.allocator.free_count)
                    break
            self.waiting.pop(0)
            self.stats.observe_queue(len(self.waiting))
            self.stats.admitted += 1
            out = None
            if shared:
                out = self._prefill_shared(req, shared)
                if out is None:
                    shared = []               # fall back to a full prefill
            if out is None and self._chunkable(req, S):
                self._chunk_job = {
                    "req": req, "slot": free_list.pop(0), "pos": 0,
                    "cache": init_cache_for(self.cfg, 1, self.ecfg.max_len),
                }
                trace.instant("serve.prefill.chunk_start", rid=req.rid,
                              prompt_len=S, chunk=self.ecfg.prefill_chunk)
                continue
            if out is None:
                out = self._prefill_request(req)
            if out is None:
                continue                      # finished "error" inside
            logits, cache1 = out
            self._install(free_list.pop(0), req, logits, cache1,
                          shared=shared)

    def _install(self, slot: int, req: Request, logits, cache1,
                 shared=()) -> None:
        """Commit a completed prefill into ``slot``: copy/page its cache
        row into the pool, sample the first token, and apply the
        admission-time finish checks. Shared by one-shot admission,
        chunked-prefill finalize and the prefix-sharing path (``shared``
        = registry pages already holding the matched prompt prefix; only
        the remainder is freshly allocated and scattered)."""
        S = int(np.asarray(req.prompt).shape[0])
        if self._paged:
            shared_arr = np.asarray(shared, np.int64)
            m = int(shared_arr.size)
            total = paging.pages_for(S, self.ecfg.page_size)
            got = self.allocator.alloc([total - m])
            if got is None:
                # Pages vanished between precheck and install (decode
                # growth during a chunked prefill): backpressure — back
                # to the head of the queue with the staging work
                # discarded. Nothing was retained yet.
                self.waiting.insert(0, req)
                self.stats.observe_queue(len(self.waiting))
                return
            fresh = got[0]
            if m:
                self.allocator.retain(shared_arr)
                self.stats.prefix_hits += 1
                self.stats.shared_page_maps += m
                trace.instant("serve.pages.prefix_hit", rid=req.rid,
                              shared=m, fresh=int(fresh.size))
            pages = np.concatenate([shared_arr, fresh])
            self.ptable.assign(slot, pages)
            layers = {}
            for name, leaf in self.cache["layers"].items():
                if name in self._paged_names:
                    kv, one = leaf["kv"], cache1[name]["kv"]
                    if name in self._local_names:
                        # Local (sliding-window) layer: the staging row
                        # is the ring buffer itself; ring slot s lives
                        # in logical page s // ps, so the ring maps onto
                        # the row's first ring_pages entries. (Sharing
                        # is gated off for hybrid patterns: m == 0.)
                        lp = pages[: min(self._ring_pages, pages.size)]
                        start = 0
                    else:
                        lp, start = fresh, m
                    layers[name] = {"kv": {
                        "k_pages": paging.scatter_prefix(
                            kv["k_pages"], one["k"], lp, start),
                        "v_pages": paging.scatter_prefix(
                            kv["v_pages"], one["v"], lp, start),
                    }}
                else:
                    layers[name] = jax.tree.map(
                        lambda pool, one_: _scatter_row(
                            pool, one_.astype(pool.dtype), slot),
                        leaf, cache1[name])
            self.cache = {"layers": layers,
                          "page_table": self.cache["page_table"]}
            if self.registry is not None:
                self.registry.register(np.asarray(req.prompt), pages)
        else:
            # Copy the single-row prefill cache into the pool at `slot`
            # (cache leaves are (layers, batch, ...); prefill batch = 1).
            self.cache = jax.tree.map(
                lambda pool, one: _scatter_row(pool, one.astype(pool.dtype),
                                               slot),
                self.cache, cache1)
        first = self._sample(logits)[0]
        req.output.append(int(first))
        self.stats.tokens_generated += 1
        budget = self._budget_of(req) - 1
        if int(first) == self.ecfg.eos_id:
            reason = "eos"
        elif budget <= 0:
            reason = "length_budget"
        elif S + 1 >= self.ecfg.max_len:
            self._warn_cache_full(req)
            reason = "cache_full"
        else:
            self.tokens = self.tokens.at[slot, 0].set(first)
            self.lengths[slot] = S
            self.budgets[slot] = budget
            self.slot_req[slot] = req
            return
        if self._paged:
            self._release(slot)               # returns the fresh pages
        self._finish(req, reason)

    # -- chunked prefill (one chunk per tick) ---------------------------
    def _chunkable(self, req: Request, S: int) -> bool:
        C = self.ecfg.prefill_chunk
        return (self._chunk_ok and self._chunk_job is None
                and C is not None and S > C
                and not getattr(req, "_no_chunk", False))

    def _advance_chunk_job(self) -> None:
        """Run ONE chunk of the staged long-prompt prefill, so decode
        ticks for resident slots interleave with the long prompt instead
        of stalling behind a monolithic prefill. The staging cache is
        contiguous (single row); pages are only claimed at finalize."""
        job = self._chunk_job
        if job is None:
            return
        req = job["req"]
        C = int(self.ecfg.prefill_chunk)
        prompt = np.asarray(req.prompt)
        S = int(prompt.size)
        lo = int(job["pos"])
        hi = min(lo + C, S)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, : hi - lo] = prompt[lo:hi]
        fn = self._chunk_prefill_fn()
        if self.injector is not None:
            self.injector.begin(StepContext(
                tick=self._tick, rids=(req.rid,), op="prefill"))
        try:
            with trace.span("serve.prefill.chunk", rid=req.rid,
                            lo=lo, hi=hi, tick=self._tick):
                logits, cache = fn(self.params, jnp.asarray(chunk),
                                   job["cache"], jnp.asarray(lo, jnp.int32),
                                   jnp.asarray(hi - lo, jnp.int32))
            self.stats.prefill_chunks += 1
        except Exception as e:                # noqa: BLE001 — jitted call
            # The chunk route carries no retry ladder of its own: fall
            # back to the one-shot path, which has retry + degrade.
            self.stats.prefill_retries += 1
            req._no_chunk = True
            self._chunk_job = None
            self.waiting.insert(0, req)
            self.stats.observe_queue(len(self.waiting))
            trace.instant("serve.prefill.chunk_abort", rid=req.rid,
                          error=repr(e))
            return
        job["cache"], job["pos"] = cache, hi
        if hi < S:
            return
        self._chunk_job = None
        if not np.isfinite(np.asarray(logits)).all():
            self.stats.nonfinite_ticks += 1
            req._no_chunk = True
            self.waiting.insert(0, req)
            self.stats.observe_queue(len(self.waiting))
            trace.instant("serve.prefill.chunk_abort", rid=req.rid,
                          error="non-finite logits")
            return
        self._install(job["slot"], req, logits, cache)

    def _chunk_prefill_fn(self, width: Optional[int] = None):
        """Jitted mid-stream prefill at chunk ``width`` (default: the
        configured ``prefill_chunk``). One LRU-cached variant per width
        — the prefix-sharing suffix path reuses the same cache, so a
        suffix whose bucket matches the chunk width shares the
        executable."""
        key = ("chunk", int(width or self.ecfg.prefill_chunk))
        if key in self._prefill_cache:
            self._prefill_cache.move_to_end(key)
            return self._prefill_cache[key]
        fn = jax.jit(make_chunked_prefill_fn(
            self.cfg, self.ecfg.max_len,
            attn_impl=self.ecfg.attn_impl,
            attn_schedule=self.ecfg.attn_schedule))
        if self.injector is not None:
            fn = self.injector.wrap_prefill(fn)
        self._prefill_cache[key] = fn
        self.stats.prefill_compiles += 1
        while len(self._prefill_cache) > self.ecfg.max_prefill_variants:
            self._prefill_cache.popitem(last=False)
            self.stats.prefill_cache_evictions += 1
        return self._prefill_cache[key]

    # -- copy-on-write prefix sharing ------------------------------------
    def _prefill_shared(self, req: Request, shared):
        """Prefill only the UNMATCHED suffix of a prompt whose prefix
        already lives in registry pages.

        The matched pages are gathered into a single-row contiguous
        staging cache (positions [0, T) hold the donor's KV bitwise),
        then the suffix runs through the chunked-prefill fn with
        ``cache_len = start`` — the same machinery whose chunked-vs-one-
        shot bitwise parity landed in PR 8, sharing its jit LRU cache.
        At least one token is always recomputed (sampling needs the
        last-token logits), and ``_install`` scatters only the fresh
        pages back — recomputed KV inside matched pages is bitwise equal
        and discarded. Returns ``(logits, staging_cache)`` or None to
        fall back to a full prefill.
        """
        ps = self.ecfg.page_size
        L = self.ecfg.max_len
        prompt = np.asarray(req.prompt)
        S = int(prompt.size)
        T = min(len(shared) * ps, S)       # prompt tokens the pages cover
        start = min(T, S - 1)              # always recompute >= 1 token
        n_suf = S - start
        C = min(bucket_len(n_suf, L) if self._bucketed else n_suf,
                L - start)                  # keep the cache write in-bounds
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :n_suf] = prompt[start:]
        pt_row = np.zeros((1, L // ps), np.int32)
        pt_row[0, : len(shared)] = shared
        pt_dev = jnp.asarray(pt_row)
        staged = init_cache_for(self.cfg, 1, L)
        for name in self._paged_names:
            pool = self.cache["layers"][name]["kv"]
            staged[name] = {"kv": {
                "k": paging.gather_prefix(pool["k_pages"], pt_dev),
                "v": paging.gather_prefix(pool["v_pages"], pt_dev),
            }}
        fn = self._chunk_prefill_fn(C)
        if self.injector is not None:
            self.injector.begin(StepContext(
                tick=self._tick, rids=(req.rid,), op="prefill"))
        try:
            with trace.span("serve.prefill.shared", rid=req.rid,
                            matched=T, suffix=n_suf, tick=self._tick):
                logits, cache1 = fn(
                    self.params, jnp.asarray(chunk), staged,
                    jnp.asarray(start, jnp.int32),
                    jnp.asarray(n_suf, jnp.int32))
            if not np.isfinite(np.asarray(logits)).all():
                raise FloatingPointError("non-finite shared-prefill logits")
        except Exception as e:            # noqa: BLE001 — jitted call
            # No retry ladder of its own: drop the sharing attempt and
            # let the one-shot path (retry + degrade) take over.
            self.stats.prefill_retries += 1
            trace.instant("serve.prefill.shared_abort", rid=req.rid,
                          error=repr(e))
            return None
        return logits, cache1

    def _cow_writes(self) -> None:
        """Copy-on-write, the host half: BEFORE the decode step, any
        active row whose next write lands in a page with refcount > 1
        gets a private copy of that page (device copy, table repoint,
        reference drop on the original). Sequential per slot, so two
        sharers hitting the same page in one tick each get their own
        copy. Refcounts only exceed 1 via the prefix registry, so this
        scan is skipped entirely when sharing is off."""
        if self.registry is None:
            return
        ps = self.ecfg.page_size
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            entry = int(self.lengths[slot]) // ps
            page = int(self.ptable.table[slot, entry])
            if page == 0 or int(self.allocator.refcount[page]) <= 1:
                continue
            got = self.allocator.alloc([1])
            if got is None:
                # Pool exhausted at the copy point: same terminal state
                # as growth exhaustion.
                self._warn_cache_full(req)
                self._release(slot)
                self._finish(req, "cache_full")
                continue
            new = int(got[0][0])
            layers = {}
            for name, leaf in self.cache["layers"].items():
                if name in self._paged_names:
                    kv = leaf["kv"]
                    layers[name] = {"kv": {
                        "k_pages": kv["k_pages"].at[:, new].set(
                            kv["k_pages"][:, page]),
                        "v_pages": kv["v_pages"].at[:, new].set(
                            kv["v_pages"][:, page]),
                    }}
                else:
                    layers[name] = leaf
            self.cache = {"layers": layers,
                          "page_table": self.cache["page_table"]}
            self.ptable.table[slot, entry] = new
            self.allocator.release(np.array([page]))   # drop our reference
            self.stats.refcount_copies += 1
            trace.instant("serve.pages.cow_copy", rid=req.rid, slot=slot,
                          src=page, dst=new)

    # -- paged bookkeeping ----------------------------------------------
    def _sync_page_table(self) -> None:
        self.cache = {"layers": self.cache["layers"],
                      "page_table": self.ptable.device()}

    def _ensure_pages(self) -> None:
        """Grow each active slot's page list to cover its next decode
        write; allocator exhaustion MID-decode finishes the victim with
        ``cache_full`` (the paged meaning: pool empty, not row full).
        Ends by refreshing the device page table — the single sync point
        per tick, before the decode step reads it."""
        if not self._paged:
            return
        ps = self.ecfg.page_size
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            need = paging.pages_for(int(self.lengths[slot]), ps)
            have = int(self.ptable.counts[slot])
            if need <= have:
                continue
            got = self.allocator.alloc([need - have])
            if got is None:
                self._warn_cache_full(req)
                self._release(slot)
                self._finish(req, "cache_full")
                continue
            self.ptable.assign(slot, got[0])
        self._cow_writes()
        self._sync_page_table()

    def defrag(self) -> int:
        """Compact live pages to the front of the pool: one stable
        partition-by-liveness permutation (``PageAllocator.defrag_plan``)
        applied to the pools, the page table, and the free bitmap.
        Decode output is unchanged — the gathered view is invariant
        under page renaming. Returns the number of live pages moved."""
        if not self._paged:
            raise ValueError("defrag() requires cache_layout='paged'")
        dest = self.allocator.defrag_plan()
        d = jnp.asarray(dest, jnp.int32)
        layers = {}
        for name, leaf in self.cache["layers"].items():
            if name in self._paged_names:
                kv = leaf["kv"]
                layers[name] = {"kv": {
                    "k_pages": jnp.zeros_like(kv["k_pages"])
                               .at[:, d].set(kv["k_pages"]),
                    "v_pages": jnp.zeros_like(kv["v_pages"])
                               .at[:, d].set(kv["v_pages"]),
                }}
            else:
                layers[name] = leaf
        self.cache = {"layers": layers,
                      "page_table": self.cache["page_table"]}
        self.ptable.remap(dest)
        if self.registry is not None:
            self.registry.remap(dest)
        moved = self.allocator.apply_defrag(dest)
        self._sync_page_table()
        return moved

    def _maybe_defrag(self) -> None:
        """Auto-defrag: ask ``policy.choose_defrag`` (fragmentation
        gauge + free-run length) once per cooldown window and compact
        when it says so — fragmentation self-heals instead of waiting
        for a host call to ``defrag()``. Bitwise-free: the gathered view
        is invariant under page renaming."""
        if (not self._paged or not self.ecfg.auto_defrag
                or self._tick - self._last_defrag
                < self.ecfg.defrag_cooldown):
            return
        if not scan_policy.choose_defrag(
                self.allocator.fragmentation(),
                self.allocator.free_count,
                self.allocator.longest_free_run(),
                threshold=self.ecfg.defrag_threshold):
            return
        self._last_defrag = self._tick
        self.stats.auto_defrags += 1
        self.defrag()

    def _prefill_request(self, req: Request):
        """Run prefill for one request with retry + degrade. Returns
        ``(logits, cache)`` or None after finishing the request."""
        with trace.span("serve.prefill", rid=req.rid, tick=self._tick,
                        prompt_len=int(np.asarray(req.prompt).size)):
            return self._prefill_request_inner(req)

    def _prefill_request_inner(self, req: Request):
        prompt = jnp.asarray(req.prompt, jnp.int32)[None]
        S = prompt.shape[1]
        fn, padded, extra = self._prefill_call(prompt, int(S))
        last_err: Optional[BaseException] = None
        for attempt in range(self.ecfg.max_step_retries + 1):
            if self.injector is not None:
                self.injector.begin(StepContext(
                    tick=self._tick, rids=(req.rid,), op="prefill"))
            try:
                out = fn(self.params, padded, *extra)
                logits = out[0]
                if np.isfinite(np.asarray(logits)).all():
                    return logits, out[1]
                self.stats.nonfinite_ticks += 1
                last_err = FloatingPointError("non-finite prefill logits")
            except Exception as e:            # noqa: BLE001 — jitted call
                last_err = e
            if attempt < self.ecfg.max_step_retries:
                self.stats.prefill_retries += 1
                if self.ecfg.retry_backoff_s:
                    time.sleep(self.ecfg.retry_backoff_s * (attempt + 1))
        # Primary route exhausted -> safe route (un-wrapped, reference
        # impls). Mark the request degraded only if the numerics differ.
        if self.ecfg.degrade_on_nonfinite or not isinstance(
                last_err, FloatingPointError):
            try:
                sfn = self._prefill_for(int(S), safe=True)
                logits, cache1 = sfn(self.params, prompt)
                if np.isfinite(np.asarray(logits)).all():
                    self.stats.degradations += 1
                    if self._prefill_safe_differs or self._bucketed:
                        req.degraded = True
                    return logits, cache1
                last_err = FloatingPointError(
                    "non-finite prefill logits on safe route")
            except Exception as e:            # noqa: BLE001
                last_err = e
        self._finish(req, "error", error=f"prefill failed: {last_err!r}")
        return None

    def _prefill_call(self, prompt: jax.Array, S: int):
        """Pick the primary prefill callable + its padded inputs."""
        if self._bucketed:
            Sb = bucket_len(S, self.ecfg.max_len)
            fn = self._prefill_for(Sb, bucketed=True)
            padded = jnp.pad(prompt, ((0, 0), (0, Sb - S)))
            return fn, padded, (jnp.asarray(S, jnp.int32),)
        return self._prefill_for(S), prompt, ()

    def _prefill_for(self, S: int, bucketed: bool = False,
                     safe: bool = False):
        """LRU-capped per-shape jitted prefill. With bucketing, distinct
        shapes grow as log2(max_len) instead of one per prompt length;
        the LRU cap bounds live executables either way."""
        key = (S, bucketed, safe)
        if key in self._prefill_cache:
            self._prefill_cache.move_to_end(key)
            return self._prefill_cache[key]
        if safe:
            fn = jax.jit(make_prefill_fn(
                self.cfg, self.ecfg.max_len, attn_impl=None,
                ssm_impl="chunked"))
        elif bucketed:
            fn = jax.jit(make_bucketed_prefill_fn(
                self.cfg, self.ecfg.max_len,
                attn_impl=self.ecfg.attn_impl,
                attn_schedule=self.ecfg.attn_schedule))
        else:
            fn = jax.jit(make_prefill_fn(
                self.cfg, self.ecfg.max_len,
                attn_impl=self.ecfg.attn_impl,
                attn_schedule=self.ecfg.attn_schedule))
        if self.injector is not None and not safe:
            # injector.begin() is issued per-attempt in _prefill_request;
            # wrapping here keeps one wrapper per cached variant.
            fn = self.injector.wrap_prefill(fn)
        self._prefill_cache[key] = fn
        self.stats.prefill_compiles += 1
        while len(self._prefill_cache) > self.ecfg.max_prefill_variants:
            self._prefill_cache.popitem(last=False)
            self.stats.prefill_cache_evictions += 1
        return self._prefill_cache[key]

    def _sample(self, logits: jax.Array) -> jax.Array:
        self.key, sub = jax.random.split(self.key)
        return sample_logits(sub, logits, self.ecfg.temperature,
                             self.ecfg.top_p)

    # -- decode ---------------------------------------------------------
    def _active(self) -> List[int]:
        return [i for i, r in enumerate(self.slot_req) if r is not None]

    def _ctx(self, active: List[int], op: str = "step"):
        rows = {self.slot_req[i].rid: i for i in active}
        return StepContext(tick=self._tick, rids=tuple(rows), op=op,
                           rows=rows)

    def _expire_deadlines(self) -> None:
        ttl_default = self.ecfg.deadline_ticks
        for req in list(self.waiting):
            ttl = (req.deadline_ticks if req.deadline_ticks is not None
                   else ttl_default)
            if ttl is not None and self._tick - req.submit_tick >= ttl:
                self.waiting.remove(req)
                self._finish(req, "deadline")
        self.stats.observe_queue(len(self.waiting))
        job = self._chunk_job
        if job is not None:
            req = job["req"]
            ttl = (req.deadline_ticks if req.deadline_ticks is not None
                   else ttl_default)
            if ttl is not None and self._tick - req.submit_tick >= ttl:
                self._chunk_job = None
                self._finish(req, "deadline")
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            ttl = (req.deadline_ticks if req.deadline_ticks is not None
                   else ttl_default)
            if ttl is not None and self._tick - req.submit_tick >= ttl:
                self._release(slot)
                self._finish(req, "deadline")

    def step(self) -> int:
        """One engine tick: expire deadlines, admit waiting, decode one
        token for every active slot. Returns the number of active slots
        the tick operated on. Bookkeeping commits only on success — a
        failed or non-finite tick leaves the pool exactly as it was."""
        t0 = time.perf_counter()
        with trace.span("serve.step", tick=self._tick + 1):
            n = self._step_inner()
        if self.metrics is not None:
            self.metrics.histogram("serve.tick_s").record(
                time.perf_counter() - t0)
        trace.counter("serve.pool", waiting=len(self.waiting), active=n)
        return n

    def _step_inner(self) -> int:
        t0 = time.perf_counter()
        self._tick += 1
        self.stats.ticks += 1
        # Phase spans: together they cover the tick, so a profiler
        # session can put each stretch of device idle time down to one.
        with trace.span("serve.admit", tick=self._tick):
            self._expire_deadlines()
            self._maybe_defrag()
            self._admit()
            self._ensure_pages()
            active = self._active()
        if not active:
            return 0
        with trace.span("serve.decode", tick=self._tick,
                        rids=[self.slot_req[i].rid for i in active]):
            result = self._robust_step(active)
        if result is None:                     # pool emptied by quarantine
            return 0
        logits, new_cache, active = result

        # -- numeric degradation ladder --------------------------------
        with trace.span("serve.sync", tick=self._tick):
            logits_np = np.asarray(logits)
            finite = np.isfinite(logits_np[active]).all()
        if not finite:
            self.stats.nonfinite_ticks += 1
            handled = False
            if self.ecfg.degrade_on_nonfinite and not self._pre_cache_gone():
                # One rung down: re-run THIS tick on the safe route
                # (never injector-wrapped). For pure-attention decode the
                # math is identical, so the rerun is bitwise lossless.
                self.stats.degradations += 1
                s_logits, s_cache = self._step_safe(
                    self.params, self.tokens, self.cache,
                    self._cache_len_vec())
                s_np = np.asarray(s_logits)
                if np.isfinite(s_np[active]).all():
                    logits, new_cache, logits_np = s_logits, s_cache, s_np
                    if self._decode_safe_differs:
                        for i in active:
                            self.slot_req[i].degraded = True
                    handled = True
            if not handled:
                return self._skip_tick(active, logits_np, new_cache, t0)
        self._nan_streak = 0

        # -- commit ----------------------------------------------------
        self.cache = new_cache
        with trace.span("serve.sample", tick=self._tick):
            nxt_np = np.asarray(self._sample(logits))
        with trace.span("serve.commit", tick=self._tick):
            new_tokens = self.tokens
            for i in active:
                req = self.slot_req[i]
                tok = int(nxt_np[i])
                req.output.append(tok)
                self.stats.tokens_generated += 1
                self.lengths[i] += 1
                self.budgets[i] -= 1
                if tok == self.ecfg.eos_id:
                    reason = "eos"
                elif self.budgets[i] <= 0:
                    reason = "length_budget"
                elif self.lengths[i] + 1 >= self.ecfg.max_len:
                    reason = "cache_full"
                    self._warn_cache_full(req)
                else:
                    new_tokens = new_tokens.at[i, 0].set(tok)
                    continue
                self._release(i)
                self._finish(req, reason)
            self.tokens = new_tokens
        if (self.ecfg.slow_tick_s is not None
                and time.perf_counter() - t0 > self.ecfg.slow_tick_s):
            self.stats.slow_ticks += 1
        return len(active)

    def _cache_len_vec(self) -> jax.Array:
        """Per-row cache lengths: inactive rows are 0 (fully masked under
        the zeroed-probability convention, so they never emit NaN)."""
        return jnp.asarray(self.lengths, jnp.int32)

    def _pre_cache_gone(self) -> bool:
        """Under donation the pre-tick cache buffers may be consumed."""
        if not self.ecfg.donate_cache:
            return False
        leaf = jax.tree.leaves(self.cache)[0]
        return getattr(leaf, "is_deleted", lambda: False)()

    def _skip_tick(self, active, logits_np, new_cache, t0) -> int:
        """Roll the tick back (trainer NaN-guard parity): nothing
        advances. Persistent non-finite ticks quarantine the offending
        rows so the pool stays live."""
        trace.instant("serve.rollback", tick=self._tick,
                      rids=[self.slot_req[i].rid for i in active],
                      nan_streak=self._nan_streak + 1)
        self.stats.skipped_ticks += 1
        self._nan_streak += 1
        if self._pre_cache_gone():
            # Donated fast path: pre-tick cache is gone, adopt the
            # written one. Attention caches self-heal (next tick rewrites
            # the same positions); recurrent state is documented lossy.
            self.cache = new_cache
        if self._nan_streak > self.ecfg.max_consecutive_nan_ticks:
            bad = [i for i in active
                   if not np.isfinite(logits_np[i]).all()]
            for i in bad:
                req = self.slot_req[i]
                self._release(i)
                self._finish(req, "error",
                             error=f"non-finite logits for "
                                   f"{self._nan_streak} consecutive ticks")
                self.stats.quarantined += 1
            self._nan_streak = 0
        if (self.ecfg.slow_tick_s is not None
                and time.perf_counter() - t0 > self.ecfg.slow_tick_s):
            self.stats.slow_ticks += 1
        return len(active)

    def _warn_cache_full(self, req: Request) -> None:
        warnings.warn(
            f"request {req.rid} ran out of KV cache (max_len="
            f"{self.ecfg.max_len}) before its token budget; finishing "
            f"with finish_reason='cache_full'", RuntimeWarning,
            stacklevel=3)

    # -- step-failure recovery -----------------------------------------
    def _robust_step(self, active: List[int]):
        """Run the wrapped decode step with retries; on persistent
        failure bisect the active set and quarantine the poison request.
        Returns ``(logits, new_cache, active)`` or None if the pool
        emptied."""
        attempts = 0
        transient_resets = 0
        last_err: Optional[BaseException] = None
        for _ in range(4 * self.ecfg.max_slots + 8):
            clv = self._cache_len_vec()   # fresh: quarantine edits lengths
            if self.injector is not None:
                self.injector.begin(self._ctx(active))
            try:
                logits, new_cache = self._wstep(
                    self.params, self.tokens, self.cache, clv)
                return logits, new_cache, active
            except Exception as e:            # noqa: BLE001 — jitted call
                last_err = e
            attempts += 1
            if attempts <= self.ecfg.max_step_retries:
                self.stats.step_retries += 1
                if self.ecfg.retry_backoff_s:
                    time.sleep(self.ecfg.retry_backoff_s * attempts)
                continue
            poison = self._bisect(active, clv)
            if poison is None:
                raise EngineStepError(
                    f"decode step failing with no active request "
                    f"implicated (ambient fault): {last_err!r}"
                ) from last_err
            if not poison:
                # Not reproducible in probes: transient that outlived the
                # retry budget. Allow one fresh retry round, then give up.
                transient_resets += 1
                if transient_resets > 1:
                    raise EngineStepError(
                        f"decode step failed after retries and probes "
                        f"could not reproduce it: {last_err!r}"
                    ) from last_err
                attempts = 0
                continue
            for slot in poison:
                req = self.slot_req[slot]
                self._release(slot)
                self._finish(req, "error",
                             error=f"quarantined by step-failure "
                                   f"bisection: {last_err!r}")
                self.stats.quarantined += 1
            active = self._active()
            if not active:
                return None
            attempts = 0
        raise EngineStepError(
            f"decode step recovery did not converge: {last_err!r}"
        ) from last_err

    def _probe(self, subset: List[int], clv) -> bool:
        """Re-issue the step as if only ``subset`` participated (the
        injector keys poison faults on participating rids). Non-donating,
        results discarded: a successful probe has no side effects."""
        self.stats.probes += 1
        if self.injector is not None:
            self.injector.begin(self._ctx(subset))
        try:
            with trace.span("serve.probe", tick=self._tick,
                            rids=[self.slot_req[i].rid for i in subset]):
                self._wstep_probe(self.params, self.tokens, self.cache, clv)
            return True
        except Exception:                      # noqa: BLE001
            return False

    def _bisect(self, active: List[int], clv) -> Optional[List[int]]:
        """Binary-search the failing subset. Returns the poison slots,
        [] when the failure won't reproduce (transient), or None when it
        reproduces with NO requests implicated (ambient)."""
        if not self._probe([], clv):
            return None
        cands = list(active)
        while len(cands) > 1:
            mid = len(cands) // 2
            lo, hi = cands[:mid], cands[mid:]
            if not self._probe(lo, clv):
                cands = lo
            elif not self._probe(hi, clv):
                cands = hi
            else:
                # Only the combination fails: not separable — quarantine
                # the whole candidate set rather than deadlock the pool.
                return cands
        if not self._probe(cands, clv):        # confirm the singleton
            return cands
        return []

    # -- driving --------------------------------------------------------
    def run_to_completion(self, max_ticks: int = 10_000,
                          strict: Optional[bool] = None) -> list[Request]:
        """Drive ticks until every request finished. Exhausting
        ``max_ticks`` with work still pending raises under ``strict``
        (default ``EngineConfig.strict_deadlines``) or finishes the
        survivors with ``finish_reason="deadline"``."""
        strict = self.ecfg.strict_deadlines if strict is None else strict
        for _ in range(max_ticks):
            if (not self.waiting and self._chunk_job is None
                    and all(r is None for r in self.slot_req)):
                break
            self.step()
        else:
            survivors = (len(self.waiting)
                         + (self._chunk_job is not None)
                         + sum(r is not None for r in self.slot_req))
            if survivors:
                if strict:
                    raise EngineDeadlineError(
                        f"run_to_completion exhausted max_ticks="
                        f"{max_ticks} with {survivors} request(s) "
                        f"unfinished")
                for req in list(self.waiting):
                    self.waiting.remove(req)
                    self._finish(req, "deadline")
                if self._chunk_job is not None:
                    req = self._chunk_job["req"]
                    self._chunk_job = None
                    self._finish(req, "deadline")
                for slot, req in enumerate(self.slot_req):
                    if req is not None:
                        self._release(slot)
                        self._finish(req, "deadline")
        return self.finished

    # -- invariants -----------------------------------------------------
    def audit(self) -> dict:
        """Lifecycle invariants the chaos wall asserts. Raises
        AssertionError on violation; returns a summary dict."""
        fin = [r.rid for r in self.finished]
        assert len(fin) == len(set(fin)), f"duplicate finished rids: {fin}"
        for req in self.finished:
            assert req.finish_reason in FINISH_REASONS, (
                f"request {req.rid} finished with invalid reason "
                f"{req.finish_reason!r}")
        live = ([r.rid for r in self.waiting]
                + ([self._chunk_job["req"].rid] if self._chunk_job else [])
                + [r.rid for r in self.slot_req if r is not None])
        assert not (set(fin) & set(live)), (
            f"rids both finished and live: {set(fin) & set(live)}")
        for req in self.waiting:
            assert req.finish_reason is None
        assert self.stats.total_finished == len(self.finished)
        if self._paged:
            # Refcount invariant: every page's count equals its live
            # table references plus the prefix registry's strong pins
            # (weak partial entries hold no reference), and the free
            # bitmap is exactly refcount == 0.
            refs = np.zeros(self.allocator.num_pages, np.int64)
            for slot in range(len(self.slot_req)):
                pages = self.ptable.pages_of(slot)
                if pages.size:
                    np.add.at(refs, pages, 1)
            if self.registry is not None:
                strong = self.registry.strong_pages()
                if strong:
                    np.add.at(refs, np.asarray(strong, np.int64), 1)
            refs[0] = 1                        # null page pin
            assert (refs == self.allocator.refcount).all(), (
                f"refcount drift: expected {refs.tolist()}, "
                f"allocator has {self.allocator.refcount.tolist()}")
            free_expect = self.allocator.refcount == 0
            free_expect[0] = False
            assert (free_expect == self.allocator.free).all(), (
                "free bitmap out of sync with refcounts")
        return {"finished": len(fin), "live": len(live),
                "stats": self.stats.as_dict()}


def _scatter_row(pool: jax.Array, one: jax.Array, slot: int) -> jax.Array:
    """Write prefill cache row(s) into the pool slot.

    pool: (L, B, ...) stacked cache; one: (L, 1, ...) single-row cache.
    """
    return jax.lax.dynamic_update_slice_in_dim(pool, one, slot, axis=1)

"""Engine health surface: counters for the serve request lifecycle.

``EngineStats`` is the single place the hardened engine records what
happened to traffic — admissions, rejections, finishes by reason,
step retries, bisection probes, quarantines, numeric degradations,
skipped (rolled-back) ticks, prefill compiles — so operators (and the
chaos tests) can assert liveness invariants without scraping logs.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.obs.metrics import Registry

#: The request terminal states. Every submitted request ends with exactly
#: one of these on ``Request.finish_reason`` (the chaos wall's invariant).
FINISH_REASONS = (
    "eos",            # sampled the eos token
    "length_budget",  # generated its max_new_tokens budget
    "cache_full",     # ran out of KV-cache slots before its budget (warned)
    "deadline",       # tick TTL expired (per-request or run_to_completion)
    "rejected",       # failed admission (cannot fit / queue full)
    "error",          # quarantined by step-failure recovery, or prefill died
    "cancelled",      # host-side cancel(rid)
)


#: Counters a healthy run leaves at 0: each counts a failure the engine
#: recovered from by retrying, bisecting, rolling back or degrading to
#: the reference route — recoveries that would hide a failing kernel.
RECOVERY_COUNTERS = ("step_retries", "prefill_retries", "quarantined",
                     "nonfinite_ticks", "degradations", "skipped_ticks")


@dataclasses.dataclass
class EngineStats:
    """Monotonic counters plus current queue gauges.

    Optionally MIRRORED into an ``obs.metrics.Registry``
    (:meth:`attach`): every counter write is reflected as a
    ``serve.stats.<name>`` gauge and every finish as a
    ``serve.finished.<reason>`` counter, so operator dashboards, the
    ``--stats-json`` surface, and the chaos-wall invariants all read one
    registry instead of scraping this dataclass. Mirroring is write-
    through (not snapshot): the registry is live mid-run.
    """

    # -- traffic -------------------------------------------------------
    ticks: int = 0                 # engine steps attempted
    submitted: int = 0             # submit() calls (incl. rejected)
    admitted: int = 0              # prefills attempted into a slot
    tokens_generated: int = 0      # sampled tokens appended to outputs
    finished: Dict[str, int] = dataclasses.field(default_factory=dict)

    # -- queue ---------------------------------------------------------
    queue_depth: int = 0           # waiting requests right now
    peak_queue_depth: int = 0

    # -- failure recovery ----------------------------------------------
    step_retries: int = 0          # failed decode-step attempts retried
    prefill_retries: int = 0       # failed prefill attempts retried
    probes: int = 0                # bisection probe calls
    quarantined: int = 0           # requests finished "error" by bisection

    # -- numeric degradation ladder -------------------------------------
    nonfinite_ticks: int = 0       # ticks whose logits came back non-finite
    degradations: int = 0          # re-runs on the degraded (reference) route
    skipped_ticks: int = 0         # ticks rolled back without advancing

    # -- perf / compile hygiene -----------------------------------------
    prefill_compiles: int = 0      # distinct prefill variants jitted
    prefill_cache_evictions: int = 0
    slow_ticks: int = 0            # wall time above EngineConfig.slow_tick_s

    # -- paged KV cache (serve/paging.py) --------------------------------
    page_allocs: int = 0           # pages handed out by the allocator
    page_frees: int = 0            # pages returned to the free pool
    page_alloc_failures: int = 0   # allocation attempts the pool refused
    prefill_chunks: int = 0        # chunked-prefill chunks executed
    defrags: int = 0               # pool compactions (partition by liveness)
    auto_defrags: int = 0          # defrags triggered by policy.choose_defrag

    # -- copy-on-write prefix sharing ------------------------------------
    prefix_hits: int = 0           # admissions that mapped registry pages
    shared_page_maps: int = 0      # pages mapped from the registry (not alloc'd)
    refcount_copies: int = 0       # COW copies (write into a refcount>1 page)

    # -- metrics mirroring ----------------------------------------------
    # ``_registry`` is deliberately NOT a dataclass field: asdict()/
    # equality stay counter-only and attachment survives neither copy
    # nor pickling (a mirror is a live wire, not state).
    def attach(self, registry: Optional[Registry]) -> "EngineStats":
        """Mirror counters into ``registry`` (write-through from now on;
        current values are published immediately). ``None`` detaches."""
        object.__setattr__(self, "_registry", registry)
        if registry is not None:
            for k, v in self.as_dict().items():
                if isinstance(v, int):
                    registry.gauge(f"serve.stats.{k}").set(v)
            for reason, nn in self.finished.items():
                registry.counter(f"serve.finished.{reason}").value = nn
        return self

    def __setattr__(self, name: str, value) -> None:
        object.__setattr__(self, name, value)
        reg = getattr(self, "_registry", None)
        if reg is not None and isinstance(value, int):
            reg.gauge(f"serve.stats.{name}").set(value)
            if name != "total_finished":
                reg.gauge("serve.stats.total_finished").set(
                    self.total_finished)

    def record_finish(self, reason: str) -> None:
        if reason not in FINISH_REASONS:
            raise ValueError(f"unknown finish reason {reason!r}; "
                             f"one of {FINISH_REASONS}")
        self.finished[reason] = self.finished.get(reason, 0) + 1
        reg = getattr(self, "_registry", None)
        if reg is not None:
            reg.counter(f"serve.finished.{reason}").inc()
            reg.gauge("serve.stats.total_finished").set(self.total_finished)

    def recoveries(self) -> Dict[str, int]:
        """The nonzero ``RECOVERY_COUNTERS`` (empty on a healthy run)."""
        return {k: getattr(self, k) for k in RECOVERY_COUNTERS
                if getattr(self, k)}

    def observe_queue(self, depth: int) -> None:
        self.queue_depth = depth
        self.peak_queue_depth = max(self.peak_queue_depth, depth)

    @property
    def total_finished(self) -> int:
        return sum(self.finished.values())

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["total_finished"] = self.total_finished
        return d

    def summary(self) -> str:
        """One operator line. Every monotonic counter appears (the
        regression test walks ``as_dict`` and asserts nothing counted is
        silently dropped here — ``prefill_retries`` / ``nonfinite_ticks``
        / ``slow_ticks`` / ``prefill_cache_evictions`` were once counted
        but never printed); ``as_dict`` stays the superset (it adds the
        ``queue_depth`` gauge and the raw ``finished`` map)."""
        fin = " ".join(f"{k}={v}" for k, v in sorted(self.finished.items()))
        return (
            f"ticks={self.ticks} submitted={self.submitted} "
            f"admitted={self.admitted} tokens={self.tokens_generated} "
            f"finished[{fin}] retries={self.step_retries} "
            f"prefill_retries={self.prefill_retries} "
            f"probes={self.probes} quarantined={self.quarantined} "
            f"nonfinite={self.nonfinite_ticks} "
            f"degradations={self.degradations} "
            f"skipped={self.skipped_ticks} "
            f"slow_ticks={self.slow_ticks} "
            f"prefill_compiles={self.prefill_compiles} "
            f"prefill_evictions={self.prefill_cache_evictions} "
            f"pages[allocs={self.page_allocs} frees={self.page_frees} "
            f"failures={self.page_alloc_failures} defrags={self.defrags} "
            f"auto_defrags={self.auto_defrags}] "
            f"sharing[prefix_hits={self.prefix_hits} "
            f"shared_page_maps={self.shared_page_maps} "
            f"refcount_copies={self.refcount_copies}] "
            f"prefill_chunks={self.prefill_chunks} "
            f"peak_queue={self.peak_queue_depth}"
        )

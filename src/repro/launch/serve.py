"""Serving driver: continuous-batching engine over a model checkpoint.

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-medium-14b \
        --smoke --requests 8 --max-new-tokens 16

Hardening knobs ride along: ``--fault-seed`` runs the request mix under
the deterministic chaos injector (transient errors / NaN logits /
stalls), ``--deadline-ticks``/``--max-waiting`` exercise admission
control and TTLs, and the run always ends with the ``EngineStats``
health line the chaos tests assert on. Without an injector the exit code
is non-zero when any request finished ``error`` or ``rejected`` or the
engine had to retry, roll back or degrade a step: on a healthy run none
of that happens, and a recovery would hide a failing kernel.

Observability knobs: ``--trace out.json`` captures the run as a Chrome
``trace_event`` file (open in ui.perfetto.dev — each tick's
``serve.step`` span and its phase spans, request lifecycle instants,
policy decisions); ``--stats-json``
prints one machine-parsable line with the full ``EngineStats.as_dict()``
plus the metrics-registry snapshot (tick-latency histogram included).
"""

from __future__ import annotations

import argparse
import json
import time
import warnings

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import Registry, trace
from repro.serve import Engine, EngineConfig, FaultInjector, Request
from repro.train.step import init_params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--top-p", type=float, default=0.95)
    ap.add_argument("--seed", type=int, default=0)
    # -- hardening / chaos knobs ---------------------------------------
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="run under the deterministic fault injector")
    ap.add_argument("--fault-error-rate", type=float, default=0.05)
    ap.add_argument("--fault-nan-rate", type=float, default=0.05)
    ap.add_argument("--fault-stall-rate", type=float, default=0.02)
    ap.add_argument("--max-waiting", type=int, default=None)
    ap.add_argument("--admission-policy", choices=["reject", "block"],
                    default="reject")
    ap.add_argument("--deadline-ticks", type=int, default=None)
    ap.add_argument("--no-bucket-prompts", action="store_true")
    # -- paged KV cache knobs ------------------------------------------
    ap.add_argument("--cache-layout",
                    choices=["contiguous", "paged", "auto"],
                    default="contiguous")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="page pool size (default: worst case + null)")
    ap.add_argument("--share-prefixes", action="store_true",
                    help="COW prefix sharing across requests (paged only)")
    ap.add_argument("--system-prompt-len", type=int, default=0,
                    help="tokens of common prefix prepended to every "
                         "prompt (makes --share-prefixes observable)")
    ap.add_argument("--attn-impl", choices=["flash"], default=None,
                    help="prefill attention route (default: dense)")
    ap.add_argument("--attn-schedule",
                    choices=["auto", "carry", "decoupled"], default="auto")
    # -- observability knobs -------------------------------------------
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="export the run as Chrome trace_event JSON")
    ap.add_argument("--stats-json", action="store_true",
                    help="print stats as one machine-parsable JSON line")
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.trace is not None:
        trace.enable()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if cfg.is_encdec:
        raise SystemExit(
            "enc-dec serving goes through repro.serve.steps directly "
            "(needs an encoder memory); see examples/serve_batch.py")
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)

    injector = None
    if args.fault_seed is not None:
        injector = FaultInjector.from_seed(
            args.fault_seed, ticks=4 * args.requests * args.max_new_tokens,
            p_error=args.fault_error_rate, p_nan=args.fault_nan_rate,
            p_stall=args.fault_stall_rate)

    metrics = Registry()
    eng = Engine(params, cfg, EngineConfig(
        max_slots=args.slots, max_len=args.max_len,
        max_new_tokens=args.max_new_tokens, temperature=args.temperature,
        top_p=args.top_p, eos_id=-1, seed=args.seed,
        max_waiting=args.max_waiting,
        admission_policy=args.admission_policy,
        deadline_ticks=args.deadline_ticks,
        bucket_prompts=not args.no_bucket_prompts,
        attn_impl=args.attn_impl, attn_schedule=args.attn_schedule,
        cache_layout=args.cache_layout, page_size=args.page_size,
        num_pages=args.num_pages, share_prefixes=args.share_prefixes),
        injector=injector, metrics=metrics)

    rng = np.random.default_rng(args.seed)
    system = rng.integers(2, cfg.vocab_size,
                          size=args.system_prompt_len).astype(np.int32)
    t0 = time.perf_counter()
    with warnings.catch_warnings():
        warnings.simplefilter("default")
        for rid in range(args.requests):
            prompt = np.concatenate([system, rng.integers(
                2, cfg.vocab_size, size=args.prompt_len).astype(np.int32)])
            eng.submit(Request(rid=rid, prompt=prompt))
        done = eng.run_to_completion()
    dt = time.perf_counter() - t0
    eng.audit()
    ntok = sum(len(r.output) for r in done)
    ok = sum(r.finish_reason in ("eos", "length_budget") for r in done)
    print(f"served {len(done)} requests, {ntok} tokens in {dt:.2f}s "
          f"({ntok / dt:.1f} tok/s, goodput {ok}/{len(done)})")
    # The human line and the machine line read the SAME counters: the
    # summary string from the dataclass, the JSON from its registry
    # mirror (EngineStats.attach keeps them write-through-identical).
    print(f"stats: {eng.stats.summary()}")
    if args.stats_json:
        print("stats-json: " + json.dumps(
            {"stats": eng.stats.as_dict(), "metrics": metrics.snapshot()},
            sort_keys=True))
    if injector is not None:
        print(f"faults fired: error={injector.fired_count('error')} "
              f"nan={injector.fired_count('nan')} "
              f"stall={injector.fired_count('stall')}")
    for r in done[:3]:
        print(f"  req {r.rid}: [{r.finish_reason}] {r.output[:10]}...")
    if args.trace is not None:
        n = len(trace.export(args.trace)["traceEvents"])
        print(f"trace: {n} events -> {args.trace}")
    if injector is None:
        failed = {r.rid: r.finish_reason for r in done
                  if r.finish_reason in ("error", "rejected")}
        recoveries = eng.stats.recoveries()
        if failed or recoveries:
            print(f"UNHEALTHY: failed finishes {failed}, "
                  f"recoveries {recoveries}")
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

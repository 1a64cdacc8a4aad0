"""Drive the system's main path once on the chip and check every result.

    python chip_smoke.py             # one chip: prefix sums, then serving
    python chip_smoke.py --chips 4   # four chips: the sharded scan only

One chip, in order:

  device   ``jax.devices()`` must be a TPU; anything else exits non-zero
           before any work (there is no CPU fallback).
  scan     ``repro.core.scan.cumsum`` through the public policy path on
           2^28 elements (f32, and int32 0/1), then every kernel family
           (``scan_blocked``, ``segscan``, ``ssm_scan``, ``compact``)
           under every schedule its wrapper offers. The fused schedule
           runs at the largest grid its semaphore chain fits.
  serve    granite-moe-1b-a400m at its published widths, seeded random
           weights, through ``serve.Engine`` with flash prefill: 8
           requests of 512 prompt tokens and 32 greedy new tokens, once
           with the contiguous KV cache and once paged.

Every scan result is compared with a plain numpy reference (float64
within a stated tolerance for floats; exact for ints and masks), and
every one-chip scan program must contain a Pallas kernel
(``tpu_custom_call``), so no interpret mode or jnp stand-in can pass.
The four-chip sharded scan runs ``scan_sharded`` as a user calls it,
whose per-chip scan is the blocked jnp scan.
Wall times printed per phase include set-up and compilation; they are
not performance numbers.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

KERNEL_MARK = "tpu_custom_call"
SEED = 0

_COMPILES = [0]


def _count_compiles():
    import jax
    from jax._src import dispatch

    def listener(event, duration, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            _COMPILES[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)


def device_phase(chips: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {d.platform!r}); this "
            f"check runs only on the chip")
    if len(devs) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, found "
            f"{len(devs)}")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _run_compiled(fn, *args):
    """Compile ``fn`` for ``args``, insist on a Pallas kernel in the
    program, run it, and return the result on the host."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    if KERNEL_MARK not in compiled.as_text():
        raise AssertionError(f"no {KERNEL_MARK} in the compiled program")
    return jax.tree.map(np.asarray, jax.block_until_ready(compiled(*args)))


def _close(name, got, ref, rtol):
    """Float check: max |got - ref| <= rtol * max(1, max |ref|)."""
    err = float(np.max(np.abs(got.astype(np.float64) - ref)))
    bound = rtol * max(1.0, float(np.max(np.abs(ref))))
    print(f"  {name}: max|err| {err:.3e} <= {bound:.3e}", flush=True)
    if not err <= bound:
        raise AssertionError(f"{name}: max|err| {err} > {bound}")


def _exact(name, got, ref):
    if not np.array_equal(got, ref):
        bad = int(np.sum(got != ref))
        raise AssertionError(f"{name}: {bad} elements differ")
    print(f"  {name}: exact", flush=True)


def _segmented_ref(v, f):
    """Per-row segmented inclusive sum in float64 (a flag starts one)."""
    cs = np.cumsum(v, axis=-1, dtype=np.float64)
    idx = np.broadcast_to(np.arange(v.shape[-1]), v.shape)
    start = np.maximum.accumulate(np.where(f != 0, idx, 0), axis=-1)
    # the sum before each segment's start (0 for an unflagged first one)
    base = (np.take_along_axis(cs, start, -1)
            - np.take_along_axis(v, start, -1).astype(np.float64))
    return cs - base


def _affine_ref(a, b):
    h = np.zeros(a.shape[::2], np.float64)
    out = np.empty(a.shape, np.float64)
    for t in range(a.shape[1]):
        h = a[:, t] * h + b[:, t]
        out[:, t] = h
    return out


# Float tolerance: the chunk chain adds about sqrt(chunks) * 2^-24 * |S|
# of f32 rounding (under 2e-5 of the running sum at these sizes); 1e-4
# holds that with margin and still catches one dropped or doubled
# 2048-element block total.
RTOL_F32 = 1e-4


def scan_phase(n_public=1 << 28, rows=8, n_rows=1 << 24, n_fused=1 << 19,
               ssm_shape=(4, 8192, 1024)):
    import jax
    import jax.numpy as jnp
    from repro.core import scan as scanlib
    from repro.kernels.compact import ops as kc_ops
    from repro.kernels.scan_blocked import ops as sb_ops
    from repro.kernels.segscan import ops as seg_ops
    from repro.kernels.ssm_scan import ops as ssm_ops

    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)

    # The paper's primitive through the public policy path.
    x = jax.random.uniform(keys[0], (n_public,), jnp.float32, -1.0, 1.0)
    got = _run_compiled(scanlib.cumsum, x)
    _close(f"cumsum f32 n={n_public}", got,
           np.cumsum(np.asarray(x), dtype=np.float64), RTOL_F32)
    del x, got
    m = jax.random.bernoulli(keys[1], 0.5, (n_public,)).astype(jnp.int32)
    got = _run_compiled(scanlib.cumsum, m)
    _exact(f"cumsum int32 n={n_public}", got,
           np.cumsum(np.asarray(m), dtype=np.int64).astype(np.int32))
    del m, got

    def rows_of(n):
        return jax.random.uniform(keys[2], (rows, n), jnp.float32, -1, 1)

    for s in ("carry", "decoupled", "tree", "fused"):
        n = n_fused if s == "fused" else n_rows
        x = rows_of(n)
        got = _run_compiled(
            lambda v, s=s: sb_ops.cumsum(v, schedule=s), x)
        _close(f"scan_blocked {s} {x.shape}", got,
               np.cumsum(np.asarray(x), -1, dtype=np.float64), RTOL_F32)
    del x, got

    for s in ("carry", "decoupled", "tree", "fused"):
        n = n_fused if s == "fused" else n_rows
        v = rows_of(n)
        f = jax.random.bernoulli(keys[3], 1e-3, (rows, n)).astype(jnp.int32)
        got = _run_compiled(
            lambda a, b, s=s: seg_ops.segmented_cumsum(a, b, schedule=s),
            v, f)
        _close(f"segscan {s} {v.shape}", got,
               _segmented_ref(np.asarray(v), np.asarray(f)), RTOL_F32)
    del v, f, got

    a = jax.random.uniform(keys[4], ssm_shape, jnp.float32, 0.9, 1.0)
    b = 0.1 * jax.random.normal(keys[5], ssm_shape, jnp.float32)
    ref = _affine_ref(np.asarray(a), np.asarray(b))
    for s in ("carry", "decoupled", "tree", "fused"):
        got = _run_compiled(
            lambda p, q, s=s: ssm_ops.ssm_scan(p, q, schedule=s), a, b)
        _close(f"ssm_scan {s} {ssm_shape}", got, ref, RTOL_F32)
    del a, b, ref, got

    # compact's wrapper reads survivor counts off the running-totals
    # chain, which the fused schedule does not keep: carry/decoupled/tree.
    m = jax.random.bernoulli(keys[6], 0.5, (rows, n_rows)).astype(jnp.int32)
    mn = np.asarray(m)
    excl = np.cumsum(mn, -1, dtype=np.int64) - mn
    dest_ref = np.where(mn != 0, excl, n_rows).astype(np.int32)
    cnt_ref = mn.sum(-1).astype(np.int32)
    for s in ("carry", "decoupled", "tree"):
        dest, cnt = _run_compiled(
            lambda v, s=s: kc_ops.mask_compact(v, schedule=s), m)
        _exact(f"compact {s} dest {m.shape}", dest, dest_ref)
        _exact(f"compact {s} counts", cnt, cnt_ref)


# bf16 weights and activations through 24 layers: the flash and dense
# prefill routes round differently. 5e-2 of the logits' scale bounds
# that at the last position.
RTOL_BF16_ROUTES = 5e-2
# The KV cache is held to each layer's relative Frobenius error, not to
# its largest entry: top-8 MoE routing turns rounding-level differences
# into other experts for a few tokens, whose entries then differ by a
# tenth of the scale while the layer agrees to 3e-2. A planted
# non-causal flash mask reads 0.28 at the first layer it reaches
# (PERF.md).
CACHE_RELF_BOUND = 0.1


def prefill_route_errors(params, cfg, prompt, max_len):
    """Prefill ``prompt`` through the flash and the dense attention
    routes; return ``[(name, error, bound)]``: the last position's logits
    (max |err| against the logit scale) and the KV cache each route
    fills (the worst layer's ``|flash - dense|_F / |dense|_F``).

    Every layer past the first computes its keys and values from the
    previous layer's attention output at every position, so the cache
    shows a wrong attention pattern at any position, not only the last.
    """
    import jax
    import jax.numpy as jnp
    from repro.serve.steps import make_prefill_fn
    tokens = jnp.asarray(prompt[None])
    outs = {}
    for impl in ("flash", None):
        fn = make_prefill_fn(cfg, max_len, attn_impl=impl)
        compiled = jax.jit(fn).lower(params, tokens).compile()
        if impl == "flash" and KERNEL_MARK not in compiled.as_text():
            raise AssertionError("flash prefill compiled without a kernel")
        logits, cache = compiled(params, tokens)
        outs[impl] = [np.asarray(a, np.float64)
                      for a in [logits] + jax.tree.leaves(cache)]

    (logits, ref_logits), *cache = zip(outs["flash"], outs[None])
    logit_err = float(np.max(np.abs(logits - ref_logits)))
    logit_bound = RTOL_BF16_ROUTES * max(1.0, float(np.max(np.abs(
        ref_logits))))
    # cache arrays stack the layers on axis 0
    relf = max(float(np.linalg.norm(g - r) / max(np.linalg.norm(r), 1e-30))
               for got, ref in cache for g, r in zip(got, ref))
    return [("prefill logits flash vs dense, max|err|", logit_err,
             logit_bound),
            ("prefill KV cache flash vs dense, worst layer relative "
             "Frobenius error", relf, CACHE_RELF_BOUND)]


def _flash_prefill_parity(params, cfg, prompt, max_len):
    for name, err, bound in prefill_route_errors(params, cfg, prompt,
                                                 max_len):
        print(f"  {name}: {err:.3e} <= {bound:.3e}", flush=True)
        if not err <= bound:
            raise AssertionError(f"{name}: {err} > {bound}")


def serve_phase(cfg=None, slots=8, max_len=4096, prompt_len=512,
                new_tokens=32, page_size=16):
    import jax
    from repro import configs
    from repro.serve import Engine, EngineConfig, Request
    from repro.train.step import init_params

    cfg = cfg or configs.get_config("granite-moe-1b-a400m")
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"  {cfg.name}: {n_params} parameters, {cfg.num_layers} layers",
          flush=True)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(2, cfg.vocab_size, prompt_len).astype(np.int32)
               for _ in range(slots)]
    _flash_prefill_parity(params, cfg, prompts[0], max_len)

    streams = {}
    worst_pages = slots * (max_len // page_size) + 1
    for layout in ("contiguous", "paged"):
        eng = Engine(params, cfg, EngineConfig(
            max_slots=slots, max_len=max_len, max_new_tokens=new_tokens,
            temperature=0.0, eos_id=-1, seed=SEED, attn_impl="flash",
            cache_layout=layout, page_size=page_size,
            num_pages=worst_pages if layout == "paged" else None))
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=p))
        t0 = time.perf_counter()
        done = sorted(eng.run_to_completion(), key=lambda r: r.rid)
        dt = time.perf_counter() - t0
        eng.audit()
        reasons = {r.finish_reason for r in done}
        if len(done) != slots or reasons != {"length_budget"}:
            raise AssertionError(f"{layout}: finishes {reasons}, "
                                 f"{len(done)}/{slots} done")
        if any(len(r.output) != new_tokens for r in done):
            raise AssertionError(f"{layout}: short outputs")
        rec = eng.stats.recoveries()
        if rec:
            raise AssertionError(f"{layout}: engine recovered from {rec}")
        streams[layout] = [list(r.output) for r in done]
        print(f"  serve {layout}: {slots} requests x {new_tokens} tokens, "
              f"all length_budget, no recoveries ({dt:.1f}s incl. "
              f"compiles)", flush=True)
    if streams["contiguous"] != streams["paged"]:
        raise AssertionError("contiguous and paged token streams differ")
    print("  serve: contiguous and paged token streams identical",
          flush=True)


def sharded_phase(chips=4, n=1 << 30):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import scan as scanlib
    from repro.dist.sharding import make_mesh

    mesh = make_mesh((chips,), ("d",))
    sh = NamedSharding(mesh, P("d"))
    x = jax.jit(lambda k: jax.random.bernoulli(k, 0.5, (n,)).astype(
        jnp.int32), out_shardings=sh)(jax.random.PRNGKey(SEED))
    ref = np.cumsum(np.asarray(x), dtype=np.int64).astype(np.int32)
    for ex in ("all_gather", "hillis_permute", "ring"):
        # scan_sharded's default per-chip scan is the blocked jnp scan,
        # so this program holds no Pallas kernel
        fn = jax.jit(lambda v, ex=ex: scanlib.scan_sharded(
            v, "sum", mesh=mesh, axis_name="d", spec=P("d"),
            carry_exchange=ex), in_shardings=sh, out_shardings=sh)
        y = jax.block_until_ready(fn(x))
        devs = {s.device for s in y.addressable_shards}
        if len(y.sharding.device_set) != chips or len(devs) != chips:
            raise AssertionError(
                f"{ex}: output spans {len(devs)} devices, not {chips}")
        _exact(f"scan_sharded {ex} n={n} on {len(devs)} devices",
               np.asarray(y), ref)


def _phase(name, fn, *args):
    t0 = time.perf_counter()
    c0 = _COMPILES[0]
    print(f"phase {name}:", flush=True)
    fn(*args)
    print(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s wall "
          f"(set-up and compiles included), {_COMPILES[0] - c0} compiles",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded scan across four chips")
    args = ap.parse_args(argv)

    device = device_phase(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    _count_compiles()
    if args.chips == 4:
        _phase("sharded scan", sharded_phase, 4)
    else:
        _phase("scan", scan_phase)
        _phase("serve", serve_phase)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

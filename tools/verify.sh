#!/usr/bin/env bash
# Repo verification: import-smoke every repro.* module, dry-run the
# benchmark harness + relational example, then the tier-1 suite
# (ROADMAP.md). The smokes catch collection-time breakage —
# ModuleNotFoundError / API drift in rarely-imported launch modules,
# rotted benchmark/example entry points — in seconds, before the
# multi-minute test run.
#
#   tools/verify.sh            # smoke + bench dry-run + example + tier-1
#   tools/verify.sh --smoke    # import smoke only
#   tools/verify.sh --fast     # everything, but tier-1 runs -m "not slow"
#                              # (skips the exhaustive grad sweeps)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== import smoke: every repro.* module =="
python - <<'EOF'
import importlib, pkgutil, sys, traceback

import repro  # noqa: F401  (src on PYTHONPATH)

failed = []
mods = ["repro"]
for m in pkgutil.walk_packages(repro.__path__, prefix="repro."):
    mods.append(m.name)
for name in mods:
    if name == "repro.launch.dryrun":
        continue  # sets XLA_FLAGS for 512 host devices on import
    try:
        importlib.import_module(name)
    except Exception:
        failed.append(name)
        traceback.print_exc()
print(f"imported {len(mods) - len(failed)}/{len(mods)} modules")
# dryrun gets a subprocess so its XLA_FLAGS mutation can't leak here;
# the child stays on the CPU, since this process may hold the chip
import subprocess
r = subprocess.run(
    [sys.executable, "-c", "import repro.launch.dryrun"],
    env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
         "JAX_PLATFORMS": "cpu"})
if r.returncode:
    failed.append("repro.launch.dryrun")
if failed:
    print("FAILED imports:", failed)
    sys.exit(1)
EOF

if [[ "${1:-}" == "--smoke" ]]; then
    exit 0
fi

echo "== scan-engine smoke: schedule x monoid bit-parity =="
python - <<'EOF'
import numpy as np
import jax.numpy as jnp
from repro.kernels.compact import ops as kc
from repro.kernels.scan_blocked import ops as sb
from repro.kernels.segscan import ops as seg
from repro.kernels.ssm_scan import ops as ssm

rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((2, 1024)), jnp.float32)
f = jnp.asarray(rng.random((2, 1024)) < 0.02, jnp.int32)
a = jnp.asarray(rng.uniform(0.8, 1.0, (1, 256, 128)), jnp.float32)
m = jnp.asarray(rng.random((2, 1024)) < 0.5, jnp.int32)
cells = {
    "sum": lambda s: (sb.cumsum(x, interpret=True, schedule=s,
                                block_n=256),),
    "segmented": lambda s: (seg.segmented_cumsum(x, f, interpret=True,
                                                 schedule=s, block_n=256),),
    "affine": lambda s: (ssm.ssm_scan(a, x[:1, :256, None] * a, block_t=64,
                                      interpret=True, schedule=s),),
    "mask": lambda s: kc.mask_compact(m, interpret=True, schedule=s,
                                      block_n=256),
}
for name, fn in cells.items():
    outs = [fn(s) for s in ("carry", "decoupled")]
    ok = all(all(bool(jnp.all(p == q)) for p, q in zip(outs[0], o))
             for o in outs[1:])
    assert ok, f"{name}: schedules diverged"
    print(f"  {name}: carry == decoupled (bitwise)")

# The tree schedule associates differently, so its bitwise bar is
# exact data: integers (and the mask monoid, which is integral).
xi = jnp.asarray(rng.integers(-9, 9, (2, 1024)), jnp.int32)
tree_cells = {
    "sum/int": lambda s: (sb.cumsum(xi, interpret=True, schedule=s,
                                    block_n=256),),
    "segmented/int": lambda s: (seg.segmented_cumsum(
        xi.astype(jnp.float32), f, interpret=True, schedule=s,
        block_n=256),),
    "mask": lambda s: kc.mask_compact(m, interpret=True, schedule=s,
                                      block_n=256),
}
for name, fn in tree_cells.items():
    outs = [fn(s) for s in ("carry", "tree")]
    ok = all(bool(jnp.all(p == q)) for p, q in zip(*outs))
    assert ok, f"{name}: tree diverged from carry on exact data"
    print(f"  {name}: tree == carry (bitwise on exact data)")
EOF

echo "== scan-backward smoke: grad(ssm_scan) as an engine fold =="
python - <<'EOF'
import jax
import jax.numpy as jnp
import numpy as np
from repro.core.scan import reference
from repro.kernels.ssm_scan import ops as ssm

rng = np.random.default_rng(4)
a = jnp.asarray(rng.uniform(0.6, 1.0, (1, 256, 16)), jnp.float32)
b = jnp.asarray(rng.standard_normal((1, 256, 16)), jnp.float32)

def loss_k(a, b):
    return jnp.sum(ssm.ssm_scan(a, b, interpret=True) ** 2)

def loss_r(a, b):
    return jnp.sum(reference.scan_ref((a, b), "affine", axis=1)[1] ** 2)

got = jax.grad(loss_k, argnums=(0, 1))(a, b)
want = jax.grad(loss_r, argnums=(0, 1))(a, b)
err = max(float(jnp.max(jnp.abs(p - q))) for p, q in zip(got, want))
assert err < 1e-4, f"ssm backward: {err} off reference autodiff"
print(f"  da/db: max|err| vs jax.grad(scan_ref) = {err:.2e}")
EOF

echo "== flash-attention smoke: engine fold schedules vs dense oracle =="
python - <<'EOF'
import numpy as np
import jax.numpy as jnp
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref

rng = np.random.default_rng(1)
B, Hkv, g, T, D = 1, 2, 2, 256, 32
q = jnp.asarray(rng.standard_normal((B, Hkv * g, T, D)), jnp.float32)
k = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)
v = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)
ref = fa_ref.mha_ref(
    q.reshape(B * Hkv * g, T, D), k.reshape(B * Hkv, T, D),
    v.reshape(B * Hkv, T, D), group=g, scale=D ** -0.5,
).reshape(q.shape)
for s in ("carry", "decoupled"):
    got = fa_ops.flash_attention(q, k, v, scale=D ** -0.5, schedule=s,
                                 interpret=True)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err < 2e-3, f"flash {s}: {err} off the dense oracle"
    print(f"  softmax_pair/{s}: max|err| vs dense = {err:.2e}")
EOF

echo "== flash-backward smoke: engine grads vs autodiff blockwise =="
python - <<'EOF'
import jax
import jax.numpy as jnp
import numpy as np
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref

rng = np.random.default_rng(2)
B, Hkv, g, T, D = 1, 2, 2, 128, 16
q = jnp.asarray(rng.standard_normal((B, Hkv * g, T, D)), jnp.float32)
k = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)
v = jnp.asarray(rng.standard_normal((B, Hkv, T, D)), jnp.float32)

def ref_loss(q, k, v):
    o = fa_ref.blockwise_ref(
        q.reshape(B * Hkv * g, T, D), k.reshape(B * Hkv, T, D),
        v.reshape(B * Hkv, T, D), group=g, scale=D ** -0.5, block_k=64)
    return jnp.sum(o ** 2)

want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
for s in ("carry", "decoupled"):
    def loss(q, k, v, s=s):
        return jnp.sum(fa_ops.flash_attention(
            q, k, v, scale=D ** -0.5, schedule=s, interpret=True) ** 2)
    got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want))
    assert err < 1e-4, f"flash bwd {s}: {err} off autodiff blockwise"
    print(f"  dq/dk/dv {s}: max|err| vs jax.grad(blockwise_ref) = {err:.2e}")
EOF

echo "== causal-bound smoke: bitwise identity + fewer cells =="
python - <<'EOF'
import jax.numpy as jnp
import numpy as np
from repro.kernels import scan_engine
from repro.kernels.flash_attention.flash_attention import (
    flash_attention_kernel)

rng = np.random.default_rng(3)
T, D, b = 512, 16, 64
q = jnp.asarray(rng.standard_normal((2, T, D)), jnp.float32)
k = jnp.asarray(rng.standard_normal((2, T, D)), jnp.float32)
v = jnp.asarray(rng.standard_normal((2, T, D)), jnp.float32)
kw = dict(scale=D ** -0.5, causal=True, block_q=b, block_k=b,
          interpret=True)
on, counts = flash_attention_kernel(q, k, v, count_cells=True, **kw)
off = flash_attention_kernel(q, k, v, use_kv_bounds=False, **kw)
assert bool(jnp.all(on == off)), "KV bound changed bits"
n = T // b
lay = scan_engine.KVBlocks(bh=2, bh_kv=2, tq=T, tk=T, d=D, bq=b, bk=b,
                           kv_bounds=(True, None, T))
assert int(counts.sum()) == 2 * lay.active_cells() < 2 * n * n
print(f"  causal prefill: bitwise identical, "
      f"{int(counts.sum())}/{2 * n * n} cells executed")
EOF

# The full benchmark dry-run below also runs the attention suite via
# run.py; this standalone call additionally exercises fig_attention's
# own CLI entry point (__main__ + --dry-run flag parsing).
echo "== attention benchmark dry-run smoke =="
python -m benchmarks.fig_attention --dry-run

echo "== benchmark dry-run smoke + bench trajectory gate =="
python -m benchmarks.run --dry-run --json /tmp/bench.json
python -m tools.bench_gate --check-schema /tmp/bench.json BENCH_*.json
# Smoke-sized timings gate loosely (CI wall-clock noise); structure,
# parity strings, bytes/flops, and error-vs-oracle gate tight.
python -m tools.bench_gate --fresh /tmp/bench.json --baseline-dir . \
    --time-tol 3.0

echo "== trace-export smoke: serve run -> Chrome trace_event JSON =="
python -m repro.launch.serve --arch stablelm-12b --smoke --requests 3 \
    --max-new-tokens 4 --temperature 0 --attn-impl flash \
    --trace /tmp/serve_trace.json --stats-json > /tmp/serve_out.txt
python - <<'EOF'
import json
doc = json.load(open("/tmp/serve_trace.json"))
evs = doc["traceEvents"]
assert any(e["name"] == "serve.step" and e["ph"] == "X" for e in evs)
assert any(e["name"] == "serve.request.finish" for e in evs)
assert any(e["name"].startswith("policy.") for e in evs)
line = [l for l in open("/tmp/serve_out.txt")
        if l.startswith("stats-json: ")][0]
parsed = json.loads(line[len("stats-json: "):])
assert parsed["stats"]["total_finished"] == 3
print(f"  {len(evs)} events ({sorted({e['name'] for e in evs})})")
EOF

echo "== examples smoke: relational query plan =="
python examples/table_queries.py

echo "== serve-chaos smoke: no request lost under seeded injection =="
python - <<'EOF'
import dataclasses, warnings
import jax, numpy as np
from repro import configs
from repro.serve import Engine, EngineConfig, FaultInjector, Request
from repro.train.step import init_params

cfg = dataclasses.replace(configs.get_smoke_config("stablelm-12b"),
                          dtype="float32")
params = init_params(jax.random.PRNGKey(0), cfg)
inj = FaultInjector.from_seed(3, ticks=40, p_error=0.15, p_nan=0.15,
                              p_stall=0.05, stall_s=0.002, poison_rids=[4])
eng = Engine(params, cfg, EngineConfig(
    max_slots=2, max_len=48, max_new_tokens=5, eos_id=-1,
    temperature=0.0), injector=inj)
rng = np.random.default_rng(7)
n = 6
for rid in range(n):
    eng.submit(Request(rid=rid, prompt=rng.integers(
        2, 500, size=int(rng.integers(3, 9))).astype(np.int32)))
with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    done = eng.run_to_completion()
eng.audit()  # raises on lost/duplicated rids or invalid finish reasons
assert sorted(r.rid for r in done) == list(range(n)), "request lost"
reasons = {r.rid: r.finish_reason for r in done}
assert reasons[4] == "error", f"poison not quarantined: {reasons}"
print(f"  {n} requests -> {reasons}")
print(f"  {eng.stats.summary()}")
EOF

echo "== paged-serve smoke: prefix-sum allocator end to end =="
python - <<'EOF'
import dataclasses, warnings
import jax, numpy as np
from repro import configs
from repro.serve import Engine, EngineConfig, Request
from repro.train.step import init_params

cfg = dataclasses.replace(configs.get_smoke_config("stablelm-12b"),
                          dtype="float32")
params = init_params(jax.random.PRNGKey(0), cfg)
eng = Engine(params, cfg, EngineConfig(
    max_slots=2, max_len=48, max_new_tokens=5, eos_id=-1,
    temperature=0.0, cache_layout="paged", page_size=8))
rng = np.random.default_rng(7)
n = 3
for rid in range(n):
    eng.submit(Request(rid=rid, prompt=rng.integers(
        2, 500, size=int(rng.integers(3, 9))).astype(np.int32)))
with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    done = eng.run_to_completion()
eng.audit()  # raises on lost/duplicated rids or invalid finish reasons
assert sorted(r.rid for r in done) == list(range(n)), "request lost"
assert all(r.output for r in done), "empty output"
assert eng.stats.page_allocs > 0, "allocator never exercised"
assert eng.allocator.in_use == 0, "pages leaked after drain"
print(f"  {n} requests on {eng.allocator.num_pages} pages "
      f"(page_size={eng.ecfg.page_size})")
print(f"  {eng.stats.summary()}")
EOF

echo "== shared-prefix smoke: COW page sharing saves allocations =="
python - <<'EOF'
import dataclasses, warnings
import jax, numpy as np
from repro import configs
from repro.serve import Engine, EngineConfig, Request
from repro.train.step import init_params

cfg = dataclasses.replace(configs.get_smoke_config("stablelm-12b"),
                          dtype="float32")
params = init_params(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(7)
system = rng.integers(2, 500, size=16).astype(np.int32)  # 2 full pages
prompts = [np.concatenate([system, rng.integers(2, 500, size=3)
                           .astype(np.int32)]) for _ in range(2)]

def drive(share):
    eng = Engine(params, cfg, EngineConfig(
        max_slots=2, max_len=48, max_new_tokens=5, eos_id=-1,
        temperature=0.0, cache_layout="paged", page_size=8,
        share_prefixes=share))
    for rid, p in enumerate(prompts):
        eng.submit(Request(rid=rid, prompt=p))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        done = eng.run_to_completion()
    eng.audit()
    return {r.rid: list(r.output) for r in done}, eng.stats

out_u, st_u = drive(False)
out_s, st_s = drive(True)
assert out_s == out_u, "sharing changed token streams"
assert st_s.page_allocs < st_u.page_allocs, (
    f"sharing saved nothing: {st_s.page_allocs} vs {st_u.page_allocs}")
assert st_s.prefix_hits >= 1 and st_s.shared_page_maps >= 2
print(f"  2 requests, common 16-token system prompt: page_allocs "
      f"{st_u.page_allocs} -> {st_s.page_allocs}, "
      f"prefix_hits={st_s.prefix_hits}, "
      f"shared_page_maps={st_s.shared_page_maps}")
print(f"  {st_s.summary()}")
EOF

echo "== tier-1 tests =="
if [[ "${1:-}" == "--fast" ]]; then
    # Exhaustive sweeps (large-shape grad walls) are marked slow; the
    # canonical tier-1 run (ROADMAP.md) executes everything.
    python -m pytest -x -q -m "not slow"
else
    python -m pytest -x -q
fi

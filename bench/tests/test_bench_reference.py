"""The plain float32 granite reference against the program at smoke
width: prefill logits and decode logits through the KV cache, with the
configuration's dropless ``capacity_factor`` of 4.0."""

import copy
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench.drivers import serve_open_loop as drv

# Same equations in float32 on both sides; they differ only in the order
# of summation (flash's online softmax, blocked products, the routed
# scatter), which moves logits of this size by about 1e-6. 1e-4 of the
# logit scale holds that and fails any change to the equations.
RTOL = 1e-4


def smoke_conf(dtype="float32"):
    conf = copy.deepcopy(spec.load_json(os.path.join(
        spec.BENCH_DIR, "configs", "granite-moe-1b-a400m.json")))
    conf["model"].update(num_layers=2, d_model=64, num_heads=4,
                         num_kv_heads=2, head_dim=16, d_ff=32, moe_d_ff=32,
                         vocab_size=256, num_experts=8, top_k=2,
                         capacity_factor=4.0, max_seq_len=128, dtype=dtype)
    conf["engine"].update(max_slots=3, max_len=128)
    conf["check"] = {"tokens": 60, "pad_to": 64}
    return conf


def test_reference_matches_prefill_and_decode_through_the_cache():
    from repro.serve.steps import make_prefill_fn, make_serve_step
    conf = smoke_conf()
    cfg = drv.model_config(conf)
    w = drv.make_weights(conf, 3)
    drv._check_layout(w, cfg)
    ref = drv.reference(conf)
    prompt = np.random.default_rng(0).integers(0, 256, 20).astype(np.int32)
    logits, cache = jax.jit(make_prefill_fn(cfg, 64, attn_impl="flash"))(
        w, jnp.asarray(prompt)[None])
    step = jax.jit(make_serve_step(cfg))
    got, toks = [np.asarray(logits[0])], []
    for j in range(6):
        toks.append(int(np.argmax(got[-1])))
        lg, cache = step(w, jnp.asarray([[toks[-1]]], jnp.int32), cache,
                         jnp.asarray([prompt.size + j], jnp.int32))
        got.append(np.asarray(lg[0]))
    seq = np.concatenate([prompt, toks]).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(w, jnp.asarray(seq), conf["model"]))
    want = want[prompt.size - 1:]
    scale = max(1.0, float(np.abs(want).max()))
    assert np.abs(np.stack(got) - want).max() <= RTOL * scale


def test_served_tokens_are_the_references_best():
    from repro.serve import Engine, EngineConfig, Request
    conf = smoke_conf()
    cfg = drv.model_config(conf)
    w = drv.make_weights(conf, 4)
    eng = Engine(w, cfg, EngineConfig(**conf["engine"]))
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, max_new_tokens=5 + i,
                    prompt=rng.integers(0, 256, n).astype(np.int32))
            for i, n in enumerate((9, 30, 17, 24))]
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    ref = drv.reference(conf)
    for r in reqs:
        g = ref.served_gaps(w, conf["model"], r.prompt,
                            np.asarray(r.output, np.int32), 64)
        assert g.size == r.max_new_tokens
        assert g.max() <= RTOL


def test_reference_depends_on_routing_and_drops_nothing():
    """With one expert per token and capacity for every token, the
    reference equals a hand-written top-1 layer; with the router's
    choice moved, the logits move."""
    conf = smoke_conf()
    m = dict(conf["model"], top_k=1)
    ref = drv.reference(conf)
    w = jax.jit(lambda k: ref.init_weights(k, m, jnp.float32))(
        jax.random.PRNGKey(0))
    toks = jnp.arange(10, dtype=jnp.int32)
    base = ref.logits(w, toks, m)
    w2 = jax.tree.map(lambda a: a, w)
    w2["blocks"]["p0_moe"]["moe"]["router"] = -w["blocks"]["p0_moe"]["moe"][
        "router"]
    assert np.abs(np.asarray(ref.logits(w2, toks, m) - base)).max() > 1e-3

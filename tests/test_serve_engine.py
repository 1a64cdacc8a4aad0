"""Serving engine: continuous batching, slot compaction, sampling."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.serve import Engine, EngineConfig, Request
from repro.train.step import init_params


@pytest.fixture(scope="module")
def small_model():
    cfg = configs.get_smoke_config("stablelm-12b")
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_more_requests_than_slots(small_model):
    cfg, params = small_model
    eng = Engine(params, cfg, EngineConfig(
        max_slots=2, max_len=48, max_new_tokens=5, eos_id=-1))
    for rid in range(5):
        eng.submit(Request(rid=rid, prompt=np.arange(3, dtype=np.int32)))
    done = eng.run_to_completion()
    assert len(done) == 5
    assert all(len(r.output) == 5 for r in done)  # max_new_tokens total


def test_engine_greedy_matches_direct_decode(small_model):
    """Engine output == hand-rolled prefill+decode loop (greedy)."""
    cfg, params = small_model
    from repro.models import lm
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    n_new = 6

    logits, cache = lm.prefill(params, jnp.asarray(prompt)[None], cfg,
                               max_len=32)
    want = [int(jnp.argmax(logits[0]))]
    tok = jnp.asarray([[want[-1]]], jnp.int32)
    pos = len(prompt)
    for _ in range(n_new - 1):
        logits, cache = lm.decode_step(params, tok, cache,
                                       jnp.asarray(pos, jnp.int32), cfg)
        want.append(int(jnp.argmax(logits[0])))
        tok = jnp.asarray([[want[-1]]], jnp.int32)
        pos += 1

    eng = Engine(params, cfg, EngineConfig(
        max_slots=1, max_len=32, max_new_tokens=n_new, temperature=0.0,
        eos_id=-1))
    eng.submit(Request(rid=0, prompt=prompt))
    done = eng.run_to_completion()
    assert done[0].output == want


def test_eos_frees_slot(small_model):
    cfg, params = small_model
    eng = Engine(params, cfg, EngineConfig(
        max_slots=1, max_len=64, max_new_tokens=50, temperature=0.0))
    # figure out the greedy first token, then make IT the eos id so the
    # request finishes immediately and the slot frees for the next one.
    probe = Engine(params, cfg, EngineConfig(
        max_slots=1, max_len=64, max_new_tokens=1, temperature=0.0,
        eos_id=-1))
    probe.submit(Request(rid=0, prompt=np.asarray([1, 2, 3], np.int32)))
    first = probe.run_to_completion()[0].output[0]

    eng = Engine(params, cfg, EngineConfig(
        max_slots=1, max_len=64, max_new_tokens=50, temperature=0.0,
        eos_id=first))
    eng.submit(Request(rid=0, prompt=np.asarray([1, 2, 3], np.int32)))
    eng.submit(Request(rid=1, prompt=np.asarray([1, 2, 3], np.int32)))
    done = eng.run_to_completion()
    assert len(done) == 2
    assert all(r.output[-1] == first for r in done)


def test_out_of_cache_surfaces_as_cache_full(small_model):
    """Regression (ISSUE 6 satellite): a sequence running out of KV cache
    before its token budget used to finish indistinguishably from EOS —
    it must now carry finish_reason="cache_full" and warn."""
    cfg, params = small_model
    eng = Engine(params, cfg, EngineConfig(
        max_slots=1, max_len=12, max_new_tokens=20, eos_id=-1,
        temperature=0.0, strict_admission=False))
    eng.submit(Request(rid=0, prompt=np.arange(2, 8, dtype=np.int32)))
    with pytest.warns(RuntimeWarning, match="cache_full"):
        done = eng.run_to_completion()
    assert done[0].finish_reason == "cache_full"
    # prefill token + decode up to the cache edge, short of the budget
    assert 0 < len(done[0].output) < 20
    assert eng.stats.finished["cache_full"] == 1


def test_run_to_completion_deadline_vs_strict(small_model):
    """Regression (ISSUE 6 satellite): exhausting max_ticks used to
    silently return with requests still waiting/active."""
    cfg, params = small_model
    def fresh():
        eng = Engine(params, cfg, EngineConfig(
            max_slots=1, max_len=48, max_new_tokens=10, eos_id=-1))
        for rid in range(3):
            eng.submit(Request(rid=rid, prompt=np.arange(3, dtype=np.int32)))
        return eng

    done = fresh().run_to_completion(max_ticks=2)
    reasons = sorted(r.finish_reason for r in done)
    assert len(done) == 3 and "deadline" in reasons  # survivors marked

    from repro.serve import EngineDeadlineError
    with pytest.raises(EngineDeadlineError):
        fresh().run_to_completion(max_ticks=2, strict=True)


def test_free_slot_compaction_ranks(small_model):
    cfg, params = small_model
    eng = Engine(params, cfg, EngineConfig(max_slots=4, max_len=32))
    eng.slot_req = [None, Request(rid=0, prompt=np.zeros(1)), None, None]
    free_idx, ranks = eng._free_slots()
    np.testing.assert_array_equal(free_idx, [0, 2, 3])
    # exclusive prefix sum of the free bitmap = compacted ranks
    np.testing.assert_array_equal(np.asarray(ranks), [0, 1, 1, 2])


def test_ssm_decode_resolves_parallel_schedule():
    """The serve engine's SSM decode/prefill class — B=1 slot, long
    sequence — must land on a parallel-sequence schedule end to end: the
    engine prefills one request at a time (B=1), and ``apply_ssm`` routes
    the cache path through ``ssm_scan(schedule="auto")``."""
    from repro.kernels.ssm_scan import ops as ssm_ops
    # decode/prefill class: one sequence, long time axis, one channel block
    assert ssm_ops.resolved_schedule((1, 1 << 22, 256)) == "decoupled"
    # training class: many (batch, channel-block) stripes -> carry chain
    assert ssm_ops.resolved_schedule((8, 4096, 4096)) == "carry"


def test_ssm_engine_end_to_end():
    """A hybrid-SSM model served end to end through ``impl="auto"`` (on
    TPU this is the kernel route; off-TPU the gate keeps the reference
    scan — either way the serve path must run)."""
    cfg = configs.get_smoke_config("zamba2-7b")
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, EngineConfig(
        max_slots=1, max_len=48, max_new_tokens=4, eos_id=-1))
    eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32)))
    done = eng.run_to_completion()
    assert len(done) == 1 and len(done[0].output) == 4


def test_ssm_serve_kernel_route_matches_reference():
    """The serve configuration's kernel route (what ``impl="auto"`` picks
    on TPU): prefill-with-cache through the Pallas affine scan must match
    the chunked reference path."""
    from repro.models.layers.ssm import apply_ssm, init_ssm, init_ssm_cache
    cfg = configs.get_smoke_config("zamba2-7b")
    cfg = dataclasses.replace(cfg, dtype="float32")
    params = init_ssm(jax.random.PRNGKey(0), cfg)
    cache = init_ssm_cache(cfg, batch=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 33, cfg.d_model))
    y_k, c_k = apply_ssm(params, x, cfg, cache=cache, impl="kernel")
    y_r, c_r = apply_ssm(params, x, cfg, cache=cache, impl="chunked")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(c_k["h"]), np.asarray(c_r["h"]),
                               rtol=2e-4, atol=2e-4)


def test_encdec_serve_path():
    cfg = configs.get_smoke_config("seamless-m4t-large-v2")
    params = init_params(jax.random.PRNGKey(0), cfg)
    from repro.models import encdec
    from repro.serve.steps import make_prefill_fn, make_serve_step
    B = 2
    embeds = jax.random.normal(jax.random.PRNGKey(1), (B, 8, 1024))
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, 6), 0,
                              cfg.vocab_size)
    logits, cache, memory = make_prefill_fn(cfg, max_len=16)(
        params, toks, embeds)
    assert logits.shape == (B, cfg.vocab_size)
    step = make_serve_step(cfg)
    logits2, cache = step(params, toks[:, :1], cache,
                          jnp.asarray(6, jnp.int32), memory)
    assert bool(jnp.isfinite(logits2).all())


# ---------------------------------------------------------------------------
# engine-backed flash prefill through serve/steps.py (schedule="auto")
# ---------------------------------------------------------------------------


def test_flash_prefill_matches_default_padded_cache(small_model):
    """Prefill through ``serve/steps.py`` with attention routed onto the
    engine-backed flash fold (schedule="auto") must score like the
    default path — including the padded-KV-cache case (cache of
    ``max_len`` slots much longer than the live prefix)."""
    from repro.serve.steps import make_prefill_fn
    cfg, params = small_model
    prompt = jnp.asarray([[5, 9, 2, 7, 1, 3]], jnp.int32)  # S=6 << 32
    lg_ref, cache_ref = make_prefill_fn(cfg, max_len=32)(params, prompt)
    lg_fl, cache_fl = make_prefill_fn(
        cfg, max_len=32, attn_impl="flash", attn_schedule="auto")(
        params, prompt)
    np.testing.assert_allclose(np.asarray(lg_fl), np.asarray(lg_ref),
                               rtol=2e-4, atol=2e-4)
    for a, b in zip(jax.tree.leaves(cache_fl), jax.tree.leaves(cache_ref)):
        assert a.shape == b.shape  # same padded-cache geometry
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("schedule", ["auto", "carry", "decoupled"])
def test_flash_prefill_vs_decode_score_parity(small_model, schedule):
    """Prefill-then-decode must score the continuation exactly like a
    one-token-longer flash prefill: the engine-backed prefill cache and
    the dense decode path agree on every schedule route."""
    from repro.models import lm
    from repro.serve.steps import make_prefill_fn
    cfg, params = small_model
    toks = jnp.asarray([[5, 9, 2, 7, 1, 3, 8]], jnp.int32)
    # scores from a full flash prefill of all 7 tokens
    lg_full, _ = make_prefill_fn(
        cfg, max_len=32, attn_impl="flash", attn_schedule=schedule)(
        params, toks)
    # scores from flash prefill of 6 + dense decode of token 7
    _, cache = make_prefill_fn(
        cfg, max_len=32, attn_impl="flash", attn_schedule=schedule)(
        params, toks[:, :-1])
    lg_dec, _ = lm.decode_step(params, toks[:, -1:], cache,
                               jnp.asarray(6, jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(lg_dec), np.asarray(lg_full),
                               rtol=2e-4, atol=2e-4)


def test_engine_flash_route_greedy_parity(small_model):
    """End to end: an Engine configured to prefill on the flash fold
    generates the same greedy tokens as the default engine."""
    cfg, params = small_model
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    outs = []
    for kw in ({}, {"attn_impl": "flash", "attn_schedule": "auto"}):
        eng = Engine(params, cfg, EngineConfig(
            max_slots=1, max_len=32, max_new_tokens=5, temperature=0.0,
            eos_id=-1, **kw))
        eng.submit(Request(rid=0, prompt=prompt))
        outs.append(eng.run_to_completion()[0].output)
    assert outs[0] == outs[1]


def test_long_kv_serve_class_lands_on_split_kv():
    """The 32k/500k-context serve class — decode/scoring rows against a
    long padded cache — must resolve schedule="auto" to the split-KV
    decoupled fold, while saturated training prefill keeps carry."""
    from repro.kernels.flash_attention import resolved_attention_schedule
    # B=1 decode, 32 q-heads, 32k cache -> decoupled
    assert resolved_attention_schedule((1, 32, 1, 128), 1 << 15) \
        == "decoupled"
    # 500k-context scoring step
    assert resolved_attention_schedule((1, 8, 1, 128), 500_000) \
        == "decoupled"
    # training prefill: 8 x 32 heads x many q blocks -> carry
    assert resolved_attention_schedule((8, 32, 8192, 128), 8192) == "carry"


def test_flash_route_keeps_cached_keys_mid_stream(small_model):
    """The padded-cache flash prefill route is guarded by a runtime
    ``cache_len == 0`` cond: a multi-token continuation against a warm
    cache (cache_len > 0) must keep the dense path's cached keys, not
    silently restart attention at position 0."""
    from repro.models import lm
    cfg, params = small_model
    toks = jnp.asarray([[5, 9, 2, 7, 1, 3, 8, 4]], jnp.int32)
    # warm the cache with the first 5 tokens (default path)
    _, _, cache = lm.forward(
        params, toks[:, :5], cfg, cache=lm.init_cache(cfg, 1, 32),
        cache_len=jnp.zeros((), jnp.int32))
    # continue with a 3-token chunk: flash-routed forward must equal the
    # dense-routed forward (the cond falls back because cache_len != 0)
    outs = {}
    for impl in (None, "flash"):
        h, _, _ = lm.forward(
            params, toks[:, 5:], cfg, cache=jax.tree.map(lambda x: x, cache),
            cache_len=jnp.asarray(5, jnp.int32), attn_impl=impl)
        outs[impl] = h
    np.testing.assert_allclose(np.asarray(outs["flash"]),
                               np.asarray(outs[None]),
                               rtol=1e-5, atol=1e-5)

"""Jittable serving step functions (these are what the dry-run lowers).

``serve_step``: ONE new token for every sequence in the batch against a KV
cache of ``max_len`` slots (the decode_32k / long_500k shapes).
``prefill``: the full-prompt pass that fills the cache (prefill_32k).

Shardings: batch over ('pod','data'); cache heads over 'model' — the KV
cache is a pytree whose leaves follow PARAM-style logical rules resolved
in ``cache_shardings``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import sharding as shd
from repro.models import encdec as encdec_mod
from repro.models import lm as lm_mod
from repro.models.config import ModelConfig

Pytree = Any


def make_serve_step(cfg: ModelConfig, unroll: bool = False,
                    ssm_impl: Optional[str] = None):
    """(params, tokens (B,1), cache, cache_len) -> (logits, new_cache).

    ``cache_len`` may be a scalar or a per-row (B,) vector (heterogeneous
    pool). ``ssm_impl`` pins the SSM scan route — the engine's
    degradation ladder builds a second step with ``ssm_impl="chunked"``
    (the jnp reference) as the safe route.
    """

    if cfg.is_encdec:
        def step(params, tokens, cache, cache_len, memory):
            return encdec_mod.serve_step(
                params, tokens, memory, cache, cache_len, cfg,
                unroll=unroll)
        return step

    def step(params, tokens, cache, cache_len):
        return lm_mod.decode_step(params, tokens, cache, cache_len, cfg,
                                  ssm_impl=ssm_impl, unroll=unroll)

    return step


def make_prefill_fn(cfg: ModelConfig, max_len: int, unroll: bool = False,
                    attn_impl: Optional[str] = None,
                    attn_schedule: str = "auto",
                    ssm_impl: Optional[str] = None):
    """``attn_impl="flash"`` routes decoder-only prefill attention through
    the engine-backed flash fold (KV cache may be longer than the prompt
    — the padded-cache case); ``attn_schedule`` picks its grid
    organization (carry | decoupled | auto, see
    ``core/scan/policy.choose_attention_schedule``)."""
    if cfg.is_encdec:
        def fn(params, tokens, embeds):
            memory = encdec_mod.encode(params, embeds, cfg, unroll=unroll)
            cache = encdec_mod.init_dec_cache(cfg, tokens.shape[0], max_len)
            hidden, cache = encdec_mod.decode_forward(
                params, tokens, memory, cfg, cache=cache,
                cache_len=jnp.zeros((), jnp.int32), unroll=unroll)
            from repro.models.layers.embedding import lm_logits
            return lm_logits(params, hidden[:, -1:], cfg)[:, 0], cache, memory
        return fn

    def fn(params, tokens, embeds=None):
        logits, cache = lm_mod.prefill(
            params, tokens, cfg, max_len, embeds=embeds,
            attn_impl=attn_impl, attn_schedule=attn_schedule,
            ssm_impl=ssm_impl, unroll=unroll)
        return logits, cache

    return fn


def bucketable(cfg: ModelConfig) -> bool:
    """True when prompt padding is semantics-free for this architecture.

    Bucketing pads prompts to a power-of-two length. Trailing pads are
    harmless only for pure global-attention stacks (pad keys land past
    the causal frontier of every real token and the logits are read at
    the true last position). Recurrent layers (ssm/xlstm) would fold the
    pads into their state, MoE would burn expert capacity on them, and
    local layers would push real keys out of the ring buffer.
    """
    return (not cfg.is_encdec
            and not cfg.frontend_tokens
            and all(k == "global" for k in cfg.layer_pattern))


def bucket_len(S: int, max_len: int, floor: int = 8) -> int:
    """Next power-of-two prompt bucket: jit variants grow as log2(max_len)
    rather than one per distinct prompt length."""
    b = floor
    while b < S:
        b *= 2
    return min(b, max_len)


def make_bucketed_prefill_fn(cfg: ModelConfig, max_len: int,
                             unroll: bool = False,
                             attn_impl: Optional[str] = None,
                             attn_schedule: str = "auto",
                             ssm_impl: Optional[str] = None):
    """``(params, tokens (B, bucket), true_len ()) -> (logits, cache)``.

    Like ``make_prefill_fn`` but tokens arrive padded to a bucket length
    and ``true_len`` (traced scalar) marks the real prompt extent: last-
    token logits are sliced at ``true_len - 1`` and the returned
    engine-side cache length must be ``true_len``, not the bucket. Only
    valid when ``bucketable(cfg)`` — the caller gates on that.
    """
    if not bucketable(cfg):
        raise ValueError(
            f"bucketed prefill requires a pure global-attention decoder; "
            f"got pattern {cfg.layer_pattern!r}")

    def fn(params, tokens, true_len):
        cache = lm_mod.init_cache(cfg, tokens.shape[0], max_len)
        hidden, _, cache = lm_mod.forward(
            params, tokens, cfg, cache=cache,
            cache_len=jnp.zeros((), jnp.int32), attn_impl=attn_impl,
            attn_schedule=attn_schedule, ssm_impl=ssm_impl, unroll=unroll)
        last = jax.lax.dynamic_slice_in_dim(hidden, true_len - 1, 1, axis=1)
        from repro.models.layers.embedding import lm_logits
        return lm_logits(params, last, cfg)[:, 0], cache

    return fn


def init_cache_for(cfg: ModelConfig, batch: int, max_len: int) -> Pytree:
    if cfg.is_encdec:
        return encdec_mod.init_dec_cache(cfg, batch, max_len)
    return lm_mod.init_cache(cfg, batch, max_len)


# ---------------------------------------------------------------------------
# paged layout (serve/paging.py): pools + page table instead of slot rows
# ---------------------------------------------------------------------------


def init_paged_cache_for(cfg: ModelConfig, batch: int, max_len: int,
                         page_size: int, num_pages: int) -> Pytree:
    """Paged decode cache: ``{"layers": ..., "page_table": ...}``.

    Attention KV leaves — global AND local (sliding-window) — become
    page POOLS of shape ``(periods, num_pages, Hkv, page_size, hd)``
    shared by all slots; a local layer's O(window) ring rides the first
    ``window // page_size`` entries of its table row (the attention path
    clamps its gather there). Recurrent (ssm/xlstm) state keeps its
    slot-indexed layout unchanged. The page table is one
    ``(batch, max_len // page_size)`` int32 array shared across layers
    (vLLM-style); entry 0 is the null page.
    """
    from repro.serve.paging import paged_layer_names, validate_paged_support
    validate_paged_support(cfg, max_len, page_size)
    layers = lm_mod.init_cache(cfg, batch, max_len)
    for name in paged_layer_names(cfg):
        kv = layers[name]["kv"]
        per = kv["k"].shape[0]
        dt = kv["k"].dtype
        shape = (per, num_pages, cfg.num_kv_heads, page_size, cfg.head_dim)
        layers[name] = {"kv": {"k_pages": jnp.zeros(shape, dt),
                               "v_pages": jnp.zeros(shape, dt)}}
    return {"layers": layers,
            "page_table": jnp.zeros((batch, max_len // page_size),
                                    jnp.int32)}


def make_paged_serve_step(cfg: ModelConfig, unroll: bool = False,
                          ssm_impl: Optional[str] = None):
    """(params, tokens (B,1), paged_cache, cache_len) -> (logits, cache).

    The paged cache bundles the page table INTO the pytree so the step
    signature matches ``make_serve_step`` exactly — retries, probes,
    donation and the degradation ladder all work unchanged. Inside the
    jit the table is broadcast to each paged layer; the attention cached
    path gathers/scatters through it (see ``models/layers/attention``).
    """
    if cfg.is_encdec:
        raise ValueError("paged cache layout is decoder-only")
    from repro.serve.paging import paged_layer_names
    names = paged_layer_names(cfg)

    def step(params, tokens, cache, cache_len):
        pt = cache["page_table"]
        layers = dict(cache["layers"])
        for name in names:
            kv = dict(layers[name]["kv"])
            per = kv["k_pages"].shape[0]
            kv["pt"] = jnp.broadcast_to(pt[None], (per,) + pt.shape)
            layers[name] = {"kv": kv}
        logits, new_layers = lm_mod.decode_step(
            params, tokens, layers, cache_len, cfg, ssm_impl=ssm_impl,
            unroll=unroll)
        out_layers = {}
        for name, c in new_layers.items():
            if name in names:
                c = {"kv": {k: v for k, v in c["kv"].items() if k != "pt"}}
            out_layers[name] = c
        return logits, {"layers": out_layers, "page_table": pt}

    return step


def make_chunked_prefill_fn(cfg: ModelConfig, max_len: int,
                            unroll: bool = False,
                            attn_impl: Optional[str] = None,
                            attn_schedule: str = "auto"):
    """``(params, tokens (1, C), cache, cache_len (), true_len ()) ->
    (logits (1, V), cache)`` — ONE prompt chunk against a staging cache.

    The engine advances a long prompt one chunk per tick so decode for
    resident sequences interleaves instead of stalling behind a
    monolithic prefill. The cached attention path already handles
    mid-stream writes (``cache_len > 0`` keeps the dense cached route;
    its ``lax.cond`` guard was built for exactly this call), and with
    trailing pads in the LAST chunk masked off by ``true_len`` the
    causal mask makes chunked prefill bit-identical to one-shot dense
    prefill. Same gate as bucketing: pure global-attention stacks only
    (recurrent layers would fold pads into state).
    """
    if not bucketable(cfg):
        raise ValueError(
            f"chunked prefill requires a pure global-attention decoder; "
            f"got pattern {cfg.layer_pattern!r}")

    def fn(params, tokens, cache, cache_len, true_len):
        hidden, _, cache = lm_mod.forward(
            params, tokens, cfg, cache=cache, cache_len=cache_len,
            attn_impl=attn_impl, attn_schedule=attn_schedule,
            unroll=unroll)
        last = jax.lax.dynamic_slice_in_dim(hidden, true_len - 1, 1, axis=1)
        from repro.models.layers.embedding import lm_logits
        return lm_logits(params, last, cfg)[:, 0], cache

    return fn


_CACHE_AXES = {
    # leaf name fragment -> logical axes (cache leaves, by convention).
    # KV caches shard the SEQUENCE over 'model' (seq_shard) — kv_heads are
    # as low as 4 (qwen3) so head-sharding caps at 4-way; seq-sharding
    # always gives the full 16-way split and the softmax combine across
    # shards is the distributed online-softmax scan (DESIGN.md §3).
    "k": ("layers", "batch", None, "seq_shard", None),
    "v": ("layers", "batch", None, "seq_shard", None),
    "conv": ("layers", "batch", None, "ssm_inner"),
    # ssm: h (L,B,heads,hd,state); mlstm: S (L,B,H,dh,dh), n (L,B,H,dh);
    # slstm: h/c/n/m (L,B,H,dh)
    "h": ("layers", "batch", "heads", None, None),
    "S": ("layers", "batch", "heads", None, None),
    "c": ("layers", "batch", "heads", None),
    "n": ("layers", "batch", "heads", None),
    "m": ("layers", "batch", "heads", None),
}


def cache_shardings(cache: Pytree, mesh: Mesh) -> Pytree:
    """NamedSharding tree for a decode cache under ``mesh``."""

    def one(path_entries, leaf):
        name = str(getattr(path_entries[-1], "key", path_entries[-1]))
        axes = _CACHE_AXES.get(name)
        if axes is not None and len(axes) != leaf.ndim and leaf.ndim >= 3:
            axes = ("layers", "batch", "heads") + (None,) * (leaf.ndim - 3)
        if axes is None or len(axes) != leaf.ndim:
            axes = ("layers", "batch") + (None,) * (leaf.ndim - 2)
        if shd.current_mesh() is None:
            with shd.use_mesh(mesh):
                spec = shd.resolve(axes)
        else:  # inherit the caller's rule overrides (e.g. long_500k)
            spec = shd.resolve(axes)
        spec = shd.sanitize_spec(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, cache)

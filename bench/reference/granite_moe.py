"""Plain float32 reference of a top-k mixture-of-experts decoder, and the
seeded weights that the benchmark serves it with.

The forward follows the configuration as run (``bench/configs/
granite-moe-1b-a400m.json``): GraniteMoe's layer equations, with the
departures that file lists. Per layer, on a residual stream ``x``:

    h = rmsnorm(x) * w1                      (eps from the file)
    q, k, v = h Wq, h Wk, h Wv               (grouped-query heads)
    q, k = rope(q), rope(k)                  (NeoX half rotation)
    x = x + softmax(q k^T * head_dim^-1/2 + causal mask) v Wo
    h = rmsnorm(x) * w2
    p = softmax(h R); the top_k experts of p, their weights renormalised
    x = x + sum_e g_e * (silu(h Wg_e) * (h Wu_e)) Wd_e

then ``logits = rmsnorm(x) * wf @ E^T`` with the tied embedding ``E``;
the input is ``E[tokens] * sqrt(d_model)``. Every expert sees every token
it is routed to (no capacity, nothing dropped), and every product runs in
float32 at ``highest`` precision.

It imports nothing of the program. ``init_weights`` draws the weights
from a key in a nested-dict layout; the benchmark hands the bf16 draw to
the program, and the reference upcasts the same draw.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: The configuration's model keys the reference reads.
SIZES = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
         "num_experts", "top_k", "moe_d_ff", "vocab_size", "norm_eps",
         "rope_theta")


def _layout(m: dict, gains: dict):
    """``[(path, shape, dtype_kind, scale)]``: ``"w"`` leaves are drawn in
    the serving dtype, ``"f32"`` leaves (router) in f32, ``"norm"``
    leaves around 1 in f32. ``gains`` maps a leaf's name to a factor on
    its ``1/sqrt(fan_in)`` scale."""
    g = lambda name: gains.get(name, 1.0)
    L, d, V = m["num_layers"], m["d_model"], m["vocab_size"]
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    E, f = m["num_experts"], m["moe_d_ff"]
    blk = ("blocks", "p0_moe")
    return [
        (("embed", "table"), (V, d), "w", 0.02),
        (("final_norm", "w"), (d,), "norm", None),
        (blk + ("norm1", "w"), (L, d), "norm", None),
        (blk + ("norm2", "w"), (L, d), "norm", None),
        (blk + ("attn", "wq"), (L, d, H * hd), "w",
         g("wq") * d ** -0.5),
        (blk + ("attn", "wk"), (L, d, K * hd), "w",
         g("wk") * d ** -0.5),
        (blk + ("attn", "wv"), (L, d, K * hd), "w", d ** -0.5),
        (blk + ("attn", "wo"), (L, H * hd, d), "w",
         g("wo") * (H * hd) ** -0.5),
        (blk + ("moe", "router"), (L, d, E), "f32", d ** -0.5),
        (blk + ("moe", "w_gate"), (L, E, d, f), "w", d ** -0.5),
        (blk + ("moe", "w_up"), (L, E, d, f), "w", d ** -0.5),
        (blk + ("moe", "w_down"), (L, E, f, d), "w", g("w_down") * f ** -0.5),
    ]


def init_weights(key, m: dict, dtype=jnp.bfloat16, gains: dict = None
                 ) -> dict:
    """Seeded weights: truncated normals at ``1/sqrt(fan_in)`` times the
    leaf's factor in ``gains`` (the embedding at 0.02), norm weights
    ``1 + 0.1 N(0, 1)``. Jit it to make every leaf on the device in one
    call."""
    layout = _layout(m, gains or {})
    keys = jax.random.split(key, len(layout))
    tree: dict = {}
    for k, (path, shape, kind, scale) in zip(keys, layout):
        if kind == "norm":
            leaf = 1.0 + 0.1 * jax.random.normal(k, shape, F32)
        else:
            leaf = jax.random.truncated_normal(k, -2.0, 2.0, shape, F32) * scale
            if kind == "w":
                leaf = leaf.astype(dtype)
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return tree


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (T, heads, hd), NeoX half rotation."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None, None].astype(F32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def fp8(a, axis=None):
    """Round to float8 e4m3 with a max-abs scale (per tensor, or per slice
    along ``axis``), back in float32: the control's precision."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def logits(w: dict, tokens, m: dict, lowp: bool = False):
    """``tokens (T,) -> logits (T, V)`` in float32.

    ``lowp=True`` is the control: every tensor that the configuration
    holds in bfloat16 is rounded to float8 e4m3 instead (weights per
    matrix; the residual stream, norm outputs, projections, attention
    output and expert activations per token). What the configuration
    keeps in float32 (norm arithmetic, router, softmax, gate weights,
    logits) stays float32.
    """
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps, theta, topk = m["norm_eps"], m["rope_theta"], m["top_k"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    r = (lambda a: fp8(a, axis=-1)) if lowp else (lambda a: a)
    rw = fp8 if lowp else (lambda a: a)
    table = rw(w["embed"]["table"].astype(F32))
    x = r(table[tokens] * math.sqrt(m["d_model"]))
    causal = pos[None, :] <= pos[:, None]

    def layer(x, lw):
        a, e = lw["attn"], lw["moe"]
        h = r(_rms(x, lw["norm1"]["w"], eps))
        q = r(h @ rw(a["wq"].astype(F32))).reshape(T, H, hd)
        k = r(h @ rw(a["wk"].astype(F32))).reshape(T, K, hd)
        v = r(h @ rw(a["wv"].astype(F32))).reshape(T, K, hd)
        q, k = r(_rope(q, pos, theta)), r(_rope(k, pos, theta))
        k = jnp.repeat(k, H // K, axis=1)
        v = jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), -1)
        o = r(jnp.einsum("hqk,khd->qhd", p, v).reshape(T, H * hd))
        x = r(x + r(o @ rw(a["wo"].astype(F32))))
        h = r(_rms(x, lw["norm2"]["w"], eps))
        probs = jax.nn.softmax(h @ e["router"], -1)
        top, idx = jax.lax.top_k(probs, topk)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(T)[:, None], idx].set(top / top.sum(-1, keepdims=True))
        wg = jax.vmap(rw)(e["w_gate"].astype(F32))
        wu = jax.vmap(rw)(e["w_up"].astype(F32))
        wd = jax.vmap(rw)(e["w_down"].astype(F32))
        act = r(jax.nn.silu(r(jnp.einsum("td,edf->tef", h, wg)))
                * r(jnp.einsum("td,edf->tef", h, wu)))
        y = r(jnp.einsum("tef,efd->ted", act, wd))
        return r(x + r(jnp.einsum("ted,te->td", y, gates))), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["p0_moe"])
    x = r(_rms(x, w["final_norm"]["w"], eps))
    return x @ table.T


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _gaps(w, tokens, targets, m, lowp):
    """Per position: how far the reference logit of ``targets`` lies below
    the reference's best, and (``lowp``) of the control's own choice."""
    with jax.default_matmul_precision("highest"):
        ref = logits(w, tokens, dict(m))
        best = ref.max(-1)
        pick = targets
        if lowp:
            pick = jnp.argmax(logits(w, tokens, dict(m), lowp=True), -1)
    return best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]


def served_gaps(w: dict, m: dict, prompt: np.ndarray, output: np.ndarray,
                pad_to: int, control: bool = False) -> np.ndarray:
    """The gap of each served token of one request (``control``: of the
    token the float8 control puts first at the same position).

    The request's prompt and served tokens run through the reference
    once, padded at the end to ``pad_to`` positions (causal, so the pad
    changes nothing before it) to keep one compiled shape.
    """
    seq = np.concatenate([prompt, output[:-1]]).astype(np.int32)
    n = seq.size
    if n > pad_to:
        raise ValueError(f"request of {n} positions exceeds pad_to={pad_to}")
    toks = np.zeros(pad_to, np.int32)
    toks[:n] = seq
    tg = np.zeros(pad_to, np.int32)
    tg[prompt.size - 1: n] = output
    g = _gaps(w, jnp.asarray(toks), jnp.asarray(tg),
              tuple((k, m[k]) for k in SIZES), control)
    return np.asarray(g)[prompt.size - 1: n]

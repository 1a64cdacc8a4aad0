"""Jitted train step: loss -> grads -> AdamW, sharded over the mesh.

Distribution features (DESIGN.md §5):
  * DP over ('pod','data'); TP/EP over 'model' — all via the logical-axis
    tables in ``repro.dist.sharding`` (params + activations).
  * Gradient **accumulation** over microbatches: ``lax.scan`` over a
    leading micro axis, f32 grad accumulator, single optimizer apply.
  * **Remat** (activation checkpointing): configurable policy on the
    layer-scan body; "nothing_saveable" minimizes live memory, "dots"
    keeps matmul outputs (less recompute — the §Perf iteration toggles
    this).
  * **Buffer donation**: params/opt-state donated (in-place update, the
    paper's in-place variant at the XLA level).
  * **Cross-pod gradient compression** lives in
    ``repro.optim.compression.compressed_psum`` (int8 + error feedback)
    for manual-DP (shard_map) deployments where the slow inter-pod hop is
    compressed and the in-pod reduce-scatter stays full precision; the
    default pjit path leaves the hierarchical reduction to XLA (see
    DESIGN.md §5 — measured trade-off in EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.data.pipeline import make_batch_specs
from repro.dist import sharding as shd
from repro.models import encdec as encdec_mod
from repro.models import lm as lm_mod
from repro.models.config import ModelConfig
from repro.optim import adamw
from repro.optim.schedule import cosine_schedule

Pytree = Any


@dataclasses.dataclass(frozen=True)
class TrainStepConfig:
    microbatches: int = 1
    remat: bool = True
    # None = auto (dense<=4k, blockwise); "flash" trains on the engine
    # kernel — its custom_vjp runs the backward as scan-engine folds, so
    # dense, blockwise and flash are grad-parity-checkable peers.
    attn_impl: Optional[str] = None
    attn_schedule: str = "auto"       # flash fold organization
    # None = auto (chunked reference when training); "kernel" trains SSM
    # layers on the engine's affine kernel — its custom_vjp runs the
    # backward as one more engine scan, mirroring attn_impl="flash".
    ssm_impl: Optional[str] = None
    unroll_layers: bool = False       # dry-run: full cost in the HLO
    loss_chunk: int = 512
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    grad_clip: float = 1.0
    weight_decay: float = 0.1
    compress_cross_pod: bool = False


def loss_fn_for(cfg: ModelConfig) -> Callable:
    if cfg.is_encdec:
        return encdec_mod.encdec_loss
    return lm_mod.lm_loss


def init_params(key, cfg: ModelConfig) -> Pytree:
    if cfg.is_encdec:
        return encdec_mod.init_encdec(key, cfg)
    return lm_mod.init_lm(key, cfg)


def _accumulate_grads(loss_fn, params, batch, tcfg: TrainStepConfig,
                      cfg: ModelConfig):
    """Microbatched value_and_grad with an f32 accumulator."""
    m = tcfg.microbatches
    if m <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, remat=tcfg.remat,
                              loss_chunk=tcfg.loss_chunk,
                              attn_impl=tcfg.attn_impl,
                              attn_schedule=tcfg.attn_schedule,
                              ssm_impl=tcfg.ssm_impl,
                              unroll=tcfg.unroll_layers),
            has_aux=True)(params)
        return loss, metrics, grads

    def reshape(x):
        B = x.shape[0]
        return x.reshape((m, B // m) + x.shape[1:])

    micro = jax.tree.map(reshape, dict(batch))
    gz = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, mb):
        gacc, lacc, macc = carry
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(p, mb, cfg, remat=tcfg.remat,
                              loss_chunk=tcfg.loss_chunk,
                              attn_impl=tcfg.attn_impl,
                              attn_schedule=tcfg.attn_schedule,
                              ssm_impl=tcfg.ssm_impl,
                              unroll=tcfg.unroll_layers),
            has_aux=True)(params)
        gacc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32) / m, gacc, grads)
        macc = jax.tree.map(lambda a, v: a + v / m, macc, metrics)
        return (gacc, lacc + loss / m, macc), None

    metrics0 = jax.tree.map(
        lambda _: jnp.zeros((), jnp.float32),
        jax.eval_shape(lambda: loss_fn(params, jax.tree.map(
            lambda x: x[0], micro), cfg, remat=False,
            loss_chunk=tcfg.loss_chunk)[1]))
    (grads, loss, metrics), _ = jax.lax.scan(
        body, (gz, jnp.zeros((), jnp.float32), metrics0), micro)
    return loss, metrics, grads


def make_train_step(
    cfg: ModelConfig,
    tcfg: TrainStepConfig = TrainStepConfig(),
    adamw_cfg: Optional[adamw.AdamWConfig] = None,
):
    """Returns ``step(params, opt_state, batch, step_idx) -> (...)``.

    Jit with shardings is applied by the caller (launch/train.py or
    launch/dryrun.py) so the same function serves CPU tests (no mesh) and
    the production mesh.
    """
    acfg = adamw_cfg or adamw.AdamWConfig(
        lr=tcfg.peak_lr, grad_clip=tcfg.grad_clip,
        weight_decay=tcfg.weight_decay)
    loss_fn = loss_fn_for(cfg)

    def step(params, opt_state, batch, step_idx):
        loss, metrics, grads = _accumulate_grads(
            loss_fn, params, batch, tcfg, cfg)
        lr = cosine_schedule(
            step_idx, peak_lr=tcfg.peak_lr, warmup_steps=tcfg.warmup_steps,
            total_steps=tcfg.total_steps)
        new_params, new_state, opt_metrics = adamw.adamw_update(
            grads, opt_state, params, acfg, lr=lr)
        metrics = dict(metrics, **opt_metrics, loss=loss)
        return new_params, new_state, metrics

    return step


def shardings_for(mesh: Mesh, params: Pytree, opt_state: Any,
                  batch_like: dict):
    """(in_shardings, out_shardings) trees for jit(train_step)."""
    with shd.use_mesh(mesh):
        pspec = shd.spec_for_params(params)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
    oshard = adamw.AdamWState(
        step=NamedSharding(mesh, P()),
        mu=pshard, nu=pshard, master=pshard)
    bspec = make_batch_specs(mesh)
    # same container type as the batch, so the trees match under jit
    bshard = type(batch_like)({
        k: NamedSharding(mesh, P(*([bspec[0]] + [None] * (v.ndim - 1))))
        if getattr(v, "ndim", 0) else NamedSharding(mesh, P())
        for k, v in batch_like.items()})
    mshard = NamedSharding(mesh, P())
    in_sh = (pshard, oshard, bshard, mshard)
    out_sh = (pshard, oshard, None)
    return in_sh, out_sh

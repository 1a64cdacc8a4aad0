"""Training driver.

Two modes:
  * ``--smoke`` (CPU): reduced config, real optimization for N steps with
    checkpointing — the end-to-end path tests/examples use.
  * full config on a mesh over the devices present — the production
    16×16 (×2 pods) layout on a full pod, data parallel over whatever is
    there otherwise (one chip runs it too); the same code path the
    dry-run lowers, with real data wiring left to the deployment
    (synthetic stream by default so the binary is self-contained).

    PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
        --smoke --steps 50
"""

from __future__ import annotations

import argparse
import logging
import os
import tempfile

import jax
import jax.numpy as jnp

from repro import configs
from repro.data import DataConfig, SyntheticDataset
from repro.dist import sharding as shd
from repro.launch import mesh as meshlib
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import adamw_init
from repro.train.step import (TrainStepConfig, init_params, make_train_step,
                              shardings_for)
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on local devices")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--peak-lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="default: repro_train under the temp directory")
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.checkpoint_dir is None:
        args.checkpoint_dir = os.path.join(tempfile.gettempdir(),
                                           "repro_train")

    enable_compile_cache()
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(message)s")

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    if cfg.is_encdec:
        dcfg_extra = {"frontend_tokens": cfg.frontend_tokens or 16}
    elif cfg.frontend_tokens:
        dcfg_extra = {"frontend_tokens": cfg.frontend_tokens}
    else:
        dcfg_extra = {}
    dcfg = DataConfig(
        seq_len=args.seq_len, global_batch=args.global_batch,
        vocab_size=cfg.vocab_size, seed=args.seed, **dcfg_extra)

    tcfg = TrainStepConfig(
        microbatches=args.microbatches, peak_lr=args.peak_lr,
        total_steps=args.steps)
    step_fn = make_train_step(cfg, tcfg)

    key = jax.random.PRNGKey(args.seed)
    if args.smoke:
        mesh = None
        params = init_params(key, cfg)
        opt = adamw_init(params)
        jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    else:
        mesh = meshlib.make_device_mesh(multi_pod=args.multi_pod)
        with shd.use_mesh(mesh):
            params_s, opt_s = jax.eval_shape(
                lambda k: (lambda p: (p, None))(init_params(k, cfg)), key)
            batch_like = SyntheticDataset(dcfg).batch_at(0)
            in_sh, out_sh = shardings_for(mesh, params_s, None, batch_like)
            params = jax.jit(
                lambda k: init_params(k, cfg), out_shardings=in_sh[0])(key)
            opt = adamw_init(params)
            jitted = jax.jit(step_fn, in_shardings=in_sh,
                             out_shardings=out_sh, donate_argnums=(0, 1))

    ds = SyntheticDataset(dcfg, mesh=mesh)
    trainer = Trainer(jitted, ds, TrainerConfig(
        total_steps=args.steps, checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir, log_every=args.log_every))
    start, params, opt = trainer.maybe_restore(params, opt)
    params, opt = trainer.run(params, opt, start_step=start)
    print(f"done: {len(trainer.history)} steps, "
          f"final loss {trainer.history[-1]['loss']:.4f}"
          if trainer.history else "done (no steps run)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

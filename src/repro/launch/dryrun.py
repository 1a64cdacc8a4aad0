import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: 512 host
placeholder devices build the production meshes; ``jit(...).lower(...)
.compile()`` runs the full GSPMD partitioner; ``memory_analysis()`` proves
the cell fits per-device HBM; ``cost_analysis()`` + the optimized-HLO
collective parse feed the roofline table (EXPERIMENTS.md §Roofline).

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-12b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/

Exit code 0 = every requested cell compiled.
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs.shapes import SHAPES, cells
from repro.dist import sharding as shd
from repro.launch import mesh as meshlib
from repro.launch.specs import input_specs, state_specs
from repro.roofline.analyze import analyze_compiled
from repro.serve.steps import (cache_shardings, make_prefill_fn,
                               make_serve_step)
from repro.train.step import TrainStepConfig, make_train_step, shardings_for


def make_mesh(name: str):
    if name == "single":
        devices = jax.devices()[:256]
        return shd.make_mesh((16, 16), ("data", "model"), devices=devices)
    if name == "multi":
        return meshlib.make_production_mesh(multi_pod=True)
    raise ValueError(name)


def rules_for_cell(cfg, shape: str, kind: str):
    """Logical-axis rule overrides per cell (see DESIGN.md §5)."""
    rules = {}
    if kind != "train":
        if SHAPES[shape].global_batch == 1:
            # long_500k: batch of 1 cannot split — shard the sequence over
            # EVERY axis instead (the KV/state sequence dim).
            rules["batch"] = ()
            rules["seq_shard"] = ("data", "model")
    return rules


@dataclasses.dataclass
class PerfKnobs:
    override_layers: int = 0   # >0: reduce depth for cost extrapolation

    """Hillclimb knobs, settable from the CLI (EXPERIMENTS.md §Perf)."""
    microbatches: int = 1
    remat: bool = True
    attn_impl: "str | None" = None
    loss_chunk: int = 512
    donate: bool = True
    # Full layer unroll so cost_analysis sees every layer (XLA counts a
    # while-loop body once). Default ON for analysis; launch/train.py uses
    # the scanned (compact-HLO) form at runtime.
    unroll: bool = True


def lower_cell(arch: str, shape: str, mesh_name: str,
               knobs: PerfKnobs = PerfKnobs()):
    """Returns (lowered, compiled, report) for one cell."""
    cfg = configs.get_config(arch)
    if knobs.override_layers:
        pat = len(cfg.layer_pattern)
        n = max(pat, knobs.override_layers - knobs.override_layers % pat)
        cfg = dataclasses.replace(cfg, num_layers=n)
    sp = SHAPES[shape]
    kind = sp.step
    mesh = make_mesh(mesh_name)
    chips = mesh.devices.size
    rules = rules_for_cell(cfg, shape, kind)
    specs = input_specs(arch, shape, cfg)
    params_s, opt_s = state_specs(cfg)

    with shd.use_mesh(mesh, rules):
        if kind == "train":
            tcfg = TrainStepConfig(
                microbatches=knobs.microbatches, remat=knobs.remat,
                attn_impl=knobs.attn_impl, loss_chunk=knobs.loss_chunk,
                unroll_layers=knobs.unroll)
            step = make_train_step(cfg, tcfg)
            in_sh, out_sh = shardings_for(
                mesh, params_s, opt_s, specs["batch"])
            jitted = jax.jit(
                step, in_shardings=in_sh, out_shardings=out_sh,
                donate_argnums=(0, 1) if knobs.donate else ())
            lowered = jitted.lower(
                params_s, opt_s, specs["batch"],
                jax.ShapeDtypeStruct((), jnp.int32))
        elif kind == "prefill":
            fn = make_prefill_fn(cfg, max_len=sp.seq_len,
                                 unroll=knobs.unroll)
            pspec = shd.spec_for_params(params_s)
            psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
            bsh = NamedSharding(mesh, shd.resolve(["batch", None]))
            args = [params_s, specs["tokens"]]
            in_sh = [psh, bsh]
            if "embeds" in specs:
                args.append(specs["embeds"])
                in_sh.append(NamedSharding(
                    mesh, shd.resolve(["batch", None, None])))
            jitted = jax.jit(fn, in_shardings=tuple(in_sh))
            lowered = jitted.lower(*args)
        else:  # decode
            fn = make_serve_step(cfg, unroll=knobs.unroll)
            pspec = shd.spec_for_params(params_s)
            psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec)
            csh = cache_shardings(specs["cache"], mesh)
            tsh = NamedSharding(mesh, shd.resolve(["batch", None]))
            args = [params_s, specs["tokens"], specs["cache"],
                    specs["cache_len"]]
            in_sh = [psh, tsh, csh, NamedSharding(mesh, P())]
            if "memory" in specs:
                args.append(specs["memory"])
                in_sh.append(NamedSharding(
                    mesh, shd.resolve(["batch", None, None])))
            jitted = jax.jit(
                fn, in_shardings=tuple(in_sh),
                donate_argnums=(2,) if knobs.donate else ())
            lowered = jitted.lower(*args)

        compiled = lowered.compile()

    report = analyze_compiled(
        compiled, arch=arch, shape=shape, mesh_name=mesh_name, chips=chips,
        cfg=cfg, batch=sp.global_batch, seq=sp.seq_len, kind=kind,
        device_kind=meshlib.TARGET_KIND)
    return lowered, compiled, report


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: str,
             knobs: PerfKnobs = PerfKnobs(), tag: str = "") -> dict:
    t0 = time.time()
    try:
        _, compiled, report = lower_cell(arch, shape, mesh_name, knobs)
        mem = compiled.memory_analysis()
        print(f"[{arch} × {shape} × {mesh_name}] COMPILED "
              f"({time.time() - t0:.1f}s)")
        print("  memory_analysis:", mem)
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        print(f"  flops={cost.get('flops', 0):.3e} "
              f"bytes={cost.get('bytes accessed', 0):.3e}")
        print(f"  collectives={report.collective_bytes}")
        print(f"  terms: compute={report.compute_s:.4f}s "
              f"memory={report.memory_s:.4f}s "
              f"collective={report.collective_s:.4f}s "
              f"dcn={report.dcn_s:.4f}s -> dominant={report.dominant}")
        rec = dataclasses.asdict(report)
        rec.update(status="ok", compile_s=time.time() - t0,
                   memory_analysis=str(mem), knobs=dataclasses.asdict(knobs))
    except Exception as e:  # noqa: BLE001 — cell failures are data
        traceback.print_exc()
        rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
               "status": "fail", "error": f"{type(e).__name__}: {e}",
               "compile_s": time.time() - t0,
               "knobs": dataclasses.asdict(knobs)}
        print(f"[{arch} × {shape} × {mesh_name}] FAILED: {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        path = os.path.join(
            out_dir, f"{arch}__{shape}__{mesh_name}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCHS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch × shape × mesh) cell")
    ap.add_argument("--meshes", default="single,multi",
                    help="comma list of meshes for --all sweeps")
    ap.add_argument("--out", default="experiments/dryrun")
    # perf knobs
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--loss-chunk", type=int, default=512)
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--no-unroll", action="store_true")
    ap.add_argument("--override-layers", type=int, default=0)
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    knobs = PerfKnobs(
        microbatches=args.microbatches, remat=not args.no_remat,
        attn_impl=args.attn_impl, loss_chunk=args.loss_chunk,
        donate=not args.no_donate, unroll=not args.no_unroll,
        override_layers=args.override_layers)

    todo = []
    if args.all:
        for arch in configs.ARCHS:
            for shape in cells(arch):
                for mesh_name in args.meshes.split(","):
                    todo.append((arch, shape, mesh_name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        todo.append((args.arch, args.shape, args.mesh))

    failures = 0
    for arch, shape, mesh_name in todo:
        rec = run_cell(arch, shape, mesh_name, args.out, knobs, args.tag)
        failures += rec["status"] != "ok"
    print(f"\n{len(todo) - failures}/{len(todo)} cells compiled")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

"""Multi-device tests — run in subprocesses so the main pytest process
keeps its single CPU device (the dry-run flag must not leak, per spec)."""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, devices: int = 8, timeout: int = 420):
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.dist.sharding import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(_ROOT, "src"))
    res = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=timeout, env=env)
    assert res.returncode == 0, res.stderr[-4000:]
    return res.stdout


@pytest.mark.parametrize("variant", [1, 2])
@pytest.mark.parametrize("exchange", ["all_gather", "hillis_permute",
                                      "ring"])
def test_scan_sharded_matches_ref(variant, exchange):
    """The paper's multithreaded two-pass scan with devices as threads."""
    out = _run(f"""
        from repro.core import scan as scanlib
        mesh = make_mesh((8,), ("d",))
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal(4096), jnp.float32)
        spec = P("d")
        xs = jax.device_put(x, NamedSharding(mesh, spec))
        y = scanlib.scan_sharded(
            xs, "sum", mesh=mesh, axis_name="d", spec=spec,
            variant={variant}, carry_exchange="{exchange}",
            local_algorithm="blocked", block_size=256)
        ref = np.cumsum(np.asarray(x), dtype=np.float64)
        np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4, atol=2e-3)
        print("OK")
    """)
    assert "OK" in out


def test_scan_sharded_affine_monoid():
    """Distributed SSM-style affine scan (sequence parallelism carry)."""
    out = _run("""
        from repro.core import scan as scanlib
        mesh = make_mesh((4,), ("d",))
        rng = np.random.default_rng(1)
        a = jnp.asarray(rng.uniform(0.8, 1.0, (512,)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((512,)), jnp.float32)
        spec = P("d")
        sh = NamedSharding(mesh, spec)
        y_a, y_b = scanlib.scan_sharded(
            (jax.device_put(a, sh), jax.device_put(b, sh)), "affine",
            mesh=mesh, axis_name="d", spec=spec,
            carry_exchange="hillis_permute", local_algorithm="ref")
        h, want = 0.0, []
        an, bn = np.asarray(a), np.asarray(b)
        for i in range(512):
            h = an[i] * h + bn[i]
            want.append(h)
        np.testing.assert_allclose(np.asarray(y_b), want, rtol=2e-3,
                                   atol=2e-3)
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_scan_sharded_exclusive():
    out = _run("""
        from repro.core import scan as scanlib
        mesh = make_mesh((8,), ("d",))
        x = jnp.arange(1, 257, dtype=jnp.float32)
        spec = P("d")
        xs = jax.device_put(x, NamedSharding(mesh, spec))
        y = scanlib.scan_sharded(xs, "sum", mesh=mesh, axis_name="d",
                                 spec=spec, exclusive=True)
        ref = np.concatenate([[0.0], np.cumsum(np.asarray(x))[:-1]])
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_compressed_psum_error_feedback():
    """int8 gradient compression: biased per step, unbiased with EF."""
    out = _run("""
        from repro.optim.compression import compressed_psum
        mesh = make_mesh((4,), ("d",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)

        def worker(x, err):
            red, new_err = compressed_psum(x[0], "d", err[0])
            return red[None], new_err[None]

        fn = jax.shard_map(worker, mesh=mesh, in_specs=(P("d"), P("d")),
                           out_specs=(P("d"), P("d")))
        err = jnp.zeros_like(g)
        exact = np.asarray(jnp.sum(g, 0))
        # step 1: quantized sum close to exact; residual nonzero
        red, err = fn(g, err)
        q_err1 = np.abs(np.asarray(red[0]) - exact).max()
        assert q_err1 < 0.1, q_err1
        # EF: summed (reduced + carried error) over repeated steps -> the
        # accumulated average converges to the exact sum
        acc = np.zeros(64)
        err = jnp.zeros_like(g)
        for _ in range(50):
            red, err = fn(g, err)
            acc += np.asarray(red[0])
        np.testing.assert_allclose(acc / 50, exact, atol=0.02)
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_dryrun_cell_small_mesh():
    """The dry-run path end-to-end on an 8-device debug mesh (structure
    identical to the 256/512-chip production run)."""
    out = _run("""
        import jax.numpy as jnp
        from repro import configs
        from repro.dist import sharding as shd
        from repro.train.step import (TrainStepConfig, make_train_step,
                                      shardings_for, init_params)
        from repro.optim import adamw_init
        cfg = configs.get_smoke_config("granite-moe-1b-a400m")
        mesh = make_mesh((2, 4), ("data", "model"))
        key = jax.random.PRNGKey(0)
        params_s = jax.eval_shape(lambda k: init_params(k, cfg), key)
        opt_s = jax.eval_shape(adamw_init, params_s)
        batch = {
            "tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32),
            "labels": jax.ShapeDtypeStruct((4, 64), jnp.int32),
            "mask": jax.ShapeDtypeStruct((4, 64), jnp.float32),
        }
        with shd.use_mesh(mesh):
            step = make_train_step(cfg, TrainStepConfig(remat=True))
            in_sh, out_sh = shardings_for(mesh, params_s, opt_s, batch)
            low = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=(0, 1)).lower(
                params_s, opt_s, batch,
                jax.ShapeDtypeStruct((), jnp.int32))
            comp = low.compile()
        cost = comp.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        assert cost.get("flops", 0) > 0
        text = comp.as_text()
        assert any(op in text for op in
                   ("all-reduce", "all-gather", "reduce-scatter"))
        print("OK")
    """)
    assert "OK" in out


def test_distributed_train_step_executes():
    """Actually EXECUTE a sharded train step on 8 CPU devices and compare
    the loss with the single-device run (SPMD correctness, not just
    compilation)."""
    out = _run("""
        import dataclasses
        import jax.numpy as jnp
        from repro import configs
        from repro.dist import sharding as shd
        from repro.optim import adamw_init
        from repro.train.step import (TrainStepConfig, make_train_step,
                                      shardings_for, init_params)
        cfg = dataclasses.replace(
            configs.get_smoke_config("gemma2-9b"), dtype="float32")
        key = jax.random.PRNGKey(0)
        params = init_params(key, cfg)
        opt = adamw_init(params)
        toks = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks,
                 "mask": jnp.ones((4, 32), jnp.float32)}
        step = make_train_step(cfg, TrainStepConfig(remat=False))
        # single device reference
        _, _, m_ref = jax.jit(step)(params, opt, batch, jnp.asarray(0))

        mesh = make_mesh((2, 4), ("data", "model"))
        with shd.use_mesh(mesh):
            in_sh, out_sh = shardings_for(mesh, params, opt, batch)
            jstep = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            pp = jax.device_put(params, in_sh[0])
            oo = jax.device_put(opt, in_sh[1])
            bb = jax.device_put(dict(batch), in_sh[2])
            _, _, m = jstep(pp, oo, bb, jnp.asarray(0))
        np.testing.assert_allclose(float(m["loss"]), float(m_ref["loss"]),
                                   rtol=2e-4)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_moe_dispatch_on_mesh():
    """The per-shard MoE dispatch (beyond-paper opt) must (a) execute on a
    real data×model mesh and (b) agree with the G=1 global dispatch when
    capacity is unconstrained (no drops ⇒ identical math, different
    partitioning)."""
    out = _run("""
        import dataclasses, os
        import jax.numpy as jnp
        from repro import configs
        from repro.dist import sharding as shd
        from repro.models.layers.moe import apply_moe, init_moe
        from repro.models.config import ModelConfig
        cfg = ModelConfig(name="t", family="moe", d_model=32, num_heads=4,
                          num_kv_heads=4, head_dim=8, d_ff=64, moe_d_ff=64,
                          vocab_size=128, num_experts=4, top_k=2,
                          capacity_factor=8.0, dtype="float32")
        params = init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32))
        y_ref, aux_ref = apply_moe(params, x, cfg)   # no mesh -> G=1

        mesh = make_mesh((4, 2), ("data", "model"))
        with shd.use_mesh(mesh):
            y_sh, aux_sh = jax.jit(
                lambda p, v: apply_moe(p, v, cfg))(params, x)
        np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-5)
        assert float(aux_sh.dropped_fraction) == 0.0
        print("OK")
    """)
    assert "OK" in out

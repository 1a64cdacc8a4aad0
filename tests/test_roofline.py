"""Roofline analyzer: HLO collective parsing + term arithmetic."""

import numpy as np
import pytest

from repro.roofline.analyze import (_parse_replica_groups, _shape_bytes,
                                    collective_bytes_from_hlo, model_flops)


def test_shape_bytes():
    assert _shape_bytes("f32[16,1024]") == 16 * 1024 * 4
    assert _shape_bytes("bf16[8,128,256]") == 8 * 128 * 256 * 2
    assert _shape_bytes("s8[100]") == 100
    assert _shape_bytes("pred[4]") == 4
    assert _shape_bytes("f32[]") == 4


def test_collective_parse_sync_and_async():
    hlo = """
      %ag = f32[64,128] all-gather(%p0), replica_groups={{0,1}}
      %ar.1 = bf16[32] all-reduce(%x), to_apply=%add
      %cp = f32[16] collective-permute(%y), source_target_pairs={{0,1}}
      %ags = (f32[8,8], f32[8,8]) all-gather-start(%a), dims={0}
      %agd = f32[8,8] all-gather-done(%ags)
      %rs = f32[4,4] reduce-scatter(%b), dimensions={0}
      %fusion = f32[99] fusion(%c), kind=kLoop
    """
    got = collective_bytes_from_hlo(hlo)
    # async -start tuples count the RESULT element once (not operand+result)
    assert got["all-gather"] == 64 * 128 * 4 + 8 * 8 * 4  # sync + start
    assert got["all-reduce"] == 32 * 2
    assert got["collective-permute"] == 16 * 4
    assert got["reduce-scatter"] == 4 * 4 * 4
    # done ops and non-collectives not double counted
    assert sum(got.values()) == (64 * 128 * 4 + 8 * 8 * 4 + 64 + 64 + 64)


def test_replica_group_iota_parsing():
    line = "replica_groups=[4,4]<=[16]"
    groups = list(_parse_replica_groups(line))
    assert groups[0] == [0, 1, 2, 3]
    assert groups[3] == [12, 13, 14, 15]

    line_t = "replica_groups=[4,4]<=[4,4]T(1,0)"
    groups_t = list(_parse_replica_groups(line_t))
    assert groups_t[0] == [0, 4, 8, 12]

    line_e = "replica_groups={{0,5},{1,6}}"
    groups_e = list(_parse_replica_groups(line_e))
    assert groups_e == [[0, 5], [1, 6]]


def test_cross_pod_detection():
    from repro.roofline.analyze import _cross_pod_bytes
    # group [0..255] stays in pod 0; [0,256] spans pods (256 chips/pod)
    hlo_in = "%ar = f32[100] all-reduce(%x), replica_groups={{0,255}}"
    hlo_span = "%ar = f32[100] all-reduce(%x), replica_groups={{0,256}}"
    assert _cross_pod_bytes(hlo_in, 256) == 0
    assert _cross_pod_bytes(hlo_span, 256) == 400
    # iota spanning: 2 groups of 256 -> in-pod; 256 groups of 2 (stride
    # 256 via transpose) -> spans
    hlo_iota = "%ag = f32[10] all-gather(%x), replica_groups=[256,2]<=[2,256]T(1,0)"
    assert _cross_pod_bytes(hlo_iota, 256) == 40


def test_model_flops_conventions():
    from repro import configs
    cfg = configs.get_config("granite-moe-1b-a400m")
    n_active = cfg.active_param_count()
    assert model_flops(cfg, 256, 4096, "train") == 6.0 * n_active * 256 * 4096
    assert model_flops(cfg, 32, 32768, "prefill") == 2.0 * n_active * 32 * 32768
    assert model_flops(cfg, 128, 32768, "decode") == 2.0 * n_active * 128


def test_end_to_end_tiny_lowering():
    """analyze_compiled on a real (1-device) compile produces finite terms."""
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.roofline.analyze import analyze_compiled
    cfg = configs.get_smoke_config("xlstm-125m")
    from repro.train.step import TrainStepConfig, make_train_step, init_params
    from repro.optim import adamw_init
    key = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda k: init_params(k, cfg), key)
    opt_s = jax.eval_shape(adamw_init, params_s)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 32), jnp.int32),
             "mask": jax.ShapeDtypeStruct((2, 32), jnp.float32)}
    step = make_train_step(cfg, TrainStepConfig(remat=False))
    comp = jax.jit(step).lower(
        params_s, opt_s, batch, jax.ShapeDtypeStruct((), jnp.int32)
    ).compile()
    rep = analyze_compiled(comp, arch="xlstm-125m", shape="t", mesh_name="1",
                           chips=1, cfg=cfg, batch=2, seq=32, kind="train",
                           device_kind="TPU v5 lite")
    assert rep.compute_s > 0 and rep.memory_s > 0
    assert rep.dominant in ("compute", "memory", "collective", "dcn")
    assert 0 < rep.useful_ratio
    # The peaks belong to a named chip: a kind with no table is an error,
    # never a silent v5e default.
    with pytest.raises(ValueError, match="no peak table"):
        analyze_compiled(comp, arch="xlstm-125m", shape="t", mesh_name="1",
                         chips=1, cfg=cfg, batch=2, seq=32, kind="train",
                         device_kind="cpu")

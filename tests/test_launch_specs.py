"""Launch layer: input specs, state specs, shape bookkeeping (no mesh)."""

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.configs.shapes import SHAPES, cells
from repro.launch.specs import input_specs, state_specs


def test_all_cells_have_specs():
    count = 0
    for arch in configs.ARCHS:
        for shape in cells(arch):
            specs = input_specs(arch, shape)
            assert specs, (arch, shape)
            count += 1
    assert count == 34


@pytest.mark.parametrize("arch", ["gemma3-12b", "qwen3-moe-235b-a22b",
                                  "seamless-m4t-large-v2",
                                  "llava-next-mistral-7b"])
def test_train_specs_shapes(arch):
    cfg = configs.get_config(arch)
    specs = input_specs(arch, "train_4k", cfg)
    batch = specs["batch"]
    sp = SHAPES["train_4k"]
    assert batch["tokens"].shape[0] == sp.global_batch
    total = batch["tokens"].shape[1]
    if "embeds" in batch:
        total += batch["embeds"].shape[1]
    assert total == sp.seq_len  # frontend + text = the assigned seq_len
    assert batch["tokens"].dtype == jnp.int32


def test_decode_specs_have_cache():
    specs = input_specs("phi3-medium-14b", "decode_32k")
    assert specs["tokens"].shape == (128, 1)  # ONE new token
    leaves = jax.tree.leaves(specs["cache"])
    assert leaves, "decode must carry a cache"
    # KV cache covers the full 32k context
    assert any(32_768 in l.shape for l in leaves)


def test_encdec_decode_has_memory():
    specs = input_specs("seamless-m4t-large-v2", "decode_32k")
    assert "memory" in specs
    assert specs["memory"].shape[0] == 128


def test_state_specs_no_allocation_and_match_param_count():
    """eval_shape param bytes ≈ the analytic param_count (within 12%) —
    validates the MODEL_FLOPS=6·N·D inputs for the roofline, including
    for the 235B config that could never allocate on this host."""
    for arch in ("qwen3-moe-235b-a22b", "gemma3-12b", "zamba2-7b"):
        cfg = configs.get_config(arch)
        params, opt = state_specs(cfg)
        n_exact = sum(l.size for l in jax.tree.leaves(params))
        n_est = cfg.param_count()
        assert abs(n_exact - n_est) / n_exact < 0.12, (
            arch, n_exact, n_est)


def test_long_500k_only_for_subquadratic():
    for arch in configs.ARCHS:
        shapes = cells(arch)
        if "long_500k" in shapes:
            assert arch in ("gemma3-12b", "gemma2-9b", "xlstm-125m",
                            "zamba2-7b")


def test_production_mesh_constants():
    from repro.launch import mesh as m
    v5e = m.peaks("TPU v5 lite")
    assert v5e.flops_bf16 == 197e12
    assert v5e.hbm_bw == 819e9
    assert v5e.ici_bw == 50e9
    assert "TPU v5e" in v5e.source
    with pytest.raises(ValueError):
        m.peaks("cpu")

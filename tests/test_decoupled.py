"""Decoupled reduce-then-scan schedule vs oracles and the carry chain.

The acceptance bar for the decoupled engine (interpret mode on CPU):
  * equivalence vs ``reference.scan_ref`` for all three monoids,
  * BIT-identity vs the carry schedule (same float association order),
  * block-size invariance, exclusive mode, cross-chunk segments,
  * the policy's batch-vs-cores schedule rule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scan as scanlib
from repro.core.scan import policy, reference
from repro.kernels.scan_blocked import ops as sb_ops
from repro.kernels.segscan import ops as seg_ops
from repro.kernels.ssm_scan import ops as ssm_ops


# ---------------------------------------------------------------------------
# cumsum (sum monoid)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", [(1, 4096), (4, 1024), (3, 2300),
                                   (1, 16384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
def test_cumsum_decoupled_matches_reference(shape, dtype):
    rng = np.random.default_rng(hash(shape) % 2**31)
    if dtype == jnp.int32:
        x = jnp.asarray(rng.integers(-9, 9, shape), dtype)
    else:
        x = jnp.asarray(rng.standard_normal(shape), dtype)
    got = sb_ops.cumsum(x, interpret=True, schedule="decoupled",
                        block_n=1024)
    ref = reference.cumsum_ref(x.astype(jnp.float32))
    # f32 tree vs sequential association drifts with N (not an error)
    tol = 0.15 if dtype == jnp.bfloat16 else 3e-3
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(ref, np.float64),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("block_n", [128, 512, 2048])
def test_cumsum_decoupled_block_invariance(block_n):
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 8192)), jnp.float32)
    got = sb_ops.cumsum(x, block_n=block_n, interpret=True,
                        schedule="decoupled")
    np.testing.assert_allclose(
        np.asarray(got), np.cumsum(np.asarray(x), -1), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("exclusive", [False, True])
def test_cumsum_decoupled_bit_identical_to_carry(exclusive):
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((2, 8192)), jnp.float32)
    carry = sb_ops.cumsum(x, exclusive=exclusive, interpret=True,
                          schedule="carry", block_n=1024)
    dec = sb_ops.cumsum(x, exclusive=exclusive, interpret=True,
                        schedule="decoupled", block_n=1024)
    assert jnp.all(carry == dec), "schedules must agree BITWISE"


def test_cumsum_decoupled_exclusive():
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((1, 4096)), jnp.float32)
    got = sb_ops.cumsum(x, exclusive=True, interpret=True,
                        schedule="decoupled", block_n=512)
    inc = np.cumsum(np.asarray(x), -1)
    ref = np.concatenate([np.zeros((1, 1), np.float32), inc[:, :-1]], -1)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# cumsum of fewer than eight rows: the Flat layout
# ---------------------------------------------------------------------------


def _flat_input(b, n, dtype, seed):
    rng = np.random.default_rng(seed)
    if dtype == jnp.int32:
        return jnp.asarray(rng.integers(-9, 9, (b, n)), dtype)
    return jnp.asarray(rng.standard_normal((b, n)), dtype)


def _running_sum(x, exclusive):
    inc = np.cumsum(np.asarray(x, np.float64), -1)
    if not exclusive:
        return inc
    return np.concatenate([np.zeros_like(inc[:, :1]), inc[:, :-1]], -1)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("exclusive", [False, True])
@pytest.mark.parametrize("n", [4 * 4096, 100003])   # whole tiles, ragged
@pytest.mark.parametrize("b", [1, 3])
def test_flat_cumsum_matches_float64(b, n, exclusive, dtype):
    """Both schedules the policy picks, on lane-dense (n/128, 128) tiles,
    against the float64 running sum: exact on integers, and carry and
    decoupled bitwise equal."""
    x = _flat_input(b, n, dtype, seed=b * n)
    ref = _running_sum(x, exclusive)
    got = {s: sb_ops.cumsum(x, exclusive=exclusive, interpret=True,
                            schedule=s, block_n=4096)
           for s in ("carry", "decoupled")}
    assert jnp.array_equal(got["carry"], got["decoupled"])
    y = np.asarray(got["decoupled"], np.float64)
    if dtype == jnp.int32:
        np.testing.assert_array_equal(y, ref)
    else:
        scale = max(1.0, np.abs(ref).max())
        assert np.abs(y - ref).max() / scale < 1e-4


@pytest.mark.parametrize("exclusive", [False, True])
def test_flat_carry_and_decoupled_bitwise_equal(exclusive):
    """The totals pass's chunk total is the scan's last element, so the
    decoupled chain combines exactly the carry schedule's values."""
    x = _flat_input(1, 6 * 8192 + 77, jnp.float32, seed=11)
    carry = sb_ops.cumsum(x, exclusive=exclusive, interpret=True,
                          schedule="carry", block_n=8192)
    dec = sb_ops.cumsum(x, exclusive=exclusive, interpret=True,
                        schedule="decoupled", block_n=8192)
    assert jnp.all(carry == dec), "schedules must agree BITWISE"


@pytest.mark.parametrize("exclusive", [False, True])
@pytest.mark.parametrize("block_n", [None, 1024, 3000, 8192])
def test_flat_chunk_invariance(block_n, exclusive):
    """Any chunk length gives the running sum, down to tiles of one vreg:
    exactly on integers (a block_n off whole vregs is rounded up to
    them), to rounding on floats."""
    xi = _flat_input(2, 3 * 8192, jnp.int32, seed=12)
    got = sb_ops.cumsum(xi, exclusive=exclusive, interpret=True,
                        schedule="decoupled", block_n=block_n)
    np.testing.assert_array_equal(np.asarray(got),
                                  _running_sum(xi, exclusive))
    xf = _flat_input(2, 3 * 8192, jnp.float32, seed=13)
    got = sb_ops.cumsum(xf, exclusive=exclusive, interpret=True,
                        schedule="decoupled", block_n=block_n)
    np.testing.assert_allclose(np.asarray(got), _running_sum(xf, exclusive),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("exclusive", [False, True])
def test_flat_gradient_is_the_suffix_sum(exclusive):
    """d/dx of <w, cumsum(x)> for one column is the suffix sum of w (one
    place later when exclusive), run through the same Flat kernel."""
    n = 2 * 4096 + 5
    x = _flat_input(1, n, jnp.float32, seed=14)[0]
    w = _flat_input(1, n, jnp.float32, seed=15)[0]
    grad = jax.grad(lambda v: jnp.sum(w * sb_ops.cumsum(
        v, exclusive=exclusive, interpret=True, block_n=4096)))(x)
    wh = np.asarray(w, np.float64)
    ref = np.cumsum(wh[::-1])[::-1]
    if exclusive:
        ref = np.concatenate([ref[1:], [0.0]])
    np.testing.assert_allclose(np.asarray(grad), ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,layout", [(1, "flat"), (8, "rows")])
def test_kernel_launch_names_the_layout(b, layout):
    """A trace says which layout served each launch: one column runs on
    Flat, eight rows fill a vreg's sublanes and stay on Rows."""
    from repro.obs import trace
    x = _flat_input(b, 3 * 1024 + b, jnp.float32, seed=16)
    t = trace.enable()
    t.clear()
    try:
        sb_ops.cumsum(x, interpret=True, schedule="decoupled")
        evs = [e["args"] for e in t.events()
               if e["name"] == "kernel.launch"]
    finally:
        trace.disable()
    assert evs and all(e["layout"] == layout for e in evs)


# ---------------------------------------------------------------------------
# segscan ((flag, value) monoid)
# ---------------------------------------------------------------------------


def test_segscan_decoupled_matches_reference():
    rng = np.random.default_rng(3)
    v = jnp.asarray(rng.standard_normal((3, 4096)), jnp.float32)
    f = jnp.asarray(rng.random((3, 4096)) < 0.02, jnp.int32)
    got = seg_ops.segmented_cumsum(v, f, interpret=True,
                                   schedule="decoupled", block_n=512)
    ref = reference.segmented_scan_ref(v, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("block_n", [128, 1024])
def test_segscan_decoupled_cross_chunk_segments(block_n):
    """A segment spanning several chunks must carry; a flag INSIDE a later
    chunk must kill the incoming carry — per chunk, not per block row."""
    rng = np.random.default_rng(4)
    v = jnp.asarray(rng.standard_normal((2, 4096)), jnp.float32)
    f = jnp.zeros((2, 4096), jnp.int32)
    # row 0: flags only at 0 and deep inside chunk 3; row 1: flag-free
    # after position 0 => the carry must cross every chunk boundary.
    f = f.at[:, 0].set(1).at[0, 3500].set(1).at[1, 130].set(1)
    got = seg_ops.segmented_cumsum(v, f, block_n=block_n, interpret=True,
                                   schedule="decoupled")
    ref = reference.segmented_scan_ref(v, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_segscan_decoupled_bit_identical_to_carry():
    rng = np.random.default_rng(5)
    v = jnp.asarray(rng.standard_normal((2, 4096)), jnp.float32)
    f = jnp.asarray(rng.random((2, 4096)) < 0.01, jnp.int32)
    carry = seg_ops.segmented_cumsum(v, f, interpret=True, schedule="carry",
                                     block_n=512)
    dec = seg_ops.segmented_cumsum(v, f, interpret=True,
                                   schedule="decoupled", block_n=512)
    assert jnp.all(carry == dec)


# ---------------------------------------------------------------------------
# ssm_scan (affine monoid)
# ---------------------------------------------------------------------------


def _affine_ref(a, b):
    (_, hb) = reference.scan_ref((a, b), "affine", axis=1)
    return hb


@pytest.mark.parametrize("shape", [(1, 2048, 128), (2, 1024, 256),
                                   (1, 1000, 64)])
def test_ssm_decoupled_matches_reference(shape):
    rng = np.random.default_rng(hash(shape) % 2**31)
    a = jnp.asarray(rng.uniform(0.7, 1.0, shape), jnp.float32)
    b = jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)
    got = ssm_ops.ssm_scan(a, b, interpret=True, schedule="decoupled",
                           block_t=128)
    ref = _affine_ref(a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("block_t", [64, 256])
def test_ssm_decoupled_block_invariance_and_bit_identity(block_t):
    rng = np.random.default_rng(6)
    a = jnp.asarray(rng.uniform(0.8, 1.0, (1, 2048, 128)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((1, 2048, 128)), jnp.float32)
    carry = ssm_ops.ssm_scan(a, b, block_t=block_t, interpret=True,
                             schedule="carry")
    dec = ssm_ops.ssm_scan(a, b, block_t=block_t, interpret=True,
                           schedule="decoupled")
    assert jnp.all(carry == dec)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(_affine_ref(a, b)),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# policy + routing
# ---------------------------------------------------------------------------


def test_policy_schedule_rule():
    # serve/decode class: one long row -> parallel sequence (Obs 3); the
    # policy never picks the semaphore-bound fused form
    assert policy.choose_schedule(1, 1 << 22) == "decoupled"
    assert policy.choose(1 << 22, batch=1).schedule == "decoupled"
    # training class: rows fill the cores -> carry chain (Obs 2)
    assert policy.choose_schedule(policy.NUM_CORES, 1 << 22) == "carry"
    assert policy.choose(1 << 22, batch=64).schedule == "carry"
    # short row: nothing to parallelize -> carry
    assert policy.choose_schedule(1, 1024) == "carry"
    # shape-oblivious callers keep the old default
    assert policy.choose(1 << 26).schedule == "carry"


def test_ops_auto_schedule_routes_by_shape():
    assert sb_ops.resolve_schedule("auto", 1, 1 << 22, 2048) == "decoupled"
    assert sb_ops.resolve_schedule("auto", 64, 1 << 22, 2048) == "carry"
    assert sb_ops.resolve_schedule("carry", 1, 1 << 22, 2048) == "carry"
    assert sb_ops.resolve_schedule("decoupled", 64, 1 << 22, 2048) \
        == "decoupled"
    # the policy sees the REAL chunk length: a huge block leaves too few
    # chunks to feed the idle cores, so auto falls back to the carry chain
    assert sb_ops.resolve_schedule("auto", 1, 1 << 14, 1 << 13) == "carry"
    with pytest.raises(ValueError):
        sb_ops.resolve_schedule("bogus", 1, 1, 2048)


def test_api_kernel_schedule_passthrough():
    x = jnp.asarray(
        np.random.default_rng(8).standard_normal(4096), jnp.float32)
    got = scanlib.scan(x, "sum", algorithm="kernel", interpret=True,
                       schedule="decoupled")
    np.testing.assert_allclose(np.asarray(got), np.cumsum(np.asarray(x)),
                               rtol=2e-4, atol=2e-4)

"""Backward as a scan: the kernel family's custom VJPs vs reference autodiff.

``cumsum`` / ``segmented_cumsum`` / ``ssm_scan`` each carry a
``jax.custom_vjp`` whose backward is ONE MORE engine scan — the flipped
scan of the incoming cotangent with transposed/rolled gates — instead of
autodiff through the Pallas kernel. The wall here:

  * ``jax.grad`` through each wrapper matches differentiating the jnp
    reference to float tolerance, across shapes, dtypes, both exclusive
    modes, and every differentiable monoid;
  * the backward really executes on the engine: with tracing enabled, a
    grad computation emits ``kernel.launch`` instants for the backward
    compilation too, not just the forward.

Degenerate (empty) inputs keep gradients well-defined via the wrappers'
early-return guards.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scan import reference
from repro.kernels.scan_blocked import ops as sb_ops
from repro.kernels.segscan import ops as seg_ops
from repro.kernels.ssm_scan import ops as ssm_ops
from repro.obs import trace

SHAPES = [(1, 256), (3, 1024), (2, 4096)]


def _tol(dtype):
    return 3e-2 if dtype == jnp.bfloat16 else 1e-4


def _assert_grads_close(g, g_ref, dtype):
    ref = np.asarray(g_ref, np.float64)
    # bf16 grads of long sums cross zero with large RELATIVE error even
    # when absolutely tiny — scale the absolute floor by the grad range.
    atol = _tol(dtype) * max(1.0, float(np.abs(ref).max()))
    np.testing.assert_allclose(
        np.asarray(g, np.float64), ref, rtol=_tol(dtype), atol=atol)


# ---------------------------------------------------------------------------
# cumsum: dx = flip(cumsum(flip(g)))  (same exclusive flag)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("exclusive", [False, True])
@pytest.mark.parametrize("shape", SHAPES)
def test_cumsum_grad_matches_reference(shape, exclusive, dtype):
    rng = np.random.default_rng(30)
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    w = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def loss_kernel(x):
        out = sb_ops.cumsum(x, exclusive=exclusive, interpret=True)
        return jnp.sum(out.astype(jnp.float32) * w)

    def loss_ref(x):
        out = reference.cumsum_ref(x.astype(jnp.float32),
                                   exclusive=exclusive)
        return jnp.sum(out * w)

    g = jax.grad(loss_kernel)(x)
    g_ref = jax.grad(loss_ref)(x)
    assert g.dtype == x.dtype
    _assert_grads_close(g, g_ref, dtype)


# ---------------------------------------------------------------------------
# segmented: dvalues = flip(segscan(flip(g), flip(shift_left(flags))))
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", SHAPES)
def test_segmented_grad_matches_reference(shape, dtype):
    rng = np.random.default_rng(31)
    v = jnp.asarray(rng.standard_normal(shape), dtype)
    f = jnp.asarray(rng.random(shape) < 0.05, jnp.int32)
    w = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def loss_kernel(v):
        out = seg_ops.segmented_cumsum(v, f, interpret=True)
        return jnp.sum(out.astype(jnp.float32) * w)

    def loss_ref(v):
        out = reference.segmented_scan_ref(v.astype(jnp.float32), f)
        return jnp.sum(out * w)

    g = jax.grad(loss_kernel)(v)
    g_ref = jax.grad(loss_ref)(v)
    assert g.dtype == v.dtype
    _assert_grads_close(g, g_ref, dtype)


def test_segmented_grad_flag_boundaries():
    """Gradients must not leak across segment boundaries: an element's
    cotangent reaches exactly its own segment's prefix positions."""
    v = jnp.zeros((8,), jnp.float32)
    f = jnp.asarray([0, 0, 0, 1, 0, 0, 1, 0], jnp.int32)

    def pick(v, i):
        return seg_ops.segmented_cumsum(v, f, interpret=True)[i]

    # d out[5] / d v: positions 3..5 (its segment so far), nothing else
    g = jax.grad(pick)(v, 5)
    np.testing.assert_array_equal(
        np.asarray(g), [0, 0, 0, 1, 1, 1, 0, 0])
    # d out[2] / d v: head segment only
    g = jax.grad(pick)(v, 2)
    np.testing.assert_array_equal(
        np.asarray(g), [1, 1, 1, 0, 0, 0, 0, 0])


# ---------------------------------------------------------------------------
# ssm (affine): lambda_t = g_t + a_{t+1} lambda_{t+1}; da = lambda * h_{t-1}
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 128, 8), (2, 512, 16), (3, 1024, 4)])
def test_ssm_grad_matches_reference(shape, dtype):
    rng = np.random.default_rng(32)
    a = jnp.asarray(rng.uniform(0.6, 1.0, shape), dtype)
    b = jnp.asarray(rng.standard_normal(shape) * 0.1, dtype)
    w = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def loss_kernel(a, b):
        h = ssm_ops.ssm_scan(a, b, interpret=True)
        return jnp.sum(h.astype(jnp.float32) * w)

    def loss_ref(a, b):
        _, h = reference.scan_ref(
            (a.astype(jnp.float32), b.astype(jnp.float32)), "affine",
            axis=1)
        return jnp.sum(h * w)

    ga, gb = jax.grad(loss_kernel, argnums=(0, 1))(a, b)
    ga_ref, gb_ref = jax.grad(loss_ref, argnums=(0, 1))(a, b)
    assert ga.dtype == a.dtype and gb.dtype == b.dtype
    _assert_grads_close(gb, gb_ref, dtype)
    _assert_grads_close(ga, ga_ref, dtype)


def test_ssm_grad_per_schedule():
    """The backward engine scan honors the caller's schedule choice —
    grads agree across all four organizations."""
    rng = np.random.default_rng(33)
    a = jnp.asarray(rng.uniform(0.6, 1.0, (2, 512, 8)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((2, 512, 8)), jnp.float32)

    def loss(a, b, schedule):
        h = ssm_ops.ssm_scan(a, b, interpret=True, schedule=schedule)
        return jnp.sum(h * h)

    grads = [jax.grad(loss, argnums=(0, 1))(a, b, s)
             for s in ("carry", "decoupled", "tree")]
    for ga, gb in grads[1:]:
        np.testing.assert_allclose(np.asarray(ga), np.asarray(grads[0][0]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(grads[0][1]),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the backward really runs on the engine (trace evidence), empties
# ---------------------------------------------------------------------------


def test_backward_launches_engine_kernels():
    """kernel.launch instants fire for the BACKWARD compilation: a grad
    through ssm_scan must add affine launches beyond the forward's, and a
    grad through cumsum adds sum launches."""
    tracer = trace.enable()
    try:
        rng = np.random.default_rng(34)
        # Launch instants fire once per COMPILATION, and the backward
        # scan deliberately reuses the forward's jitted impl (same
        # shapes, same statics). So: never warm any shape used here —
        # a forward-only call on a fresh shape compiles once, and a
        # fresh grad compiles the forward-under-AD AND the backward.
        a = jnp.asarray(rng.uniform(0.6, 1.0, (1, 320, 8)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((1, 320, 8)), jnp.float32)

        tracer.clear()
        ssm_ops.ssm_scan(a, b, interpret=True)
        fwd = [e for e in tracer.events() if e["name"] == "kernel.launch"
               and e["args"]["monoid"] == "affine"]
        assert len(fwd) == 1

        tracer.clear()
        a2, b2 = a[:, :192], b[:, :192]        # fresh shape for the grad
        jax.grad(lambda a, b: jnp.sum(
            ssm_ops.ssm_scan(a, b, interpret=True) ** 2),
            argnums=(0, 1))(a2, b2)
        both = [e for e in tracer.events() if e["name"] == "kernel.launch"
                and e["args"]["monoid"] == "affine"]
        assert len(both) >= 2, \
            "grad must launch the engine for the backward scan too"

        tracer.clear()
        x = jnp.asarray(rng.standard_normal((1, 320)), jnp.float32)
        jax.grad(lambda x: jnp.sum(
            sb_ops.cumsum(x, interpret=True) ** 2))(x)
        sums = [e for e in tracer.events() if e["name"] == "kernel.launch"
                and e["args"]["monoid"] == "sum"]
        assert len(sums) >= 2, "forward AND backward cumsum launches"
    finally:
        trace.disable()


def test_empty_inputs_have_grads():
    x = jnp.zeros((2, 0), jnp.float32)
    g = jax.grad(lambda x: jnp.sum(
        sb_ops.cumsum(x, interpret=True)))(x)
    assert g.shape == (2, 0)
    a = jnp.zeros((2, 0, 4), jnp.float32)
    ga, gb = jax.grad(lambda a, b: jnp.sum(
        ssm_ops.ssm_scan(a, b, interpret=True)), argnums=(0, 1))(a, a)
    assert ga.shape == (2, 0, 4) and gb.shape == (2, 0, 4)

"""Monoid-law property tests (hypothesis): the algebra every algorithm
in the package relies on. If these fail, nothing else is trustworthy."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.scan import assoc

_f = st.floats(-10, 10, width=32)
_pos = st.floats(0.125, 2.0, width=32)


def _close(a, b, tol=1e-3):
    np.testing.assert_allclose(
        np.asarray(jnp.asarray(a), np.float64),
        np.asarray(jnp.asarray(b), np.float64), rtol=tol, atol=tol)


def _tclose(ta, tb, tol=1e-3):
    import jax
    for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
        _close(a, b, tol)


@pytest.mark.parametrize("name", ["sum", "max", "min", "prod"])
@given(x=_f, y=_f, z=_f)
@settings(max_examples=40, deadline=None)
def test_scalar_monoid_associativity(name, x, y, z):
    m = assoc.get(name)
    a, b, c = (jnp.float32(v) for v in (x, y, z))
    _tclose(m.combine(m.combine(a, b), c), m.combine(a, m.combine(b, c)))


@pytest.mark.parametrize("name", ["sum", "max", "min", "prod"])
@given(x=_f)
@settings(max_examples=20, deadline=None)
def test_scalar_monoid_identity(name, x):
    m = assoc.get(name)
    a = jnp.float32(x)
    e = m.identity_like(a)
    _tclose(m.combine(e, a), a)
    _tclose(m.combine(a, e), a)


@given(a1=_pos, b1=_f, a2=_pos, b2=_f, a3=_pos, b3=_f)
@settings(max_examples=40, deadline=None)
def test_affine_associativity(a1, b1, a2, b2, a3, b3):
    m = assoc.AFFINE
    e1 = (jnp.float32(a1), jnp.float32(b1))
    e2 = (jnp.float32(a2), jnp.float32(b2))
    e3 = (jnp.float32(a3), jnp.float32(b3))
    _tclose(m.combine(m.combine(e1, e2), e3),
            m.combine(e1, m.combine(e2, e3)), tol=1e-2)


@given(a=_pos, b=_f)
@settings(max_examples=20, deadline=None)
def test_affine_identity(a, b):
    m = assoc.AFFINE
    e = (jnp.float32(a), jnp.float32(b))
    ident = m.identity_like(e)
    _tclose(m.combine(ident, e), e)
    _tclose(m.combine(e, ident), e)


@given(m1=_f, s1=_pos, m2=_f, s2=_pos, m3=_f, s3=_pos)
@settings(max_examples=40, deadline=None)
def test_softmax_pair_associativity(m1, s1, m2, s2, m3, s3):
    m = assoc.SOFTMAX_PAIR
    e1 = (jnp.float32(m1), jnp.float32(s1))
    e2 = (jnp.float32(m2), jnp.float32(s2))
    e3 = (jnp.float32(m3), jnp.float32(s3))
    _tclose(m.combine(m.combine(e1, e2), e3),
            m.combine(e1, m.combine(e2, e3)), tol=1e-2)


def test_softmax_pair_equals_logsumexp():
    """Scanning the softmax-pair monoid = running (max, sumexp)."""
    import jax
    from repro.core.scan import reference
    xs = jnp.asarray(np.random.default_rng(0).standard_normal(64),
                     jnp.float32)
    elems = (xs, jnp.ones_like(xs))
    m_run, s_run = reference.scan_ref(elems, assoc.SOFTMAX_PAIR, axis=0)
    lse = np.asarray(m_run) + np.log(np.asarray(s_run))
    want = [float(jax.nn.logsumexp(xs[: i + 1])) for i in range(64)]
    np.testing.assert_allclose(lse, want, rtol=1e-5, atol=1e-5)


def test_fold_order_preserved_noncommutative():
    """Monoid.fold must respect operand order (affine is non-commutative)."""
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.uniform(0.5, 1.5, 13), jnp.float32)
    b = jnp.asarray(rng.standard_normal(13), jnp.float32)
    fa, fb = assoc.AFFINE.fold((a, b), axis=0)
    # sequential left fold
    sa, sb = jnp.float32(1.0), jnp.float32(0.0)
    for i in range(13):
        sa, sb = assoc.AFFINE.combine((sa, sb), (a[i], b[i]))
    _close(fa, sa, 1e-4)
    _close(fb, sb, 1e-4)


@given(st.lists(st.tuples(st.booleans(), _f), min_size=1, max_size=60))
@settings(max_examples=25, deadline=None)
def test_segmented_lift_matches_python(pairs):
    """Segmented-sum scan == python loop with resets."""
    from repro.core.scan import reference
    flags = jnp.asarray([int(f) for f, _ in pairs], jnp.int32)
    vals = jnp.asarray([v for _, v in pairs], jnp.float32)
    seg = assoc.segmented(assoc.SUM)
    _, out = reference.scan_ref((flags, vals), seg, axis=0)
    acc, want = 0.0, []
    for f, v in pairs:
        acc = v if f else acc + v
        want.append(acc)
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# registry-wide law sweep: every entry in assoc.REGISTRY, including
# SOFTMAX_PAIR and MATRIX_AFFINE
# ---------------------------------------------------------------------------


def _element_for(name, rng_vals):
    """Build one monoid element for ``name`` from 4 drawn floats."""
    x, y, z, w = (jnp.float32(v) for v in rng_vals)
    if name in ("sum", "max", "min", "prod"):
        return x
    if name == "affine":
        return (jnp.abs(x) + jnp.float32(0.125), y)
    if name == "matrix_affine":
        # scalar decay broadcasting over a (2, 2) matrix update
        a = jnp.abs(x) + jnp.float32(0.125)
        B = jnp.stack([jnp.stack([y, z]), jnp.stack([z, w])])
        return (jnp.broadcast_to(a, (2, 2)), B)
    if name == "softmax_pair":
        return (x, jnp.abs(y) + jnp.float32(0.125))
    raise AssertionError(f"unhandled registry monoid {name!r}")


_quad = st.tuples(_f, _f, _f, _f)


@pytest.mark.parametrize("name", sorted(assoc.REGISTRY))
@given(e1=_quad, e2=_quad, e3=_quad)
@settings(max_examples=25, deadline=None)
def test_registry_monoid_associativity(name, e1, e2, e3):
    m = assoc.REGISTRY[name]
    a, b, c = (_element_for(name, e) for e in (e1, e2, e3))
    _tclose(m.combine(m.combine(a, b), c), m.combine(a, m.combine(b, c)),
            tol=1e-2)


@pytest.mark.parametrize("name", sorted(assoc.REGISTRY))
@given(e=_quad)
@settings(max_examples=15, deadline=None)
def test_registry_monoid_identity(name, e):
    m = assoc.REGISTRY[name]
    a = _element_for(name, e)
    ident = m.identity_like(a)
    _tclose(m.combine(ident, a), a)
    _tclose(m.combine(a, ident), a)


# ---------------------------------------------------------------------------
# the NEG_INF finite-mask invariant (softmax max-carry edge elements)
# ---------------------------------------------------------------------------


_maybe_masked = st.sampled_from(["live", "masked"])


@given(k1=_maybe_masked, k2=_maybe_masked, k3=_maybe_masked,
       e1=_quad, e2=_quad, e3=_quad)
@settings(max_examples=25, deadline=None)
def test_softmax_pair_neg_inf_edges_stay_finite(k1, k2, k3, e1, e2, e3):
    """Fully-masked blocks enter the fold as (NEG_INF, bk) elements; any
    mix of masked/live operands must combine NaN-free and associatively
    — this is what the kernels' finite NEG_INF (vs a true -inf) buys."""
    m = assoc.SOFTMAX_PAIR

    def elem(kind, vals):
        mm, ss = _element_for("softmax_pair", vals)
        if kind == "masked":
            mm = jnp.float32(assoc.NEG_INF)
        return (mm, ss)

    a, b, c = elem(k1, e1), elem(k2, e2), elem(k3, e3)
    left = m.combine(m.combine(a, b), c)
    right = m.combine(a, m.combine(b, c))
    for leaf in (*left, *right):
        assert not bool(jnp.isnan(leaf)), (k1, k2, k3)
    _tclose(left, right, tol=1e-2)


def test_neg_inf_finite_sentinel_vs_true_inf():
    """Why NEG_INF is finite: a true -inf max-carry NaNs the rescale
    (``-inf - -inf``); the -1e30 sentinel keeps exp(0)=1 arithmetic."""
    m = assoc.SOFTMAX_PAIR
    masked = (jnp.float32(assoc.NEG_INF), jnp.float32(4.0))
    out = m.combine(masked, masked)
    assert not any(bool(jnp.isnan(leaf)) for leaf in out)
    np.testing.assert_allclose(float(out[1]), 8.0)  # exp(0) = 1 arithmetic
    inf_masked = (jnp.float32(-jnp.inf), jnp.float32(4.0))
    out_inf = m.combine(inf_masked, inf_masked)
    assert bool(jnp.isnan(out_inf[1]))  # the failure the sentinel avoids


# ---------------------------------------------------------------------------
# kernel-side carried payload: the (m, l, acc) triple of the flash spec
# ---------------------------------------------------------------------------


def _payload_elem(vals, masked=False):
    x, y, z, w = (jnp.float32(v) for v in vals)
    mm = jnp.float32(assoc.NEG_INF) if masked else x
    ll = jnp.abs(y) + jnp.float32(0.125)
    acc = jnp.stack([z, w])
    return (mm[None], ll[None], acc)


@given(k1=_maybe_masked, k2=_maybe_masked, k3=_maybe_masked,
       e1=_quad, e2=_quad, e3=_quad)
@settings(max_examples=25, deadline=None)
def test_softmax_payload_triple_associativity(k1, k2, k3, e1, e2, e3):
    """The kernel spec's combine carries the weighted-value accumulator
    alongside the (m, l) pair; the lifted triple must stay associative
    (including NEG_INF masked operands) or the split-KV decoupled fold
    would diverge from the carry chain."""
    spec = assoc.softmax_pair_kernel_spec(scale=1.0)
    a = _payload_elem(e1, k1 == "masked")
    b = _payload_elem(e2, k2 == "masked")
    c = _payload_elem(e3, k3 == "masked")
    left = spec.combine(spec.combine(a, b), c)
    right = spec.combine(a, spec.combine(b, c))
    for leaf in (*left, *right):
        assert not bool(jnp.any(jnp.isnan(leaf)))
    _tclose(left, right, tol=1e-2)


@given(e=_quad)
@settings(max_examples=15, deadline=None)
def test_softmax_payload_identity_fills(e):
    """The spec's fills (NEG_INF, 0, 0) are a two-sided identity — the
    fold seeds and the chunk chain rely on it."""
    spec = assoc.softmax_pair_kernel_spec(scale=1.0)
    a = _payload_elem(e)
    ident = tuple(jnp.full_like(leaf, f)
                  for leaf, f in zip(a, spec.fills))
    _tclose(spec.combine(ident, a), a)
    _tclose(spec.combine(a, ident), a)

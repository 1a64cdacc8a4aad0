"""Monoid-generic scan engine: schedule parity, policy boundaries.

The acceptance bar for the engine refactor (interpret mode on CPU):
  * carry / decoupled return BIT-identical results for all four
    registered monoids across dtypes — the paper's organization/operator
    split holds exactly, not just approximately;
  * the tree schedule (Blelloch in-tile sweep) is bitwise identical to
    the other two wherever ``combine`` is associative in machine
    arithmetic — integers, and floats on exactly-representable data —
    and agrees to float tolerance on arbitrary normals (its balanced
    tree associates differently, so bitwise equality on arbitrary
    floats is mathematically impossible, not an implementation gap);
  * the ``policy.choose_schedule`` rule at its boundaries
    (batch == cores, single-block rows, the tree block threshold,
    itemsize mixes);
  * the engine registry covers the five families and the library monoids
    carry their kernel specs.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scan import assoc, policy, reference
from repro.kernels import scan_engine
from repro.kernels.compact import ops as kc_ops
from repro.kernels.scan_blocked import ops as sb_ops
from repro.kernels.scan_engine import monoids
from repro.kernels.segscan import ops as seg_ops
from repro.kernels.ssm_scan import ops as ssm_ops

# The pair whose in-tile network is shared — bitwise on ANY data. (The
# fused schedule shares it too, but runs only compiled on a TPU.)
SCHEDULES = ("carry", "decoupled")
# With the tree — bitwise on exactly-representable data (the tree's
# different association is exact there).
SCHEDULES4 = ("carry", "decoupled", "tree")


def _all_bit_identical(outs):
    first = outs[0]
    return all(
        all(bool(jnp.all(a == b)) for a, b in zip(first, o))
        for o in outs[1:])


# ---------------------------------------------------------------------------
# schedule-parity sweep: 2 schedules x 4 monoids x dtypes, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32, jnp.bfloat16])
@pytest.mark.parametrize("exclusive", [False, True])
def test_parity_sum(dtype, exclusive):
    rng = np.random.default_rng(0)
    if dtype == jnp.int32:
        x = jnp.asarray(rng.integers(-9, 9, (2, 4096)), dtype)
    else:
        x = jnp.asarray(rng.standard_normal((2, 4096)), dtype)
    outs = [
        (sb_ops.cumsum(x, exclusive=exclusive, interpret=True, schedule=s,
                       block_n=512),)
        for s in SCHEDULES
    ]
    assert _all_bit_identical(outs), "sum schedules must agree BITWISE"
    ref = reference.cumsum_ref(x.astype(jnp.float32))
    if exclusive:
        ref = jnp.pad(ref, ((0, 0), (1, 0)))[:, :-1]
    tol = 0.15 if dtype == jnp.bfloat16 else 3e-3
    np.testing.assert_allclose(
        np.asarray(outs[0][0], np.float64), np.asarray(ref, np.float64),
        rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_parity_segmented(dtype):
    rng = np.random.default_rng(1)
    if dtype == jnp.int32:
        v = jnp.asarray(rng.integers(-9, 9, (2, 4096)), dtype)
    else:
        v = jnp.asarray(rng.standard_normal((2, 4096)), dtype)
    f = jnp.asarray(rng.random((2, 4096)) < 0.02, jnp.int32)
    outs = [
        (seg_ops.segmented_cumsum(v, f, interpret=True, schedule=s,
                                  block_n=512),)
        for s in SCHEDULES
    ]
    assert _all_bit_identical(outs)
    ref = reference.segmented_scan_ref(v.astype(jnp.float32), f)
    np.testing.assert_allclose(
        np.asarray(outs[0][0], np.float64), np.asarray(ref, np.float64),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_parity_affine(dtype):
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.uniform(0.7, 1.0, (1, 2048, 128)), dtype)
    b = jnp.asarray(rng.standard_normal((1, 2048, 128)) * 0.1, dtype)
    outs = [
        (ssm_ops.ssm_scan(a, b, interpret=True, schedule=s, block_t=128),)
        for s in SCHEDULES
    ]
    assert _all_bit_identical(outs)
    _, ref = reference.scan_ref(
        (a.astype(jnp.float32), b.astype(jnp.float32)), "affine", axis=1)
    tol = 0.1 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(outs[0][0], np.float64), np.asarray(ref, np.float64),
        rtol=tol, atol=tol)


def test_parity_mask():
    rng = np.random.default_rng(3)
    m = jnp.asarray(rng.random((3, 4096)) < 0.5, jnp.int32)
    outs = [
        kc_ops.mask_compact(m, interpret=True, schedule=s, block_n=512)
        for s in SCHEDULES
    ]
    assert _all_bit_identical(outs)
    mn = np.asarray(m)
    excl = np.cumsum(mn, -1) - mn
    np.testing.assert_array_equal(
        np.asarray(outs[0][0]), np.where(mn != 0, excl, 4096))
    np.testing.assert_array_equal(np.asarray(outs[0][1]), mn.sum(-1))


# ---------------------------------------------------------------------------
# parity with the tree on exact data, + tree float tolerance
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("exclusive", [False, True])
def test_parity4_sum_exact(dtype, exclusive):
    """All three schedules bitwise on integer-valued data — f32/bf16
    included, since small integers are exactly representable and the
    engine widens bf16 accumulation to f32."""
    rng = np.random.default_rng(20)
    x = jnp.asarray(rng.integers(-9, 9, (2, 4096)), dtype)
    outs = [
        (sb_ops.cumsum(x, exclusive=exclusive, interpret=True, schedule=s,
                       block_n=512),)
        for s in SCHEDULES4
    ]
    assert _all_bit_identical(outs), \
        "tree must match carry BITWISE on exact data"


def test_parity4_segmented_exact():
    rng = np.random.default_rng(21)
    v = jnp.asarray(rng.integers(-9, 9, (2, 4096)), jnp.float32)
    f = jnp.asarray(rng.random((2, 4096)) < 0.02, jnp.int32)
    outs = [
        (seg_ops.segmented_cumsum(v, f, interpret=True, schedule=s,
                                  block_n=512),)
        for s in SCHEDULES4
    ]
    assert _all_bit_identical(outs)


def test_parity4_affine_exact():
    """Exact affine data: gates in {±1} and integer offsets compose to
    integer-valued states, so the tree's re-association is bit-exact."""
    rng = np.random.default_rng(22)
    a = jnp.asarray(rng.choice([-1.0, 1.0], (1, 2048, 128)), jnp.float32)
    b = jnp.asarray(rng.integers(-3, 4, (1, 2048, 128)), jnp.float32)
    outs = [
        (ssm_ops.ssm_scan(a, b, interpret=True, schedule=s, block_t=128),)
        for s in SCHEDULES4
    ]
    assert _all_bit_identical(outs)
    _, ref = reference.scan_ref((a, b), "affine", axis=1)
    np.testing.assert_array_equal(np.asarray(outs[0][0]), np.asarray(ref))


def test_parity4_mask_exact():
    rng = np.random.default_rng(23)
    m = jnp.asarray(rng.random((3, 4096)) < 0.5, jnp.int32)
    outs = [
        kc_ops.mask_compact(m, interpret=True, schedule=s, block_n=512)
        for s in SCHEDULES4
    ]
    assert _all_bit_identical(outs)


@pytest.mark.parametrize("exclusive", [False, True])
def test_tree_float_tolerance(exclusive):
    """On arbitrary float normals the tree associates differently —
    bitwise is impossible, but it must agree with carry (and the
    oracle) to float tolerance."""
    rng = np.random.default_rng(24)
    x = jnp.asarray(rng.standard_normal((2, 4096)), jnp.float32)
    tree = sb_ops.cumsum(x, exclusive=exclusive, interpret=True,
                         schedule="tree", block_n=512)
    carry = sb_ops.cumsum(x, exclusive=exclusive, interpret=True,
                          schedule="carry", block_n=512)
    np.testing.assert_allclose(np.asarray(tree), np.asarray(carry),
                               rtol=2e-4, atol=2e-4)
    ref = reference.cumsum_ref(x)
    if exclusive:
        ref = jnp.pad(ref, ((0, 0), (1, 0)))[:, :-1]
    np.testing.assert_allclose(np.asarray(tree), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_tree_non_pow2_tile():
    """Tiles whose length is not a power of two exercise the identity
    pad inside the Blelloch network (96 -> 128)."""
    rng = np.random.default_rng(25)
    x = jnp.asarray(rng.integers(-9, 9, (2, 480)), jnp.int32)
    lay = scan_engine.Rows(2, 480, 1, 96)
    (tree,) = scan_engine.scan((x,), monoids.SUM, lay, schedule="tree",
                               interpret=True)
    (carry,) = scan_engine.scan((x,), monoids.SUM, lay, schedule="carry",
                                interpret=True)
    np.testing.assert_array_equal(np.asarray(tree), np.asarray(carry))


def test_tree_fold_routes_to_carry_fold():
    """Carried-payload (transform) monoids have no in-block element axis
    to tree-organize: schedule='tree' must run the carry fold — same
    outputs as carry, no error."""
    rng = np.random.default_rng(26)
    q = jnp.asarray(rng.standard_normal((2, 128, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 128, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 128, 16)), jnp.float32)
    spec = monoids.softmax_pair(scale=0.25)
    lay = scan_engine.KVBlocks(bh=2, bh_kv=2, tq=128, tk=128, d=16,
                               bq=128, bk=64)
    out_t = scan_engine.scan((q, k, v), spec, lay, schedule="tree",
                             interpret=True)
    out_c = scan_engine.scan((q, k, v), spec, lay, schedule="carry",
                             interpret=True)
    assert _all_bit_identical([out_t, out_c])


def test_segmented_messy_flags_match_reference():
    """Fractional and negative nonzero flags are boundaries too — the
    kernel route must normalize with ``!= 0``, not truncate or max."""
    v = jnp.ones((8,), jnp.float32)
    for flags in (jnp.asarray([0, 0, 0.5, 0, 0.5, 0, 0, 0], jnp.float32),
                  jnp.asarray([0, 0, -1, 0, -3, 0, 0, 0], jnp.int32)):
        got = seg_ops.segmented_cumsum(v, flags, interpret=True)
        ref = reference.segmented_scan_ref(v, flags)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
        assert np.asarray(got).tolist() == [1, 2, 1, 2, 1, 2, 3, 4]


def test_fused_falls_back_to_decoupled():
    """Fused never falls back to decoupled. Its semaphore chain runs only
    compiled on a TPU, on grids that fit the chip's semaphore memory;
    anywhere else a fused request raises rather than running decoupled
    under fused's name, and folds, which have no fused form, refuse it
    too."""
    from repro.kernels.scan_engine import schedules
    x = jnp.asarray(
        np.random.default_rng(4).standard_normal((1, 8192)), jnp.float32)
    with pytest.raises(ValueError, match="interpret"):
        sb_ops.cumsum(x, interpret=True, schedule="fused", block_n=1024)
    big = scan_engine.Rows(1, (schedules.FUSED_MAX_CELLS + 1) * 128, 1, 128)
    xb = jnp.zeros(big.shape, jnp.float32)
    with pytest.raises(ValueError, match="semaphore"):
        scan_engine.scan((xb,), monoids.SUM, big, schedule="fused")
    lay = scan_engine.Rows(1, 8192, 1, 1024)
    with pytest.raises(ValueError, match="running"):
        scan_engine.scan((x,), monoids.SUM, lay, schedule="fused",
                         return_totals=True)
    q = jnp.ones((2, 128, 16), jnp.float32)
    kv = scan_engine.KVBlocks(bh=2, bh_kv=2, tq=128, tk=128, d=16,
                              bq=128, bk=64)
    with pytest.raises(ValueError, match="no fused form"):
        scan_engine.scan((q, q, q), monoids.softmax_pair(), kv,
                         schedule="fused", interpret=True)


# ---------------------------------------------------------------------------
# engine surface: registry, specs, validation
# ---------------------------------------------------------------------------


def test_registry_covers_five_families():
    assert set(scan_engine.monoids.REGISTRY) == {
        "sum", "segmented_sum", "affine", "mask", "softmax_pair"}
    for name, factory in scan_engine.monoids.REGISTRY.items():
        spec = factory()
        assert isinstance(spec, assoc.KernelSpec)
        assert len(spec.fills) == spec.n_leaves


def test_totals_chain_bitwise_across_schedules():
    """``scan(..., return_totals=True)`` returns the RUNNING chunk-totals
    chain (combined through chunk j): identical bits under all three
    schedules (integer data, so the tree is exact too), last column ==
    the row reduction — what ``mask_compact`` uses for O(B·chunks)
    survivor counts (ROADMAP follow-up)."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(-9, 9, (3, 2048)), jnp.int32)
    lay = scan_engine.Rows(3, 2048, 1, 256)
    chains = []
    for s in SCHEDULES4:
        (out,), (tot,) = scan_engine.scan(
            (x,), monoids.SUM, lay, schedule=s, interpret=True,
            return_totals=True)
        assert tot.shape == (3, 8)
        np.testing.assert_array_equal(
            np.asarray(tot[:, -1]), np.asarray(x).sum(-1))
        chains.append((out, tot))
    assert _all_bit_identical(chains)


def test_mask_compact_counts_from_totals_chain():
    """Counts derived from the totals chain == a full jnp reduction,
    for every schedule, ragged lengths and float masks included."""
    rng = np.random.default_rng(10)
    for shape in ((2, 517), (4, 4096), (1, 128)):
        m = jnp.asarray(rng.random(shape) < 0.3, jnp.float32)
        for s in SCHEDULES4:
            _, counts = kc_ops.mask_compact(m, interpret=True, schedule=s,
                                            block_n=256)
            np.testing.assert_array_equal(
                np.asarray(counts), (np.asarray(m) != 0).sum(-1))


def test_library_monoids_carry_kernel_specs():
    assert assoc.SUM.kernel_spec is assoc.SUM_KERNEL
    assert assoc.AFFINE.kernel_spec is assoc.AFFINE_KERNEL
    assert assoc.segmented(assoc.SUM).kernel_spec \
        is assoc.SEGMENTED_SUM_KERNEL
    assert assoc.segmented(assoc.MAX).kernel_spec is None  # not registered


def test_engine_rejects_unknown_schedule_and_bad_exclusive():
    x = jnp.ones((2, 256), jnp.float32)
    lay = scan_engine.Rows(2, 256, 2, 128)
    with pytest.raises(ValueError):
        scan_engine.scan((x,), monoids.SUM, lay, schedule="bogus")
    m = jnp.ones((2, 256), jnp.int32)
    with pytest.raises(ValueError):
        scan_engine.scan((m,), monoids.mask(256), lay, schedule="carry",
                         exclusive=True)
    with pytest.raises(ValueError):
        scan_engine.Rows(2, 300, 2, 128)  # not divisible by the block


# ---------------------------------------------------------------------------
# policy boundaries (choose_schedule)
# ---------------------------------------------------------------------------


def test_choose_schedule_batch_boundary():
    n = 1 << 22
    cores = policy.NUM_CORES
    # batch == cores: rows exactly fill the machine -> carry
    assert policy.choose_schedule(cores, n) == "carry"
    # one fewer row: spare = cores // (cores-1) == 1 < 2 -> still carry
    assert policy.choose_schedule(cores - 1, n) == "carry"
    # half the cores busy -> parallel-sequence schedule
    assert policy.choose_schedule(cores // 2, n) == "decoupled"


def test_choose_schedule_single_block_rows():
    # a row inside ONE block has nothing to parallelize, whatever batch is
    assert policy.choose_schedule(1, 2048, block_elems=2048) == "carry"
    assert policy.choose_schedule(1, 4096, block_elems=4096) == "carry"
    # chunks must cover the spare cores: 4 chunks < 8 spare -> carry
    assert policy.choose_schedule(1, 8192, block_elems=2048) == "carry"
    # exactly spare chunks -> flip
    n = policy.NUM_CORES * 2048
    assert policy.choose_schedule(1, n, block_elems=2048) == "decoupled"


def test_choose_schedule_tree_boundary():
    """The policy never answers tree: its in-tile network does about
    twice the log network's work on the chip, and no cell has shown it
    winning. Saturated rows keep carry and under-subscribed rows spread
    with decoupled, whatever the block size."""
    n = 1 << 22
    cores = policy.NUM_CORES
    for block in (2048, 8192, 16384, 1 << 16):
        assert policy.choose_schedule(cores, n, block_elems=block) == "carry"
        assert policy.choose_schedule(cores * 4, n,
                                      block_elems=block) == "carry"
        assert policy.choose_schedule(cores // 2, n,
                                      block_elems=block) == "decoupled"
    assert not hasattr(policy, "TREE_BLOCK_ELEMS")


@pytest.mark.parametrize("itemsize", [1, 2, 4, 8])
def test_choose_itemsize_mixes(itemsize):
    """The algorithm threshold scales with itemsize; the schedule rule is
    itemsize-blind (it counts chunks, not bytes)."""
    n = 1 << 21  # 2M elems: spans the VMEM budget across the dtype sweep
    choice = policy.choose(n, itemsize=itemsize, batch=1)
    if n * itemsize <= policy.VMEM_BLOCK_BUDGET:
        assert choice.algorithm == "horizontal"
    else:
        assert choice.algorithm == "kernel"
        assert choice.schedule == "decoupled"
    assert policy.choose_schedule(1, n) == "decoupled"


def test_schedule_threaded_through_api():
    """core.scan.api 'auto' hands the policy's schedule to the kernel."""
    from repro.core import scan as scanlib
    x = jnp.asarray(
        np.random.default_rng(5).standard_normal(4096), jnp.float32)
    got = scanlib.scan(x, "sum", algorithm="kernel", interpret=True,
                       schedule="decoupled")
    np.testing.assert_allclose(np.asarray(got), np.cumsum(np.asarray(x)),
                               rtol=2e-4, atol=2e-4)

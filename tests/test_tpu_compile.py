"""The main path's kernels compile for a TPU v5e, at real sizes.

No chip is needed: the TPU compiler is installed, and it compiles for a
``v5e:2x2`` topology that is described rather than attached. These
tests catch what interpret mode cannot — block shapes the chip's tiling
refuses, layouts Mosaic cannot lower, grids that overflow the chip's
semaphore memory — before any chip time is spent. Each compile passes
``interpret=False`` itself: code that asks ``jax.default_backend()``
sees the CPU here and would otherwise pick interpret mode.

Nothing touches the topology while this module is imported: it is
described in the module-scoped fixture, which skips when it cannot be.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import scan as scanlib
from repro.dist.sharding import make_mesh
from repro.kernels import scan_engine
from repro.kernels.compact import ops as kc_ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.scan_blocked import ops as sb_ops
from repro.kernels.scan_engine import monoids
from repro.kernels.segscan import ops as seg_ops
from repro.kernels.ssm_scan import ops as ssm_ops
from repro.relational.compact import filter_compact

ROWS, N = 8, 1 << 24        # the (8, 2^24) class of chip_smoke.py
N_FUSED = 1 << 19           # 256 cells: the largest fused grid that fits
SSM = (4, 8192, 1024)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes, kernel=True):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    return compiled


F32, I32, BF16 = jnp.float32, jnp.int32, jnp.bfloat16


def _sum(s):
    n = N_FUSED if s == "fused" else N
    return (lambda x: sb_ops.cumsum(x, schedule=s, interpret=False),
            [((ROWS, n), F32)])


def _segmented(s):
    n = N_FUSED if s == "fused" else N
    return (lambda v, f: seg_ops.segmented_cumsum(
        v, f, schedule=s, interpret=False), [((ROWS, n), F32),
                                             ((ROWS, n), I32)])


def _affine(s):
    return (lambda a, b: ssm_ops.ssm_scan(a, b, schedule=s,
                                          interpret=False),
            [(SSM, F32), (SSM, F32)])


def _mask(s):
    if s == "fused":
        # compact's wrapper keeps running totals, which fused does not;
        # the engine runs the mask monoid fused without them
        lay = scan_engine.Rows(ROWS, N_FUSED, ROWS, 2048)
        return (lambda m: scan_engine.scan(
            (m,), monoids.mask(N_FUSED), lay, schedule="fused"),
            [((ROWS, N_FUSED), I32)])
    return (lambda m: kc_ops.mask_compact(m, schedule=s, interpret=False),
            [((ROWS, N), I32)])


FAMILIES = {"sum": _sum, "segmented": _segmented, "affine": _affine,
            "mask": _mask}


@pytest.mark.parametrize("schedule", ["carry", "decoupled", "tree",
                                      "fused"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_schedule_compiles(one_chip, family, schedule):
    fn, shapes = FAMILIES[family](schedule)
    _compile(fn, one_chip, *shapes)


def test_fused_grid_past_semaphore_memory_refused(one_chip):
    """A fused grid one row of chunks past the cap is refused before the
    compiler is asked — never run as another schedule."""
    fn, shapes = _sum("fused")
    (rows, n), dt = shapes[0]
    with pytest.raises(ValueError, match="semaphore"):
        _compile(fn, one_chip, ((rows, 2 * n), dt))


@pytest.mark.parametrize("n", [1 << 26])
def test_public_cumsum_compiles(one_chip, n):
    """The paper's headline operation: one long f32 row through the
    public policy path."""
    _compile(lambda x: scanlib.cumsum(x, interpret=False), one_chip,
             ((n,), F32))


_ARRAY = re.compile(
    r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(", re.M)


def _arrays(hlo_text):
    """(elements, bytes, opcode) of every array-valued instruction."""
    out = []
    for dt, dims, op in _ARRAY.findall(hlo_text):
        size = 1
        for d in filter(None, dims.split(",")):
            size *= int(d)
        bits = int(re.sub(r"\D", "", dt) or 8)        # pred: a byte
        out.append((size, size * bits // 8, op))
    return out


def test_public_cumsum_column_reaches_the_kernels_by_bitcast(one_chip):
    """scan.column's call: a 2^28 f32 column is viewed as lane-dense
    (n/1024, 8, 128) vregs by bitcasts alone — no copy or relayout of
    the 1 GiB operand — and its chunk totals are 1,024 (1, 128) planes,
    every chain array under 1 MiB."""
    n = 1 << 28
    compiled = _compile(lambda x: scanlib.cumsum(x, interpret=False),
                        one_chip, ((n,), F32))
    arrays = _arrays(compiled.as_text())
    column = {op for size, _, op in arrays if size == n}
    assert column == {"parameter", "bitcast", "custom-call"}, column
    chains = [nbytes for size, nbytes, _ in arrays if 1 < size < n]
    assert chains and max(chains) < 1 << 20, chains
    assert max(size for size, _, _ in arrays if size < n) == 1024 * 128


@pytest.mark.parametrize("n", [2049, 65536])
def test_filter_compact_compiles(one_chip, n):
    """The serve engine's page-pool compaction (2049 = 8 slots x 256
    pages + the null page) and a large pool."""
    _compile(lambda v, m: filter_compact(v, m, algorithm="kernel",
                                         interpret=False),
             one_chip, ((n,), I32), ((n,), jnp.bool_))


# granite-moe-1b-a400m attention widths: 16 query heads, 8 kv, head 64.
Q = (1, 16, 4096, 64)
KV = (1, 8, 4096, 64)


@pytest.mark.parametrize("schedule", ["carry", "decoupled"])
def test_flash_forward_compiles(one_chip, schedule):
    _compile(lambda q, k, v: flash_attention(
        q, k, v, causal=True, schedule=schedule, interpret=False),
        one_chip, (Q, BF16), (KV, BF16), (KV, BF16))


@pytest.mark.parametrize("schedule", ["carry", "decoupled"])
def test_flash_backward_compiles(one_chip, schedule):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, schedule=schedule,
                              interpret=False)
        return jnp.sum(out.astype(F32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
             (Q, BF16), (KV, BF16), (KV, BF16))


@pytest.mark.parametrize("exchange", ["all_gather", "hillis_permute",
                                      "ring"])
def test_sharded_scan_compiles_for_four_chips(topo, exchange):
    """``chip_smoke.py --chips 4``: 2^30 int32 over a 4-chip mesh through
    ``scan_sharded``'s default per-chip scan (the blocked jnp scan; no
    Pallas kernel), each chip holding its own quarter."""
    mesh = make_mesh((4,), ("d",), devices=topo.devices)
    sh = NamedSharding(mesh, P("d"))
    compiled = _compile(
        lambda v: scanlib.scan_sharded(
            v, "sum", mesh=mesh, axis_name="d", spec=P("d"),
            carry_exchange=exchange),
        sh, ((1 << 30,), I32), kernel=False)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == (1 << 30) * 4 // 4
    assert mem.output_size_in_bytes == (1 << 30) * 4 // 4

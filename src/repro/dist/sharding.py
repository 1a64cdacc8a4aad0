"""Logical-axis sharding tables + the installed-mesh context.

Model code never names mesh axes directly.  It annotates arrays with
LOGICAL axes ("batch", "heads", "mlp", ...) via ``repro.dist.shard`` and
this module resolves them against the currently installed mesh through a
rules table (logical axis -> tuple of mesh axes).  The production meshes
(launch/mesh.py) use axes ('pod',) 'data', 'model'; tests install small
debug meshes; with no mesh installed every annotation is a no-op — the
same model code runs single-device CPU tests and 512-chip dry-runs.

Rule overrides per launch cell (e.g. long_500k's sequence-over-everything
sharding) are passed to ``use_mesh(mesh, rules)`` and merged over the
defaults for the duration of the context.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Any, Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Pytree = Any


def make_mesh(shape, axes, *, devices=None) -> Mesh:
    """A mesh whose axes are all ``Auto``: the compiler propagates
    shardings from the annotations, as every caller here expects.
    (``jax.make_mesh`` defaults to ``Explicit`` axes, under which a
    constant built inside ``shard_map`` or ``jit`` without a mesh
    context has no sharding to take.)"""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))

# Logical axis -> mesh axes (filtered to the installed mesh's axis names).
# The data-parallel axes shard 'batch'; the tensor/expert-parallel axis
# 'model' shards exactly one logical dim per array (GSPMD forbids reuse of
# a mesh axis within one spec — the tables below are arranged so resolved
# specs never repeat an axis).
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),               # sequence replicated by default ...
    "seq_shard": ("model",),  # ... except KV/state slots in serve cells
    "embed": (),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "experts": ("model",),
    "capacity": (),
    "ssm_inner": ("model",),
    "layers": (),
}


class _State(threading.local):
    def __init__(self):
        self.stack: list[tuple[Mesh, dict[str, tuple[str, ...]]]] = []


_STATE = _State()


def current_mesh() -> Optional[Mesh]:
    """The innermost installed mesh, or None outside any ``use_mesh``."""
    return _STATE.stack[-1][0] if _STATE.stack else None


def current_rules() -> dict[str, tuple[str, ...]]:
    return _STATE.stack[-1][1] if _STATE.stack else dict(DEFAULT_RULES)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[dict] = None):
    """Install ``mesh`` (+ optional logical-rule overrides) for the block."""
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update({k: tuple(v) if not isinstance(v, str) else (v,)
                       for k, v in rules.items()})
    _STATE.stack.append((mesh, merged))
    try:
        with mesh:
            yield mesh
    finally:
        _STATE.stack.pop()


def resolve(axes) -> P:
    """Logical axis names (or None) per dim -> PartitionSpec.

    Unknown logical names and names whose mesh axes are absent from the
    installed mesh resolve to None (replicated).
    """
    mesh = current_mesh()
    rules = current_rules()
    entries = []
    for ax in axes:
        if ax is None:
            entries.append(None)
            continue
        mesh_axes = rules.get(ax, ())
        if mesh is not None:
            mesh_axes = tuple(a for a in mesh_axes if a in mesh.shape)
        if not mesh_axes:
            entries.append(None)
        elif len(mesh_axes) == 1:
            entries.append(mesh_axes[0])
        else:
            entries.append(mesh_axes)
    return P(*entries)


def sanitize_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop spec entries that do not divide the dim (or reuse an axis).

    jit in/out shardings require every sharded dim to be divisible by the
    product of its mesh-axis sizes; undivisible entries degrade to
    replicated rather than error (small debug meshes, odd head counts).
    """
    used: set = set()
    entries = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            entries.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        axes = tuple(a for a in axes if a in mesh.shape and a not in used)
        while axes and dim % math.prod(mesh.shape[a] for a in axes):
            axes = axes[:-1]           # shed trailing axes until it fits
        used.update(axes)
        if not axes:
            entries.append(None)
        elif len(axes) == 1:
            entries.append(axes[0])
        else:
            entries.append(axes)
    return P(*entries)


def shard(x: jax.Array, *axes) -> jax.Array:
    """Constrain ``x``'s sharding by logical axes; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = sanitize_spec(resolve(axes), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding (Megatron-style tensor parallelism over 'model').
#
# Keyed on the leaf's dict key; the tuple gives logical axes for the
# TRAILING dims — leading dims (the stacked-layers 'periods' axis) are
# replicated. 3D entries are the MoE per-expert stacks: experts over
# 'model', per-expert matrices replicated (the expert einsum then carries
# (shards@data, E@model) — see models/layers/moe.py).
# ---------------------------------------------------------------------------

PARAM_RULES: dict[str, tuple] = {
    # attention: fan-out sharded on q/k/v, fan-in on the output proj
    "wq": (None, "heads"),
    "wk": (None, "kv_heads"),
    "wv": (None, "kv_heads"),
    "wo": ("heads", None),
    # dense mlp
    "w_up": (None, "mlp"),
    "w_gate": (None, "mlp"),
    "w_down": ("mlp", None),
    # xlstm projections
    "w_q": (None, "ssm_inner"),
    "w_k": (None, "ssm_inner"),
    "w_v": (None, "ssm_inner"),
    "w_out": ("ssm_inner", None),
    # mamba-style ssm
    "in_proj": (None, "ssm_inner"),
    "out_proj": ("ssm_inner", None),
    # embedding / head
    "table": ("vocab", None),
    "lm_head": (None, "vocab"),
    "router": (None, None),
}

_MOE_RULES: dict[str, tuple] = {
    "w_up": ("experts", None, "mlp"),
    "w_gate": ("experts", None, "mlp"),
    "w_down": ("experts", "mlp", None),
}


def _leaf_rule(path, leaf) -> tuple:
    names = [str(getattr(p, "key", getattr(p, "name", p))) for p in path]
    name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    if name == "w" and parent in PARAM_RULES:   # {"lm_head": {"w": ...}}
        name = parent
    rule = PARAM_RULES.get(name)
    if rule is None:
        return (None,) * leaf.ndim
    moe = _MOE_RULES.get(name)
    # Stacked-layer leaves carry a leading 'periods' dim; MoE leaves carry
    # a leading experts dim on top of the 2D rule — disambiguate by ndim.
    if moe is not None and leaf.ndim >= 3 and leaf.ndim - len(moe) in (0, 1):
        rule = moe
    if len(rule) > leaf.ndim:
        return (None,) * leaf.ndim
    return (None,) * (leaf.ndim - len(rule)) + tuple(rule)


def spec_for_params(params: Pytree) -> Pytree:
    """PartitionSpec tree for a parameter pytree under the installed mesh.

    Call inside ``use_mesh``; unknown leaves replicate. Specs are
    sanitized against leaf shapes, so odd dims degrade gracefully.
    """
    mesh = current_mesh()

    def one(path, leaf):
        spec = resolve(_leaf_rule(path, leaf))
        if mesh is not None:
            spec = sanitize_spec(spec, leaf.shape, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(one, params)

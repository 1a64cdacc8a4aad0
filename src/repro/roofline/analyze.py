"""Three-term roofline from a compiled (AOT) step.

    compute    = HLO_FLOPs   / (chips · peak_FLOP/s)
    memory     = HLO_bytes   / (chips · HBM_bw)
    collective = coll_bytes  / (chips · link_bw · links)

MEASURED CONVENTION: ``compiled.cost_analysis()`` on an SPMD-partitioned
module reports the PER-DEVICE program (verified: an 8-way-sharded matmul
reports total/8 flops), i.e. the "/ chips" division in the formulas above
is already applied by XLA. The terms below therefore use the per-device
numbers directly against per-chip peak rates — equivalent to the spec's
formulas. The same holds for the optimized HLO text: collective op shapes
are per-device shapes, so summed collective bytes are per-chip wire bytes
(all-gather output = full gathered tensor ≈ bytes through each chip's
links for a ring schedule; all-reduce counted once ≈ the reduce-scatter
half — a deliberate ~2x-optimistic convention, constant across cells).

Collective bytes are NOT in cost_analysis: we parse the optimized HLO
(``compiled.as_text()``) and sum shapes of every all-gather / all-reduce
/ reduce-scatter / all-to-all / collective-permute. Cross-pod collectives
(replica groups spanning pods) are attributed to the DCN term separately
— the slow hop at 1000+ node scale.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Optional

import numpy as np

from repro.launch import mesh as meshlib

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  "bf16[16,1024,512]{2,1,0} all-gather(...)"
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    nbytes = _DTYPE_BYTES.get(dt)
    if nbytes is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nbytes


def collective_bytes_from_hlo(hlo_text: str) -> dict[str, int]:
    """Sum of output-shape bytes per collective kind in the optimized HLO.

    Output-shape convention: for all-gather the output is the gathered
    (full) tensor = bytes that cross links; for reduce-scatter the input
    is larger but wire bytes ≈ input ≈ output·shards — we report output
    bytes for a conservative, uniform convention and scale per-op in the
    roofline terms where it matters. Fusion parameters are skipped; both
    sync and async (``-start``) forms are counted once.
    """
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        # match "<name> = <shape(s)> <op>(" — shape may be a tuple
        m = re.match(
            r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([a-z][a-z\-]*)\(",
            line)
        if not m:
            continue
        shape_str, op = m.groups()
        base = op.replace("-start", "").replace("-done", "")
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        pieces = [_shape_bytes(p.group(0)) for p in
                  re.finditer(r"[a-z0-9]+\[[0-9,]*\]", shape_str)]
        if not pieces:
            continue
        # async ("-start") ops produce (operand, result) tuples — count the
        # RESULT (last element), matching the sync-op output convention.
        total = pieces[-1] if op.endswith("-start") and len(pieces) > 1 \
            else sum(pieces)
        out[base] += total
    return out


def _parse_replica_groups(line: str):
    """Yield device-id groups from either HLO replica-group syntax.

    Explicit:  replica_groups={{0,1},{2,3}}
    Iota:      replica_groups=[4,4]<=[16]            (reshape of arange)
               replica_groups=[4,4]<=[4,4]T(1,0)     (transposed arange)
    """
    m = re.search(r"replica_groups=\{\{([^=]*?)\}\}", line)
    if m:
        for grp in m.group(1).split("},{"):
            yield [int(x) for x in re.findall(r"\d+", grp)]
        return
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?",
        line)
    if not m:
        return
    g, s, dims_s, perm_s = m.groups()
    dims = [int(x) for x in dims_s.split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if perm_s:
        ids = ids.transpose([int(x) for x in perm_s.split(",")])
    ids = ids.reshape(int(g), int(s))
    for row in ids:
        yield row.tolist()


def _cross_pod_bytes(hlo_text: str, chips_per_pod: int) -> int:
    """Bytes of collectives whose replica groups span pod boundaries."""
    total = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(
            r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+([a-z][a-z\-]*)\(",
            line)
        if not m:
            continue
        shape_str, op = m.groups()
        base = op.replace("-start", "").replace("-done", "")
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        spans = False
        for ids in _parse_replica_groups(line):
            if ids and (max(ids) // chips_per_pod) != (min(ids) //
                                                       chips_per_pod):
                spans = True
                break
        # collective-permute: source_target_pairs instead of replica_groups
        if not spans and "source_target_pairs" in line:
            pm = re.search(r"source_target_pairs=\{([^}]*(?:\},\{[^}]*)*)\}",
                           line)
            if pm:
                for pair in re.findall(r"\{(\d+),(\d+)\}", pm.group(0)):
                    a, b = int(pair[0]), int(pair[1])
                    if a // chips_per_pod != b // chips_per_pod:
                        spans = True
                        break
        if spans:
            pieces = [_shape_bytes(p.group(0)) for p in
                      re.finditer(r"[a-z0-9]+\[[0-9,]*\]", shape_str)]
            if pieces:
                total += (pieces[-1] if op.endswith("-start")
                          and len(pieces) > 1 else sum(pieces))
    return total


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: dict[str, int]
    cross_pod_bytes: int
    compute_s: float
    memory_s: float
    collective_s: float
    dcn_s: float
    dominant: str
    model_flops: float
    useful_ratio: float        # MODEL_FLOPS / HLO_FLOPs
    bytes_per_device: Optional[float] = None
    peak_memory_per_device: Optional[float] = None

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=1)

    @property
    def bound(self) -> float:
        """Roofline-implied step seconds (max of the three terms)."""
        return max(self.compute_s, self.memory_s, self.collective_s,
                   self.dcn_s)


def model_flops(cfg, batch: int, seq: int, kind: str) -> float:
    """MODEL_FLOPS = 6·N·D for training, 2·N·D for inference forward.

    N = active params (MoE: top-k experts only); D = tokens processed.
    Decode processes batch·1 new tokens per step.
    """
    n = cfg.active_param_count()
    if kind == "train":
        per_tok = 6.0 * n
        tokens = batch * seq
    elif kind == "prefill":
        per_tok = 2.0 * n
        tokens = batch * seq
    else:  # decode: one token per sequence
        per_tok = 2.0 * n
        tokens = batch * 1
    return per_tok * tokens


def analyze_compiled(
    compiled,
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    cfg,
    batch: int,
    seq: int,
    kind: str,
    device_kind: str,
    hlo_text: Optional[str] = None,
    chips_per_pod: int = 256,
) -> RooflineReport:
    """``device_kind`` names the chip the program is costed for — its
    peaks come from ``launch.mesh.PEAKS``, and an unknown kind raises."""
    pk = meshlib.peaks(device_kind)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes_from_hlo(text)
    xpod = _cross_pod_bytes(text, chips_per_pod) if chips > chips_per_pod \
        else 0
    coll_total = sum(coll.values())

    # cost_analysis numbers are PER-DEVICE (see module docstring): compare
    # against per-chip peak rates directly.
    compute_s = flops / pk.flops_bf16
    memory_s = nbytes / pk.hbm_bw
    collective_s = coll_total / (pk.ici_bw * pk.ici_links)
    dcn_s = xpod / pk.dcn_bw

    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s, "dcn": dcn_s}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cfg, batch, seq, kind)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {
            "bytes_per_device": float(
                getattr(ma, "argument_size_in_bytes", 0) +
                getattr(ma, "output_size_in_bytes", 0)),
            "peak_memory_per_device": float(
                getattr(ma, "temp_size_in_bytes", 0) +
                getattr(ma, "argument_size_in_bytes", 0)),
        }
    except Exception:  # noqa: BLE001 — memory stats are best-effort
        pass

    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=nbytes, collective_bytes=coll,
        cross_pod_bytes=xpod, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dcn_s=dcn_s, dominant=dominant,
        model_flops=mf,
        # useful_ratio compares per-device useful flops to per-device HLO
        # flops (cost_analysis is per-device).
        useful_ratio=((mf / chips) / flops if flops else 0.0),
        **mem,
    )

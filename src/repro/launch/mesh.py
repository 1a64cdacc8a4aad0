"""Mesh builders and per-chip peaks (importing never touches devices).

Target: TPU v5e. Single pod = 16×16 = 256 chips (data, model); multi-pod
= 2 pods = 512 chips with a leading 'pod' axis. DCN links the pods; ICI
links chips in-pod — the axis order (pod outermost) matches GSPMD's
expectation that the slowest collective axis is outermost.
"""

from __future__ import annotations

import dataclasses

import jax

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_device_mesh(*, multi_pod: bool = False):
    """The production layout when all of its chips are present, else one
    data axis over every device present (one chip: a 1×1 mesh)."""
    n = len(jax.devices())
    if n == (512 if multi_pod else 256):
        return make_production_mesh(multi_pod=multi_pod)
    if multi_pod:
        raise ValueError(f"the multi-pod mesh needs 512 devices, found {n}")
    return make_mesh((n, 1), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks the roofline divides by."""

    flops_bf16: float   # FLOP/s
    hbm_bw: float       # bytes/s
    ici_bw: float       # bytes/s per link, one direction
    ici_links: int      # in-pod 2D torus
    dcn_bw: float       # bytes/s per host NIC (the pod axis)
    source: str


#: Keyed by ``jax.devices()[0].device_kind``.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9, ici_links=4,
        dcn_bw=25e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip (4 links "
               "of 50 GB/s). DCN 25 GB/s is an assumed 200 Gbit/s host "
               "NIC, not a published chip figure."),
}

#: The chip the dry-run's production meshes model.
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """The peak table of ``device_kind``; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None

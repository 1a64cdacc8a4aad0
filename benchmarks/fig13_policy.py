"""Paper Fig. 13 — high-bandwidth memory: when NOT to partition.

On KNL's MCDRAM the paper found partitioning overhead exceeds its payoff
once bandwidth is abundant. Codified in ``core/scan/policy.py``: we show
the policy flipping algorithms as the bandwidth regime changes, and the
roofline arithmetic behind it (bytes moved × bandwidth vs sync overhead)
for the v5e HBM numbers.
"""

from __future__ import annotations

from benchmarks.common import Table
from repro.core.scan.policy import choose
from repro.launch.mesh import TARGET_KIND, peaks


def run() -> Table:
    t = Table("Fig 13 — policy under bandwidth regimes",
              ["n floats", "bandwidth", "algorithm", "block", "reason"])
    for n in (1 << 14, 1 << 22, 1 << 28):
        for abundant in (False, True):
            c = choose(n, itemsize=4, bandwidth_abundant=abundant)
            t.add(n, "abundant" if abundant else "bound", c.algorithm,
                  c.block_size, c.reason[:48])
    return t


def run_traffic_model() -> Table:
    """Bytes-moved model behind Obs 2 (per element, f32):
    unfused two-pass = 4 slow-memory ops/elem (r+w pass1, r+w pass2) for
    v1; partitioned = 2 (r+w once, second pass in cache)."""
    t = Table("Fig 13b — slow-memory traffic model @ v5e HBM",
              ["algorithm", "bytes/elem", "s per Gelem", "note"])
    rows = [
        ("TwoPass v1", 16, "pass1 r+w, pass2 r+w"),
        ("TwoPass v2", 12, "pass1 r, pass2 r+w"),
        ("Blocked(-P)", 8, "one fused pass: r+w"),
        ("Kernel(-P)", 8, "same, explicit VMEM tiles"),
    ]
    for name, bpe, note in rows:
        t.add(name, bpe, bpe * 1e9 / peaks(TARGET_KIND).hbm_bw, note)
    return t


if __name__ == "__main__":
    run().show()
    run_traffic_model().show()

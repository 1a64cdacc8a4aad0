"""The paper's multithreaded two-pass scan across devices (shard_map).

Runs on 8 placeholder CPU devices; the same code drives the 256-chip
mesh. Shows variants 1/2 and the three carry-exchange schedules with
their collective footprints.

    PYTHONPATH=src python examples/distributed_scan.py
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax                                                        # noqa: E402
import jax.numpy as jnp                                           # noqa: E402
import numpy as np                                                # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P        # noqa: E402

from repro.core import scan as scanlib                            # noqa: E402
from repro.dist.sharding import make_mesh                         # noqa: E402
from repro.roofline.analyze import collective_bytes_from_hlo      # noqa: E402


def main():
    mesh = make_mesh((8,), ("d",))
    n = 1 << 20
    x = jnp.asarray(np.random.default_rng(0).standard_normal(n), jnp.float32)
    sh = NamedSharding(mesh, P("d"))
    xs = jax.device_put(x, sh)
    ref = np.cumsum(np.asarray(x), dtype=np.float64)

    for variant in (1, 2):
        for exchange in ("all_gather", "hillis_permute", "ring"):
            fn = jax.jit(lambda v: scanlib.scan_sharded(
                v, "sum", mesh=mesh, axis_name="d", spec=P("d"),
                variant=variant, carry_exchange=exchange,
                local_algorithm="blocked", block_size=1 << 16))
            y = fn(xs)
            ok = np.allclose(np.asarray(y), ref, rtol=1e-3, atol=1e-1)
            coll = collective_bytes_from_hlo(
                fn.lower(xs).compile().as_text())
            total = sum(coll.values())
            print(f"variant={variant} exchange={exchange:<14} ok={ok} "
                  f"collective_bytes={total}")

    # The affine monoid (SSM sequence parallelism) over the same machinery.
    a = jnp.asarray(np.random.default_rng(1).uniform(0.9, 1.0, n),
                    jnp.float32)
    b = jnp.asarray(np.random.default_rng(2).standard_normal(n), jnp.float32)
    _, h = scanlib.scan_sharded(
        (jax.device_put(a, sh), jax.device_put(b, sh)), "affine",
        mesh=mesh, axis_name="d", spec=P("d"),
        carry_exchange="hillis_permute", local_algorithm="ref")
    print("distributed affine scan final state:", float(h[-1]))


if __name__ == "__main__":
    main()

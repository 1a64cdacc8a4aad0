"""Distribution layer: logical-axis sharding over the installed mesh."""

from repro.dist import sharding
from repro.dist.sharding import (current_mesh, make_mesh, resolve,
                                 sanitize_spec, shard, spec_for_params,
                                 use_mesh)

__all__ = [
    "current_mesh", "make_mesh", "resolve", "sanitize_spec", "shard",
    "sharding", "spec_for_params", "use_mesh",
]

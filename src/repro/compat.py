"""JAX API helpers shared across the repo."""
from __future__ import annotations

import jax


def shard_map(fn, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map``; ``check_vma=False`` skips replication checking."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

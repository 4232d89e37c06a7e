"""The repo's spelling of two JAX entry points.

``shard_map`` is ``jax.shard_map`` with the replication check off by
default: collectives inside the worker are explicit, so the engine only
ever disables it. ``enable_x64`` is ``jax.enable_x64``. One installation
is supported (the JAX in this container); there is no fallback to the
``jax.experimental`` spellings of older releases.
"""

import jax

__all__ = ["enable_x64", "shard_map"]

enable_x64 = jax.enable_x64


def shard_map(f, mesh=None, in_specs=None, out_specs=None, check_vma=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

"""Uniform ``shard_map`` front-end.

``shard_map`` lives at ``jax.shard_map`` (its replication check is the
``check_vma`` keyword). All internal users (``core.grid``, ``core.bsp``,
``distributed.overlap``) import it from here, so the keyword default is
set in one place.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the replication check off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)

"""Device mesh construction.

The reference routes work to storage nodes through a region cache over
gRPC; here placement is a jax.sharding.Mesh. Two axes:

  * "shard" — the data-partition axis (the region analogue). Scan/agg
    fragments data-parallel over it; join exchanges all_to_all over it.
    Laid out innermost so its collectives ride ICI.
  * "dcn"   — the multi-slice tier. Hierarchical merges (partial aggs)
    reduce over "shard" first, then "dcn", mirroring the reference's
    node-local workers -> cross-node coprocessor merge split.

A 1-D mesh (dcn=1) is the common case on a single slice.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["make_mesh", "shard_axis", "dcn_axis"]


shard_axis = "shard"
dcn_axis = "dcn"


def make_mesh(n_shards: Optional[int] = None, n_dcn: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a ("dcn", "shard") mesh over the available devices."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_shards is None:
        n_shards = len(devs) // n_dcn
    total = n_dcn * n_shards
    if total > len(devs):
        raise ValueError(
            f"mesh {n_dcn}x{n_shards} needs {total} devices, have {len(devs)}")
    grid = np.asarray(devs[:total]).reshape(n_dcn, n_shards)
    return Mesh(grid, (dcn_axis, shard_axis))

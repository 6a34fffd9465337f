"""The one ``shard_map`` entry point every caller goes through.

Wraps ``jax.shard_map`` so the offload runtime, tests and benchmarks
spell its replication / varying-manual-axes checker the same way
(``check=``, forwarded as ``check_vma=``).
"""

from __future__ import annotations

import jax

__all__ = ["shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """Map ``f`` over shards of a mesh; ``check`` toggles the
    varying-manual-axes checker (``check_vma``)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)

"""``shard_map`` with the replication check off, shared by the runtime.

Both callers (``runtime.pipeline``, ``runtime.butterfly_sharding``) disable
the check on purpose: the pipeline's output psum breaks per-shard
replication tracking by construction, and the butterfly wrapper psums its
weight gradients explicitly so their semantics never depend on the check's
behavior.
"""

from __future__ import annotations

import jax


def shard_map_compat(f, *, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


__all__ = ["shard_map_compat"]

"""L3 cluster + device-mesh parallelism.

Reference: cluster.go, gossip/, broadcast.go, http/client.go. Two scales of
parallelism live here:

- ``topology`` / ``cluster`` / ``client``: host-level scale-out — hash
  partitioning, replica chains, HTTP scatter-gather, anti-entropy;
- ``mesh``: chip-level scale-out — jax.sharding.Mesh execution of whole
  query batches with psum reductions over ICI (replaces the reference's
  per-node goroutine hot loop AND its HTTP reduce for intra-pod shards);
- ``multihost``: jax.distributed process-group init + DCN/ICI-aware mesh
  construction (words axis pinned within a host's ICI domain).
"""

from pilosa_tpu.parallel.topology import (
    PARTITION_N,
    Node,
    Topology,
    partition,
)

__all__ = ["Node", "Topology", "partition", "PARTITION_N", "shard_map"]


def shard_map(f, *, mesh, in_specs, out_specs, check_rep: bool = True):
    """THE repo-wide ``shard_map`` entry: every mesh program imports it
    from here, so a jax bump that moves or renames the API edits one
    site (``check_rep`` is jax's ``check_vma``).

    Lazy jax import: ``pilosa_tpu.parallel`` is imported by topology-only
    consumers (config, the analyzer fixtures) that must not pay — or
    trigger — a jax import."""
    import jax

    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_rep,
    )

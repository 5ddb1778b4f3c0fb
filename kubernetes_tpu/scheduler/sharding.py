"""Mesh plumbing for the sharded drain path.

One rule table decides how every tensor the drain ships to the device is
partitioned over the mesh, keyed by TENSOR NAME (the partition-rule-
matching pattern of SNIPPETS.md [2]): the mirror's node rows, the pod
batch's mask/score tables, the topology index's [T, N] dom tables and the
gang kernel's dom_tab all resolve their PartitionSpec here instead of each
call site hand-picking one. Names that match no rule replicate — a NEW
tensor is safe by default and must be added here explicitly to shard.

Mesh resolution: the production drain takes its mesh from the `mesh`
argument (a jax.sharding.Mesh, the string "auto", or a device count) or,
when the caller passes None, from the KTPU_MESH environment variable —
`KTPU_MESH=auto` turns every local device into a 1-D "nodes" mesh, making
the mesh the default execution substrate without code changes; unset/0
keeps the single-device path.

Kernel selection (the pjit-vs-shard_map choice of SNIPPETS.md [3]): with a
mesh active, batches on the class-indexed scan route to the shard_map
kernel (kernels/batch.py schedule_batch_sharded) — per-shard filter+score
with an explicit cross-shard argmax — unless KTPU_SHARD_MAP=0 pins them to
the GSPMD path (jit over sharded inputs, XLA chooses the collectives).
"""

from __future__ import annotations

import os
import re
from typing import Optional

import numpy as np

#: mesh axis the node dimension shards over
NODE_AXIS = "nodes"

#: tensors whose LEADING axis is the node axis: the mirror's per-node
#: cfg/usage rows, the kernel usage carry, nominated reservations, and
#: the spread zone-id vector
_NODE_LEADING = re.compile(
    r"^(alloc|used|nz_used|nonzero_used|pod_count|max_pods|node_ok"
    r"|mem_pressure|valid|count|spread_zone)$")

#: tensors whose TRAILING axis is the node axis: the deduplicated
#: mask/score tables, soft base rows, the spread-count carry (chained or
#: not), and the topology/gang [T, N] node->domain tables
_NODE_TRAILING = re.compile(
    r"^(unique_masks|unique_scores|spread|soft_base|anti_dom"
    r"|soft_dom|dom_tab)$")

#: tensors carried per TENANT, not per node: the DRF usage carry
#: ([T, R], tenant-leading) and its [R] capacity row. Both are tiny and
#: consumed whole by every shard's ordering kernel, so they REPLICATE
#: by the default rule — named here so the rule is a decision, not an
#: accident of the fallthrough (add a rule above if T ever grows to a
#: shardable size).
_TENANT_REPLICATED = ("tenant_usage", "tenant_capacity")

def spec_for(name: str, ndim: int):
    """The PartitionSpec for tensor `name` (first matching rule wins;
    scalars and unmatched names replicate)."""
    from jax.sharding import PartitionSpec as P
    if ndim == 0:
        return P()
    if _NODE_LEADING.match(name):
        return P(NODE_AXIS) if ndim == 1 else P(NODE_AXIS, None)
    if _NODE_TRAILING.match(name) and ndim >= 2:
        return P(None, NODE_AXIS)
    return P()


def put(mesh, name: str, arr):
    """Host array -> device, placed by the name-keyed rule table (plain
    transfer when no mesh is active)."""
    import jax
    import jax.numpy as jnp
    if mesh is None:
        return jnp.asarray(arr)
    from jax.sharding import NamedSharding
    return jax.device_put(np.asarray(arr),
                          NamedSharding(mesh, spec_for(name, np.ndim(arr))))


def n_shards(mesh) -> int:
    """Shard count on the node axis (1 when unsharded)."""
    if mesh is None or NODE_AXIS not in mesh.axis_names:
        return 1
    return int(mesh.shape[NODE_AXIS])


def shard_divisible(n: int, shards: int) -> int:
    """Smallest multiple of `shards` >= n (the mirror's capacity pad)."""
    if shards <= 1:
        return n
    return n + (-n) % shards


def resolve_mesh(mesh=None):
    """Normalize the scheduler's `mesh` argument to a Mesh or None.

    A jax.sharding.Mesh passes through after a "nodes"-axis check (a
    foreign mesh must fail HERE with a clear error, not mid-drain inside
    the first NamedSharding upload). "auto" builds a 1-D "nodes" mesh
    over every local device; an int n takes the first n devices — n <= 1
    means EXPLICITLY single-device, immune to the env (the parity
    baselines' escape hatch). None consults KTPU_MESH (same forms;
    ""/"0"/unset means no mesh), so an operator flips the whole drain
    onto the mesh with one env var.
    """
    source = "mesh argument"
    if mesh is None:
        mesh = os.environ.get("KTPU_MESH", "")
        source = "KTPU_MESH"
        if mesh in ("", "0", "none"):
            return None
    if isinstance(mesh, str) and mesh != "auto":
        mesh = int(mesh)
    if isinstance(mesh, (str, int)):
        import jax
        from jax.sharding import Mesh
        devices = jax.devices()
        if mesh != "auto":
            if mesh <= 1:
                return None
            if len(devices) < mesh:
                raise ValueError(
                    f"{source} wants {mesh} devices, only "
                    f"{len(devices)} available — refusing a silently "
                    "degenerate mesh")
            devices = devices[:mesh]
        if len(devices) < 2:
            # "auto" on a one-device host is the single-device path, by
            # definition — said aloud, so nobody reads a one-chip run as
            # a sharded one
            import logging
            logging.getLogger("scheduler").warning(
                "%s=auto found %d device: the drain runs single-device, "
                "no mesh", source, len(devices))
            return None
        return Mesh(np.array(devices), (NODE_AXIS,))
    if NODE_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh axes {mesh.axis_names} carry no '{NODE_AXIS}' axis — "
            "the partition rules shard the node dimension over it")
    return mesh


def shard_map_enabled() -> bool:
    """False pins mesh batches to the GSPMD (pjit) path — the selection
    knob the CPU-sharded smoke uses as its control."""
    return os.environ.get("KTPU_SHARD_MAP", "1") != "0"


def use_shard_map(mesh, capacity: int) -> bool:
    """True when the class-indexed scan should take the shard_map kernel:
    a 1-D node mesh is active, the kernel knob is on, and the node axis
    divides exactly (the mirror guarantees this; a foreign capacity —
    hand-built tensors in tests — falls back to GSPMD instead of
    miscompiling)."""
    shards = n_shards(mesh)
    return (mesh is not None and shards > 1
            and len(mesh.axis_names) == 1
            and shard_map_enabled()
            and capacity % shards == 0)

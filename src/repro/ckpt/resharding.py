"""Resharding loader: re-materialize checkpointed state on a different mesh.

Checkpoints store *global* host arrays (per-buffer files), so restoring onto
a different mesh shape — vertical scaling (``update``), migration to a
bigger/smaller slice, or elastic recovery after node loss — is a
``jax.device_put`` with the target ``NamedSharding``s.  The ``ShardingRules``
recompute the PartitionSpecs for the new mesh; dimensions that no longer
divide the axis sizes fall back to replication automatically.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.sharding.rules import ShardingRules


def reshard_tree(host_tree: Any, shardings: Any) -> Any:
    """device_put each leaf with its target sharding."""
    return jax.tree.map(jax.device_put, host_tree, shardings)


def reshard_params(cfg, host_params: Any, new_mesh,
                   policy: str = "fsdp_tp") -> Any:
    rules = ShardingRules(cfg, new_mesh, policy)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), host_params)
    shardings = rules.param_shardings(abstract)
    return reshard_tree(host_params, shardings)


def reshard_snapshot_buffers(cfg, buffers: dict, new_mesh,
                             policy: str = "fsdp_tp") -> dict:
    """Reshard the checkpointed buffer dict; params/opt get param rules,
    other buffers are placed replicated (they are small or re-created)."""
    replicated = NamedSharding(new_mesh, PartitionSpec())
    out = {}
    for buff_id, tree in buffers.items():
        if buff_id in ("params",):
            out[buff_id] = reshard_params(cfg, tree, new_mesh, policy)
        elif buff_id in ("opt_state",):
            # moments share the param layout
            rules = ShardingRules(cfg, new_mesh, policy)
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
            m_abs = abstract.get("m") if isinstance(abstract, dict) else None
            if m_abs is not None:
                sh = rules.param_shardings(m_abs)
                out[buff_id] = {
                    "m": reshard_tree(tree["m"], sh),
                    "v": reshard_tree(tree["v"], sh),
                    "count": jax.device_put(tree["count"], replicated),
                }
            else:
                out[buff_id] = jax.device_put(tree, replicated)
        else:
            out[buff_id] = jax.device_put(tree, replicated)
    return out

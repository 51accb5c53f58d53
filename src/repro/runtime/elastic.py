"""Elastic scaling: re-mesh + re-shard on membership change.

Protocol (driven by launch/train.py):
  1. coordinator epoch bumps (join/leave/failure detected);
  2. every surviving host finishes (or abandons) its in-flight step, enters
     the failure-aware barrier for the new epoch;
  3. the training driver rebuilds the mesh over the surviving device set
     (dp shrinks/grows; tp is fixed by the model), re-derives shardings, and
     restores the last complete checkpoint with the new sharding layout —
     checkpoints are stored logically unsharded, so re-sharding is a
     device_put with new NamedShardings;
  4. the data loader re-shards its index space to (host_id', n_hosts') — the
     deterministic per-index corpus makes the stream exact-continued.

The container is single-process, so "hosts" here are logical dp groups; the
mesh is rebuilt over the same physical CPU devices with a different dp
extent.  All state-carrying logic (checkpoint round-trip, spec re-derivation,
bit-exact resume) is the real thing and is covered by tests.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

from ..checkpoint.manager import CheckpointManager
from ..parallel import steps as steps_lib


@dataclass
class ElasticPlan:
    epoch: int
    n_data: int
    n_model: int
    global_batch: int


def plan_for_membership(n_alive_hosts: int, devices_per_host: int,
                        n_model: int, global_batch: int, epoch: int) -> ElasticPlan:
    """dp extent = alive devices / tp; batch stays constant (grad-accum picks
    up the slack) as long as dp divides it."""
    total = n_alive_hosts * devices_per_host
    n_data = max(1, total // n_model)
    while global_batch % n_data:
        n_data -= 1
    return ElasticPlan(epoch=epoch, n_data=n_data, n_model=n_model,
                       global_batch=global_batch)


def build_mesh(plan: ElasticPlan):
    devs = jax.devices()[: plan.n_data * plan.n_model]
    import numpy as np

    arr = np.array(devs).reshape(plan.n_data, plan.n_model)
    return jax.sharding.Mesh(arr, ("data", "model"))


def reshard_state(state, sc, mesh):
    """Re-device-put a (restored, host-resident) train state with the specs
    of the new mesh."""
    with jax.set_mesh(mesh):
        specs = steps_lib.train_state_pspecs(state, sc)
        flat_s, tdef = jax.tree_util.tree_flatten(state)
        flat_p = tdef.flatten_up_to(specs)
        out = [
            jax.device_put(x, jax.sharding.NamedSharding(mesh, p))
            for x, p in zip(flat_s, flat_p)
        ]
        return tdef.unflatten(out)


def resume_elastic(ckpt: CheckpointManager, proto_state, sc, plan: ElasticPlan):
    """Restore latest complete checkpoint and reshard onto the new mesh.
    Returns (state, step). Bit-exactness is tested (same step → same loss
    trajectory across a dp 4→2→4 resize)."""
    mesh = build_mesh(plan)
    restored, step = ckpt.restore(proto_state)
    return reshard_state(restored, sc, mesh), step, mesh

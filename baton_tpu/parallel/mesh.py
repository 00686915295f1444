"""Device-mesh helpers.

The reference's "cluster" is a dict of HTTP clients
(client_manager.py:100-109). Here the cluster of *simulated* clients is a
``jax.sharding.Mesh`` with a ``clients`` axis: per-client params, opt
state, and data shards live distributed along it, the round broadcast is
replication across it, and FedAvg is a psum over it (ICI within a host,
DCN across hosts — XLA routes the collective).

All PartitionSpecs come from :mod:`baton_tpu.parallel.partition` — this
module only builds meshes and places arrays.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from baton_tpu.parallel.partition import (  # noqa: F401  (re-exported)
    CLIENT_AXIS,
    client_spec,
    replicated_spec,
)


def make_mesh(
    n_devices: Optional[int] = None,
    axis_names: Sequence[str] = (CLIENT_AXIS,),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """1-D (or reshaped n-D) mesh over the available devices.

    For multi-host pods, ``jax.devices()`` already spans hosts; the
    clients axis then runs over ICI+DCN and the psum in
    :func:`baton_tpu.ops.aggregation.psum_weighted_mean` becomes a
    cross-host collective — the TPU-native analogue of the reference's
    HTTP weight gather.
    """
    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"make_mesh({n_devices}) asked for more devices than "
                f"exist ({len(devs)} {devs[0].platform} device(s)); a "
                "smaller mesh would run the program on fewer chips than "
                "the caller believes")
        devs = devs[:n_devices]
    n = len(devs)
    # All devices go on the first axis; callers wanting a factored
    # multi-axis layout (e.g. clients×model) should construct Mesh
    # directly with their shape.
    shape = (n,) + (1,) * (len(axis_names) - 1)
    arr = np.asarray(devs).reshape(shape)
    return Mesh(arr, axis_names)


def client_sharding(mesh: Mesh, axis: str = CLIENT_AXIS) -> NamedSharding:
    """Sharding for ``[C, ...]`` stacked client arrays: dim 0 over the
    client mesh axis, everything else replicated."""
    return NamedSharding(mesh, client_spec(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (the global model each round —
    the TPU analogue of the reference's full-state broadcast,
    manager.py:77-86)."""
    return NamedSharding(mesh, replicated_spec())


def shard_client_arrays(tree, mesh: Mesh, axis: str = CLIENT_AXIS):
    """Device-put a ``[C, ...]`` pytree sharded along the client axis."""
    sharding = client_sharding(mesh, axis)
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sharding), tree)


def require_clients_mesh(mesh: Mesh, aggregator_spec, who: str) -> None:
    """Shared construction-time contract for the client-axis wrappers
    (FedPer / StatefulClients / ClusteredFedSim): a clients-only mesh,
    no hybrid model axis, and the mean combine rule (the sharded kernels
    aggregate with psum means; robust order statistics need the full
    stack on one device)."""
    from baton_tpu.parallel.partition import MODEL_AXIS

    if MODEL_AXIS in mesh.axis_names:
        raise ValueError(
            f"{who} shards client state over the {CLIENT_AXIS!r} axis; "
            "the hybrid clients x model mesh is not supported here"
        )
    if CLIENT_AXIS not in mesh.axis_names:
        raise ValueError(
            f"mesh has axes {mesh.axis_names} but {who} needs a "
            f"{CLIENT_AXIS!r} axis"
        )
    if aggregator_spec[0] != "mean":
        raise ValueError(
            f"sharded {who} aggregates with a psum mean; robust rules "
            "need the full stack on one device — use a meshless FedSim"
        )

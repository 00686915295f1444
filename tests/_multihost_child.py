"""Child process for the two-process DCN federation test.

NOT a pytest module (leading underscore): launched by
tests/test_multihost.py as ``python _multihost_child.py <coord> <n> <pid>``.
Each process contributes 4 virtual CPU devices; jax.distributed joins
them into one 8-device runtime, make_hybrid_mesh lays out
``clients(4, over DCN) x model(2, "ICI")``, and the production FedAvg
collective (ops/aggregation.py::psum_weighted_mean) runs with the
clients axis genuinely crossing the process boundary. Success = every
process prints the closed-form weighted mean.
"""

import json
import os
import sys
from functools import partial

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from baton_tpu.ops.aggregation import psum_weighted_mean  # noqa: E402
from baton_tpu.parallel.multihost import (  # noqa: E402
    initialize_multihost,
    make_hybrid_mesh,
)


def main() -> None:
    coord, n_proc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    idx = initialize_multihost(coord, n_proc, pid)
    assert idx == pid, (idx, pid)
    assert jax.process_count() == n_proc
    assert jax.device_count() == 4 * n_proc

    mesh = make_hybrid_mesh([("model", 2)], dcn_axis="clients")
    assert dict(mesh.shape) == {"clients": 2 * n_proc, "model": 2}

    # deterministic per-client params + sample weights, same on every
    # process; the global arrays are assembled from per-process shards
    c, d = mesh.shape["clients"], 8
    rng = np.random.default_rng(0)
    theta = {
        "w": rng.normal(size=(c, d)).astype(np.float32),
        "b": rng.normal(size=(c,)).astype(np.float32),
    }
    weights = (np.arange(c) + 1).astype(np.float32)
    expected = {
        k: (weights.reshape((c,) + (1,) * (v.ndim - 1)) * v).sum(0)
        / weights.sum()
        for k, v in theta.items()
    }

    def garr(v, spec):
        return jax.make_array_from_callback(
            v.shape, NamedSharding(mesh, spec), lambda i: v[i]
        )

    g_theta = {
        "w": garr(theta["w"], P("clients", None)),
        "b": garr(theta["b"], P("clients")),
    }
    g_w = garr(weights, P("clients"))

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=({"w": P("clients", None), "b": P("clients")}, P("clients")),
        out_specs={"w": P(), "b": P()},
    )
    def fedavg(local, w):
        return psum_weighted_mean(local, w, "clients")

    out = jax.jit(fedavg)(g_theta, g_w)
    for k in expected:
        got = np.asarray(jax.device_get(out[k]))
        np.testing.assert_allclose(got, expected[k], rtol=1e-5, atol=1e-6)

    print(json.dumps({
        "pid": pid,
        "process_count": jax.process_count(),
        "global_devices": jax.device_count(),
        "mesh": dict(mesh.shape),
        "ok": True,
    }), flush=True)


if __name__ == "__main__":
    main()

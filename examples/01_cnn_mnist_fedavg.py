"""BASELINE config 1: 2-layer CNN / MNIST, 4-worker FedAvg.

The TPU-native analogue of the reference's two-process demo
(reference demo.py:62-77): the four "workers" are indices on a vmapped
client axis, the round broadcast is parameter replication, and FedAvg
is the engine's weighted tree mean. Prints per-round train loss and a
final federated eval.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

from baton_tpu.data.datasets import load_mnist
from baton_tpu.data.partition import iid_partition
from baton_tpu.data.synthetic import synthetic_image_clients
from baton_tpu.models.cnn import cnn_mnist_model
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel.engine import FedSim
from baton_tpu.parallel.mesh import make_mesh


def run(n_clients=4, n_rounds=4, n_epochs=2, batch_size=32,
        n_per_client=64, use_mesh=False, seed=0,
        data_dir=None, download=False, real_data=False):
    rng = np.random.default_rng(seed)
    if real_data:
        train, _test, info = load_mnist(
            data_dir=data_dir, download=download, fallback="synthetic",
            seed=seed,
        )
        print(f"dataset: mnist (synthetic={info['synthetic']})")
        n_keep = min(n_clients * n_per_client, len(train["y"]))
        sel = rng.permutation(len(train["y"]))[:n_keep]
        datasets = iid_partition({k: v[sel] for k, v in train.items()},
                                 n_clients, rng)
    else:
        datasets = synthetic_image_clients(rng, n_clients,
                                           n_per_client=n_per_client)
    data, n_samples = stack_client_datasets(datasets, batch_size=batch_size)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    mesh = None
    if use_mesh:
        n_dev = len(jax.devices())
        mesh = make_mesh(n_devices=n_dev) if n_dev > 1 else None

    model = cnn_mnist_model()
    sim = FedSim(model, batch_size=batch_size,
                 optimizer=optax.sgd(0.01, momentum=0.9), mesh=mesh)
    params = sim.init(jax.random.key(seed))

    for r in range(n_rounds):
        res = sim.run_round(params, data, n_samples,
                            jax.random.fold_in(jax.random.key(seed + 1), r),
                            n_epochs=n_epochs)
        params = res.params
        print(f"round {r}: loss/epoch "
              f"{[round(float(x), 4) for x in res.loss_history]}")

    metrics = sim.evaluate_round(params, data, n_samples)
    print(f"federated eval: loss {metrics['loss']:.4f} "
          f"accuracy {metrics['accuracy']:.3f} over {int(metrics['n'])} samples")
    return metrics


if __name__ == "__main__":
    from baton_tpu.utils.profiling import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    p.add_argument("--mesh", action="store_true",
                   help="shard the client axis over all visible devices")
    p.add_argument("--data-dir", default=None,
                   help="directory holding MNIST idx/npz files")
    p.add_argument("--download", action="store_true")
    args = p.parse_args()
    if args.scale == "full":
        m = run(n_clients=4, n_rounds=20, n_epochs=4, n_per_client=15000,
                use_mesh=args.mesh, real_data=True,
                data_dir=args.data_dir, download=args.download)
    else:
        m = run(use_mesh=args.mesh, real_data=bool(args.data_dir),
                data_dir=args.data_dir, download=args.download)
    assert m["accuracy"] > 0.5, "demo should learn the class prototypes"

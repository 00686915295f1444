"""BASELINE config 2: ResNet-18 / CIFAR-10, non-IID Dirichlet clients.

The north-star workload (BASELINE.md): simulated FedAvg clients with
label-skew shards, trained in bf16 on a client-sharded mesh. Shows the
three scale levers: ``wave_size`` (HBM ceiling — clients are processed
in accumulating waves), the mesh (clients sharded over chips, FedAvg as
an ICI psum), and checkpoint/resume for long runs.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.data.datasets import load_cifar10
from baton_tpu.data.partition import dirichlet_partition, partition_stats
from baton_tpu.models.resnet import resnet18_cifar_model
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel.engine import FedSim
from baton_tpu.parallel.mesh import make_mesh


def make_data(rng, n_total, n_clients, alpha, image_size=32, n_classes=10,
              data_dir=None, download=False):
    """Real CIFAR-10 when available (data_dir / download), otherwise the
    deterministic synthetic surrogate — the loader reports which via
    ``info['synthetic']``."""
    train, _test, info = load_cifar10(
        data_dir=data_dir, download=download, fallback="synthetic",
        seed=int(rng.integers(1 << 31)),
    )
    print(f"dataset: {info['name']} (synthetic={info['synthetic']}, "
          f"source={info['source']})")
    if n_total < len(train["y"]):
        sel = rng.permutation(len(train["y"]))[:n_total]
        train = {k: v[sel] for k, v in train.items()}
    if image_size != train["x"].shape[1]:  # tiny-scale smoke runs
        train = dict(train)
        train["x"] = train["x"][:, :image_size, :image_size, :]
    shards = dirichlet_partition(train, n_clients, rng, alpha=alpha)
    return shards


def run(n_clients=16, n_total=1024, alpha=0.5, n_rounds=3, n_epochs=1,
        batch_size=32, wave_size=None, use_mesh=False,
        checkpoint_dir=None, seed=0, model_fn=None,
        compute_dtype=jnp.bfloat16, image_size=32,
        data_dir=None, download=False):
    rng = np.random.default_rng(seed)
    shards = make_data(rng, n_total, n_clients, alpha, image_size=image_size,
                       data_dir=data_dir, download=download)
    stats = partition_stats(shards)
    print(f"{n_clients} Dirichlet(alpha={alpha}) shards, "
          f"sizes {[s['n'] for s in stats[:8]]}…")
    data, n_samples = stack_client_datasets(shards, batch_size=batch_size)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    n_samples = jnp.asarray(n_samples)

    mesh = None
    if use_mesh and len(jax.devices()) > 1:
        mesh = make_mesh(n_devices=len(jax.devices()))

    model = (model_fn or resnet18_cifar_model)(compute_dtype=compute_dtype)
    sim = FedSim(model, batch_size=batch_size, learning_rate=0.05, mesh=mesh)
    params = sim.init(jax.random.key(seed))

    checkpointer = None
    if checkpoint_dir:
        from baton_tpu.utils.checkpoint import Checkpointer

        checkpointer = Checkpointer(checkpoint_dir)

    params, history = sim.run_rounds(
        params, data, n_samples, jax.random.key(seed + 1),
        n_rounds=n_rounds, n_epochs=n_epochs, wave_size=wave_size,
        checkpointer=checkpointer,
    )
    print(f"loss: {history[0]:.4f} -> {history[-1]:.4f} over {n_rounds} rounds")
    metrics = sim.evaluate_round(params, data, n_samples)
    print(f"federated eval: loss {metrics['loss']:.4f} "
          f"accuracy {metrics['accuracy']:.3f}")
    if checkpointer is not None:
        checkpointer.close()
    return history, metrics


if __name__ == "__main__":
    from baton_tpu.utils.profiling import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    p.add_argument("--mesh", action="store_true")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--data-dir", default=None,
                   help="directory holding cifar-10-batches-py/ or cifar10.npz")
    p.add_argument("--download", action="store_true",
                   help="fetch CIFAR-10 if missing (needs network)")
    args = p.parse_args()
    if args.scale == "full":
        run(n_clients=128, n_total=50_000, n_rounds=100, n_epochs=1,
            wave_size=32, use_mesh=args.mesh,
            checkpoint_dir=args.checkpoint_dir,
            data_dir=args.data_dir, download=args.download)
    else:
        history, _ = run(use_mesh=args.mesh,
                         checkpoint_dir=args.checkpoint_dir,
                         data_dir=args.data_dir, download=args.download)
        assert history[-1] < history[0], "loss should fall"

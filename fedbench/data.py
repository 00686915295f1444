"""The one general generator of a cell's cohort: client sizes from the
cell's distribution spec, inputs and labels from the configuration's
input spec, everything from ``--seed``.

Inputs are made on the device in one jitted call, in the ``[C,
capacity, ...]`` layout ``FedSim`` takes
(``baton_tpu.ops.padding.stack_client_datasets``: capacity is the
largest client rounded up to a batch multiple, rows past a client's
``n_samples`` are zero). Labels are a fixed function of the inputs, so
a falling loss can be required of every cell.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from fedbench import manifest


def data_key(seed: int):
    """The key a cohort is drawn from. ``rbg`` (the device's own bit
    generator) and not the default threefry: XLA:TPU takes 73 s to
    compile threefry normals of the 32-client image cohort and 2 s with
    ``rbg`` (compiled for the described v5e, PR 22). Same seed, same
    backend, same inputs."""
    return jax.random.key(seed, impl="rbg")


def client_sizes(root: str, spec: dict, n_clients: int, seed: int) -> np.ndarray:
    """``n_samples[C]`` from ``fedbench/cohorts/<kind>.py``."""
    module = manifest.load_module(root, "cohorts", spec["kind"])
    sizes = np.asarray(
        module.sizes(spec, n_clients, np.random.default_rng(seed)), np.int32)
    if sizes.shape != (n_clients,) or (sizes < 0).any():
        raise ValueError(f"cohort {spec} gave sizes of shape {sizes.shape}")
    return sizes


def capacity_for(n_samples: np.ndarray, batch: int) -> int:
    return int(math.ceil(int(n_samples.max()) / batch) * batch)


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _images(shape, n_classes, n_clients, capacity, n_samples, key):
    kx, kp = jax.random.split(key)
    x = jax.random.normal(kx, (n_clients, capacity) + shape, jnp.float32)
    proj = jax.random.normal(kp, (math.prod(shape), n_classes), jnp.float32)
    y = jnp.argmax(x.reshape(n_clients, capacity, -1) @ proj, axis=-1)
    real = jnp.arange(capacity)[None, :] < n_samples[:, None]
    x = jnp.where(real.reshape(real.shape + (1,) * len(shape)), x, 0.0)
    return {"x": x, "y": jnp.where(real, y, 0).astype(jnp.int32)}


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _tokens(vocab, n_classes, seq_len, n_clients, capacity, n_samples, key):
    x = jax.random.randint(key, (n_clients, capacity, seq_len), 0, vocab,
                           jnp.int32)
    real = jnp.arange(capacity)[None, :] < n_samples[:, None]
    x = jnp.where(real[..., None], x, 0)
    return {"x": x, "y": (x[..., 0] % n_classes).astype(jnp.int32)}


def make_cohort(spec: dict, n_samples: np.ndarray, capacity: int,
                seq_len, key) -> dict:
    """``{"x": [C, capacity, ...], "y": [C, capacity]}`` on the default
    device. ``spec`` is the configuration's resolved input spec."""
    n = jnp.asarray(n_samples, jnp.int32)
    c = int(n_samples.shape[0])
    if spec["kind"] == "image":
        return _images(tuple(spec["shape"]), int(spec["n_classes"]), c,
                       capacity, n, key)
    if spec["kind"] == "tokens":
        return _tokens(int(spec["vocab"]), int(spec["n_classes"]),
                       int(seq_len), c, capacity, n, key)
    raise ValueError(f"unknown input kind {spec['kind']!r}")

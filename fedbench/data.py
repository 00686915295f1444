"""The one general generator of a cell's cohort: client sizes from the
cell's distribution spec (``fedbench/cohorts/<kind>.py``), inputs and
labels from the configuration's input spec
(``fedbench/inputs/<kind>.py``), everything from ``--seed``.

Inputs are made on the device in one jitted call, in the ``[C,
capacity, ...]`` layout ``FedSim`` takes
(``baton_tpu.ops.padding.stack_client_datasets``: capacity is the
largest client rounded up to a batch multiple, rows past a client's
``n_samples`` are zero). Labels are a fixed function of the inputs, so
a falling loss can be required of every cell.
"""

from __future__ import annotations

import math

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


def make_cohort(root: str, spec: dict, n_samples: np.ndarray, capacity: int,
                seq_len, key) -> dict:
    """``{"x": [C, capacity, ...], "y": [C, capacity, ...]}`` on the
    default device, from ``fedbench/inputs/<kind>.py``. ``spec`` is the
    configuration's resolved input spec."""
    module = manifest.load_module(root, "inputs", spec["kind"])
    return module.make(spec, int(n_samples.shape[0]), capacity, seq_len,
                       jnp.asarray(n_samples, jnp.int32), key)

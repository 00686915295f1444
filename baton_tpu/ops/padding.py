"""Ragged-data padding — static shapes for XLA, exact counts for FedAvg.

Clients hold different amounts of data (the reference demo draws
``32·randint(5,20)`` samples per client per round, demo.py:52-59). XLA
wants static shapes, and the sample-weighted FedAvg math wants *exact*
per-client counts (manager.py:119-126). The contract: every client
dataset is padded (with zeros) to a shared ``capacity``, its real rows
first, and the true row count travels alongside as ``n_samples``.
Validity masks are derived from ``n_samples`` inside the jitted trainer,
so padding never contributes to losses, gradients, or aggregation
weights. Any capacity is taken: where the batch size does not divide
it the trainer shares the rows equally among the epoch's steps
(core/training.py), and ``FedSim`` computes only the rows its cohort
holds, whatever the capacity it is handed (parallel/engine.py).
:func:`stack_client_datasets` still rounds its capacity up to a batch
multiple, which is what the engines beside ``FedSim`` step through.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence, Tuple

import numpy as np


def round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def pad_to_capacity(array: np.ndarray, capacity: int) -> np.ndarray:
    """Zero-pad axis 0 of ``array`` to ``capacity`` rows."""
    n = array.shape[0]
    if n > capacity:
        raise ValueError(f"dataset has {n} rows > capacity {capacity}")
    if n == capacity:
        return array
    pad = np.zeros((capacity - n,) + array.shape[1:], dtype=array.dtype)
    return np.concatenate([array, pad], axis=0)


def pad_dataset(
    data: Dict[str, np.ndarray], capacity: int
) -> Tuple[Dict[str, np.ndarray], int]:
    """Pad every array in ``data`` to ``capacity`` rows; returns
    ``(padded, n_samples)``."""
    n = next(iter(data.values())).shape[0]
    padded = {k: pad_to_capacity(np.asarray(v), capacity) for k, v in data.items()}
    return padded, n


def stack_client_datasets(
    datasets: Sequence[Dict[str, np.ndarray]],
    batch_size: int,
    capacity: int | None = None,
) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """Pad + stack per-client datasets into ``[C, capacity, ...]`` arrays.

    Returns ``(stacked_data, n_samples[C])`` — the layout the simulation
    engine vmaps/shards over. ``capacity`` defaults to the largest client
    dataset rounded up to a batch multiple.
    """
    if not datasets:
        raise ValueError("no client datasets")
    sizes = [next(iter(d.values())).shape[0] for d in datasets]
    if capacity is None:
        capacity = round_up(max(sizes), batch_size)
    else:
        capacity = round_up(capacity, batch_size)
    keys = list(datasets[0].keys())
    stacked = {
        k: np.stack([pad_to_capacity(np.asarray(d[k]), capacity) for d in datasets])
        for k in keys
    }
    return stacked, np.asarray(sizes, dtype=np.int32)

"""Every client holds the same number of samples: ``{"kind": "const", "n": 48}``."""

import numpy as np


def sizes(spec: dict, n_clients: int, rng: np.random.Generator) -> np.ndarray:
    return np.full((n_clients,), int(spec["n"]), dtype=np.int32)

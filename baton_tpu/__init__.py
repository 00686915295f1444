"""baton_tpu — a TPU-native (JAX/XLA) federated-learning framework.

Capabilities mirror the reference runtime ``mynameisfiber/baton``
(/root/reference): a manager orchestrates training *rounds* across elastic
clients; each client trains the global model locally on private data; the
manager combines results with sample-weighted FedAvg
(reference: manager.py:113-132).

Design stance (not a port): the core is a TPU-resident *simulation engine*
in which a "client" is an index along a sharded mesh axis, not a process.
Local training is a jit-compiled ``lax.scan`` train loop vmapped over the
client axis; the round broadcast is parameter replication; FedAvg is a
``psum`` of sample-weighted parameter sums over ICI. The HTTP control
plane (``baton_tpu.server``) is retained at the edge for real external
clients and reference-protocol compatibility.

Layout:
  core/      model contract + jitted local training
  ops/       aggregation kernels + ragged-data padding
  parallel/  mesh helpers + the simulation engine
  models/    model zoo (linear, MLP, CNN, ...)
  data/      synthetic data + IID/Dirichlet partitioners
"""

import time as _time

_IMPORT_T0 = _time.perf_counter()

__version__ = "0.1.0"

from baton_tpu.core.model import FedModel  # noqa: F401
from baton_tpu.core.training import LocalTrainer, make_local_trainer  # noqa: F401
from baton_tpu.ops.aggregation import weighted_tree_mean  # noqa: F401
from baton_tpu.parallel.engine import FedSim, RoundResult  # noqa: F401

#: host seconds this package's import took in this process, whatever it
#: pulled in that was not yet imported (JAX itself, where the caller had
#: not imported it): the part of a job's start that only the program can
#: shorten
IMPORT_S = _time.perf_counter() - _IMPORT_T0

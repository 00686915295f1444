"""Auxiliary subsystems (SURVEY §5 / §7 step 7).

The reference has none of these — no checkpointing (a manager restart
loses the global model, SURVEY §5 "Checkpoint/resume: absent"), no
metrics beyond prints, no profiler, no fault injection. They are new
capabilities, flagged as such in SURVEY, built TPU-first:

* :mod:`baton_tpu.utils.checkpoint` — orbax round-granular save/resume.
* :mod:`baton_tpu.utils.metrics` — counters/gauges/timers + JSON export.
* :mod:`baton_tpu.utils.profiling` — profiler spans, device timing, memory plans.
* :mod:`baton_tpu.utils.faults` — HTTP-layer fault injection for
  elasticity tests.
"""

from baton_tpu.utils.checkpoint import Checkpointer, RestoredState
from baton_tpu.utils.metrics import Metrics
from baton_tpu.utils.profiling import annotate, timed

__all__ = [
    "Checkpointer",
    "RestoredState",
    "Metrics",
    "annotate",
    "timed",
]

"""What the program's build ledger says of a job's start, for the
set-up readers of ``layer_metrics/``: ``baton_tpu.obs.compute.builds()``
hears every jaxpr trace, lowering and backend build that JAX makes in
the process (``jax.monitoring``) and keeps their seconds by program."""

from __future__ import annotations

from typing import Optional


def total(counters: dict, key: str) -> Optional[float]:
    """``builds().totals()[key]`` of this process, whatever built the
    program (the cell's ``FedSim``, the probe's reference, an eager
    operation of the harness); ``None`` in a rehearsal, which reports
    no time (its ``counters`` hold no ``init_s``), and where the program
    has no ledger (a tree from before it)."""
    if "init_s" not in counters:
        return None
    try:
        from baton_tpu.obs.compute import builds
    except ImportError:
        return None
    return builds().totals()[key]

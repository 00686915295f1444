"""Device milliseconds of one execution of the wave program in the
state-space branches (``models/state_space.py::mamba2_apply``: the two
projections with their adapters, the multipliers, the convolution, the
gates, the gated norm) with the recurrence they hold: ops whose
innermost part is ``ssm``, ``ssm_conv`` or ``ssd_scan``, by self time.
Mean over the cell's devices. ``None`` where no op carried such a
scope."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    found = [ms for ms in (wave_ms_under(reduced, part=part)
                           for part in ("ssm", "ssm_conv", "ssd_scan"))
             if ms is not None]
    return sum(found) if found else None

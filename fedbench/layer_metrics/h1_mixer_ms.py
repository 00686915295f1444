"""Device milliseconds of one execution of the wave program in the
parallel mixers (``models/llama.py::_parallel_apply``: the state-space
branch, ``models/state_space.py::mamba2_apply``, and the attention
branch, ``models/transformer.py::mha_apply``, over one normed input,
each with its adapters and multipliers, and their sum): ops whose
innermost part is ``parallel_mixer``, ``ssm``, ``ssm_conv``,
``ssd_scan`` or ``attention``, by self time. Mean over the cell's
devices. ``None`` where no op carried such a scope."""

from fedbench.trace_reduce import wave_ms_under

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    if wave_ms_under(reduced, part="parallel_mixer") is None \
            and wave_ms_under(reduced, part="ssm") is None:
        return None  # attention alone is another model's mixer
    found = [ms for ms in (wave_ms_under(reduced, part=part)
                           for part in ("parallel_mixer", "ssm", "ssm_conv",
                                        "ssd_scan", "attention"))
             if ms is not None]
    return sum(found) if found else None

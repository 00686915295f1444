"""Device milliseconds of one execution of the wave program (today
``jit__wave_sums_vmap``, or the jitted shard_map kernel on a mesh): the
vmapped local training of one wave and its weighted sums. Mean over
the cell's devices."""

from fedbench.trace_reduce import wave_module

LAYER = "local training + model"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    if reduced is None:
        return None
    per_device = []
    for device in reduced["devices"].values():
        wave = wave_module(device)
        per_device.append(device["module_s"][wave] / device["module_runs"][wave])
    return 1e3 * sum(per_device) / len(per_device)

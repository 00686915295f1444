"""Device milliseconds a round of every compiled module other than
the wave program: key splitting, slices, ``_pad_wave``,
``_acc_tree_add``, the divide of the mean, the loss fetch. Mean over
the cell's devices."""

from fedbench.trace_reduce import wave_module

LAYER = "aggregation"
UNIT = "ms"
MOVES = "round_s"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    if reduced is None:
        return None
    per_device = []
    for device in reduced["devices"].values():
        wave = wave_module(device)
        per_device.append(sum(s for name, s in device["module_s"].items()
                              if name != wave))
    return 1e3 * sum(per_device) / len(per_device) / reduced["n_rounds"]

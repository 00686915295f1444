"""Convolution ops' share of their roofline: the least time the chip
could take for the round's required convolution work (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, from
``fedbench/flops/<config>.py`` and ``fedbench/peaks.json``; compute is
the bound that applies to ResNet-18 at batch 32) over the device time
of the ops XLA classes as convolutions."""

from fedbench.roofline import roofline_share

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return roofline_share(reduced, cell, "conv")

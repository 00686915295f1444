"""Matrix-product ops' share of their roofline: the least time the
chip could take for the round's required matmul work (blocks,
attention, pooler, head; embeddings are lookups and count 0; compute is
the bound that applies at 128 tokens x batch 32) over the device time
of the ops XLA classes as dots or convolutions-as-matmul."""

from fedbench.roofline import roofline_share

LAYER = "kernels"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "device_trace"


def read(reduced, counters, cell):
    return roofline_share(reduced, cell, "matmul")

"""The allocator's high-water marks, ``peak_bytes_in_use +
peak_bytes_reserved`` (live arrays and a running program's temporaries
are counted apart, so the sum is an upper bound on the simultaneous
peak), on the fullest of the cell's devices. It decides the largest
wave that fits."""

LAYER = "device memory"
UNIT = "GiB"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_counter"


def read(reduced, counters, cell):
    peak = counters.get("peak_hbm_bytes")
    return None if peak is None else peak / 2**30

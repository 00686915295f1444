"""Of ``build_backend_s``, the seconds of builds the persistent cache did
not serve (no ``/jax/compilation_cache/cache_hits`` inside the event):
0.0 on a warm run, most of it on a cold one. A ``setup_s`` read beside
seconds of this is a cold or part-cold run, not a regression. Part
cold is the common case on a machine whose cache other cells have
filled: a cell's first run there is served what it shares with them
and compiles what is its own (``resnet18_c128_w32``: the 128 clients'
images, the accumulate and the slices, 8.6-9.3 s three times, 0.0 on
the second run of one call; PERF.md section 6, PR 50)."""

from fedbench.build_split import total

LAYER = "set-up"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(reduced, counters, cell):
    return total(counters, "cold_s")

"""The share of the window's ``baton.round.sync`` spans that found their
loss sum done when the host came to wait for it (``ready``, summed over
the window's spans, over their count): the host arrived late. It does
not say the host set the pace. A window's first sync settles a round
the harness has already fetched between its calls, so one sync a window
is ``ready`` by construction and the floor is 100 / the traced rounds
(20 % at five rounds, 50 % at two); and where the runtime holds the
host inside ``baton.round.stage`` until a program leaves its queue (the
waved cell, PR 36) the host reaches every sync late while the chip
idles under 2 %: 100 % there. Above its cell's floor it is the first
number to move when a wave gets shorter than the head before it."""

SYNC = "baton.round.sync"

LAYER = "round loop"
UNIT = "%"
MOVES = "samples_per_s_per_chip"
SOURCE = "program_span"


def read(reduced, counters, cell):
    if reduced is None:
        return None
    runs = reduced["span_runs"].get(SYNC)
    ready = reduced["span_attrs"].get(SYNC, {}).get("ready")
    if not runs or ready is None:
        return None
    return 100.0 * ready / runs

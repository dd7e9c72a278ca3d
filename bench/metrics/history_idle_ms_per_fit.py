"""``history_idle_ms_per_fit`` (scan engine: ``PimGrid.fit``'s per-step
unpacking of each chunk's stacked metrics, callbacks included):
milliseconds a fit in which the chip ran nothing while the host was
inside the program's ``pim.history`` spans, mean over the cell's chips.
The spans' own length is no measure: their first slice of a chunk
waits for the chip to run it."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_fit(ctx, spans.HISTORY)

"""``unattributed_idle_share`` (device): the share of the traced window
in which no operation ran on the chip while the host was inside a fit
(``pim.fit``) but inside none of its named parts (``pim.prepare``,
``pim.dispatch``, ``pim.history``), averaged over the cell's chips, in
percent: the device idle that the program's spans do not name."""

from bench import spans


def read(ctx):
    t = ctx.trace
    if not t.window_ns or not t.devices or not spans.instrumented(t):
        return None
    return 100.0 * spans.idle_split(t)["unattributed"] / t.window_ns

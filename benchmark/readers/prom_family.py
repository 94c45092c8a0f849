"""prom_delta for a family that the program may not have yet: the same
params and the same arithmetic, but a family absent from the closing
scrape gives nothing. prom_delta's "sum" reads an absent family as a
change of 0, which for a counter new to the program (the compile counter,
the stack timers) would write 0 where nothing was counted.

params: as prom_delta's."""

from . import prom_delta


def read(params: dict, ctx: dict):
    closing = ctx["scrapes"][params.get("to", "window_end")]["metrics"]
    if params["family"] not in closing:
        return None
    return prom_delta.read(params, ctx)

"""Triaged scheduler ticks per superstep launch (the program's own
``QueryReport`` counters), over the traced window."""


def read(ctx):
    launches = sum(c["supersteps"] for c in ctx["calls"])
    if not launches:
        return None
    return sum(c["triaged_ticks"] for c in ctx["calls"]) / launches

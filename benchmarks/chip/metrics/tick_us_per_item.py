"""Host microseconds per finished item in the event engine's ticks
outside triage and association (the ready map, the shed and the enqueue
per route): the program's ``engine_tick_s``, the self time of its
``engine.tick`` spans."""
from chipbench import stages


def read(ctx):
    return stages.us_per_item(ctx, ("engine_tick_s",))

"""Host microseconds per finished item in the event engine's driver loop
outside ticks (the event handlers: transfers, service completions, Eq. 7
dispatch, answers): the program's ``engine_drive_s``, the self time of
its ``engine.drive`` span."""
from chipbench import stages


def read(ctx):
    return stages.us_per_item(ctx, ("engine_drive_s",))

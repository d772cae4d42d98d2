"""Host microseconds per finished item spent around the event loop: the
stream's re-homing, the engine's set-up and the report
(``stream_s`` + ``engine_setup_s`` + ``engine_finalize_s``)."""
from chipbench import stages


def read(ctx):
    return stages.us_per_item(
        ctx, ("stream_s", "engine_setup_s", "engine_finalize_s"))

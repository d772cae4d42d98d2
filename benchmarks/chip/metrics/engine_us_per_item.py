"""Host microseconds per finished item in the event engine: each call's
wall time less its frontend stages and its triage time, over its items."""

FRONTEND = ("render_s", "framediff_s", "classify_s")


def read(ctx):
    items = sum(c["items"] for c in ctx["calls"])
    if not items:
        return None
    rest = sum(c["wall_s"] - sum(c["stage_timings"].get(k, 0.0)
                                 for k in FRONTEND + ("triage_s",))
               for c in ctx["calls"])
    return 1e6 * rest / items

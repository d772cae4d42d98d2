"""Host milliseconds per scheduler tick in the pixel frontend's
``classify_s`` stage (``QueryReport.stage_timings``), over the traced window."""


def read(ctx):
    ticks = sum(c["ticks"] for c in ctx["calls"])
    total = sum(c["stage_timings"].get("classify_s", 0.0) for c in ctx["calls"])
    if not ticks or not any("classify_s" in c["stage_timings"]
                            for c in ctx["calls"]):
        return None
    return 1e3 * total / ticks

"""Sums of the program's ``QueryReport.stage_timings`` keys over the calls
of a window, for the metrics that read the program's own spans."""
from typing import Dict, Optional, Sequence


def seconds(ctx: Dict, keys: Sequence[str]) -> Optional[float]:
    """Summed seconds of ``keys`` over every call; None where a call
    lacks one of them (a program without the spans that fill them)."""
    calls = ctx["calls"]
    if not calls or any(k not in c["stage_timings"]
                        for c in calls for k in keys):
        return None
    return sum(c["stage_timings"][k] for c in calls for k in keys)


def us_per_item(ctx: Dict, keys: Sequence[str]) -> Optional[float]:
    """Microseconds of ``keys`` per finished item over the window."""
    s = seconds(ctx, keys)
    items = sum(c["items"] for c in ctx["calls"])
    if s is None or not items:
        return None
    return 1e6 * s / items

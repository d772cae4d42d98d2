"""Simulated camera-seconds of every call completed in the window, over
the window's wall time: how many cameras one chip keeps up with."""


def read(ctx):
    return sum(c["camera_s"] for c in ctx["calls"]) / ctx["window_s"]

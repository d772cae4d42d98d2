"""Seconds from process start to the window: imports, JAX start-up,
traffic and weights, warm-up and any compilation."""


def read(ctx):
    return ctx["setup_s"]

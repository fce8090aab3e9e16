"""Seconds from process start to the window's start: JAX on the chip,
lane state on the device, the compiled programs (from the persistent
cache after a cell's first run), every session opened over the wire and
the warm traffic answered."""


def read(ctx):
    return ctx["setup_s"]

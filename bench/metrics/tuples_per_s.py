"""Tuples the service absorbed per second of the window.

Tuples of the appends acknowledged inside the window, less the growth of
the engine's host backlog between the window's edges, over the window's
seconds: deferring device work does not raise it."""


def read(ctx):
    w = ctx["window"]
    return (w["tuples_acked_in_window"] - ctx["backlog_growth"]) \
        / ctx["seconds"]

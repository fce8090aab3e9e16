"""Share of the window the engine thread spent preparing scan inputs:
the ``pack_ms`` + ``h2d_ms`` of every flush row of the window, of any
scope, over the window's milliseconds.  The thread waits for each
scan's stats before it packs the next batch, so the device has nothing
of this flush to run meanwhile."""


def read(ctx):
    rows = [r for r in ctx["rows"] if r.get("pack_ms") is not None]
    if not rows:
        return None
    prep = sum(r["pack_ms"] + r["h2d_ms"] for r in rows)
    return 100.0 * prep / (ctx["seconds"] * 1000.0)

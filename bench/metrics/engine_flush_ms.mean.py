"""Mean ``flush_ms`` of the engine-wide flushes of the window (telemetry
rows with ``scope == "engine"``; the flush waits for every segment's
stats, so the time covers the device work)."""


def read(ctx):
    ms = [r["flush_ms"] for r in ctx["rows"]
          if r["scope"] == "engine" and r["flush_ms"] is not None]
    return sum(ms) / len(ms) if ms else None

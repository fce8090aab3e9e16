"""Mean ``h2d_ms`` of the window's engine-wide flushes (telemetry rows
with ``scope == "engine"``): host time spent copying every scan
segment's batch to the chip and waiting for it (``scan.h2d``)."""


def read(ctx):
    ms = [r["h2d_ms"] for r in ctx["rows"]
          if r["scope"] == "engine" and r.get("h2d_ms") is not None]
    return sum(ms) / len(ms) if ms else None

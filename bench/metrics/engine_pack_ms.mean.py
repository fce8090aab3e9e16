"""Mean ``pack_ms`` of the window's engine-wide flushes (telemetry rows
with ``scope == "engine"``): host time spent packing the dense chunk
batch of every scan segment (the program's ``scan.pack`` step)."""


def read(ctx):
    ms = [r["pack_ms"] for r in ctx["rows"]
          if r["scope"] == "engine" and r.get("pack_ms") is not None]
    return sum(ms) / len(ms) if ms else None

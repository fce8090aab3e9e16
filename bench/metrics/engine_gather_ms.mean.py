"""Mean ``gather_ms`` of the window's engine-wide flushes (telemetry rows
with ``scope == "engine"``): host time spent gathering the flush's lane
bucket out of the lane table and scattering it back, both waited on (the
program's ``scan.gather`` and ``scan.scatter`` steps)."""


def read(ctx):
    ms = [r["gather_ms"] for r in ctx["rows"]
          if r["scope"] == "engine" and r.get("gather_ms") is not None]
    return sum(ms) / len(ms) if ms else None

"""Mean ``run_ms`` of the window's engine-wide flushes (telemetry rows
with ``scope == "engine"``): the compiled scans plus the waits for their
stats (``scan.run``), device time as the engine thread sees it."""


def read(ctx):
    ms = [r["run_ms"] for r in ctx["rows"]
          if r["scope"] == "engine" and r.get("run_ms") is not None]
    return sum(ms) / len(ms) if ms else None

"""Mean ``forced_sessions`` of the window's engine-wide flushes
(telemetry rows with ``scope == "engine"``): the sessions whose tails
one flush forced, one per distinct query of the service's batch -- the
queries one engine-wide flush answers."""


def read(ctx):
    n = [r["forced_sessions"] for r in ctx["rows"]
         if r["scope"] == "engine" and r.get("forced_sessions") is not None]
    return sum(n) / len(n) if n else None

"""Share of the lane-steps that the window's engine-wide flushes scanned
which carried a real chunk, counted over the lanes each flush scanned
(telemetry column ``scan_lanes``: its lane bucket, pads included): sum
of chunks over sum of scan_lanes x width.  A program whose rows lack
``scan_lanes`` gives nothing to read."""


def read(ctx):
    rows = [r for r in ctx["rows"]
            if r["scope"] == "engine" and r.get("scan_lanes") is not None]
    steps = sum(r["scan_lanes"] * r["lane_width"] for r in rows)
    if not steps:
        return None
    return 100.0 * sum(r["chunks"] for r in rows) / steps

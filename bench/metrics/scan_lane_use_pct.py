"""Share of the lane-steps that the window's engine-wide flushes scanned
which carried a real chunk: sum of chunks over sum of lanes x width."""


def read(ctx):
    rows = [r for r in ctx["rows"] if r["scope"] == "engine"]
    steps = sum(ctx["num_lanes"] * r["lane_width"] for r in rows)
    if not steps:
        return None
    return 100.0 * sum(r["chunks"] for r in rows) / steps

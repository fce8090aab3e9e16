"""The readers of the flush's lane-bucket columns (``scan_lanes``,
``gather_ms``) on hand-built telemetry rows."""
import pytest

import run


def _row(scope, **cols):
    return {"scope": scope, "flush_ms": 100.0, **cols}


ROWS = [
    _row("engine", chunks=30, lane_width=2, scan_lanes=32, active_lanes=20,
         gather_ms=4.0),
    _row("engine", chunks=10, lane_width=1, scan_lanes=16, active_lanes=10,
         gather_ms=2.0),
    _row("session", chunks=3, lane_width=3, scan_lanes=1, active_lanes=1,
         gather_ms=0.5),
    _row("admit", chunks=8, lane_width=1, scan_lanes=8, active_lanes=8,
         gather_ms=1.0),
]


def _read(name, rows):
    return run.load_reader("metrics", name).read(
        {"rows": rows, "seconds": 10.0})


@pytest.mark.parametrize("name,want", [
    # (30 + 10) chunks over (32 x 2 + 16 x 1) lane-steps
    ("scan_bucket_use_pct", 100.0 * 40 / 80),
    ("engine_gather_ms.mean", 3.0),
])
def test_reader_on_rows(name, want):
    assert _read(name, ROWS) == pytest.approx(want)


@pytest.mark.parametrize("name", ["scan_bucket_use_pct",
                                  "engine_gather_ms.mean"])
def test_needs_engine_rows_with_the_columns(name):
    """No engine rows, or rows of a program that scans the whole lane
    table (no ``scan_lanes`` / ``gather_ms``), give nothing to read."""
    assert _read(name, [r for r in ROWS if r["scope"] != "engine"]) is None
    assert _read(name, [_row("engine", chunks=5, lane_width=1)]) is None
    assert _read(name, []) is None

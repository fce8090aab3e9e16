"""The readers of the flush's scan-step columns (``pack_ms``, ``h2d_ms``,
``run_ms``, ``forced_sessions``) on hand-built telemetry rows."""
import pytest

import run


def _row(scope, **cols):
    return {"scope": scope, "flush_ms": 100.0, **cols}


ROWS = [
    _row("engine", pack_ms=10.0, h2d_ms=200.0, run_ms=1800.0, segments=2,
         forced_sessions=60),
    _row("engine", pack_ms=30.0, h2d_ms=400.0, run_ms=2200.0, segments=1,
         forced_sessions=100),
    _row("session", pack_ms=1.0, h2d_ms=2.0, run_ms=5.0, segments=1),
    _row("admit", pack_ms=0.5, h2d_ms=0.5, run_ms=3.0, segments=1),
]


def _read(name, rows, seconds=10.0):
    return run.load_reader("metrics", name).read(
        {"rows": rows, "seconds": seconds})


@pytest.mark.parametrize("name,want", [
    ("engine_pack_ms.mean", 20.0),
    ("engine_h2d_ms.mean", 300.0),
    ("engine_run_ms.mean", 2000.0),
    ("engine_flush_queries.mean", 80.0),
    # (10 + 200 + 30 + 400 + 1 + 2 + 0.5 + 0.5) ms of a 10-s window
    ("scan_prep_pct", 100.0 * 644.0 / 10_000.0),
])
def test_reader_on_rows(name, want):
    assert _read(name, ROWS) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "engine_pack_ms.mean", "engine_h2d_ms.mean", "engine_run_ms.mean",
    "engine_flush_queries.mean"])
def test_no_engine_rows_reads_none(name):
    assert _read(name, [r for r in ROWS if r["scope"] != "engine"]) is None
    assert _read(name, []) is None


@pytest.mark.parametrize("name", [
    "engine_pack_ms.mean", "engine_h2d_ms.mean", "engine_run_ms.mean",
    "engine_flush_queries.mean", "scan_prep_pct"])
def test_rows_without_the_columns_read_none(name):
    """A program that does not time its scan steps (rows with
    ``flush_ms`` only) gives nothing to read, and no error."""
    assert _read(name, [_row("engine"), _row("session")]) is None


def test_scan_prep_reads_session_rows_alone():
    rows = [r for r in ROWS if r["scope"] == "session"]
    assert _read("scan_prep_pct", rows, seconds=1.0) == pytest.approx(0.3)

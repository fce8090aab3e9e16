"""A whole CPU run of hll-16k's deployment and mix cut to 64 tenants,
few of them with a chunk at any flush: the run is correct, and every
engine-wide flush scans a lane bucket smaller than the lane table."""
import json

import pytest

import run
from workload import BENCH_DIR, Cell


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    monkeypatch.setattr(run, "use_compile_cache", lambda: None)


def many_tenant_cell(rate: float = 60.0) -> Cell:
    """hll-16k with 64 tenants and lanes of a few registers: under the
    zipfian mix most tenants have no chunk at a given flush."""
    cfg = json.loads((BENCH_DIR / "configs" / "hll-16k.json").read_text())
    cfg.update(tenants=64, primary_slots=64, secondary_slots=4, chunk=256,
               num_pri=4, num_sec=2, p=6, cells=64, key_domain=1 << 12)
    mix = json.loads(
        (BENCH_DIR / "traffic" / "zipf-over-hll.json").read_text())
    mix.update(append_tuples=[64, 384], connections=2, prelude_s=1.0,
               requests_per_s=rate)
    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    return Cell(name="tiny-hll-many", chips=1, config=cfg, traffic=mix,
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


def test_many_tenant_run_scans_only_active_lanes(monkeypatch):
    engines = []
    orig = run.telemetry

    def spy(engine):
        engines.append(engine)
        return orig(engine)

    monkeypatch.setattr(run, "telemetry", spy)
    res = run.run_cell(many_tenant_cell(), 2**31 + 5, 2.0, trace=False)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    eng = engines[-1]
    rows = [r for r in eng.telemetry_record(validate=False)["rows"]
            if r["scope"] == "engine" and r["scan_lanes"]]
    assert rows, "no engine-wide flush scanned a lane"
    for r in rows:
        assert r["active_lanes"] <= r["scan_lanes"] < eng.num_lanes, r

"""A cell small enough for a CPU test run: histo-1k's deployment and
mix with a handful of lanes, bins and tuples, and its HyperLogLog
counterpart (the reference and the harness serve both apps)."""
import json

from workload import BENCH_DIR, Cell


def tiny_cell(app: str = "histo", rate: float = 80.0) -> Cell:
    cfg = json.loads((BENCH_DIR / "configs" / "histo-1k.json").read_text())
    cfg.update(app=app, tenants=8, primary_slots=8, secondary_slots=2,
               chunk=256, num_pri=4, num_sec=2, key_domain=1 << 12)
    if app == "histo":
        cfg.update(bins=512, cells=512)
    else:
        cfg.update(p=6, cells=64)
    mix = json.loads((BENCH_DIR / "traffic" / "zipf-over.json").read_text())
    mix.update(append_tuples=[64, 384], connections=2, prelude_s=1.0,
               requests_per_s=rate)
    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    return Cell(name=f"tiny-{app}", chips=1, config=cfg, traffic=mix,
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])

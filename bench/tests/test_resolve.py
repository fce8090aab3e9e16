"""A cell, a mix and a metric are found by name: adding one adds files
and a ``BENCHMARK.json`` entry and edits nothing."""
import json
import shutil

import pytest

import run
import workload as wl


def test_every_cell_of_the_benchmark_resolves():
    bench = json.loads((wl.BENCH_DIR.parent / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = wl.resolve(w["name"], wl.BENCH_DIR.parent)
        assert cell.rate > 0
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for m in bench["end_to_end"] + bench["per_layer"]:
            assert (wl.BENCH_DIR / "metrics" / f"{m['name']}.py").exists()


def test_a_throwaway_mix_resolves(tmp_path):
    shutil.copytree(wl.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((wl.BENCH_DIR.parent / "BENCHMARK.json").read_text())
    mix = json.loads((wl.BENCH_DIR / "traffic" / "zipf-over.json")
                     .read_text())
    mix.update(key_alphas=[3.0], requests_per_s=7.5)
    (tmp_path / "bench" / "traffic" / "throwaway.json").write_text(
        json.dumps(mix))
    bench["workloads"].append({"name": "histo-1k.throwaway",
                               "config": "histo-1k", "traffic": "throwaway",
                               "chips": 1, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = wl.resolve("histo-1k.throwaway", tmp_path)
    assert cell.traffic["key_alphas"] == [3.0] and cell.rate == 7.5
    assert cell.config["primary_slots"] == 1024
    with pytest.raises(KeyError):
        wl.resolve("histo-1k.nothing", tmp_path)


def test_readers_leave_out_what_they_cannot_read():
    ctx = {"trace": None, "rows": [], "spans": [], "chunks": 0,
           "num_lanes": 8}
    for name in ("device_idle_pct", "route_accumulate_roofline",
                 "engine_flush_ms.mean", "scan_lane_use_pct",
                 "sec_chunk_share_pct"):
        assert run.load_reader("metrics", name).read(ctx) is None, name


def test_roofline_share_is_tuples_over_kernel_time():
    rows = [{"tuples": 1_000_000, "scope": "engine"}]
    ctx = {"trace": {"kernels": {"route_accumulate": {"seconds": 1.0}}},
           "rows": rows, "device_kind": "TPU v5 lite"}
    share = run.load_reader("metrics",
                            "route_accumulate_roofline").read(ctx)
    assert share == pytest.approx(100.0 * 8e6 / 819e9)
    ctx["device_kind"] = "TPU v4"
    with pytest.raises(KeyError):
        run.load_reader("metrics", "route_accumulate_roofline").read(ctx)

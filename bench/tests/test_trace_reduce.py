"""The trace reduction, on a small trace recorded on a TPU v5e (an
engine of 10 histogram lanes: one engine-wide flush and one per-session
query inside ``bench.window``) and on hand-made intervals."""
import dataclasses
from pathlib import Path

import pytest

import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "tpu_small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return tr.reduce(ProfileData.from_file(str(TRACE)), 1,
                     tr.default_kernels())


def test_window_busy_and_kernel(reduced):
    assert 0.0435 < reduced["window_s"] < 0.0445
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    k = reduced["kernels"]["route_accumulate"]["seconds"]
    # two flushes of route_accumulate: most of the busy time, none of
    # the idle time
    assert 0.6 * reduced["busy_s"] < k <= reduced["busy_s"]
    assert reduced["collective_s_chip0"] == 0.0


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert 0 < len(ops) <= tr.TOP
    assert ops[0][0].startswith("route_accumulate")
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= tr.TOP
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) <= idle + 1e-9
    assert all(isinstance(n, str) and n for n, _ in gaps)


@dataclasses.dataclass
class Ev:
    name: str
    start_ns: int
    duration_ns: int

    @property
    def end_ns(self):
        return self.start_ns + self.duration_ns


def test_union_and_gaps():
    u = tr._union([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert u == [(0, 20), (30, 45)]
    assert tr._gaps(u, (-5, 50)) == [(-5, 0), (20, 30), (45, 50)]


def test_self_time_subtracts_nested_ops():
    evs = [Ev("while", 0, 100), Ev("a", 10, 20), Ev("b", 50, 30),
           Ev("c", 200, 5)]
    got = {e.name: t for e, t in tr._self_times(evs)}
    assert got == {"while": 50, "a": 20, "b": 30, "c": 5}


def test_short_name():
    assert tr.short_name("%fusion.3 = s32[4]{0} fusion(%x)") == "fusion.3"

"""The traffic generator: the same seed gives the same requests, and
every seed does the same work in another order."""
import json

import numpy as np
import pytest

import workload as wl

ROOT = wl.BENCH_DIR.parent


def _load(cfg, mix, uniform=False):
    c = json.loads((wl.BENCH_DIR / "configs" / f"{cfg}.json").read_text())
    t = json.loads((wl.BENCH_DIR / "traffic" / f"{mix}.json").read_text())
    if uniform:     # YCSB's requestdistribution=uniform, uniform keys
        t.update(request_distribution={"zipf": 0.0}, key_alphas=[0.0])
    return c, t


@pytest.mark.parametrize("uniform", [False, True])
def test_same_seed_same_requests(uniform):
    c, t = _load("histo-1k", "zipf-over", uniform)
    a = wl.make_schedule(c, t, 2**31 + 5, 4.0, 50.0)
    b = wl.make_schedule(c, t, 2**31 + 5, 4.0, 50.0)
    for f in ("t", "tenant", "op", "lo", "hi", "tuples", "rank"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


@pytest.mark.parametrize("uniform", [False, True])
def test_seeds_do_the_same_work(uniform):
    c, t = _load("histo-1k", "zipf-over", uniform)
    runs = [wl.make_schedule(c, t, s, 6.0, 80.0) for s in (1, 2, 2**40)]

    def work(r):
        w = slice(r.n_warm, None)
        app = r.op[w] == wl.APPEND
        return (np.sort((r.hi - r.lo)[w][app]),
                np.bincount(r.rank[r.tenant[w][app]], minlength=1024),
                np.bincount(r.rank[r.tenant[w][~app]], minlength=1024))

    first = work(runs[0])
    for r in runs[1:]:
        for a, b in zip(first, work(r)):
            np.testing.assert_array_equal(a, b)
    sizes, _, per_rank_q = first
    n = 80 * (6 + t["prelude_s"])
    assert len(sizes) + per_rank_q.sum() == n
    assert per_rank_q.sum() == round(80 * 6 * t["query_proportion"]) \
        + round(80 * t["prelude_s"] * t["query_proportion"])
    assert sizes.min() >= 1024 and sizes.max() <= 2048
    assert not np.array_equal(runs[0].t, runs[1].t)


def test_arrivals_fill_the_window_in_order():
    c, t = _load("histo-1k", "zipf-over")
    s = wl.make_schedule(c, t, 9, 5.0, 100.0)
    tw = s.t[s.n_warm:]
    assert (np.diff(tw) >= 0).all()
    assert tw.min() >= -t["prelude_s"] and tw.max() < 5.0
    assert int((tw >= 0).sum()) == 500
    # each append owns its own rows of the tuples, in schedule order
    app = np.flatnonzero(s.op == wl.APPEND)
    assert (s.lo[app[1:]] == s.hi[app[:-1]]).all()
    assert s.hi[app[-1]] == len(s.tuples)
    assert ((s.hi - s.lo)[s.op == wl.QUERY] == 0).all()


def test_busy_tenants_follow_the_request_distribution():
    c, t = _load("histo-1k", "zipf-over")
    s = wl.make_schedule(c, t, 4, 20.0, 100.0)
    per_rank = np.bincount(s.rank[s.tenant[s.n_warm:]], minlength=1024)
    pmf = wl.zipf_pmf(1024, 0.99)
    np.testing.assert_allclose(per_rank / per_rank.sum(), pmf, atol=2e-3)
    c, t = _load("histo-1k", "zipf-over", uniform=True)
    s = wl.make_schedule(c, t, 4, 20.0, 100.0)
    per_rank = np.bincount(s.rank[s.tenant[s.n_warm:]], minlength=1024)
    # one request of rounding per op and segment
    assert per_rank.max() - per_rank.min() <= 4


def test_keys_stay_in_domain_and_skew_per_rank():
    c, t = _load("histo-1k", "zipf-over")
    s = wl.make_schedule(c, t, 3, 5.0, 100.0)
    keys = s.tuples[:, 0]
    assert keys.min() >= 0 and keys.max() < c["key_domain"]
    # the rank-3 tenant draws alpha 2.0: its hottest key holds most tuples
    ten = int(np.flatnonzero(s.rank == 3)[0])
    rows = [np.arange(s.lo[i], s.hi[i]) for i in range(len(s))
            if s.tenant[i] == ten and s.op[i] == wl.APPEND]
    k = s.tuples[np.concatenate(rows), 0]
    assert np.bincount(k).max() > 0.5 * len(k)


def test_sampled_checks_hold_the_busiest_sessions():
    c, t = _load("histo-1k", "zipf-over")
    s = wl.make_schedule(c, t, 3, 10.0, 100.0)
    chk = wl.sample_checks(s, 3)
    busiest = int(np.flatnonzero(s.rank == 0)[0])
    assert busiest in chk["closes"]
    last_q = max(i for i in range(len(s))
                 if s.tenant[i] == busiest and s.op[i] == wl.QUERY)
    assert last_q in chk["queries"]


def test_answers_after_an_append_of_unknown_fate_are_not_compared():
    c, t = _load("histo-1k", "zipf-over")
    c = dict(c, bins=512, cells=512, key_domain=1 << 12)
    s = wl.make_schedule(c, t, 5, 10.0, 100.0)
    chk = wl.sample_checks(s, 5)
    acked = np.ones(len(s), bool)
    full = wl.expected_answers(c, s, acked, chk)
    busiest = int(np.flatnonzero(s.rank == 0)[0])
    mine = np.flatnonzero(s.tenant == busiest)
    lost = mine[(s.op[mine] == wl.APPEND) & (mine > s.n_warm)][0]
    unknown = np.zeros(len(s), bool)
    unknown[lost] = True
    part = wl.expected_answers(c, s, acked, chk, unknown=unknown)
    gone = set(full) - set(part)
    assert f"c{busiest}" in gone
    assert all(k == f"c{busiest}" or int(k[1:]) > lost for k in gone)
    assert all(np.array_equal(part[k], full[k]) for k in part)

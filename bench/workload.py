"""Resolve a cell by name and generate its traffic from the seed.

Everything here is data driven: a cell of ``BENCHMARK.json`` names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``, which holds its offered rate and the knee
it was set from); a metric is read by ``bench/metrics/<metric>.py``.
Adding a cell, a mix or a metric adds files and entries and edits none.

The mix is YCSB's core workload shape over sessions: each request picks
its tenant from the request distribution and is a query with the mix's
``query_proportion``, an append otherwise.  The schedule is open loop:
requests arrive at the mix's fixed rate, at times drawn as a Poisson
process conditioned on its count.  Every seed does the same work in
another order: the number of appends and queries, the multiset of append
sizes and the number of each per tenant rank are fixed by the rate and
the mix; the seed draws the arrival times, their order, which session
holds which rank, and the keys.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from reference import running
from zipfgen import ZipfSampler, apportion, zipf_pmf

BENCH_DIR = Path(__file__).resolve().parent
SEED_MOD = 1 << 64


@dataclasses.dataclass
class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads``, resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def rate(self) -> float:
        """Offered requests per second, appends and queries together."""
        return float(self.traffic["requests_per_s"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % SEED_MOD, *salt])


@dataclasses.dataclass
class Schedule:
    """The requests of one run, in send order.

    ``t`` is the scheduled send time in seconds from the window's start
    (negative in the prelude, ``-inf`` for the warm traffic sent before
    it), ``tenant`` the session
    index, ``op`` 0 for append and 1 for query, ``lo:hi`` the append's
    rows of ``tuples``.  ``rank`` is each session's popularity rank (0 is
    the busiest)."""

    t: np.ndarray
    tenant: np.ndarray
    op: np.ndarray
    lo: np.ndarray
    hi: np.ndarray
    tuples: np.ndarray
    rank: np.ndarray
    n_warm: int

    def __len__(self) -> int:
        return len(self.t)


APPEND, QUERY = 0, 1


def _keys(sampler: ZipfSampler, rng, n: int, mult: np.ndarray,
          add: np.ndarray, domain: int) -> np.ndarray:
    """Zipf ranks mapped through a per-tuple affine bijection of the key
    domain (a power of two), so each tenant has its own hot keys."""
    r = sampler.ranks(rng, n).astype(np.int64)
    return (r * mult + add) & (domain - 1)


def _segment(rng, t0: float, t1: float, n: int, q_share: float, pmf,
             lo_n: int, hi_n: int):
    """``n`` requests over ``[t0, t1)``: sorted arrival times, each
    request's op and the popularity rank of its tenant, and the sizes of
    the appends in arrival order.  The (rank, op) pairs (appends and
    queries each apportioned to the popularity ``pmf``) and the sizes
    (spread evenly over ``[lo_n, hi_n]``) are the same multisets for
    every seed."""
    n_q = int(round(n * q_share))
    n_a = n - n_q
    every_rank = np.arange(len(pmf))
    ranks = np.concatenate([np.repeat(every_rank, apportion(pmf, n_a)),
                            np.repeat(every_rank, apportion(pmf, n_q))])
    ops = np.repeat(np.array([APPEND, QUERY], np.int8), [n_a, n_q])
    order = rng.permutation(n)
    sizes = lo_n + (np.arange(n_a) * (hi_n - lo_n + 1)) // max(n_a, 1)
    rng.shuffle(sizes)
    times = np.sort(rng.uniform(t0, t1, n))
    return times, ranks[order], ops[order], sizes


def make_schedule(config: dict, traffic: dict, seed: int, seconds: float,
                  rate: float) -> Schedule:
    """The run's requests.  Before the window, in set-up: one chunk each
    for two sessions, then a query of each (the shapes of both flush
    tiers), then ``prelude_s`` seconds of the mix itself, so that the
    window starts in the mix's steady state.  In the window: ``rate *
    seconds`` requests."""
    tenants = int(config["tenants"])
    domain = int(config["key_domain"])
    if domain & (domain - 1):
        raise ValueError(f"key_domain {domain} is not a power of two")
    rng = rng_for(seed, 1)
    pmf = zipf_pmf(tenants, float(traffic["request_distribution"]["zipf"]))
    q_share = float(traffic["query_proportion"])
    lo_n, hi_n = (int(x) for x in traffic["append_tuples"])
    pre = float(traffic["prelude_s"])
    segs = [_segment(rng, -pre, 0.0, int(round(rate * pre)), q_share, pmf,
                     lo_n, hi_n),
            _segment(rng, 0.0, seconds, int(round(rate * seconds)), q_share,
                     pmf, lo_n, hi_n)]
    times, rank_seq, ops, sizes = (np.concatenate([x[k] for x in segs])
                                   for k in range(4))
    tenant_of_rank = rng.permutation(tenants)
    is_app = ops == APPEND

    chunk = int(config["chunk"])
    all_sizes = np.concatenate([[chunk, chunk], sizes]).astype(np.int64)
    ends = np.cumsum(all_sizes)
    starts = ends - all_sizes
    total = int(ends[-1])

    alphas = [float(a) for a in traffic["key_alphas"]]
    app_rank = np.concatenate([[tenants - 1, tenants - 2], rank_seq[is_app]])
    app_alpha = np.asarray([alphas[r % len(alphas)] for r in app_rank])
    app_alpha[:2] = 0.0
    key_rng = rng_for(seed, 2)
    mult = key_rng.integers(0, domain // 2, tenants) * 2 + 1
    add = key_rng.integers(0, domain, tenants)
    app_tenant = tenant_of_rank[app_rank]
    per_tuple_tenant = np.repeat(app_tenant, all_sizes)
    keys = np.empty(total, np.int64)
    for a in sorted(set(app_alpha.tolist())):
        rows = np.repeat(app_alpha == a, all_sizes)
        keys[rows] = _keys(ZipfSampler(domain, a), key_rng, int(rows.sum()),
                           mult[per_tuple_tenant[rows]],
                           add[per_tuple_tenant[rows]], domain)
    values = key_rng.integers(0, 2**31 - 1, total)
    tuples = np.stack([keys, values], axis=1).astype(np.int32)

    # the warm traffic: two appends, then a query of each
    warm_t = np.full(4, -np.inf)
    warm_tenant = app_tenant[[0, 1, 0, 1]]
    warm_op = np.array([APPEND, APPEND, QUERY, QUERY], np.int8)
    app_at = 2 + np.cumsum(is_app) - 1          # each append's tuple rows
    lo = np.where(is_app, starts[np.minimum(app_at, len(starts) - 1)], 0)
    hi = np.where(is_app, ends[np.minimum(app_at, len(ends) - 1)], 0)
    rank = np.empty(tenants, np.int64)
    rank[tenant_of_rank] = np.arange(tenants)
    return Schedule(
        t=np.concatenate([warm_t, times]),
        tenant=np.concatenate([warm_tenant,
                               tenant_of_rank[rank_seq]]).astype(np.int64),
        op=np.concatenate([warm_op, ops]).astype(np.int8),
        lo=np.concatenate([starts[[0, 1]], [0, 0], lo]).astype(np.int64),
        hi=np.concatenate([ends[[0, 1]], [0, 0], hi]).astype(np.int64),
        tuples=tuples, rank=rank, n_warm=4)


def sample_checks(sched: Schedule, seed: int, n_top: int = 8,
                  n_queries: int = 32, n_closes: int = 8) -> Dict[str, list]:
    """Which answers a run keeps and compares, drawn from the seed: the
    last query of each of the ``n_top`` busiest sessions (the longest
    streams), ``n_queries`` other queries, and the closes of the
    ``n_top`` busiest sessions plus ``n_closes`` others."""
    rng = rng_for(seed, 3)
    q_idx = np.flatnonzero(sched.op == QUERY)
    top = set(np.argsort(sched.rank)[:n_top].tolist())
    last: Dict[int, int] = {}
    for i in q_idx:
        if int(sched.tenant[i]) in top:
            last[int(sched.tenant[i])] = int(i)
    rest = np.setdiff1d(q_idx, list(last.values()))
    picked = rng.choice(rest, min(n_queries, len(rest)), replace=False) \
        if len(rest) else np.zeros(0, np.int64)
    others = np.setdiff1d(np.unique(sched.tenant), list(top))
    closes = sorted(top) + sorted(rng.choice(
        others, min(n_closes, len(others)), replace=False).tolist())
    return {"queries": sorted(int(i) for i in [*last.values(), *picked]),
            "closes": [int(c) for c in closes]}


def expected_answers(config: dict, sched: Schedule, acked: np.ndarray,
                     checks: Dict[str, list],
                     control: Optional[str] = None,
                     unknown: Optional[np.ndarray] = None
                     ) -> Dict[str, np.ndarray]:
    """Reference answers for the sampled requests, keyed ``q<i>`` (the
    query at schedule index ``i``) and ``c<tenant>`` (a close).  Only
    appends the service acknowledged (``acked[i]``) count.  An append
    whose fate the client cannot know (``unknown[i]``: given up unanswered,
    or lost with its connection) may or may not have been applied, so no
    answer of its session after it is compared.

    ``control`` puts a tempting shortcut in the program's place:
    ``int16`` counters (wrapping), or ``stale_tail``, an answer that
    leaves out the ragged tail past the session's last full chunk."""
    want_q = set(checks["queries"])
    want_c = set(checks["closes"])
    tenants = sorted({int(sched.tenant[i]) for i in want_q} | want_c)
    chunk = int(config["chunk"])
    out: Dict[str, np.ndarray] = {}
    for ten in tenants:
        idx = np.flatnonzero(sched.tenant == ten)
        parts: List[np.ndarray] = []
        for i in idx:
            if unknown is not None and unknown[i] and sched.op[i] == APPEND:
                break
            if sched.op[i] == APPEND:
                if acked[i]:
                    parts.append(sched.tuples[sched.lo[i]:sched.hi[i], 0])
            elif i in want_q:
                out[f"q{i}"] = _answer(config, parts, chunk, control)
        else:
            if ten in want_c:
                out[f"c{ten}"] = _answer(config, parts, chunk, control)
    return out


def _answer(config, parts, chunk, control):
    keys = np.concatenate(parts) if parts else np.zeros(0, np.int64)
    if control == "stale_tail":
        keys = keys[:len(keys) // chunk * chunk]
    ref = running(config)
    ref.add(keys)
    ans = ref.snapshot()
    if control == "int16":
        ans = ans.astype(np.int16).astype(np.int64)
    elif control not in (None, "stale_tail"):
        raise ValueError(f"unknown control {control!r}")
    return ans

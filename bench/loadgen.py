"""Open-loop load generator: the client side of one run, in its own process.

It never initialises JAX on the chip (the parent starts it with
``JAX_PLATFORMS=cpu``); it runs numpy, sockets and the service's wire
client.  It talks to the parent over lines on stdin/stdout:

    parent -> CONNECT host port   the service is up (the schedule is made
                                  meanwhile, while the parent warms up)
    child  -> READY {...}      sessions opened, warm traffic answered
    parent -> GO <t0>          window starts at monotonic time t0 (the
                               prelude's requests go out before it)
    child  -> WINDOW {...}     every window request answered (or given up)
    parent -> VERIFY           close the sampled sessions and compare
    child  -> RESULT {...}     the comparison against the reference

Each session's requests travel in schedule order on one connection, so
the service applies them in that order and a query's answer is fixed by
the appends scheduled before it.  Latency is timed from each request's
scheduled send time; how late the generator sent is reported beside it.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

import numpy as np

import workload as wl

DRAIN_S = 60.0          # an answer may come this long after the close
OPEN_WAVE = 1024        # opens in flight at once in set-up


def emit(tag: str, obj) -> None:
    sys.stdout.write(f"{tag} {json.dumps(obj)}\n")
    sys.stdout.flush()


def log(msg: str) -> None:
    print(f"[loadgen {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


async def readline() -> str:
    line = await asyncio.get_running_loop().run_in_executor(
        None, sys.stdin.readline)
    if not line:
        raise SystemExit("loadgen: parent closed stdin")
    return line.strip()


async def run(args) -> None:
    from repro.serve.errors import RetryableError
    from repro.serve.service import AsyncServiceClient

    cell = json.loads(args.cell)
    cfg, traffic, rate = cell["config"], cell["traffic"], cell["rate"]
    t_gen = time.monotonic()
    sched = wl.make_schedule(cfg, traffic, args.seed, args.seconds, rate)
    checks = wl.sample_checks(sched, args.seed)
    keep = set(checks["queries"])
    log(f"{len(sched)} requests ({int((sched.op == wl.QUERY).sum())} "
        f"queries) at {rate:.1f} requests/s made in "
        f"{time.monotonic() - t_gen:.1f} s")

    _, host, port = (await readline()).split()      # CONNECT host port
    n_conn = int(traffic["connections"])
    conns = [await AsyncServiceClient.connect(host, int(port),
                                              trace=False)
             for _ in range(n_conn)]
    tenants = int(cfg["tenants"])
    conn_of = [conns[i % n_conn] for i in range(tenants)]
    sids = []
    for lo in range(0, tenants, OPEN_WAVE):     # under max_pending
        sids += await asyncio.gather(*(conn_of[i].open(f"tenant-{i}")
                                       for i in range(lo, min(lo + OPEN_WAVE,
                                                              tenants))))
    shape = (int(cfg["num_pri"]), -(-int(cfg["cells"]) // int(cfg["num_pri"])))

    n = len(sched)
    t_done = np.full(n, np.nan)
    t_sent = np.full(n, np.nan)
    ok = np.zeros(n, bool)
    # applied or not, the client cannot tell: given up, or lost in transit
    unknown = np.zeros(n, bool)
    errors: dict = {}
    answers: dict = {}
    wrong_shape = [0]

    async def send(i: int) -> None:
        ten = int(sched.tenant[i])
        c = conn_of[ten]
        t_sent[i] = time.monotonic()
        try:
            if sched.op[i] == wl.APPEND:
                await c.append(sids[ten],
                               sched.tuples[sched.lo[i]:sched.hi[i]])
            else:
                got = await c.query(sids[ten])
                if got.shape != shape:
                    wrong_shape[0] += 1
                elif i in keep:
                    answers[f"q{i}"] = got
            ok[i] = True
        except Exception as e:      # counted as failed; the run goes on
            errors[type(e).__name__] = errors.get(type(e).__name__, 0) + 1
            # a retryable refusal is answered before anything is applied
            unknown[i] = not isinstance(e, RetryableError)
        t_done[i] = time.monotonic()

    await asyncio.gather(*(send(i) for i in range(sched.n_warm)))
    emit("READY", {"sessions": tenants, "requests": n,
                   "warm_ok": bool(ok[:sched.n_warm].all())})

    cmd = await readline()
    t0 = float(cmd.split()[1])
    loop = asyncio.get_running_loop()
    tasks = []
    for i in range(sched.n_warm, n):
        delay = t0 + sched.t[i] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(loop.create_task(send(i)))
    t_close = t0 + args.seconds
    await asyncio.sleep(max(t_close - time.monotonic(), 0.0))
    inflight_at_close = int(sum(not t.done() for t in tasks))
    done, pending = await asyncio.wait(
        tasks, timeout=max(t_close + DRAIN_S - time.monotonic(), 0.0))
    for k, t in enumerate(tasks):
        if t in pending:
            t.cancel()
            unknown[sched.n_warm + k] = True
    run_ = slice(sched.n_warm, n)          # prelude and window
    win = sched.t[run_] >= 0
    sched_abs = t0 + sched.t[run_]
    lat_ms = (t_done[run_] - sched_abs) * 1e3
    late_ms = (t_sent[run_] - sched_abs) * 1e3
    is_app = sched.op[run_] == wl.APPEND
    ok_r = ok[run_]
    n_tup = (sched.hi - sched.lo)[run_]
    acked_in_window = ok_r & is_app & (t_done[run_] >= t0) \
        & (t_done[run_] <= t_close)
    # whether the request queue grows: latency of the window's first and
    # last fifth (the knee sweep reads them)
    t_rel = sched.t[run_]
    fifth = [ok_r & (t_rel >= 0) & (t_rel < args.seconds / 5),
             ok_r & (t_rel >= args.seconds * 4 / 5)]
    emit("WINDOW", {
        "attempted": int(n - sched.n_warm),
        "failed": int((~ok_r).sum()),
        "errors": errors,
        "never_answered": len(pending),
        "inflight_at_close": inflight_at_close,
        "appends": int((is_app & win).sum()),
        "queries": int((~is_app & win).sum()),
        "tuples_offered": int(n_tup[is_app & win].sum()),
        "tuples_acked_in_window": int(n_tup[acked_in_window].sum()),
        "append_lat_ms": lat_ms[is_app & ok_r & win].tolist(),
        "query_lat_ms": lat_ms[~is_app & ok_r & win].tolist(),
        "prelude_query_lat_ms": lat_ms[~is_app & ok_r & ~win].tolist(),
        "late_ms": np.nan_to_num(late_ms[win], nan=-1.0).tolist(),
        "last_answer_s": float(np.nanmax(t_done[run_]) - t0),
        # above the knee this over the window's seconds is the capacity
        "answered_in_window": int((ok_r & (t_done[run_] >= t0)
                                   & (t_done[run_] <= t_close)).sum()),
        "lat_p50_first_last_fifth_ms": [
            float(np.median(lat_ms[f])) if f.any() else None for f in fifth],
    })

    await readline()            # VERIFY
    for ten in checks["closes"]:
        try:
            answers[f"c{ten}"] = await conn_of[ten].close(sids[ten])
        except Exception as e:
            errors[f"close:{type(e).__name__}"] = 1
    want = wl.expected_answers(cfg, sched, ok, checks, unknown=unknown)
    if args.control is not None:
        # the control: the reference in a lower precision, or with a
        # shortcut, stands in the program's place
        answers = wl.expected_answers(cfg, sched, ok, checks,
                                      control=args.control, unknown=unknown)
    mismatched_cells = 0
    missing = 0
    for key, ref in want.items():
        got = answers.get(key)
        if got is None:
            # a sampled query that failed is already counted as failed
            missing += int(key.startswith("c") or ok[int(key[1:])])
            continue
        if got.shape != ref.shape:
            mismatched_cells += int(ref.size)
            continue
        mismatched_cells += int((got.astype(np.int64) != ref).sum())
    emit("RESULT", {"compared": len(want) - missing, "missing": missing,
                    "mismatched_cells": mismatched_cells,
                    "wrong_shape": wrong_shape[0],
                    # sampled answers after an append of unknown fate
                    "not_comparable": len(checks["queries"])
                    + len(checks["closes"]) - len(want)})
    for c in conns:
        await c.aclose()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", required=True)
    ap.add_argument("--cell", required=True,
                    help="JSON: the cell's config, traffic and rate")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(args.root) / "src"))
    asyncio.run(run(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())

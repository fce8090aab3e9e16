#!/usr/bin/env python3
"""Smoke test of the session service on a TPU: the main path, end to end.

    python3 chip_smoke.py              # one chip: service + kernel phases
    python3 chip_smoke.py --chips 4    # four chips: lane mesh vs one device

One chip (the default) runs two phases on ``jax.devices()[0]``:

* service -- a ``SessionService`` over a histogram ``SessionEngine`` whose
  1024 primary + 64 secondary lanes hold 1.36 GiB of lane state on the
  device.  Four wire clients (one tenant hot at Zipf 1.5) open sessions,
  append ragged batches, query (engine-wide and per-session flushes) and
  close.  Every answer must equal ``histo.oracle`` and the hot tenant
  must be granted secondary lanes.
* kernels -- HyperLogLog (p = 14, Redis's register count) and count-min
  heavy hitters (depth 4, width 16384) through ``SessionEngine``, which
  drive ``route_accumulate`` with ``max`` and ``cms_update``; answers must
  equal ``hll.oracle`` / ``hhd.oracle``.

``--chips 4`` runs only the lane mesh: a ``SessionEngine`` sharded over
four chips, with secondary-lane re-grants whose shadow-buffer folds cross
chips, compared bit for bit with the same traffic on a one-device engine
and with the oracle.

Timings printed here are smoke timings (one run, compilation separate),
not benchmark numbers.  The script exits non-zero when JAX finds no TPU,
when any phase fails or when any answer disagrees with its oracle; its
last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GIB = 1 << 30
DOMAIN = 1 << 22                  # key domain of every generated stream

# deployment-size histogram service: 20 PE rows x 16384 bins x 4 B per
# lane, 1088 lanes -> 1.36 GiB of lane state
SERVICE = dict(bins=262144, num_pri=16, num_sec=4, chunk=4096,
               primary_slots=1024, secondary_slots=64,
               tenants={"hot": 1.5, "uniform": 0.0, "mild": 0.5,
                        "warm": 1.0},
               hot_chunks=40, cold_chunks=1, rounds=2)
KERNELS = dict(num_pri=16, num_sec=4, chunk=4096, primary_slots=4,
               secondary_slots=4, hll_p=14, cms_depth=4, cms_width=16384,
               hot_chunks=12, cold_chunks=2)
# four chips: 16 lanes, 4 per chip; primaries sit on chips 0-1 and the
# secondary lanes on chips 2-3, so every re-grant fold is a cross-chip psum
MESH = dict(bins=262144, num_pri=16, num_sec=4, chunk=4096,
            primary_slots=8, secondary_slots=8, rounds=3, hot_chunks=6)


def log(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


def ragged(rng, chunks: int, chunk: int) -> int:
    """A tuple count of ``chunks`` full chunks plus a ragged tail."""
    return chunks * chunk + int(rng.integers(1, chunk))


def check(name: str, got, want) -> None:
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=name)


def service_phase(cfg) -> None:
    from repro.apps import histo
    from repro.data.zipf import zipf_tuples
    from repro.serve import ServiceClient, SessionEngine, SessionService
    from repro.serve.service import ServiceConfig

    spec = histo.make_spec(cfg["bins"], DOMAIN, cfg["num_pri"])
    eng = SessionEngine(spec, num_pri=cfg["num_pri"], num_sec=cfg["num_sec"],
                        chunk_size=cfg["chunk"],
                        primary_slots=cfg["primary_slots"],
                        secondary_slots=cfg["secondary_slots"])
    log(f"service: lane state bytes {eng.state_bytes} "
        f"({eng.state_bytes / GIB:.3f} GiB, {eng.num_lanes} lanes)")
    if eng.state_bytes < GIB:
        raise SystemExit("service: lane state is below 1 GiB")

    rng = np.random.default_rng(0)
    keys = {t: [] for t in cfg["tenants"]}
    with SessionService(eng, ServiceConfig()) as svc:
        host, port = svc.address
        clients = {t: ServiceClient(host, port, timeout=900.0)
                   for t in cfg["tenants"]}
        sids = {t: c.open(t) for t, c in clients.items()}
        for r in range(cfg["rounds"]):
            for i, (t, alpha) in enumerate(cfg["tenants"].items()):
                n = ragged(rng, cfg["hot_chunks"] if t == "hot"
                           else cfg["cold_chunks"], cfg["chunk"])
                batch = zipf_tuples(n, DOMAIN, alpha, seed=100 * r + i)
                keys[t].append(batch[:, 0])
                # ragged appends: split each batch at two random points
                cuts = np.sort(rng.integers(1, n, 2))
                for part in np.split(batch, cuts):
                    clients[t].append(sids[t], part)
            # the hot tenant's query forces the engine-wide flush, where
            # the backlog scheduler grants it secondary lanes
            got = clients["hot"].query(sids["hot"], scope="engine")
            check(f"round {r} hot engine query", got, histo.oracle(
                np.concatenate(keys["hot"]), cfg["bins"], DOMAIN,
                cfg["num_pri"]))
            for t in cfg["tenants"]:
                got = clients[t].query(sids[t])
                check(f"round {r} {t} query", got, histo.oracle(
                    np.concatenate(keys[t]), cfg["bins"], DOMAIN,
                    cfg["num_pri"]))
            log(f"service: round {r} queries equal histo.oracle")
        for t, c in clients.items():
            merged, stats = c.close(sids[t])
            check(f"{t} close", merged, histo.oracle(
                np.concatenate(keys[t]), cfg["bins"], DOMAIN,
                cfg["num_pri"]))
            if t == "hot" and stats["sec_lane_flushes"] == 0:
                raise SystemExit("service: the hot tenant never ran on a "
                                 "secondary lane")
            log(f"service: {t} close equals histo.oracle "
                f"({stats['tuples_flushed']} tuples, "
                f"{stats['sec_lane_flushes']} chunks on secondary lanes)")
            c.close_conn()
    rows = [r for r in eng.telemetry_record()["rows"]
            if r["scope"] == "engine"]
    first, steady = rows[0], rows[-1]
    log(f"service: smoke timing (not a benchmark number): engine-wide "
        f"flush of {eng.num_lanes} lanes, first {first['flush_ms']} ms "
        f"(compile {first['compile_stall_ms']} ms), steady "
        f"{steady['flush_ms']} ms ({steady['n_retraces']} compiles), "
        f"{steady['sec_granted']} secondary lanes granted")
    if first["sec_granted"] == 0:
        raise SystemExit("service: no secondary lane was granted")


def _drive_app(name, spec, oracle, cfg) -> None:
    """Hot + uniform tenants with ragged appends through one engine: an
    engine-wide flush (which grants the hot tenant secondary lanes), then
    per-session queries and closes, each checked against ``oracle``."""
    from repro.data.zipf import zipf_tuples
    from repro.serve import SessionEngine

    eng = SessionEngine(spec, num_pri=cfg["num_pri"], num_sec=cfg["num_sec"],
                        chunk_size=cfg["chunk"],
                        primary_slots=cfg["primary_slots"],
                        secondary_slots=cfg["secondary_slots"])
    rng = np.random.default_rng(1)
    sids, keys = {}, {}
    for i, (t, alpha) in enumerate({"hot": 1.5, "uniform": 0.0}.items()):
        sids[t] = eng.open(t)
        n = ragged(rng, cfg["hot_chunks"] if t == "hot" else cfg["cold_chunks"],
                   cfg["chunk"])
        batch = zipf_tuples(n, DOMAIN, alpha, seed=7 + i)
        keys[t] = batch[:, 0]
        eng.append(sids[t], batch[: n // 3])
        eng.append(sids[t], batch[n // 3:])
    t0 = time.perf_counter()
    eng.flush()
    log(f"{name}: smoke timing (not a benchmark number): first engine-wide "
        f"flush {1e3 * (time.perf_counter() - t0):.1f} ms incl. compile")
    for t, sid in sids.items():
        check(f"{name} {t} query", eng.query(sid), oracle(keys[t]))
    for t, sid in sids.items():
        merged, stats = eng.close(sid)
        check(f"{name} {t} close", merged, oracle(keys[t]))
        if t == "hot" and stats["sec_lane_flushes"] == 0:
            raise SystemExit(f"{name}: the hot tenant never ran on a "
                             "secondary lane")
    log(f"{name}: every query and close equals {name}.oracle")


def kernel_phase(cfg) -> None:
    from repro.apps import hhd, hll

    p, m = cfg["hll_p"], cfg["num_pri"]
    _drive_app("hll", hll.make_spec(p, m),
               lambda k: hll.oracle(k, p, m), cfg)
    d, w = cfg["cms_depth"], cfg["cms_width"]
    _drive_app("hhd", hhd.make_spec(d, w, m),
               lambda k: hhd.oracle(k, d, w, m), cfg)


def mesh_phase(cfg) -> None:
    """The same traffic through a four-chip lane mesh and a one-device
    engine: answers bit-exact with each other and with the oracle, and at
    least one secondary lane re-granted (a cross-chip fold)."""
    from repro.apps import histo
    from repro.core.distributed import lane_mesh
    from repro.data.zipf import zipf_tuples
    from repro.serve import SessionEngine

    spec = histo.make_spec(cfg["bins"], DOMAIN, cfg["num_pri"])
    tenants = range(cfg["primary_slots"])
    rng = np.random.default_rng(2)
    # rounds[r][i]: tenant i's batch in round r; the hot tenant moves
    rounds = [[zipf_tuples(ragged(rng, cfg["hot_chunks"] if i == r else 0,
                                  cfg["chunk"]), DOMAIN, 1.5, seed=100 * r + i)
               for i in tenants] for r in range(cfg["rounds"])]

    def drive(mesh):
        eng = SessionEngine(spec, num_pri=cfg["num_pri"],
                            num_sec=cfg["num_sec"], chunk_size=cfg["chunk"],
                            primary_slots=cfg["primary_slots"],
                            secondary_slots=cfg["secondary_slots"],
                            mesh=mesh)
        sids = [eng.open(f"t{i}") for i in tenants]
        answers = {}
        for r, batches in enumerate(rounds):
            for sid, batch in zip(sids, batches):
                eng.append(sid, batch)
            eng.flush()                  # grants move to the new hot tenant
            answers[f"q{r}"] = eng.query(sids[r])
        for i, sid in enumerate(sids):
            answers[f"c{i}"] = eng.close(sid)[0]
        return eng, answers

    t0 = time.perf_counter()
    dist, got = drive(lane_mesh(4))
    log(f"mesh: four-chip engine done in {time.perf_counter() - t0:.1f} s "
        f"(smoke timing, compile included); "
        f"{dist.slot_reschedules} secondary-lane re-grants")
    if dist.slot_reschedules == 0:
        raise SystemExit("mesh: no secondary lane was re-granted")
    _, want = drive(None)
    for name in want:
        check(f"mesh vs one device: {name}", got[name], want[name])
    log(f"mesh: {len(want)} answers bit-exact vs the one-device engine")
    for i in tenants:
        keys = np.concatenate([batches[i][:, 0] for batches in rounds])
        check(f"mesh close {i} vs oracle", got[f"c{i}"],
              histo.oracle(keys, cfg["bins"], DOMAIN, cfg["num_pri"]))
    log(f"mesh: {len(tenants)} closes equal histo.oracle")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: service + kernel phases on one chip; "
                         "4: only the four-chip lane mesh phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.core import compilemon
    from repro.kernels import dispatch

    log(f"device {dev.device_kind} x{len(devices)}; compile cache "
        f"{compilemon.use_persistent_cache()}")
    realization = dispatch.resolve()
    log(f"kernel realization: route_accumulate={realization} "
        f"cms_update={realization} (int32 and float32 alike)")
    if realization != dispatch.PALLAS:
        raise SystemExit(f"kernels resolved to {realization!r}, not the "
                         "native Pallas kernels")
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(MESH)
    else:
        service_phase(SERVICE)
        kernel_phase(KERNELS)
    log(f"peak_bytes_in_use {dev.memory_stats()['peak_bytes_in_use']}; "
        f"phases took {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

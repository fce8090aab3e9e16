"""Stateful differential test harness for the serving stack (ISSUE 7).

Drives random interleavings of ``open`` / ``open_batch`` / ``append`` /
``query`` / ``close`` / ``flush`` / ``flush_session`` / ``recover``
against TWO implementations in lockstep:

  * the real ``serve.SessionEngine`` (on the default device, on a
    caller's one-device mesh, and durable -- a recovered durable engine
    must be bit-exact vs never having crashed);
  * ``OracleModel``, a pure-numpy model of the documented semantics --
    FIFO waitlist into the lowest free slot, chunk-granular engine-wide
    flushes, everything-through per-session flushes, ``ValueError`` for
    unknown/closed sids, ``RuntimeError`` for queued-session queries and
    data-bearing queued closes.

After EVERY operation the harness asserts:

  answers      query/close results equal the numpy histogram oracle over
               the model's appended keys (bit-exact);
  errors       the engine and the model raise the same exception class;
  slots        slot conservation -- admitted sids hold unique primary
               slots, the engine's slot table, FIFO queue, and free-slot
               heap match the model exactly (admission order AND slot
               placement are deterministic, the documented contract);
  backlog      per-session ``backlog_tuples`` equals the model's pending
               count and the engine's own pending-array accounting;
  buckets      once the AOT table is warm, every subsequent telemetry
               row reports ``n_retraces == 0`` -- storms included.

Two drivers share the harness: a seeded random walk that ALWAYS runs
(hypothesis-free, tier-1 everywhere), and a Hypothesis
``RuleBasedStateMachine`` (skipped when hypothesis is not installed --
``pip install -r requirements-dev.txt``).  The machine's example budget
is profile-switched: the default ``storm-fast`` profile keeps tier-1
quick; CI's slow job exports ``STORM_PROFILE=storm-full`` for the
200-example run (the acceptance bar).
"""
from __future__ import annotations

import os
import sys
import tempfile
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from repro.apps import histo
from repro.serve import SessionEngine
from repro.serve.durability import DurableSessionEngine
from repro.serve.errors import (ClosedSessionError, QueuedSessionError,
                                UnknownSessionError)
from repro.serve.service import (ServiceClient, ServiceConfig,
                                 SessionService, encode_frame)

BINS, DOMAIN, M, CHUNK = 32, 1 << 12, 4, 64
PRIMARY, SECONDARY, AOT = 2, 1, 2


def _spec():
    return histo.make_spec(BINS, DOMAIN, M)


def _mk_data(seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, DOMAIN, size=n, dtype=np.int64)
    return np.stack([keys, np.ones_like(keys)], axis=1).astype(np.int32)


def _oracle(keys_parts: List[np.ndarray]) -> np.ndarray:
    keys = (np.concatenate(keys_parts) if keys_parts
            else np.zeros(0, np.int64))
    return histo.oracle(keys, BINS, DOMAIN, M)


# ---------------------------------------------------------------------------
# The pure-numpy oracle engine
# ---------------------------------------------------------------------------

class OracleModel:
    """Host-side model of SessionEngine's documented semantics: session
    bookkeeping is exact (slots, queue, pending counts); answers are the
    numpy histogram oracle over every key appended so far (the engine's
    chunking-invariance guarantee makes flush timing answer-invisible)."""

    def __init__(self, primary_slots: int, chunk: int):
        self.primary = primary_slots
        self.chunk = chunk
        self.sessions: Dict[int, Dict[str, Any]] = {}
        self.slot_sid: List[Optional[int]] = [None] * primary_slots
        self.queue: List[int] = []
        self.free: List[int] = list(range(primary_slots))   # kept sorted
        self.next_sid = 0

    # -- internals
    def _admit(self) -> None:
        while self.queue and self.free:
            sid = self.queue.pop(0)
            slot = self.free.pop(0)            # lowest free slot, FIFO sid
            self.slot_sid[slot] = sid
            self.sessions[sid]["slot"] = slot

    def _get(self, sid: int, allow_closed: bool = False) -> Dict[str, Any]:
        s = self.sessions.get(sid)
        if s is None:
            raise UnknownSessionError(f"unknown session id {sid}")
        if s["closed"] and not allow_closed:
            raise ClosedSessionError(f"session {sid} is closed")
        return s

    # -- ops (mirror the engine API)
    def open(self, tenant: str) -> int:
        sid = self.next_sid
        self.next_sid += 1
        self.sessions[sid] = {"tenant": tenant, "keys": [], "pending": 0,
                              "slot": None, "closed": False}
        self.queue.append(sid)
        self._admit()
        return sid

    def append(self, sid: int, data: np.ndarray) -> None:
        s = self._get(sid)
        if len(data):
            s["keys"].append(np.asarray(data)[:, 0].copy())
            s["pending"] += len(data)

    def open_batch(self, tenants: List[str],
                   first: Optional[List[Optional[np.ndarray]]]) -> List[int]:
        sids = []
        for i, t in enumerate(tenants):
            sid = self.open(t)
            sids.append(sid)
            if first is not None and first[i] is not None:
                self.append(sid, first[i])
        for sid in sids:                       # the storm flush: full
            s = self.sessions[sid]             # chunks of ADMITTED storm
            if s["slot"] is not None:          # sessions run immediately
                s["pending"] %= self.chunk
        return sids

    def flush(self, force=()) -> None:
        force = set(force)
        self._admit()
        for sid in self.slot_sid:
            if sid is None:
                continue
            s = self.sessions[sid]
            s["pending"] = 0 if sid in force else s["pending"] % self.chunk

    def flush_session(self, sid: int) -> None:
        s = self._get(sid)
        if s["slot"] is None:
            raise QueuedSessionError(f"session {sid} is queued")
        s["pending"] = 0

    def query(self, sid: int, scope: str = "session") -> np.ndarray:
        s = self._get(sid)
        if s["slot"] is None:
            raise QueuedSessionError(f"session {sid} is queued")
        if scope == "engine":
            self.flush(force=(sid,))
        else:
            s["pending"] = 0
        return _oracle(s["keys"])

    def close(self, sid: int) -> np.ndarray:
        s = self._get(sid)
        if s["slot"] is None and s["pending"]:
            raise QueuedSessionError(f"session {sid} is queued with data")
        out = _oracle(s["keys"])
        s["pending"] = 0
        if s["slot"] is not None:
            self.slot_sid[s["slot"]] = None
            self.free = sorted(self.free + [s["slot"]])
            s["slot"] = None
        else:
            self.queue.remove(sid)
        s["closed"] = True
        self._admit()
        return out


# ---------------------------------------------------------------------------
# The differential harness
# ---------------------------------------------------------------------------

class DifferentialHarness:
    """One op stream, two implementations, invariants after every op.

    With ``network=True`` (ISSUE 9) every session op travels through a
    LIVE in-process ``SessionService`` endpoint instead of calling the
    engine directly: two concurrent client connections alternate
    request-for-request, the wire clients re-raise the exact taxonomy
    classes the engine raises (so ``_both``'s error parity holds
    unchanged), and ``op_net_drop`` injects a forced disconnect
    mid-append -- a half-frame then a dead socket, which must never
    touch engine state.  The service runs ``admission="fifo"`` so the
    oracle's FIFO slot model stays exact.  ``flush``/``flush_session``
    are engine-side maintenance (not wire ops) and keep calling the
    engine directly -- safe, because the blocking clients return only
    after the service's single-writer worker went idle."""

    def __init__(self, *, mesh1: bool = False, durable: bool = False,
                 workdir=None, network: bool = False):
        self.spec = _spec()
        self.durable = durable
        self.workdir = workdir
        self.network = network
        mesh = jax.make_mesh((1,), ("lanes",)) if mesh1 else None
        self.mesh = mesh
        kw = dict(num_pri=M, num_sec=2, chunk_size=CHUNK,
                  primary_slots=PRIMARY, secondary_slots=SECONDARY,
                  aot_buckets=AOT, mesh=mesh)
        if durable:
            assert workdir is not None
            self.eng = DurableSessionEngine(self.spec, directory=workdir,
                                            checkpoint_every=2, keep=2, **kw)
        else:
            self.eng = SessionEngine(self.spec, **kw)
        self.svc = None
        self.clients: List[ServiceClient] = []
        self._op_i = 0
        if network:
            self._start_service()
        self.model = OracleModel(PRIMARY, CHUNK)
        self.warmed_at: Optional[int] = None   # telemetry row index where
        self.n_recovers = 0                    # the AOT table became warm

    def _start_service(self) -> None:
        self.svc = SessionService(self.eng, ServiceConfig(admission="fifo"))
        self.svc.start()
        self.clients = [ServiceClient(*self.svc.address) for _ in range(2)]

    def _stop_service(self) -> None:
        if self.svc is None:
            return
        for c in self.clients:
            c.close_conn()
        self.clients = []
        self.svc.stop()
        self.svc = None

    def _ep(self):
        """The endpoint under test: the engine, or (network mode) one of
        two concurrent wire clients, alternating per op."""
        if not self.network:
            return self.eng
        self._op_i += 1
        return self.clients[self._op_i % len(self.clients)]

    def shutdown(self) -> None:
        self._stop_service()
        if isinstance(self.eng, DurableSessionEngine):
            self.eng.shutdown()

    # -- lockstep execution with error parity
    def _both(self, eng_fn, model_fn):
        try:
            got, got_exc = eng_fn(), None
        except (ValueError, RuntimeError) as e:
            got, got_exc = None, type(e)
        try:
            want, want_exc = model_fn(), None
        except (ValueError, RuntimeError) as e:
            want, want_exc = None, type(e)
        assert got_exc is want_exc, (
            f"error divergence: engine raised {got_exc}, "
            f"oracle model raised {want_exc}")
        self.check()
        return got, want

    # -- ops
    def op_open(self, tenant: str) -> Optional[int]:
        ep = self._ep()
        got, want = self._both(lambda: ep.open(tenant),
                               lambda: self.model.open(tenant))
        assert got == want
        return got

    def op_open_batch(self, tenants: List[str],
                      first: Optional[List[Optional[np.ndarray]]]):
        ep = self._ep()
        got, want = self._both(
            lambda: ep.open_batch(tenants, first=first),
            lambda: self.model.open_batch(list(tenants), first))
        assert got == want
        row = self.eng._telemetry[-1]
        assert row["scope"] == "admit"
        assert row["n_admitted"] + row["n_queued_batch"] == len(tenants)
        # O(buckets), not O(sessions): the storm scans in width segments
        max_chunks = max((0 if f is None else len(f) // CHUNK
                          for f in (first or [])), default=0)
        assert row["n_scan_dispatches"] <= max(1, max_chunks)
        return got

    def op_append(self, sid: int, data: np.ndarray) -> None:
        ep = self._ep()
        self._both(lambda: ep.append(sid, data),
                   lambda: self.model.append(sid, data))

    def op_query(self, sid: int, scope: str = "session") -> None:
        ep = self._ep()
        got, want = self._both(lambda: ep.query(sid, scope=scope),
                               lambda: self.model.query(sid, scope))
        if want is not None:
            np.testing.assert_array_equal(np.asarray(got), want)

    def op_close(self, sid: int) -> None:
        ep = self._ep()
        got, want = self._both(lambda: ep.close(sid),
                               lambda: self.model.close(sid))
        if want is not None:
            np.testing.assert_array_equal(np.asarray(got[0]), want)

    def op_net_drop(self, sid: int, data: np.ndarray) -> None:
        """Forced disconnect mid-append: a raw connection ships HALF of
        a well-formed append frame and dies.  No complete frame ever
        reached the codec, so neither implementation moves -- the next
        ``check()`` proves the engine bit-identical to the model."""
        assert self.network
        a = np.ascontiguousarray(data)
        frame = encode_frame(
            {"op": "append", "sid": int(sid), "id": 1,
             "array": {"dtype": a.dtype.str, "shape": list(a.shape)}},
            a.tobytes())
        raw = ServiceClient(*self.svc.address)
        raw.send_raw(frame[:max(9, len(frame) // 2)])
        raw.close_conn()
        self.check()

    def op_flush(self) -> None:
        self._both(lambda: self.eng.flush(), lambda: self.model.flush())

    def op_flush_session(self, sid: int) -> None:
        self._both(lambda: self.eng.flush_session(sid),
                   lambda: self.model.flush_session(sid))

    def op_recover(self) -> None:
        """Abandon the engine (the in-process crash idiom: the WAL is
        flushed per record, checkpoints are atomic) and resume from
        disk; the model keeps running untouched -- a recovered engine
        must be indistinguishable from one that never crashed."""
        assert self.durable
        self._stop_service()           # network mode: the front door dies
        self.eng.shutdown()            # with the process it fronted
        self.eng = SessionEngine.recover(self.spec, self.workdir,
                                         mesh=self.mesh)
        if self.network:               # ...and a NEW service fronts the
            self._start_service()      # recovered engine
        assert self.eng.recovery_info["replay_anomalies"] == 0, \
            self.eng.recovery_info
        self.n_recovers += 1
        # restored telemetry is the OLD engine's tail (already checked);
        # the zero-retrace invariant restarts at the recovery point
        self.warmed_at = (len(self.eng._telemetry)
                          if self.eng._aot else None)
        self.check()
        for sid, ms in self.model.sessions.items():
            if ms["slot"] is not None and not ms["closed"]:
                self.op_query(sid)             # answers survived the crash
                break

    # -- the invariants
    def check(self) -> None:
        eng, m = self.eng, self.model
        # slot conservation + deterministic placement: the engine's slot
        # table, FIFO queue, and free-slot heap all match the model
        assert eng._next_sid == m.next_sid
        assert list(eng._slot_sid) == list(m.slot_sid)
        assert list(eng._queue) == list(m.queue)
        assert sorted(eng._free_slots) == m.free
        occupied = {i for i, sid in enumerate(eng._slot_sid)
                    if sid is not None}
        assert occupied.isdisjoint(eng._free_slots)
        assert occupied | set(eng._free_slots) == set(range(m.primary))
        for sid, es in eng.sessions.items():
            if es.slot is not None:
                assert eng._slot_sid[es.slot] == sid and not es.closed
        # backlog accounting: engine counters == model pending == the
        # engine's own pending-array bookkeeping
        assert set(eng.sessions) == set(m.sessions)
        for sid, ms in m.sessions.items():
            es = eng.sessions[sid]
            assert es.closed == ms["closed"]
            assert es.backlog_tuples == ms["pending"], (
                f"sid {sid}: backlog {es.backlog_tuples} != model "
                f"pending {ms['pending']}")
            assert es.backlog_tuples == sum(
                len(a) for a in es.pending_arrays())
        # bucket-table hit: once warm, NOTHING on any flush path (storm
        # admissions included) may retrace (listify: the telemetry
        # store is a ring deque, which does not slice)
        rows = list(eng._telemetry)
        if self.warmed_at is None and eng._aot:
            self.warmed_at = len(rows)
        if self.warmed_at is not None:
            for row in rows[self.warmed_at:]:
                assert row["n_retraces"] == 0, (
                    f"retrace after warmup: {row}")


# ---------------------------------------------------------------------------
# Driver 1: seeded random walk (hypothesis-free; always runs in tier-1)
# ---------------------------------------------------------------------------

def _known_sid(rng, h: DifferentialHarness, bad: bool = False) -> int:
    if bad or not h.model.sessions:
        return int(rng.integers(10_000, 20_000))
    sids = sorted(h.model.sessions)
    return int(sids[rng.integers(len(sids))])


def _random_walk(h: DifferentialHarness, seed: int, n_ops: int,
                 max_recovers: int = 2) -> Dict[str, int]:
    rng = np.random.default_rng(seed)
    ops = ["open", "open_batch", "append", "append", "append_bad",
           "query", "query_engine", "close", "close_bad",
           "flush", "flush_session"]
    if h.durable:
        ops.append("recover")
    if h.network:
        ops.append("net_drop")
    counts = {op: 0 for op in ops}
    for step in range(n_ops):
        op = ops[rng.integers(len(ops))]
        if op == "recover" and counts["recover"] >= max_recovers:
            op = "open_batch"                 # recovery re-warms: cap it
        counts[op] = counts.get(op, 0) + 1
        if op == "open":
            h.op_open(f"t{rng.integers(3)}")
        elif op == "open_batch":
            k = int(rng.integers(1, 5))
            first = [None if rng.integers(4) == 0
                     else _mk_data(int(rng.integers(1 << 30)),
                                   int(rng.integers(0, 3 * CHUNK)))
                     for _ in range(k)]
            h.op_open_batch([f"t{rng.integers(3)}" for _ in range(k)],
                            first)
        elif op == "append":
            h.op_append(_known_sid(rng, h),
                        _mk_data(int(rng.integers(1 << 30)),
                                 int(rng.integers(0, 3 * CHUNK))))
        elif op == "append_bad":
            h.op_append(_known_sid(rng, h, bad=True), _mk_data(0, 4))
        elif op == "query":
            h.op_query(_known_sid(rng, h))
        elif op == "query_engine":
            h.op_query(_known_sid(rng, h), scope="engine")
        elif op == "close":
            h.op_close(_known_sid(rng, h))
        elif op == "close_bad":
            h.op_close(_known_sid(rng, h, bad=True))
        elif op == "flush":
            h.op_flush()
        elif op == "flush_session":
            h.op_flush_session(_known_sid(rng, h))
        elif op == "recover":
            h.op_recover()
        elif op == "net_drop":
            h.op_net_drop(_known_sid(rng, h),
                          _mk_data(int(rng.integers(1 << 30)),
                                   int(rng.integers(1, 2 * CHUNK))))
    return counts


@pytest.mark.parametrize("mode", ["local_durable", "mesh1", "service"])
def test_random_walk_differential(mode, tmp_path):
    """100 random ops against the numpy oracle, invariants after every
    one -- the hypothesis-free differential net (local+durable engine
    with mid-walk recoveries, the mesh-of-1 engine, and the network
    service endpoint with forced mid-append disconnects and recovery
    ACROSS a service restart)."""
    durable = mode in ("local_durable", "service")
    h = DifferentialHarness(mesh1=mode == "mesh1", durable=durable,
                            workdir=tmp_path / "d" if durable else None,
                            network=mode == "service")
    try:
        counts = _random_walk(h, seed=20260808, n_ops=100)
        # the walk must actually exercise the storm + recovery paths
        assert counts["open_batch"] >= 5
        if durable:
            assert counts["recover"] >= 1 and h.n_recovers >= 1
        if mode == "service":
            # ...and the wire-specific rules: forced disconnects landed,
            # and both client connections carried traffic
            assert counts["net_drop"] >= 1
            assert h._op_i > 2
    finally:
        h.shutdown()


def test_service_concurrent_clients_bit_exact():
    """TRUE concurrency through the front door: two clients fire
    appends/queries at two sessions simultaneously from two threads.
    The single-writer worker serializes them; appends commute, so the
    engine must land bit-exact on the oracle regardless of arrival
    order."""
    import threading

    h = DifferentialHarness(network=True)
    try:
        sid_a = h.op_open("a")
        sid_b = h.op_open("b")
        parts = {sid_a: [], sid_b: []}
        errs = []

        def _pump(cli, sid, seed):
            try:
                for i in range(8):
                    d = _mk_data(seed + i, int(17 + 13 * i) % (2 * CHUNK))
                    cli.append(sid, d)
                    parts[sid].append(d[:, 0])
                    cli.query(sid)     # interleaved reads race the peer
            except Exception as e:     # pragma: no cover - must not happen
                errs.append(e)

        t1 = threading.Thread(target=_pump,
                              args=(h.clients[0], sid_a, 1000))
        t2 = threading.Thread(target=_pump,
                              args=(h.clients[1], sid_b, 2000))
        t1.start(); t2.start()
        t1.join(timeout=120); t2.join(timeout=120)
        assert not t1.is_alive() and not t2.is_alive()
        assert errs == []
        # sync the model with what the threads appended, then the full
        # invariant sweep + oracle-exact answers
        for sid in (sid_a, sid_b):
            s = h.model.sessions[sid]
            s["keys"].extend(parts[sid])
            s["pending"] = 0           # each thread's last op is a query
        h.check()
        h.op_query(sid_a)
        h.op_query(sid_b)
        h.op_close(sid_a)
        h.op_close(sid_b)
    finally:
        h.shutdown()


def test_random_walk_storm_heavy():
    """A storm-weighted walk: repeated over-capacity open_batch bursts
    with closes draining the FIFO queue between them."""
    h = DifferentialHarness()
    rng = np.random.default_rng(7)
    for burst in range(6):
        k = int(rng.integers(2, 6))
        first = [_mk_data(100 * burst + i, int(rng.integers(0, 3 * CHUNK)))
                 for i in range(k)]
        h.op_open_batch([f"b{burst}-{i}" for i in range(k)], first)
        for sid in sorted(h.model.sessions):
            if rng.integers(2) and not h.model.sessions[sid]["closed"]:
                h.op_close(sid)
    # drain everything; every remaining answer stays oracle-exact
    for sid in sorted(h.model.sessions):
        if not h.model.sessions[sid]["closed"]:
            h.op_close(sid)
    assert all(s["closed"] for s in h.model.sessions.values())


# ---------------------------------------------------------------------------
# Driver 2: Hypothesis stateful machine (CI; skipped without hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, settings
    from hypothesis import strategies as st
    from hypothesis.stateful import (RuleBasedStateMachine, precondition,
                                     rule)
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover - CI installs it
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    settings.register_profile(
        "storm-fast", max_examples=5, stateful_step_count=10,
        deadline=None, suppress_health_check=list(HealthCheck))
    settings.register_profile(
        "storm-full", max_examples=200, stateful_step_count=20,
        deadline=None, suppress_health_check=list(HealthCheck))
    settings.load_profile(os.environ.get("STORM_PROFILE", "storm-fast"))

    class _StormMachine(RuleBasedStateMachine):
        """Random interleavings of the full session API against the
        oracle model; every rule ends in DifferentialHarness.check()."""

        mesh1 = False
        durable = False
        network = False

        def __init__(self):
            super().__init__()
            self._tmp = tempfile.TemporaryDirectory() if self.durable \
                else None
            self.h = DifferentialHarness(
                mesh1=self.mesh1, durable=self.durable,
                workdir=self._tmp.name if self._tmp else None,
                network=self.network)

        def teardown(self):
            self.h.shutdown()
            if self._tmp is not None:
                self._tmp.cleanup()

        def _sid(self, pick: int) -> int:
            sids = sorted(self.h.model.sessions)
            return sids[pick % len(sids)] if sids else 10_000 + pick

        @rule(t=st.integers(0, 2))
        def open(self, t):
            self.h.op_open(f"t{t}")

        @rule(k=st.integers(1, 4), seed=st.integers(0, 2**31 - 1),
              sizes=st.lists(st.integers(0, 3 * CHUNK), min_size=1,
                             max_size=4))
        def open_batch(self, k, seed, sizes):
            sizes = (sizes * k)[:k]
            first = [_mk_data(seed + i, n) for i, n in enumerate(sizes)]
            self.h.op_open_batch([f"s{seed % 5}-{i}" for i in range(k)],
                                 first)

        @rule(pick=st.integers(0, 63), seed=st.integers(0, 2**31 - 1),
              n=st.integers(0, 3 * CHUNK))
        def append(self, pick, seed, n):
            self.h.op_append(self._sid(pick), _mk_data(seed, n))

        @rule(sid=st.integers(10_000, 10_063))
        def append_unknown(self, sid):
            self.h.op_append(sid, _mk_data(0, 4))

        @rule(pick=st.integers(0, 63),
              scope=st.sampled_from(["session", "engine"]))
        def query(self, pick, scope):
            self.h.op_query(self._sid(pick), scope=scope)

        @rule(pick=st.integers(0, 63))
        def close(self, pick):
            self.h.op_close(self._sid(pick))

        @rule()
        def flush(self):
            self.h.op_flush()

        @rule(pick=st.integers(0, 63))
        def flush_session(self, pick):
            self.h.op_flush_session(self._sid(pick))

        @precondition(lambda self: self.durable and self.h.n_recovers < 2)
        @rule()
        def recover(self):
            self.h.op_recover()

        @precondition(lambda self: self.network)
        @rule(pick=st.integers(0, 63), seed=st.integers(0, 2**31 - 1),
              n=st.integers(1, 2 * CHUNK))
        def net_drop(self, pick, seed, n):
            self.h.op_net_drop(self._sid(pick), _mk_data(seed, n))

    class _LocalDurableStorm(_StormMachine):
        durable = True

    class _Mesh1Storm(_StormMachine):
        mesh1 = True

    class _ServiceStorm(_StormMachine):
        # every op through the live wire endpoint, recoveries restart
        # the service, forced disconnects sprinkled in
        durable = True
        network = True

    TestStormStatefulLocalDurable = _LocalDurableStorm.TestCase
    TestStormStatefulMesh1 = _Mesh1Storm.TestCase
    TestStormStatefulService = _ServiceStorm.TestCase
else:                                    # tier-1 without hypothesis: the
    @pytest.mark.skip(reason="stateful machine needs hypothesis "
                      "(pip install -r requirements-dev.txt); the "
                      "random-walk differential tests above still ran")
    def test_storm_stateful_machine():   # pragma: no cover
        pass

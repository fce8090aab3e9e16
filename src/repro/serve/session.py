"""Continuous-batching session serving over the resumable Ditto executor
(DESIGN.md §8).

``StreamEngine`` serves whole, one-shot streams.  ``SessionEngine`` is the
datacenter shape on top of the same architecture: tenants ``open()`` a
session, ``append()`` arbitrary-length (ragged) tuple batches as they
arrive, ``query()`` a merged-buffer snapshot mid-stream, and ``close()``.
It is the analytics analogue of ``DecodeEngine``'s continuous batching --
sessions are the new requests, executor lanes are the new decode slots --
and one level up it replays the paper's skew-oblivious move: **sessions
are the new tuples, stream slots are the new PEs**.

Slot model
  The engine owns ``primary_slots + secondary_slots`` lanes of ONE
  vmapped resumable executor (a stacked ``ExecState`` with a leading
  lanes axis, advanced by a single batched ``lax.scan`` per flush).
  Every admitted session owns one primary lane for its whole life --
  the analogue of a PriPE owning a state partition.  Secondary lanes
  are the SecPEs of the serving layer: each flush, the paper's greedy
  scheduler (``scheduler.schedule_secpes``) runs over per-session
  chunk **backlog** and grants hot sessions extra lanes; a session's
  chunks then stripe round-robin across its lane group.  When a
  secondary lane is re-granted to a different session, its buffers are
  merged into the old owner's primary lane and reset -- exactly the
  SecPE shadow-buffer merge of §IV-B, lifted one level.

Suspend/resume + ragged input
  Appends buffer host-side until a flush; full chunks go straight into
  the lanes, and a query/close forces the ragged tail through as a
  masked final chunk (``data.pipeline.chunk_stream``'s padded-tail
  path), which the executor treats as an exact no-op.  ``query`` is a
  non-destructive merge: primary + granted secondary lanes combine
  like SecPE shadow buffers (add/max), leaving every buffer intact so
  the stream keeps running.  Merged results are therefore bit-exact
  against the one-shot executor on the same tuples for the integer
  paper apps, regardless of append chunking, tails, or slot grants.

Latency tiering (per-session flush)
  ``query``/``close`` default to ``flush_session``: only the queried
  session's lane group runs (its own backlog width, <= 1 + granted
  lanes instead of all engine lanes), so a tenant's query latency is
  bounded by its OWN backlog under many-tenant load.  ``flush()``
  remains the engine-wide path (and the only place slot re-scheduling
  happens); both produce identical results for any interleaving.

Lane buckets (active-lane flushes)
  Every flush -- engine-wide, per-session, admission -- scans only the
  lanes that carry a chunk: they are gathered out of the lane table
  into a **lane bucket**, the power-of-two ceiling of their count
  (capped at the lanes a device holds), padded with other lanes that
  carry all-masked chunks (exact no-ops -- a padded lane's state rides
  through the scan bit-identically), scanned, and scattered back.  A
  flush costs what its active lanes cost, not what the lane table
  holds, and its host bookkeeping walks only the sessions with work.

The lane table (DESIGN.md §9, docs/distributed.md)
  The lanes live in one table, split over the ``lanes`` axis of a mesh
  (``core.distributed.make_lane_table``): ``mesh=None`` is the mesh of
  the default device, ``SessionEngine(mesh=...)`` spreads P devices x
  lanes_per_device lanes, one engine serving more tenants than one
  device's lane budget.  Every lane operation -- gather, scatter, scan,
  snapshot, fold, reset -- is one body over a device's slice lifted by
  ``shard_map``, so one chip and four run the same programs.  Flushes
  stay collective-free: each device gathers its own active lanes into
  the bucket of the busiest device's count and scans them; a snapshot
  reads its one lane on the owning device, and a cross-device slot
  re-grant runs the §IV-B shadow-buffer merge as a psum over the lanes
  axis.

AOT shape buckets (compile-stall elimination)
  Ragged appends produce ragged flush batches, and every new
  (lane bucket, scan width) shape is a fresh jit trace -- a silent
  multi-hundred-ms stall on the flush path.  With
  ``SessionEngine(aot_buckets=W)`` scan widths round up to powers of
  two and are chopped into segments of at most ``W``, and ``warmup()``
  AOT-lowers and compiles ONE executable per (lane bucket, width) up
  front (the lane table's scan of
  ``core.executor.ResumableExecutor.scan_lanes``, lowered and compiled)
  and primes every fixed-shape helper, so steady-state
  traffic -- however ragged -- never compiles again.  Warmup runs
  explicitly or at the first ``append`` (when the tuple dtype/shape
  becomes known); ``recover`` lands a restored engine in the same
  buckets before replaying the WAL tail.

Batched admission (session storms)
  Admitting sessions one at a time re-opens the retrace/dispatch hole
  the bucket table closed: a storm of N new tenants (the memcached
  request-path scenario) would cost O(N) lane inits and O(N) scans.
  ``open_batch(tenants, first=...)`` packs the whole storm -- every
  open plus its first append -- into ONE batched lane-init (a single
  gather-free ``x.at[idx].set`` over all admitted lanes) and one
  lane-bucketed scan over the admitted primary lanes, chopped into the
  same AOT width segments as a flush: O(buckets) dispatches for a
  thousand-session storm.  The storm's scan uses the lane buckets of
  every flush, so the zero-steady-retrace invariant holds THROUGH
  storms, on any mesh.  Ragged first-append
  tails stay buffered (answers are chunking-invariant), keeping the
  storm path bit-exact vs serial admission.  Overflow is strictly
  FIFO: tenants past ``primary_slots`` queue in ``open_batch`` call
  order and admit deterministically as slots free.

Telemetry + observability (DESIGN.md §11, docs/observability.md)
  Per-flush counters (tuples, chunks, lane width, secondary grants,
  slot re-schedules, backlog, occupancy, modeled cycles -- plus
  ``n_retraces`` / ``compile_stall_ms`` observed during the flush, via
  ``core.compilemon``'s jax.monitoring listener) accumulate into a
  schema-v1 benchmark record (``telemetry_record``), the same shape
  ``benchmarks.common`` validates and ``benchmarks.run`` reports.  The
  row store is a RING (``telemetry_cap=`` rows, oldest dropped first,
  drops counted under ``extra['telemetry']``), so a long-running engine
  holds a bounded tail instead of leaking memory, and
  ``telemetry_record(validate=True)`` validates only the rows appended
  since the previous call (O(new), not O(history)).

  The same rows feed the engine's ``obs=`` bundle (``repro.obs``): a
  metrics registry (``flush_latency_ms{scope}``, ``lane_occupancy
  {lane}``, ``secondary_grants_total{tenant}``, ``backlog_depth
  {tenant}``, retrace counters -- Prometheus-exportable) and a span
  tracer (``engine.flush`` / ``scan.gather`` / ``scan.segment`` >
  ``scan.pack`` / ``scan.h2d`` / ``scan.run`` / ``scan.scatter`` /
  ``engine.admit_storm`` / ``merge.snapshot`` ... as Perfetto
  ``trace_event`` JSON, each span also a JAX profiler annotation).
  Every flush row carries its scan steps' host times (``gather_ms``,
  ``pack_ms``, ``h2d_ms``, ``run_ms``, ``segments``) and its lane
  counts (``scan_lanes``, ``active_lanes``) whether tracing is on or
  off.  Pass one
  ``Observability`` to share a registry across engines, ``obs=False``
  to disable (every op an early return -- the serving bench asserts
  the enabled overhead stays under its bound).

Durability (DESIGN.md §10, docs/durability.md)
  ``serve.durability`` wraps this engine in a per-tenant write-ahead
  log plus periodic lane-state checkpoints (the whole lane table
  through ``checkpoint.CheckpointManager``);
  ``SessionEngine.recover`` restores the newest checkpoint, replays
  only the WAL tail past its watermark, and resumes every open session
  bit-exactly after a crash, on any mesh.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import re
import time
from collections import deque
from typing import Any, Deque, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compilemon
from repro.core import distributed as core_distributed
from repro.core import executor as core_executor
from repro.core import scheduler
from repro.data.pipeline import pad_tail_chunk
from repro.serve.errors import (ClosedSessionError, QueuedSessionError,
                                ShapeMismatchError, UnknownSessionError)
from repro import obs as obs_lib

TELEMETRY_SCHEMA_VERSION = 1   # mirrors benchmarks.common.SCHEMA_VERSION


@dataclasses.dataclass
class SessionStats:
    """Host-side per-session aggregation of the executor's ExecStats."""

    tuples_appended: int = 0
    tuples_flushed: int = 0
    chunks_flushed: int = 0
    queries: int = 0
    modeled_cycles: float = 0.0
    max_load: int = 0
    exec_reschedules: int = 0
    sec_lane_flushes: int = 0     # chunks this session ran on secondary lanes

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _Session:
    sid: int
    tenant: str
    slot: Optional[int]                 # primary lane id, None while queued
    backlog: Deque[np.ndarray]          # appended arrays, FIFO; never
    backlog_off: int = 0                # re-copied -- backlog_off marks the
    backlog_tuples: int = 0             # consumed prefix of backlog[0]
    stats: SessionStats = dataclasses.field(default_factory=SessionStats)
    closed: bool = False

    def pending_arrays(self) -> List[np.ndarray]:
        """The buffered remainder as a list of array views (first entry
        trimmed past ``backlog_off``); concatenates nothing."""
        if not self.backlog:
            return []
        first = self.backlog[0]
        head = first[self.backlog_off:] if self.backlog_off else first
        return [head, *list(self.backlog)[1:]]


class _ScanClock:
    """Host time one flush spent in each step of its scan
    (``SessionEngine._scan_lanes`` and its segments), summed; becomes
    the telemetry row's ``pack_ms`` / ``h2d_ms`` / ``run_ms`` /
    ``segments`` columns, ``gather_ms`` (the lane bucket's gather plus
    scatter), ``scan_lanes`` (the lanes the scan presented, pads
    included), ``active_lanes`` (the lanes that carried a chunk) and
    ``table_updates`` (the lane-table scatter, fold and reset
    dispatches of the flush: the engine's count, ``table_updates0`` at
    its start, read again by ``columns``)."""

    __slots__ = ("pack_ns", "h2d_ns", "run_ns", "segments", "gather_ns",
                 "scan_lanes", "active_lanes", "table_updates0")

    def __init__(self, table_updates0: int):
        self.pack_ns = self.h2d_ns = self.run_ns = self.segments = 0
        self.gather_ns = self.scan_lanes = self.active_lanes = 0
        self.table_updates0 = table_updates0

    def add(self, pack_ns: int, h2d_ns: int, run_ns: int) -> None:
        self.pack_ns += pack_ns
        self.h2d_ns += h2d_ns
        self.run_ns += run_ns
        self.segments += 1

    def lanes(self, gather_ns: int, scan_lanes: int,
              active_lanes: int) -> None:
        self.gather_ns += gather_ns
        self.scan_lanes += scan_lanes
        self.active_lanes += active_lanes

    def columns(self, table_updates: int) -> Dict[str, Any]:
        return {"pack_ms": round(self.pack_ns / 1e6, 3),
                "h2d_ms": round(self.h2d_ns / 1e6, 3),
                "run_ms": round(self.run_ns / 1e6, 3),
                "segments": self.segments,
                "gather_ms": round(self.gather_ns / 1e6, 3),
                "scan_lanes": self.scan_lanes,
                "active_lanes": self.active_lanes,
                "table_updates": table_updates - self.table_updates0}


class _EngineMetrics:
    """The engine's metric family handles, resolved once against one
    ``obs.MetricsRegistry`` (re-requesting a family is idempotent, so
    engines sharing a registry share series).  The full catalog with
    semantics lives in docs/observability.md."""

    # bounded label cardinality: past these, per-lane / per-tenant gauge
    # series collapse to the aggregate (a 1024-slot storm engine should
    # not mint 1024 Prometheus series per flush)
    MAX_LANE_SERIES = 128
    MAX_TENANT_SERIES = 32

    def __init__(self, reg):
        c, g, h = reg.counter, reg.gauge, reg.histogram
        self.flush_ms = h("flush_latency_ms",
                          "wall-clock per flush, by flush tier",
                          labels=("scope",))
        self.admit_ms = h("admit_latency_ms",
                          "wall-clock per open_batch admission storm")
        self.flushes = c("flushes_total", "flushes run, by tier",
                         labels=("scope",))
        self.tuples = c("tuples_flushed_total",
                        "real tuples through the lanes")
        self.chunks = c("chunks_flushed_total",
                        "chunks through the lanes (padding excluded)")
        self.retraces = c("retraces_total",
                          "jit compiles observed on the flush path "
                          "(compilemon delta per flush)")
        self.stall = c("compile_stall_ms_total",
                       "compile stall milliseconds on the flush path")
        self.opened = c("sessions_opened_total", "sessions opened")
        self.closed = c("sessions_closed_total", "sessions closed")
        self.appends = c("appends_total", "append() calls accepted")
        self.app_tuples = c("appended_tuples_total",
                            "tuples accepted by append()")
        self.queries = c("queries_total", "query() calls, by flush tier",
                         labels=("scope",))
        self.storms = c("storms_total", "open_batch admission storms")
        self.admitted = c("storm_admitted_total",
                          "sessions admitted via open_batch")
        self.grants = c("secondary_grants_total",
                        "secondary-lane grants, by receiving tenant",
                        labels=("tenant",))
        self.active = g("active_sessions", "sessions holding a slot")
        self.queued = g("queued_sessions", "sessions waiting for a slot")
        self.slot_occ = g("slot_occupancy",
                          "active / primary_slots fraction")
        self.lanes_busy = g("lanes_busy", "lanes owned by some session")
        self.occupancy = g("lane_occupancy",
                           "1 when the lane is owned by a session "
                           "(omitted past MAX_LANE_SERIES lanes)",
                           labels=("lane",))
        self.backlog_tot = g("backlog_tuples",
                             "host-buffered tuples across open sessions")
        self.backlog = g("backlog_depth",
                         "host-buffered tuples by tenant (top "
                         "MAX_TENANT_SERIES by depth)",
                         labels=("tenant",))
        self.sec_granted = g("secondary_lanes_granted",
                             "secondary lanes currently granted")
        self.sched_granted = g("sched_n_granted",
                               "grants in the last scheduling plan")
        self.sched_load = g("sched_post_plan_max_load",
                            "max per-slot load after the last plan "
                            "(the paper's post-plan balance metric)")
        self.tele_dropped = c("telemetry_dropped_rows_total",
                              "telemetry rows lost to the ring cap")


class SessionEngine:
    """Slot-managed multi-tenant sessions over one vmapped executor.

    Args:
      spec: the DittoSpec every session runs (one engine = one app).
      num_pri/num_sec/chunk_size: executor shape per lane, or ``tuned=``
        a repro.tune.TunedPlan supplying them.  Explicit num_sec /
        chunk_size / kernel_backend override the plan's values (the
        ``make_executor`` contract); an explicit num_pri that CONFLICTS
        with the plan raises instead -- the plan's X and route plan are
        tuned at its M, so overriding M would silently invalidate them.
      primary_slots: max concurrently admitted sessions; further ``open``
        calls queue and admit as slots free (continuous batching).
        **Overflow contract**: the waitlist is strictly FIFO by
        ``open``/``open_batch`` call order -- when slots free (a
        ``close``), the longest-waiting sid admits first, into the
        lowest-numbered free slot; admission order and slot placement
        are deterministic, never a function of dict/set iteration.  A
        queued session accepts ``append`` (host-buffered); ``query``
        raises ``RuntimeError`` until it is admitted, and ``close``
        raises while it holds buffered data (refusing to discard).
      secondary_slots: extra lanes the backlog scheduler grants to hot
        sessions (0 disables tenant-level skew scheduling).  Requires a
        decomposable spec (``spec.merge is None``): cross-lane merging is
        the add/max shadow-buffer combine.
      min_grant_chunks: a session must have at least this many backlog
        chunks before it can be granted a secondary lane (a helper lane
        for <2 chunks cannot shorten the scan).
      mesh: a ``jax.sharding.Mesh`` with a ``lanes`` axis, over which
        the slot lanes are split (DESIGN.md §9):
        ``primary_slots + secondary_slots`` must be divisible by the
        axis size.  ``mesh=None`` (default) is the one-device mesh over
        the default device; every mesh runs the same lane programs
        (``core.distributed.make_lane_table``).
      obs: observability wiring (``repro.obs``): ``None`` -> a fresh
        enabled ``Observability`` bundle on ``self.obs``; ``False`` ->
        a disabled bundle (every metric op / span an early return); an
        ``Observability`` instance is shared as-is (one registry +
        tracer scraped across engines).
      telemetry_cap: ring size for the per-flush telemetry rows
        (default 4096; ``None`` = unbounded, the pre-ring behavior).
        Overflowed rows drop oldest-first and are counted under
        ``telemetry_record()['extra']['telemetry']['dropped_rows']`` --
        lifetime ``totals`` are unaffected by drops.
      aot_buckets: enable the AOT shape-bucketed flush path.  An int is
        the max scan width per flush segment (rounded up to a power of
        two); an iterable of widths uses its max.  ``warmup()``
        pre-compiles one executable per (lane bucket, width in
        1,2,...,W) and wider flushes chop into W-wide segments, so a
        warmed engine NEVER retraces on the flush path.  ``None``
        (default) compiles on first use (one retrace per fresh shape,
        lane buckets and ``_batch_width`` keeping them logarithmic).
      **executor_kw: forwarded to ``core.make_resumable_executor``
        (profile_chunks, threshold, mem_width_tuples, kernel_backend).
    """

    def __init__(self, spec, *, num_pri: Optional[int] = None,
                 num_sec: Optional[int] = None,
                 chunk_size: Optional[int] = None, tuned=None,
                 primary_slots: int = 4, secondary_slots: int = 2,
                 min_grant_chunks: int = 2, mesh=None, aot_buckets=None,
                 kernel_backend: Optional[str] = None, obs=None,
                 telemetry_cap: Optional[int] = 4096, **executor_kw):
        if tuned is not None:
            if num_pri is not None and num_pri != tuned.num_pri:
                raise ValueError(f"num_pri={num_pri} conflicts with the "
                                 f"tuned plan's num_pri={tuned.num_pri}")
            num_pri = tuned          # TunedPlan resolution lives in core
        if num_pri is None:
            raise TypeError("SessionEngine needs num_pri/num_sec/chunk_size "
                            "or tuned=TunedPlan")
        if primary_slots < 1:
            raise ValueError("SessionEngine needs at least one primary slot")
        if secondary_slots > 0 and spec.merge is not None:
            raise ValueError(
                f"{spec.name}: non-decomposable buffers cannot be combined "
                "across lanes; use secondary_slots=0")
        self.spec = spec
        self.primary_slots = primary_slots
        self.secondary_slots = secondary_slots
        self.min_grant_chunks = min_grant_chunks
        self.num_lanes = primary_slots + secondary_slots

        self._res = core_executor.make_resumable_executor(
            spec, num_pri, num_sec, chunk_size,
            kernel_backend=kernel_backend, **executor_kw)
        self.num_pri, self.num_sec = self._res.num_pri, self._res.num_sec
        self.chunk_size = self._res.chunk_size
        fresh = self._res.init_state()
        self._fresh = fresh
        # the lane table: every flush path scans a BUCKET of lanes (the
        # lanes that carry a chunk, gathered on each device into a
        # power-of-two lane count), scanned, scattered back.  The engine
        # owns the table, and the programs that return an updated table
        # (scatter, fold, reset) take it DONATED: XLA writes the touched
        # lanes in place, and the old table is deleted -- every caller
        # rebinds ``self._states`` to the result (``_update_table``)
        self._table = core_distributed.make_lane_table(
            self._res, mesh, self.num_lanes)
        self.lanes_per_device = self._table.lanes_per_device
        self._states = self._table.init_states()
        self._table_updates = 0    # lane-table update dispatches, lifetime

        # --- the lane buckets: 1, 2, 4, ... lanes per device, capped at
        # lanes_per_device (padding never outgrows the lane table); with
        # aot_buckets=W also the scan widths 1, 2, ..., W
        self._lane_buckets = tuple(sorted(
            {self._lane_bucket(1 << k)
             for k in range(self.lanes_per_device.bit_length() + 1)}))
        self._aot: Dict[Tuple, Any] = {}      # (bucket, width) -> exec
        self._aot_info: Optional[Dict[str, Any]] = None
        if aot_buckets is None:
            self._aot_widths = None
        else:
            if isinstance(aot_buckets, (int, np.integer)):
                max_w = int(aot_buckets)
            else:
                widths = [int(w) for w in aot_buckets]
                max_w = max(widths) if widths else 0
            if max_w < 1:
                raise ValueError(f"aot_buckets={aot_buckets!r}: need a "
                                 "max scan width >= 1")
            max_w = 1 << (max_w - 1).bit_length()        # pow2 ceiling
            self._aot_widths = tuple(1 << k
                                     for k in range(max_w.bit_length()))

        # jit the slot scheduler ONCE: schedule_secpes builds its scan
        # eagerly, which re-traces (and re-compiles) on every call --
        # a per-flush compile stall the monitor would charge to us
        self._plan_sec = jax.jit(
            lambda w: scheduler.schedule_secpes(
                w, self.secondary_slots,
                min_load=float(self.min_grant_chunks)))

        compilemon.install()
        self.obs = obs_lib.resolve(obs)
        self._mx = _EngineMetrics(self.obs.registry)
        self._n_retraces = 0
        self._compile_stall_ms = 0.0
        self._storms = 0                   # open_batch calls
        self._n_admitted_batch = 0         # sessions admitted via storms
        self._admit_stall_ms = 0.0         # wall-clock inside open_batch
        self._n_retraces_admit = 0         # compiles observed during storms

        self.sessions: Dict[int, _Session] = {}
        # host index of the work: per-flush bookkeeping walks these
        # sids, never the whole session table
        self._pending: set = set()          # sids holding buffered tuples
        self._backlog_total = 0             # their buffered tuples
        self._depth_shown: set = set()      # tenants with a depth gauge
        self._queue: Deque[int] = deque()                # sids awaiting a slot
        self._slot_sid: List[Optional[int]] = [None] * primary_slots
        self._free_slots: List[int] = list(range(primary_slots))  # min-heap
        self._sec_assign = np.full(secondary_slots, -1, np.int64)
        self._next_sid = 0
        self._feat_shape: Optional[tuple] = None
        self._dtype = None
        self._flush_no = 0
        self._slot_reschedules = 0
        self._gauge_scan_last = 0.0     # last lane/tenant gauge rescan
        if telemetry_cap is not None and int(telemetry_cap) < 1:
            raise ValueError(f"telemetry_cap={telemetry_cap}: need >= 1 "
                             "rows, or None for unbounded")
        self.telemetry_cap = (None if telemetry_cap is None
                              else int(telemetry_cap))
        self._telemetry: Deque[Dict[str, Any]] = \
            deque(maxlen=self.telemetry_cap)
        self._telemetry_total = 0      # rows ever recorded (ring-proof)
        self._telemetry_dropped = 0    # rows lost to the ring cap
        self._rows_validated = 0       # high-water mark for incremental
                                       # telemetry_record(validate=True)

    # ------------------------------------------------------------- lifecycle

    def open(self, tenant: str = "default") -> int:
        """Open a session; admitted to a primary slot immediately when one
        is free, else queued until ``flush`` frees one (slots recycle as
        sessions close -- the continuous-batching admission path)."""
        sid = self._next_sid
        self._next_sid += 1
        self.sessions[sid] = _Session(sid, tenant, slot=None,
                                      backlog=deque())
        self._queue.append(sid)
        self._admit()
        self._mx.opened.inc()
        return sid

    def open_batch(self, tenants: Iterable[str],
                   first: Optional[Iterable[Optional[np.ndarray]]] = None
                   ) -> List[int]:
        """Admit a STORM of new sessions in one batched admission step.

        Semantically identical to ``open(t)`` (+ ``append(sid, f)`` when
        ``first`` is given) per tenant, in order -- same sids, same FIFO
        queueing past ``primary_slots``, bit-exact answers -- but the
        admitted sessions' first backlog chunks run NOW through one
        batched lane-init plus one pow2-bucketed scan over the admitted
        primary lanes (``_flush_admission``): O(width buckets) scan
        dispatches for the whole storm instead of O(sessions).  With
        ``aot_buckets=`` the admission shapes are part of the
        ``warmup()`` table, so a warmed engine absorbs a storm with
        ZERO retraces (the ``n_retraces_admit`` telemetry total).

        Args:
          tenants: tenant names, one new session each, opened in order.
          first: optional per-tenant first append (same length; entries
            may be ``None``).  Ragged sub-chunk tails stay host-buffered
            exactly as a serial ``append`` would leave them.

        Returns the new sids, aligned with ``tenants``.  Appends one
        ``scope="admit"`` telemetry row carrying ``n_admitted``,
        ``n_queued_batch``, ``n_scan_dispatches`` and ``admit_ms``."""
        tenants = list(tenants)
        if first is not None:
            first = list(first)
            if len(first) != len(tenants):
                raise ValueError(
                    f"open_batch: {len(tenants)} tenants but {len(first)} "
                    "first-append entries (pass one per tenant, or None)")
        snap = compilemon.snapshot()
        t0 = time.perf_counter()
        clock = _ScanClock(self._table_updates)
        with self.obs.span("engine.admit_storm", cat="admit",
                           n_tenants=len(tenants)) as sp:
            sids: List[int] = []
            for i, tenant in enumerate(tenants):
                sid = self.open(tenant)     # virtual dispatch: the durable
                sids.append(sid)            # engine WAL-logs each open/append
                if first is not None and first[i] is not None:
                    self.append(sid, first[i])
            admitted = [sid for sid in sids
                        if self.sessions[sid].slot is not None]
            group_chunks, width, flushed = \
                self._flush_admission(admitted, clock)
            sp.set(n_admitted=len(admitted),
                   n_scan_dispatches=clock.segments)
        ms = (time.perf_counter() - t0) * 1e3
        delta = compilemon.since(snap)
        self._storms += 1
        self._n_admitted_batch += len(admitted)
        self._admit_stall_ms += ms
        self._n_retraces_admit += delta.n_compiles
        self._mx.storms.inc()
        self._mx.admitted.inc(len(admitted))
        self._mx.admit_ms.observe(ms)
        self._record_flush(flushed, group_chunks, width, scope="admit",
                           snap=snap, ms=ms,
                           extra={"n_admitted": len(admitted),
                                  "n_queued_batch": len(sids) - len(admitted),
                                  "n_scan_dispatches": clock.segments,
                                  "admit_ms": round(ms, 3),
                                  **clock.columns(self._table_updates)})
        self._flush_no += 1
        return sids

    def append(self, sid: int, data: np.ndarray) -> None:
        """Append a tuple batch of ANY length (ragged welcome) to an open
        session.  Buffers host-side; full chunks run at the next flush."""
        s = self._session(sid)
        data = np.asarray(data)
        if data.ndim == 1:
            data = data[:, None]
        if self._feat_shape is None:
            self._feat_shape, self._dtype = data.shape[1:], data.dtype
            if self._aot_widths and not self._aot:
                self.warmup()        # deferred startup warmup: the tuple
                                     # shape is now known
        elif data.shape[1:] != self._feat_shape:
            raise ShapeMismatchError(
                f"append shape {data.shape[1:]} != engine tuple "
                f"shape {self._feat_shape}")
        if len(data):
            s.backlog.append(data)
            s.backlog_tuples += len(data)
            self._pending.add(sid)
            self._backlog_total += len(data)
            s.stats.tuples_appended += len(data)
            self._mx.appends.inc()
            self._mx.app_tuples.inc(len(data))

    def query(self, sid: int, *, scope: str = "session"):
        """Merged-buffer snapshot of everything appended so far.

        Forces this session's backlog (including the ragged tail, as a
        masked chunk) through the lanes, then combines its primary lane
        with any granted secondary lanes -- non-destructively, like the
        merger reading PriPE+SecPE buffers without resetting them, so the
        session keeps streaming afterwards.

        ``scope`` picks the flush tier (identical results either way):
        ``"session"`` (default) runs ``flush_session`` -- only this
        session's lane group scans, so the latency is bounded by the
        session's OWN backlog; ``"engine"`` runs a full ``flush`` (every
        admitted session advances, secondary grants re-scheduled), the
        pre-latency-tiering behavior."""
        s = self._session(sid)
        if s.slot is None:
            raise QueuedSessionError(
                f"session {sid} is queued (all {self.primary_slots} primary "
                "slots busy); nothing has run yet -- close another session "
                "to admit it before querying")
        if scope == "session":
            self.flush_session(sid)
        elif scope == "engine":
            self.flush(force=(sid,))
        else:
            raise ValueError(f"query scope {scope!r} not in "
                             "('session', 'engine')")
        s.stats.queries += 1
        self._mx.queries.inc(scope=scope)
        return self._snapshot(s)

    def close(self, sid: int):
        """Final flush + snapshot; frees the session's lanes for queued
        tenants.  Returns (merged_buffers, stats_dict).  Closing a
        still-queued session is only allowed while it is empty (closing
        buffered data unseen would silently discard it)."""
        s = self._session(sid)
        if s.slot is None and s.backlog_tuples:
            raise QueuedSessionError(
                f"session {sid} is queued with {s.backlog_tuples} buffered "
                "tuples; close another session to admit it first (refusing "
                "to discard data)")
        if s.slot is not None:
            self.flush_session(sid)
        merged = self._snapshot(s)
        if s.slot is not None:
            lanes = self._lane_group(s.slot)
            for j in range(self.secondary_slots):
                if self._sec_assign[j] == s.slot:
                    self._sec_assign[j] = -1
            # one batched reset of the whole lane group (primary +
            # granted secondaries) instead of one dispatch per lane
            self._reset_group(lanes)
            self._slot_sid[s.slot] = None
            heapq.heappush(self._free_slots, s.slot)
            s.slot = None
        else:
            self._queue.remove(sid)
        s.closed = True
        self._admit()
        self._mx.closed.inc()
        return merged, s.stats.as_dict()

    # ----------------------------------------------------------------- flush

    def flush(self, force: Iterable[int] = ()) -> None:
        """Advance every admitted session's stream by its backlogged
        chunks in ONE batched scan.

        1. admit queued sessions into free primary slots;
        2. run the paper's greedy scheduler over per-slot chunk backlog
           to (re-)grant secondary lanes; a re-granted lane's buffers
           merge into its old session first (shadow-buffer semantics);
        3. stripe each session's full chunks across its lane group (the
           ``force`` sessions also flush their ragged tail as a masked
           chunk) -- only sessions with work are walked;
        4. the lanes that carry a chunk are gathered into a lane bucket
           (``_scan_lanes``), one vmapped scan advances them together --
           per width segment, through the AOT bucket table when
           ``aot_buckets=`` is enabled -- and they are scattered back.

        The telemetry row's ``forced_sessions`` counts the ``force``
        sessions: the queries this one flush answers.
        """
        snap = compilemon.snapshot()
        t0 = time.perf_counter()
        clock = _ScanClock(self._table_updates)
        with self.obs.span("engine.flush", scope="engine") as sp:
            force = set(force)
            self._admit()
            with self.obs.span("sched.regrant", cat="sched"):
                self._reschedule_secondary()

            lanes: List[int] = []
            lane_chunks: List[List[np.ndarray]] = []
            lane_masks: List[List[np.ndarray]] = []
            row_sessions: List[_Session] = []
            flushed_tuples = 0
            with self.obs.span("flush.stripe", cat="sched"):
                groups = self._secondary_groups()
                work = [self.sessions[sid] for sid in self._pending]
                for s in sorted((s for s in work if s.slot is not None
                                 and (s.sid in force or s.backlog_tuples
                                      >= self.chunk_size)),
                                key=lambda s: s.slot):
                    group = [s.slot, *groups.get(s.slot, ())]
                    gc, gm, n_real = self._take_striped(
                        s, group, flush_tail=s.sid in force)
                    for ln, c, m in zip(group, gc, gm):
                        if c:
                            lanes.append(ln)
                            lane_chunks.append(c)
                            lane_masks.append(m)
                            row_sessions.append(s)
                    flushed_tuples += n_real
            width = self._scan_lanes(lanes, lane_chunks, lane_masks,
                                     row_sessions, "engine", clock)
            sp.set(tuples=flushed_tuples, width=width)
        self._record_flush(flushed_tuples, lane_chunks, width, snap=snap,
                           ms=(time.perf_counter() - t0) * 1e3,
                           extra={**clock.columns(self._table_updates),
                                  "forced_sessions": len(force)})
        self._flush_no += 1

    def flush_session(self, sid: int) -> None:
        """Advance ONLY this session's stream: its backlog (ragged tail
        included, as a masked chunk) stripes across its current lane
        group and a single vmapped scan over the group's lanes that
        carry a chunk runs it -- the latency-tiering fast path behind
        ``query``.

        No admission and no secondary re-scheduling happen here (both
        stay on the engine-wide ``flush``), so the cost is bounded by
        this session's own backlog.  The lanes go through the same lane
        bucket as every flush (``_scan_lanes``): padded with lanes
        OUTSIDE the group carrying all-masked chunks -- a fully masked
        scan leaves an ``ExecState`` bit-identical (the executor's
        validity-mask no-op), so the padded lanes are written back
        unchanged and the scan hits a pre-compiled bucket."""
        snap = compilemon.snapshot()
        t0 = time.perf_counter()
        clock = _ScanClock(self._table_updates)
        s = self._session(sid)
        if s.slot is None:
            raise QueuedSessionError(
                f"session {sid} is queued (all {self.primary_slots} primary "
                "slots busy); nothing has run yet -- close another session "
                "to admit it first")
        with self.obs.span("engine.flush_session", scope="session",
                           sid=sid, tenant=s.tenant) as sp:
            group = self._lane_group(s.slot)
            gc, gm, n_real = self._take_striped(s, group, flush_tail=True)
            live = [k for k, c in enumerate(gc) if c]
            group_chunks = [gc[k] for k in live]
            width = self._scan_lanes([group[k] for k in live], group_chunks,
                                     [gm[k] for k in live], [s] * len(live),
                                     "session", clock)
            sp.set(tuples=n_real, width=width)
        self._record_flush(n_real, group_chunks, width, scope="session",
                           snap=snap, ms=(time.perf_counter() - t0) * 1e3,
                           extra=clock.columns(self._table_updates))
        self._flush_no += 1

    def _flush_admission(self, sids: List[int], clock: "_ScanClock"):
        """The storm flush behind ``open_batch``: run the newly admitted
        sessions' first backlog chunks as one batched lane-init plus one
        bucketed scan over their primary lanes.

        Only FULL chunks run (``flush_tail=False``): answers are
        chunking-invariant, so deferring ragged tails to the next
        query/close keeps the path bit-exact vs serial admission, and a
        session whose first append is sub-chunk costs zero dispatches.
        A newly admitted session holds no secondary grants, so its lane
        group is exactly its primary lane; the scan goes through the
        lane bucket of every flush (``_scan_lanes``).

        Returns ``(group_chunks, width, flushed_tuples)`` for the caller's
        telemetry row; the scan's step times and segments add into
        ``clock``."""
        live = [self.sessions[sid] for sid in sids
                if self.sessions[sid].backlog_tuples >= self.chunk_size]
        if not live:
            return [], 0, 0
        lanes = [s.slot for s in live]
        with self.obs.span("admit.lane_init", cat="admit",
                           n_lanes=len(lanes)):
            self._reset_group(lanes)
        group_chunks: List[List[np.ndarray]] = []
        group_masks: List[List[np.ndarray]] = []
        flushed = 0
        for s in live:
            gc, gm, n_real = self._take_striped(s, [s.slot],
                                                flush_tail=False)
            group_chunks.append(gc[0])
            group_masks.append(gm[0])
            flushed += n_real
        width = self._scan_lanes(lanes, group_chunks, group_masks, live,
                                 "admit", clock)
        return group_chunks, width, flushed

    # ------------------------------------------------------ lane buckets

    def _lane_bucket(self, n: int) -> int:
        """Lanes per device a scan of ``n`` lanes (on its busiest
        device) presents: the power-of-two ceiling of ``n``, capped at
        ``lanes_per_device`` -- the full table pays no padding and the
        padding lanes always exist."""
        return min(1 << (n - 1).bit_length(), self.lanes_per_device)

    def _bucket_rows(self, lanes: List[int], fill: bool = True):
        """Lay the distinct lane ids ``lanes`` out as a lane bucket.

        Each device takes its own lanes (none cross devices) and pads
        them up to ``b``, the bucket of the busiest device's count: with
        other lanes of the same device, which will carry all-masked
        chunks (``fill``), or with ``-1`` (the reset's pads).  Returns
        ``(rows, idx, b)``: ``rows[k]`` is the position in ``lanes`` of
        the bucket's row ``k`` (``-1`` for a pad) and ``idx[k]`` its lane
        id on its device; device ``d`` holds rows ``d*b .. (d+1)*b - 1``.
        O(bucket + lanes)."""
        lpd = self.lanes_per_device
        per: List[List[int]] = [[] for _ in range(self._table.num_devices)]
        for pos, ln in enumerate(lanes):
            per[ln // lpd].append(pos)
        b = self._lane_bucket(max(len(p) for p in per))
        rows: List[int] = []
        idx: List[int] = []
        for d, ps in enumerate(per):
            local = [lanes[p] - d * lpd for p in ps]
            taken = set(local)
            pads = (i for i in range(lpd) if i not in taken)
            rows += ps + [-1] * (b - len(ps))
            idx += local + [next(pads) if fill else -1
                            for _ in range(b - len(ps))]
        return rows, np.asarray(idx, np.int32), b

    def _scan_lanes(self, lanes, lane_chunks, lane_masks, row_sessions,
                    scope: str, clock: "_ScanClock") -> int:
        """Advance lanes ``lanes`` by their chunk lists -- the one scan
        of all three flush paths.  The lanes are laid out as a lane
        bucket (``_bucket_rows``); ``scan.gather`` takes the bucket's
        states out of the lane table on each device, ``_scan_batch``
        runs its segments, ``scan.scatter`` writes the states back.  Pad
        lanes carry all-masked chunks, so their states return
        bit-identical.  The gather and the scatter are waited on and add
        into ``clock``.  Returns the scan width."""
        if not any(lane_chunks):
            return 0
        rows, idx, b = self._bucket_rows(lanes)
        pick = (lambda xs, pad: [pad if r < 0 else xs[r] for r in rows])
        t0 = time.perf_counter_ns()
        with self.obs.span("scan.gather", cat="scan", scope=scope,
                           lanes=len(idx)):
            idx = jax.device_put(idx, self._table.sharding)
            sub = jax.block_until_ready(
                self._table.gather(self._states, idx))
        gather_ns = time.perf_counter_ns() - t0
        sub, width = self._scan_batch(
            sub, pick(lane_chunks, []), pick(lane_masks, []),
            pick(row_sessions, None), b, scope, clock)
        t0 = time.perf_counter_ns()
        with self.obs.span("scan.scatter", cat="scan", scope=scope,
                           lanes=len(idx)):
            self._update_table(self._table.scatter, idx, sub)
            jax.block_until_ready(self._states)
        clock.lanes(gather_ns + time.perf_counter_ns() - t0, len(idx),
                    len(lanes))
        return width

    def _reset_group(self, lanes: List[int]) -> None:
        """Reset the distinct lanes ``lanes`` to fresh state in one
        batched lane-init: each device resets its own, its ids padded
        with ``-1`` up to a lane bucket, so the reset shapes are the
        lane buckets."""
        _, idx, _ = self._bucket_rows(lanes, fill=False)
        self._update_table(self._table.reset,
                           jax.device_put(idx, self._table.sharding))

    def _update_table(self, program, *args) -> None:
        """Run a lane-table update ``program(states, *args)`` (the
        scatter, fold or reset) and rebind ``self._states`` to its
        result.  The program takes the table donated, so the call
        deletes the old table: nothing may hold it across an update."""
        self._states = program(self._states, *args)
        self._table_updates += 1

    def _scan_batch(self, state, lane_chunks, lane_masks, row_sessions,
                    bucket: int, scope: str, clock: "_ScanClock"):
        """Advance the bucket ``state`` by every scan segment of one
        flush batch.  Each segment runs three timed steps under its
        ``scan.segment`` span:

        * ``scan.pack``: ``_pack_chunks``, the dense host batch;
        * ``scan.h2d``: the batch copied to the device (split over the
          lanes axis) and waited on;
        * ``scan.run``: the compiled scan plus the wait for its stats
          (``_apply_exec_stats``) -- device time as this thread sees it.

        ``(bucket, width)`` looks the executable up in the AOT table;
        the lane table's ``scan`` is the jit fallback.  The step times
        add into ``clock`` whether tracing is on or off.  Returns
        ``(state, width)``."""
        span = self.obs.span
        width = 0
        for off, w in self._segments(lane_chunks):
            with span("scan.segment", cat="scan", scope=scope, offset=off,
                      width=w):
                t0 = time.perf_counter_ns()
                with span("scan.pack", cat="scan"):
                    chunks, mask = self._pack_chunks(
                        lane_chunks, lane_masks, w, offset=off)
                t1 = time.perf_counter_ns()
                with span("scan.h2d", cat="scan"):
                    chunks, mask = jax.block_until_ready(
                        jax.device_put((chunks, mask), self._table.sharding))
                t2 = time.perf_counter_ns()
                with span("scan.run", cat="scan"):
                    state, stats = self._aot.get(
                        (bucket, w), self._table.scan)(state, chunks, mask)
                    self._apply_exec_stats(
                        stats, row_sessions,
                        [min(max(len(c) - off, 0), w) for c in lane_chunks])
                t3 = time.perf_counter_ns()
            clock.add(t1 - t0, t2 - t1, t3 - t2)
            width += w
        return state, width

    def _segments(self, lane_chunks):
        """Yield the ``(offset, width)`` scan segments covering the
        widest lane.  Plain path: ONE power-of-two segment
        (``_batch_width``, retraces stay logarithmic).  AOT path: chop
        into bucket widths ``<= W`` -- a scan is sequential, so running
        two segments with the state carried between them is bit-exact
        vs one wide scan, and every segment hits a pre-compiled
        executable."""
        wmax = max((len(c) for c in lane_chunks), default=0)
        if not wmax:
            return
        if not self._aot_widths:
            yield 0, self._batch_width(lane_chunks)
            return
        cap = self._aot_widths[-1]
        off = 0
        while off < wmax:
            rem = wmax - off
            w = cap if rem >= cap else 1 << (rem - 1).bit_length()
            yield off, w
            off += w

    def warmup(self, *, dtype=None, feat_shape=None) -> Dict[str, Any]:
        """Pre-compile the whole AOT bucket table so steady-state
        traffic never retraces (requires ``aot_buckets=``).

        For every lane bucket (``_lane_buckets``: 1, 2, 4, ... lanes per
        device, up to ``lanes_per_device``) AOT-lowers and compiles one
        scan executable per width (the lane table's ``scan``, lowered
        and compiled) and primes the
        bucket's gather, scatter and lane reset by executing them on
        scratch states; then primes the remaining fixed-shape entry
        points (merge, fold, the secondary scheduler) -- so ``flush`` /
        ``flush_session`` / ``open_batch`` / ``query`` / ``close`` are
        all compile-free afterwards.  ``table_in_place`` ("n of m")
        counts the primed lane-table update programs (each bucket's
        scatter and reset, the fold) whose executable aliases the
        donated table into its output.

        Needs the engine tuple dtype+shape: either call after the first
        ``append`` (``append`` triggers warmup automatically then), or
        pass ``dtype=`` and ``feat_shape=`` to warm up before any data
        arrives (what ``recover`` does, from the checkpoint meta).
        Returns the warmup info dict also exposed under
        ``telemetry_record()['extra']['aot']``."""
        if not self._aot_widths:
            raise RuntimeError("warmup() needs SessionEngine(aot_buckets=...)")
        if dtype is not None:
            dtype = np.dtype(dtype)
            if self._dtype is not None and dtype != self._dtype:
                raise ValueError(f"warmup dtype {dtype} != engine tuple "
                                 f"dtype {self._dtype}")
            self._dtype = dtype
        if feat_shape is not None:
            feat_shape = tuple(int(d) for d in feat_shape)
            if self._feat_shape is not None and feat_shape != self._feat_shape:
                raise ValueError(f"warmup feat_shape {feat_shape} != engine "
                                 f"tuple shape {self._feat_shape}")
            self._feat_shape = feat_shape
        if self._dtype is None or self._feat_shape is None:
            raise RuntimeError(
                "warmup() before the tuple shape is known: pass dtype= and "
                "feat_shape=, or append data first")
        t0 = time.perf_counter()
        before = compilemon.snapshot()
        c, feat = self.chunk_size, self._feat_shape
        table = self._table
        scratch = table.init_states()
        n_dev = table.num_devices
        in_place: List[bool] = []       # per lane-table update program
        for b in self._lane_buckets:
            idx = jax.device_put(np.tile(np.arange(b, dtype=np.int32), n_dev),
                                 table.sharding)
            sub = table.gather(scratch, idx)
            for w in self._aot_widths:
                zc = jax.ShapeDtypeStruct((n_dev * b, w, c, *feat),
                                          self._dtype, sharding=table.sharding)
                zm = jax.ShapeDtypeStruct((n_dev * b, w, c), np.bool_,
                                          sharding=table.sharding)
                self._aot[(b, w)] = table.scan.lower(sub, zc, zm).compile()
            # the update programs take ``scratch`` donated: rebind it
            scratch = table.scatter(scratch, idx, sub)
            in_place.append(self._aliases_table(table.scatter, scratch,
                                                idx, sub))
            scratch = table.reset(scratch, idx)
            in_place.append(self._aliases_table(table.reset, scratch, idx))
        # remaining fixed-shape entry points (query/close/re-grant): a
        # plain execution populates their jit caches
        table.snapshot(scratch, 0)
        if self.secondary_slots and self.spec.merge is None:
            scratch = table.fold(scratch, self.primary_slots, 0)
            in_place.append(self._aliases_table(
                table.fold, scratch, self.primary_slots, 0))
        self._res.merge_state(self._fresh)
        self.plan_secondary(np.zeros(self.primary_slots, np.float32))
        d = compilemon.since(before)
        self._aot_info = {
            "widths": [int(w) for w in self._aot_widths],
            "lane_buckets": [int(b) for b in self._lane_buckets],
            "n_executables": len(self._aot),
            "warmup_ms": round((time.perf_counter() - t0) * 1e3, 3),
            "warmup_compiles": int(d.n_compiles),
            "warmup_compile_ms": float(d.stall_ms),
            "table_in_place": f"{sum(in_place)} of {len(in_place)}",
        }
        return self._aot_info

    @staticmethod
    def _aliases_table(program, states, *args) -> bool:
        """Whether the compiled ``program(states, *args)`` updates the
        lane table in place: its executable's ``input_output_alias``
        maps every leaf of ``states`` (the first parameters) into the
        output.  The executable is the one the program's last call
        compiled; lowering it again compiles nothing."""
        head = program.lower(states, *args).compile().as_text()
        head = head.split("\n", 1)[0]
        aliased = head.partition("input_output_alias=")[2]
        aliased = aliased.partition("entry_computation_layout")[0]
        params = {int(p) for p in re.findall(r"\}:\s*\((\d+),", aliased)}
        return params >= set(range(len(jax.tree.leaves(states))))

    def _secondary_groups(self) -> Dict[int, List[int]]:
        """Primary slot -> the secondary lanes granted to it, for every
        slot that holds a grant (one pass over the grant table)."""
        groups: Dict[int, List[int]] = {}
        for j, slot in enumerate(self._sec_assign.tolist()):
            if slot >= 0:
                groups.setdefault(slot, []).append(self.primary_slots + j)
        return groups

    def _lane_group(self, slot: int) -> List[int]:
        """The lane ids a primary slot currently owns: its primary lane
        plus every secondary lane granted to it."""
        return [slot] + [self.primary_slots + j
                         for j in range(self.secondary_slots)
                         if self._sec_assign[j] == slot]

    def _take_striped(self, s: _Session, lanes: List[int],
                      flush_tail: bool):
        """Pop the session's pending chunks and stripe them round-robin
        over its lane group, with the flush accounting (tuples / chunks
        / sec-lane stats) -- the one striping rule BOTH flush tiers use,
        so they cannot drift apart."""
        chunks, masks = self._take_chunks(s, flush_tail=flush_tail)
        gc: List[List[np.ndarray]] = [[] for _ in lanes]
        gm: List[List[np.ndarray]] = [[] for _ in lanes]
        for k, (c, m) in enumerate(zip(chunks, masks)):
            g = k % len(lanes)
            gc[g].append(c)
            gm[g].append(m)
            if lanes[g] != s.slot:
                s.stats.sec_lane_flushes += 1
        n_real = int(sum(m.sum() for m in masks))
        s.stats.tuples_flushed += n_real
        s.stats.chunks_flushed += len(chunks)
        return gc, gm, n_real

    @staticmethod
    def _batch_width(lane_chunks) -> int:
        """Scan width for a flush batch: the widest lane's chunk count,
        rounded up to a power of two so jit retraces stay logarithmic;
        0 when nothing is pending."""
        w = max((len(c) for c in lane_chunks), default=0)
        return 1 << (w - 1).bit_length() if w else 0

    def _pack_chunks(self, lane_chunks, lane_masks, width, offset=0):
        """Pack per-lane chunk/mask lists into the dense
        [lanes, width, chunk, feat] batch the vmapped scan takes --
        ``offset`` selects the chunk window ``[offset, offset+width)``
        of each lane (the AOT segment loop); unfilled rows stay
        all-masked zero padding (exact no-ops).

        Returns HOST (numpy) arrays on purpose: ``_scan_batch``
        device_puts host memory straight to each shard -- resharding an
        already-device-resident array instead goes through jax's
        jit(_multi_slice), which compiles once per (shape, width) and
        would show up as steady-state retraces."""
        c = self.chunk_size
        feat = self._feat_shape or (1,)
        chunks = np.zeros((len(lane_chunks), width, c, *feat),
                          self._dtype or np.int32)
        mask = np.zeros((len(lane_chunks), width, c), bool)
        for ln in range(len(lane_chunks)):
            row_c = lane_chunks[ln][offset:offset + width]
            row_m = lane_masks[ln][offset:offset + width]
            for k, (ch, m) in enumerate(zip(row_c, row_m)):
                chunks[ln, k] = ch
                mask[ln, k] = m
        return chunks, mask

    def _apply_exec_stats(self, stats, row_sessions, row_counts):
        """Fold the scan's per-(lane, chunk) ExecStats into each row's
        owning session (first ``row_counts[row]`` entries are real).
        The device transfer is LAZY: an all-padding batch (no real
        session rows) never forces a sync on the flush path."""
        live = [(row, s, k)
                for row, (s, k) in enumerate(zip(row_sessions, row_counts))
                if s is not None and k > 0]
        if not live:
            return
        cycles = np.asarray(stats.modeled_cycles)       # [rows, width]
        loads = np.asarray(stats.max_load)
        resched = np.asarray(stats.rescheduled)
        for row, s, k in live:
            s.stats.modeled_cycles += float(cycles[row, :k].sum())
            s.stats.max_load = max(s.stats.max_load,
                                   int(loads[row, :k].max()))
            s.stats.exec_reschedules += int(resched[row, :k].sum())

    def _take_chunks(self, s: _Session, flush_tail: bool):
        """Pop full chunks (plus, when forced, the masked ragged tail)
        off a session's backlog; the sub-chunk remainder stays buffered.
        Only the CONSUMED tuples are ever copied (``_pop_backlog``) --
        repeated small appends cost O(taken) per flush, not
        O(total backlog)."""
        c = self.chunk_size
        avail = s.backlog_tuples
        take = avail if flush_tail else (avail // c) * c
        if not take:
            return [], []
        data = self._pop_backlog(s, take)
        nfull = len(data) // c
        chunks = [data[k * c:(k + 1) * c] for k in range(nfull)]
        masks = [np.ones(c, bool)] * nfull
        if nfull * c < len(data):
            padded, m = pad_tail_chunk(data[nfull * c:], c)
            chunks.append(padded)
            masks.append(m)
        return chunks, masks

    def _pop_backlog(self, s: _Session, n: int) -> np.ndarray:
        """Consume exactly ``n`` tuples off the backlog front: exhausted
        arrays pop left, a partially consumed head just advances
        ``backlog_off`` -- the unconsumed remainder is never copied."""
        parts: List[np.ndarray] = []
        need = n
        while need:
            head = s.backlog[0]
            rest = len(head) - s.backlog_off
            if rest <= need:
                parts.append(head[s.backlog_off:])
                s.backlog.popleft()
                s.backlog_off = 0
                need -= rest
            else:
                parts.append(head[s.backlog_off:s.backlog_off + need])
                s.backlog_off += need
                need = 0
        s.backlog_tuples -= n
        self._backlog_total -= n
        if not s.backlog_tuples:
            self._pending.discard(s.sid)
        return parts[0] if len(parts) == 1 else np.concatenate(parts, axis=0)

    # ------------------------------------------------------- slot scheduling

    def _admit(self) -> List[int]:
        """Admit queued sids into free primary slots: strictly FIFO by
        ``open`` order, each into the LOWEST-numbered free slot (the
        documented overflow contract -- deterministic admission order
        AND slot placement).  The free-slot min-heap makes this O(log
        slots) per admission, so a thousand-session ``open_batch`` does
        not pay an O(slots) scan per open.  Returns the admitted sids."""
        admitted: List[int] = []
        while self._queue and self._free_slots:
            sid = self._queue.popleft()
            slot = heapq.heappop(self._free_slots)
            self._slot_sid[slot] = sid
            self.sessions[sid].slot = slot
            admitted.append(sid)
        return admitted

    def _backlog_chunks(self) -> np.ndarray:
        """Per-primary-slot pending chunk counts -- the workload histogram
        of the serving layer (sessions are the tuples, slots the PEs)."""
        out = np.zeros(self.primary_slots, np.float32)
        for sid in self._pending:
            s = self.sessions[sid]
            if s.slot is not None:
                out[s.slot] = s.backlog_tuples // self.chunk_size
        return out

    def _index_backlog(self) -> None:
        """Rebuild the host index of the work (``_pending``,
        ``_backlog_total``) from the session table, after the table was
        replaced wholesale (checkpoint restore)."""
        live = [s for s in self.sessions.values() if not s.closed]
        self._pending = {s.sid for s in live if s.backlog_tuples}
        self._backlog_total = sum(s.backlog_tuples for s in live)

    def plan_secondary(self, backlog_chunks: np.ndarray) -> np.ndarray:
        """Greedy max-backlog splitting: ``scheduler.schedule_secpes`` over
        the per-slot chunk backlog, with grants to sessions below
        ``min_grant_chunks`` suppressed (the scheduler's ``min_load``
        floor).  Exposed for tests: the tenant-level plan must inherit
        the paper's Fig. 5 properties."""
        if self.secondary_slots == 0:
            return np.zeros(0, np.int64)
        return np.asarray(self._plan_sec(
            jnp.asarray(backlog_chunks, jnp.float32))).astype(np.int64)

    def _reschedule_secondary(self) -> None:
        backlog = self._backlog_chunks()
        new = self.plan_secondary(backlog)
        for j in range(self.secondary_slots):
            old = int(self._sec_assign[j])
            if old == int(new[j]):
                continue
            if old >= 0:
                # the lifted §IV-B merge: shadow lane folds into its old
                # session's primary lane before re-assignment
                self._update_table(self._table.fold,
                                   self.primary_slots + j, old)
                self._slot_reschedules += 1
            self._sec_assign[j] = new[j]
            if self.obs.enabled and int(new[j]) >= 0:
                sid = self._slot_sid[int(new[j])]
                if sid is not None:
                    self._mx.grants.inc(tenant=self.sessions[sid].tenant)
        if self.obs.enabled and self.secondary_slots:
            summary = scheduler.plan_summary(backlog, new)
            self._mx.sched_granted.set(summary["n_granted"])
            self._mx.sched_load.set(summary["max_load_after"])

    # ------------------------------------------------------------- snapshots

    def _snapshot(self, s: _Session):
        if s.slot is None:
            # only reachable closing an EMPTY queued session (query/close
            # with data refuse above): nothing ran, buffers are pristine
            return jax.tree.map(np.asarray,
                                self._res.merge_state(self._fresh))
        with self.obs.span("merge.snapshot", cat="merge", sid=s.sid,
                           tenant=s.tenant):
            merged = jax.tree.map(np.asarray,
                                  self._table.snapshot(self._states, s.slot))
            for j in range(self.secondary_slots):
                if self._sec_assign[j] == s.slot:
                    contrib = jax.tree.map(np.asarray, self._table.snapshot(
                        self._states, self.primary_slots + j))
                    combine = (np.add if self.spec.combine == "add"
                               else np.maximum)
                    merged = jax.tree.map(combine, merged, contrib)
        return merged

    # ------------------------------------------------------------- telemetry

    def _record_flush(self, tuples: int, lane_chunks, width: int,
                      scope: str = "engine", snap=None,
                      extra: Optional[Dict[str, Any]] = None,
                      ms: Optional[float] = None) -> None:
        delta = compilemon.since(snap) if snap is not None else None
        if delta is not None:
            self._n_retraces += delta.n_compiles
            self._compile_stall_ms += delta.stall_ms
        active = self.primary_slots - len(self._free_slots)
        backlog = self._backlog_total
        row = {
            "flush": self._flush_no,
            "scope": scope,
            "active_sessions": active,
            "queued_sessions": len(self._queue),
            "tuples": int(tuples),
            "chunks": int(sum(len(c) for c in lane_chunks)),
            "lane_width": int(width),
            "sec_granted": int((self._sec_assign >= 0).sum()),
            "slot_reschedules": int(self._slot_reschedules),
            "backlog_tuples": int(backlog),
            "slot_occupancy": round(active / self.primary_slots, 4),
            "n_retraces": 0 if delta is None else int(delta.n_compiles),
            "compile_stall_ms": (0.0 if delta is None
                                 else float(delta.stall_ms)),
            "flush_ms": None if ms is None else round(ms, 3),
        }
        if extra:
            row.update(extra)
        if (self._telemetry.maxlen is not None
                and len(self._telemetry) == self._telemetry.maxlen):
            self._telemetry_dropped += 1
            self._mx.tele_dropped.inc()
        self._telemetry.append(row)
        self._telemetry_total += 1
        if self.obs.enabled:
            self._emit_flush_metrics(row, ms)

    # floor between two lane/tenant gauge rescans in _emit_flush_metrics
    # (class attr so a test can zero it to make every flush rescan)
    _GAUGE_SCAN_S = 0.05

    def _emit_flush_metrics(self, row: Dict[str, Any],
                            ms: Optional[float]) -> None:
        """Mirror one telemetry row into the metrics registry (counters
        add the per-flush deltas, gauges track the latest state).  Only
        called with ``obs.enabled``; per-lane / per-tenant series are
        capped (``_EngineMetrics.MAX_*_SERIES``)."""
        m, scope = self._mx, row["scope"]
        m.flushes.inc(scope=scope)
        m.tuples.inc(row["tuples"])
        m.chunks.inc(row["chunks"])
        m.retraces.inc(row["n_retraces"])
        m.stall.inc(row["compile_stall_ms"])
        if ms is not None:
            m.flush_ms.observe(ms, scope=scope)
        m.active.set(row["active_sessions"])
        m.queued.set(row["queued_sessions"])
        m.slot_occ.set(row["slot_occupancy"])
        m.backlog_tot.set(row["backlog_tuples"])
        m.sec_granted.set(row["sec_granted"])
        if row["n_retraces"]:
            self.obs.tracer.instant(
                "compile.retrace", cat="compile", scope=scope,
                n=row["n_retraces"], stall_ms=row["compile_stall_ms"])
        if scope == "session":
            return      # lane/tenant gauges reflect ENGINE-wide state;
                        # the per-session tier does not rescan it
        # the lane/tenant gauge rescan below walks every slot and sorts
        # tenant depths -- O(slots + tenants) per flush adds up under a
        # flush storm, and gauges only need freshness, so rescan at most
        # every _GAUGE_SCAN_S (counters/histograms above stay exact)
        now = time.monotonic()
        if now - self._gauge_scan_last < self._GAUGE_SCAN_S:
            return
        self._gauge_scan_last = now
        n_sec = int((self._sec_assign >= 0).sum())
        m.lanes_busy.set(self.primary_slots - len(self._free_slots) + n_sec)
        if self.num_lanes <= m.MAX_LANE_SERIES:
            busy = {slot for slot, sid in enumerate(self._slot_sid)
                    if sid is not None}
            busy |= {self.primary_slots + j
                     for j in range(self.secondary_slots)
                     if self._sec_assign[j] >= 0}
            for ln in range(self.num_lanes):
                m.occupancy.set(1.0 if ln in busy else 0.0, lane=str(ln))
        # only sessions holding backlog have depth; a tenant whose depth
        # gauge was shown and has drained reads 0
        depth: Dict[str, int] = {}
        for sid in self._pending:
            s = self.sessions[sid]
            if s.slot is not None:
                depth[s.tenant] = depth.get(s.tenant, 0) + s.backlog_tuples
        shown = set(sorted(depth, key=lambda t: (-depth[t], t))
                    [:m.MAX_TENANT_SERIES])
        for tenant in self._depth_shown - shown:
            m.backlog.set(0, tenant=tenant)
        for tenant in shown:
            m.backlog.set(depth[tenant], tenant=tenant)
        self._depth_shown = shown

    # ------------------------------------------------------- live load views

    def lane_loads(self) -> Tuple[np.ndarray, np.ndarray]:
        """``(loads, occupied)``: per-primary-slot backlog in CHUNKS plus
        a boolean occupancy mask -- the live workload histogram the skew
        monitor (``obs/skew.py``) and the ``/statusz`` endpoint read.
        Pure host-side dict walks; no device sync."""
        occupied = np.array([sid is not None for sid in self._slot_sid],
                            dtype=bool)
        return self._backlog_chunks().astype(np.float64), occupied

    def tenant_loads(self) -> Tuple[Dict[str, int], Dict[str, int]]:
        """``(occupancy, backlog_tuples)`` per tenant over non-closed
        sessions -- slot-held AND engine-queued both count, which is the
        Eq. 2 admission controller's definition of tenant heat (the
        service's scored-admission path and the skew monitor's score
        spread must agree on it, so it lives here once)."""
        occ: Dict[str, int] = {}
        bl: Dict[str, int] = {}
        for s in self.sessions.values():
            if s.closed:
                continue
            occ[s.tenant] = occ.get(s.tenant, 0) + 1
            bl[s.tenant] = bl.get(s.tenant, 0) + int(s.backlog_tuples)
        return occ, bl

    @property
    def state_bytes(self) -> int:
        """Bytes of the lanes-stacked executor state the engine keeps on
        its device(s) -- every lane's PE buffers and carry."""
        return sum(x.nbytes for x in jax.tree.leaves(self._states))

    @property
    def slot_reschedules(self) -> int:
        """Lifetime secondary-lane re-assignments (the lifted §IV-B
        shadow-buffer merges) -- the skew monitor's grant-churn series."""
        return self._slot_reschedules

    def stats_dict(self) -> Dict[str, Any]:
        """Occupancy, queue depths and lifetime totals as one JSON-able
        dict (the engine half of the service's ``/statusz`` body)."""
        return {
            "open_sessions": sum(not s.closed
                                 for s in self.sessions.values()),
            "free_slots": len(self._free_slots),
            "engine_queue": len(self._queue),
            "primary_slots": self.primary_slots,
            "secondary_slots": self.secondary_slots,
            "totals": self.telemetry_record(
                validate=False)["extra"]["totals"],
        }

    def telemetry_record(self, validate: bool = True) -> Dict[str, Any]:
        """Per-flush telemetry as a schema-v1 benchmark record (the shape
        ``benchmarks.common.validate_record`` accepts): rows = one dict
        per flush (the ring tail -- up to ``telemetry_cap`` newest rows),
        extra = engine config + lifetime totals + ring accounting
        (``extra['telemetry']``: cap / rows_total / dropped_rows).

        ``validate=True`` validates INCREMENTALLY: only rows appended
        since the last validated call are re-checked (plus the O(1)
        envelope), so polling telemetry every flush costs O(new rows)
        per call instead of O(full history) -- the lifetime cost is
        linear in rows recorded."""
        totals = {
            "sessions_opened": self._next_sid,
            "flushes": self._flush_no,
            "slot_reschedules": self._slot_reschedules,
            "tuples_flushed": int(sum(s.stats.tuples_flushed
                                      for s in self.sessions.values())),
            "n_retraces": int(self._n_retraces),
            "compile_stall_ms": round(self._compile_stall_ms, 3),
            # storm admission: n_retraces_admit is a SUBSET of n_retraces
            # (compiles observed inside open_batch count in both)
            "storms": int(self._storms),
            "batch_admitted": int(self._n_admitted_batch),
            "n_retraces_admit": int(self._n_retraces_admit),
            "admit_stall_ms": round(self._admit_stall_ms, 3),
        }
        rows = list(self._telemetry)
        rec = {
            "schema_version": TELEMETRY_SCHEMA_VERSION,
            "bench": "session_engine",
            "title": (f"SessionEngine telemetry ({self.spec.name}, "
                      f"{self.primary_slots}P+{self.secondary_slots}S slots)"),
            "status": "ok",
            "rows": rows,
            "extra": {
                "config": {
                    "app": self.spec.name,
                    "num_pri": self.num_pri, "num_sec": self.num_sec,
                    "chunk_size": self.chunk_size,
                    "primary_slots": self.primary_slots,
                    "secondary_slots": self.secondary_slots,
                    "mesh_devices": self._table.num_devices,
                    "lanes_per_device": self.lanes_per_device,
                    "aot_buckets": (None if self._aot_widths is None
                                    else int(self._aot_widths[-1])),
                },
                "aot": self._aot_info,
                "totals": totals,
                "telemetry": {
                    "cap": self.telemetry_cap,
                    "rows_total": int(self._telemetry_total),
                    "dropped_rows": int(self._telemetry_dropped),
                },
            },
        }
        if validate:
            try:
                from benchmarks.common import validate_record
            except ImportError:          # src-only install: shape documented
                pass                     # above; benchmarks validate in CI
            else:
                # incremental: the first _rows_validated rows ever
                # recorded passed a prior call, and ring drops come off
                # the OLD end -- so in the retained window the
                # unvalidated suffix starts at validated-count minus
                # total drops (clamped: a drop of never-validated rows
                # just means the whole window is unvalidated)
                new_from = max(
                    self._rows_validated
                    - (self._telemetry_total - len(rows)), 0)
                validate_record({**rec, "rows": rows[new_from:]})
                self._rows_validated = self._telemetry_total
        return rec

    # ------------------------------------------------------------ durability

    @classmethod
    def recover(cls, spec, directory, *, mesh=None, guard=None, **overrides):
        """Resume a crashed/preempted durable engine from ``directory``:
        restore the newest lane-state checkpoint, replay the WAL tail
        past its flush watermark, and return a
        ``serve.DurableSessionEngine`` whose open sessions answer
        ``query()`` bit-exactly as an uninterrupted run would
        (DESIGN.md §10, docs/durability.md)."""
        from repro.serve import durability
        return durability.recover(spec, directory, mesh=mesh, guard=guard,
                                  **overrides)

    # --------------------------------------------------------------- helpers

    def session_stats(self, sid: int) -> Dict[str, Any]:
        return self._session(sid, allow_closed=True).stats.as_dict()

    def _session(self, sid: int, allow_closed: bool = False) -> _Session:
        s = self.sessions.get(sid)
        if s is None:
            n_open = sum(not x.closed for x in self.sessions.values())
            raise UnknownSessionError(
                f"unknown session id {sid}: this engine has issued "
                f"{self._next_sid} sid(s), {n_open} open "
                f"({len(self._queue)} of them queued) -- append/query/"
                "close need a sid returned by open()/open_batch()")
        if s.closed and not allow_closed:
            raise ClosedSessionError(
                f"session {sid} (tenant {s.tenant!r}) is closed; a "
                "closed sid cannot be reused -- open() a new session")
        return s

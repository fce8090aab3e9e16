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

Distributed mode (DESIGN.md §9, docs/distributed.md)
  ``SessionEngine(mesh=...)`` shards the lane axis over the mesh's
  ``lanes`` axis via ``core.distributed.make_lane_sharded_executor``:
  P devices x lanes_per_device lanes, one engine serving more tenants
  than one device's lane budget.  Flushes stay collective-free (lanes
  are independent streams, shard_map + local vmap); a cross-device slot
  re-grant runs the §IV-B shadow-buffer merge as a psum over the lanes
  axis.  A mesh of size 1 is bit-exact vs the unsharded engine.

AOT shape buckets (compile-stall elimination)
  Ragged appends produce ragged flush batches, and every new
  (lane count, scan width) shape is a fresh jit trace -- a silent
  multi-hundred-ms stall on the flush path.  With
  ``SessionEngine(aot_buckets=W)`` both flush tiers route through a
  **bucket table**: scan widths round up to powers of two (as before)
  and are chopped into segments of at most ``W``; per-session lane
  groups round up to power-of-two buckets padded with all-masked zero
  lanes (exact no-ops -- a padded lane's state rides through the scan
  bit-identically).  ``warmup()`` AOT-lowers and compiles ONE
  executable per bucket up front (``jit(scan_lanes).lower().compile()``
  on ``core.executor.ResumableExecutor.scan_lanes``, local and mesh
  variants alike) and primes every fixed-shape helper, so steady-state
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
  pow2-bucketed scan over the admitted primary lanes, chopped into the
  same AOT width segments as a flush: O(buckets) dispatches for a
  thousand-session storm.  Admission lane-group shapes (the pow2
  ceiling of the admitted count, capped at ``primary_slots``) are part
  of the ``warmup()`` table, so the zero-steady-retrace invariant
  holds THROUGH storms, local and mesh alike.  Ragged first-append
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
  tracer (``engine.flush`` / ``scan.segment`` / ``engine.admit_storm``
  / ``merge.snapshot`` ... as Perfetto ``trace_event`` JSON).  Pass one
  ``Observability`` to share a registry across engines, ``obs=False``
  to disable (every op an early return -- the serving bench asserts
  the enabled overhead stays under its bound).

Durability (DESIGN.md §10, docs/durability.md)
  ``serve.durability`` wraps this engine in a per-tenant write-ahead
  log plus periodic lane-state checkpoints (``executor.take_lanes`` of
  every lane through ``checkpoint.CheckpointManager``);
  ``SessionEngine.recover`` restores the newest checkpoint, replays
  only the WAL tail past its watermark, and resumes every open session
  bit-exactly after a crash -- in local and ``mesh=`` mode alike.
"""
from __future__ import annotations

import contextlib
import dataclasses
import heapq
import time
from collections import deque
from typing import Any, Deque, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compilemon
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
      mesh: a ``jax.sharding.Mesh`` with a ``lanes_axis`` axis.  When
        given, the slot lanes are sharded over that axis (DESIGN.md §9):
        ``primary_slots + secondary_slots`` must be divisible by the
        axis size.  ``mesh=None`` (default) keeps everything on the
        current device; a mesh of size 1 is bit-exact vs ``mesh=None``.
      lanes_axis: the mesh axis name holding the lanes (default
        ``"lanes"``).
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
        (default) keeps the plain jit path (one retrace per fresh
        shape, ``_batch_width`` keeping them logarithmic).
      **executor_kw: forwarded to ``core.make_resumable_executor``
        (profile_chunks, threshold, mem_width_tuples, kernel_backend).
    """

    def __init__(self, spec, *, num_pri: Optional[int] = None,
                 num_sec: Optional[int] = None,
                 chunk_size: Optional[int] = None, tuned=None,
                 primary_slots: int = 4, secondary_slots: int = 2,
                 min_grant_chunks: int = 2, mesh=None,
                 lanes_axis: str = "lanes", aot_buckets=None,
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
        if mesh is not None and lanes_axis not in dict(mesh.shape):
            raise ValueError(
                f"mesh has no '{lanes_axis}' axis; mesh axes: "
                f"{tuple(dict(mesh.shape))}")
        self.spec = spec
        self.primary_slots = primary_slots
        self.secondary_slots = secondary_slots
        self.min_grant_chunks = min_grant_chunks
        self.num_lanes = primary_slots + secondary_slots
        self.mesh = mesh

        self._res = core_executor.make_resumable_executor(
            spec, num_pri, num_sec, chunk_size,
            kernel_backend=kernel_backend, **executor_kw)
        self.num_pri, self.num_sec = self._res.num_pri, self._res.num_sec
        self.chunk_size = self._res.chunk_size
        fresh = self._res.init_state()
        self._fresh = fresh
        self._sharded = None
        if mesh is not None:
            from repro.core import distributed as core_distributed
            self._sharded = core_distributed.make_lane_sharded_executor(
                self._res, mesh, self.num_lanes, axis=lanes_axis)
            self.lanes_per_device = self._sharded.lanes_per_device
            self._states = self._sharded.init_states()
            self._run_lanes = self._sharded.run_lanes
            self._merge_lane = self._sharded.merge_lane
            self._reset_lane = self._sharded.reset_lane
            if spec.merge is None:
                self._fold_lane = self._sharded.fold_lane
        else:
            self.lanes_per_device = self.num_lanes
            self._states = core_executor.stack_states(fresh, self.num_lanes)
            self._run_lanes = jax.jit(self._res.scan_lanes)
            self._merge_lane = jax.jit(
                lambda states, i: self._res.merge_state(
                    jax.tree.map(lambda x: x[i], states)))
            self._reset_lane = jax.jit(
                lambda states, i: jax.tree.map(
                    lambda x, f: x.at[i].set(f), states, self._fresh))
            if spec.merge is None:
                self._fold_lane = jax.jit(self._fold_lane_impl)
        # per-session flush runs the lane GROUP locally in both modes:
        # take_lanes gathers the group's ExecStates across device
        # boundaries, the vmapped scan resumes them here, put_lanes
        # scatters them back (cross-device suspend/resume, DESIGN.md §9)
        self._run_group = jax.jit(self._res.scan_lanes)
        self._take_lanes = jax.jit(core_executor.take_lanes)
        self._put_lanes = jax.jit(core_executor.put_lanes)
        # batched lane-init: reset a GROUP of lanes to fresh state in one
        # dispatch (close's group reset, the storm-admission lane-init).
        # Duplicate indices are legal -- the same fresh value lands twice
        # -- so fixed-shape callers may pad idx by repeating a lane.
        self._reset_lanes = jax.jit(
            lambda states, idx: jax.tree.map(
                lambda x, f: x.at[idx].set(f), states, self._fresh))

        # --- AOT shape buckets: widths 1,2,...,W plus the power-of-two
        # lane-group sizes a per-session flush can present (capped at
        # num_lanes -- padding never outgrows the lane table)
        self._aot: Dict[Tuple, Any] = {}      # bucket key -> compiled exec
        self._aot_info: Optional[Dict[str, Any]] = None
        if aot_buckets is None:
            self._aot_widths = None
            self._group_buckets: Tuple[int, ...] = ()
            self._admit_buckets: Tuple[int, ...] = ()
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
            self._group_buckets = tuple(sorted(
                {self._group_bucket(g)
                 for g in range(1, 2 + self.secondary_slots)}))
            self._admit_buckets = tuple(sorted(
                {self._admit_bucket(k)
                 for k in range(1, 1 + self.primary_slots)}))

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
            group_chunks, width, flushed, n_disp = \
                self._flush_admission(admitted)
            sp.set(n_admitted=len(admitted),
                   n_scan_dispatches=int(n_disp))
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
                                  "n_scan_dispatches": int(n_disp),
                                  "admit_ms": round(ms, 3)})
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
            with self.obs.span("engine.append", cat="session",
                               sid=sid, n=len(data)):
                s.backlog.append(data)
                s.backlog_tuples += len(data)
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
            states = self._reset_lanes(self._states,
                                       np.asarray(lanes, np.int32))
            self._states = (states if self._sharded is None
                            else self._sharded.shard_states(states))
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
           chunk); idle lanes carry all-masked padding;
        4. one vmapped ``run_chunks`` advances all lane states together
           -- per width segment, through the AOT bucket table when
           ``aot_buckets=`` is enabled.
        """
        snap = compilemon.snapshot()
        t0 = time.perf_counter()
        with self.obs.span("engine.flush", scope="engine") as sp:
            force = set(force)
            self._admit()
            with self.obs.span("sched.regrant", cat="sched"):
                self._reschedule_secondary()

            lane_chunks: List[List[np.ndarray]] = [[] for _ in range(self.num_lanes)]
            lane_masks: List[List[np.ndarray]] = [[] for _ in range(self.num_lanes)]
            lane_sid: List[Optional[int]] = [None] * self.num_lanes
            flushed_tuples = 0
            for slot, sid in enumerate(self._slot_sid):
                if sid is None:
                    continue
                s = self.sessions[sid]
                lanes = self._lane_group(slot)
                for ln in lanes:
                    lane_sid[ln] = sid
                gc, gm, n_real = self._take_striped(
                    s, lanes, flush_tail=sid in force)
                for g, ln in enumerate(lanes):
                    lane_chunks[ln].extend(gc[g])
                    lane_masks[ln].extend(gm[g])
                flushed_tuples += n_real

            row_sessions = [None if sid is None else self.sessions[sid]
                            for sid in lane_sid]
            width = 0
            segs = list(self._segments(lane_chunks))
            with self._segment_loop_span(segs, "engine") as seg_span:
                for off, w in segs:
                    with seg_span(off, w):
                        chunks, mask = self._pack_chunks(
                            lane_chunks, lane_masks, w, offset=off)
                        if self._sharded is not None:  # split over the mesh
                            chunks = jax.device_put(
                                chunks, self._sharded.lane_sharding)
                            mask = jax.device_put(
                                mask, self._sharded.lane_sharding)
                        run = self._aot.get(("eng", w), self._run_lanes)
                        self._states, stats = run(self._states, chunks, mask)
                        self._apply_exec_stats(
                            stats, row_sessions,
                            [min(max(len(c) - off, 0), w)
                             for c in lane_chunks])
                    width += w
            sp.set(tuples=flushed_tuples, width=width)
        self._record_flush(flushed_tuples, lane_chunks, width, snap=snap,
                           ms=(time.perf_counter() - t0) * 1e3)
        self._flush_no += 1

    def flush_session(self, sid: int) -> None:
        """Advance ONLY this session's stream: its backlog (ragged tail
        included, as a masked chunk) stripes across its current lane
        group and a single vmapped scan over <= 1 + granted lanes runs
        it -- the latency-tiering fast path behind ``query``.

        No admission and no secondary re-scheduling happen here (both
        stay on the engine-wide ``flush``), so the cost is bounded by
        this session's own backlog.  In distributed mode the lane group
        is gathered across device boundaries (``executor.take_lanes``),
        resumed locally, and scattered back -- when all of the session's
        lanes live on one device, the gather touches a single shard (the
        local-shard fast path).

        With ``aot_buckets=`` enabled the lane group rounds up to a
        power-of-two bucket, padded with lanes OUTSIDE the group
        carrying all-masked zero chunks: a fully masked scan leaves an
        ``ExecState`` bit-identical (the executor's validity-mask
        no-op), so the padded lanes are written back unchanged and the
        scan hits a pre-compiled bucket instead of retracing."""
        snap = compilemon.snapshot()
        t0 = time.perf_counter()
        s = self._session(sid)
        if s.slot is None:
            raise QueuedSessionError(
                f"session {sid} is queued (all {self.primary_slots} primary "
                "slots busy); nothing has run yet -- close another session "
                "to admit it first")
        with self.obs.span("engine.flush_session", scope="session",
                           sid=sid, tenant=s.tenant) as sp:
            lanes = self._lane_group(s.slot)
            group_chunks, group_masks, n_real = self._take_striped(
                s, lanes, flush_tail=True)
            width = 0
            if any(group_chunks):
                n_real_lanes = len(lanes)
                if self._aot_widths:
                    bucket = self._group_bucket(n_real_lanes)
                    if bucket > n_real_lanes:
                        in_group = set(lanes)
                        pads = [ln for ln in range(self.num_lanes)
                                if ln not in in_group][:bucket - n_real_lanes]
                        lanes = lanes + pads
                        group_chunks = group_chunks + [[] for _ in pads]
                        group_masks = group_masks + [[] for _ in pads]
                row_sessions = [s] * n_real_lanes + \
                    [None] * (len(lanes) - n_real_lanes)
                idx = np.asarray(lanes, np.int32)
                sub = self._gather_group(self._states, idx)
                segs = list(self._segments(group_chunks))
                with self._segment_loop_span(segs, "session") as seg_span:
                    for off, w in segs:
                        with seg_span(off, w):
                            arr, msk = self._pack_chunks(group_chunks,
                                                         group_masks, w,
                                                         offset=off)
                            run = self._aot.get(("grp", len(lanes), w),
                                                self._run_group)
                            sub, stats = run(sub, arr, msk)
                            self._apply_exec_stats(
                                stats, row_sessions,
                                [min(max(len(c) - off, 0), w)
                                 for c in group_chunks])
                        width += w
                self._states = self._scatter_group(self._states, idx, sub)
            sp.set(tuples=n_real, width=width)
        self._record_flush(n_real, group_chunks, width, scope="session",
                           snap=snap, ms=(time.perf_counter() - t0) * 1e3)
        self._flush_no += 1

    def _flush_admission(self, sids: List[int]):
        """The storm flush behind ``open_batch``: run the newly admitted
        sessions' first backlog chunks as one batched lane-init plus one
        pow2-bucketed scan over their primary lanes.

        Only FULL chunks run (``flush_tail=False``): answers are
        chunking-invariant, so deferring ragged tails to the next
        query/close keeps the path bit-exact vs serial admission, and a
        session whose first append is sub-chunk costs zero dispatches.
        A newly admitted session holds no secondary grants, so its lane
        group is exactly its primary lane -- the storm group is the
        admitted lanes, padded up to the admission bucket with OTHER
        real lanes carrying all-masked chunks (written back
        bit-identically, the ``flush_session`` pad rule).  The lane-init
        idx pads with DUPLICATE admitted lanes instead: resetting a
        fresh lane twice is a no-op, while resetting another session's
        lane would destroy it.

        Returns ``(group_chunks, width, flushed_tuples,
        n_scan_dispatches)`` for the caller's telemetry row."""
        live = [self.sessions[sid] for sid in sids
                if self.sessions[sid].backlog_tuples >= self.chunk_size]
        if not live:
            return [], 0, 0, 0
        lanes = [s.slot for s in live]
        n_real_lanes = len(lanes)
        bucket = (self._admit_bucket(n_real_lanes) if self._aot_widths
                  else n_real_lanes)
        init_idx = lanes + [lanes[0]] * (bucket - n_real_lanes)
        with self.obs.span("admit.lane_init", cat="admit",
                           n_lanes=n_real_lanes, bucket=bucket):
            states = self._reset_lanes(self._states,
                                       np.asarray(init_idx, np.int32))
            self._states = (states if self._sharded is None
                            else self._sharded.shard_states(states))
        group_chunks: List[List[np.ndarray]] = []
        group_masks: List[List[np.ndarray]] = []
        flushed = 0
        for s in live:
            gc, gm, n_real = self._take_striped(s, [s.slot],
                                                flush_tail=False)
            group_chunks.append(gc[0])
            group_masks.append(gm[0])
            flushed += n_real
        if bucket > n_real_lanes:
            in_group = set(lanes)
            pads = [ln for ln in range(self.num_lanes)
                    if ln not in in_group][:bucket - n_real_lanes]
            lanes = lanes + pads
            group_chunks += [[] for _ in pads]
            group_masks += [[] for _ in pads]
        row_sessions = live + [None] * (len(lanes) - n_real_lanes)
        idx = np.asarray(lanes, np.int32)
        sub = self._gather_group(self._states, idx)
        width = n_disp = 0
        for off, w in self._segments(group_chunks):
            with self.obs.span("scan.segment", cat="scan", scope="admit",
                               offset=off, width=w):
                arr, msk = self._pack_chunks(group_chunks, group_masks, w,
                                             offset=off)
                run = self._aot.get(("grp", len(lanes), w), self._run_group)
                sub, stats = run(sub, arr, msk)
                self._apply_exec_stats(
                    stats, row_sessions,
                    [min(max(len(c) - off, 0), w) for c in group_chunks])
            width += w
            n_disp += 1
        self._states = self._scatter_group(self._states, idx, sub)
        return group_chunks, width, flushed, n_disp

    # ------------------------------------------------------- AOT bucket table

    def _admit_bucket(self, k: int) -> int:
        """Admission-storm lane bucket: the power-of-two ceiling of the
        ``k`` admitted sessions, capped at ``primary_slots`` -- a storm
        can never admit more than every primary lane, so the full-house
        storm pays no padding and the pad lanes always exist."""
        return min(1 << (k - 1).bit_length(), self.primary_slots)

    def _group_bucket(self, g: int) -> int:
        """Lane-group bucket: the power-of-two ceiling of ``g``, capped
        at the LARGEST group a session can own (its primary lane + every
        secondary lane) -- the maximal group never pays padding, and the
        padding lanes always exist."""
        gmax = min(1 + self.secondary_slots, self.num_lanes)
        return min(1 << (g - 1).bit_length(), gmax)

    # per-flush ceiling on individual scan.segment spans: a 256-chunk
    # flush through width-2 AOT buckets is 128 segments, and 128 span
    # emits per flush is pure tracer churn on the hot path -- past the
    # cap the whole loop gets ONE aggregate ``scan.segments`` span
    # (args: n_segments, width) instead
    _SEGMENT_SPAN_CAP = 16

    @contextlib.contextmanager
    def _segment_loop_span(self, segs, scope: str):
        """Context for a flush's segment loop, yielding the per-segment
        span factory: detailed ``scan.segment`` spans up to
        ``_SEGMENT_SPAN_CAP`` segments, ONE aggregate ``scan.segments``
        span over the whole loop past it."""
        if len(segs) <= self._SEGMENT_SPAN_CAP:
            yield lambda off, w: self.obs.span(
                "scan.segment", cat="scan", scope=scope,
                offset=off, width=w)
            return
        null = contextlib.nullcontext()
        with self.obs.span("scan.segments", cat="scan", scope=scope,
                           n_segments=len(segs),
                           width=sum(w for _, w in segs)):
            yield lambda off, w: null

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

        AOT-lowers and compiles one executable per engine-wide scan
        width (``jit(scan_lanes).lower().compile()``, sharded over the
        mesh when distributed) and one per (lane-group bucket, width)
        for the per-session tier, then primes every remaining
        fixed-shape entry point (lane gather/scatter, merge, reset,
        fold, the secondary scheduler) by executing it on scratch
        states -- so ``flush`` / ``flush_session`` / ``query`` /
        ``close`` are all compile-free afterwards.

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
        scratch = (self._sharded.init_states() if self._sharded is not None
                   else core_executor.stack_states(self._fresh,
                                                   self.num_lanes))

        def zeros(lanes, w):
            zc = np.zeros((lanes, w, c, *feat), self._dtype)
            zm = np.zeros((lanes, w, c), bool)
            return zc, zm

        for w in self._aot_widths:
            zc, zm = zeros(self.num_lanes, w)
            if self._sharded is not None:
                zc = jax.device_put(zc, self._sharded.lane_sharding)
                zm = jax.device_put(zm, self._sharded.lane_sharding)
            self._aot[("eng", w)] = \
                self._run_lanes.lower(scratch, zc, zm).compile()
        # one executable per (lane-group bucket, width) serves BOTH the
        # per-session flush tier and the admission-storm path: compiled
        # executables key on argument shapes alone, so the two bucket
        # families share the ("grp", b, w) table
        for b in sorted({*self._group_buckets, *self._admit_buckets}):
            idx = np.arange(b, dtype=np.int32)
            sub = self._gather_group(scratch, idx)    # primes the gather
            for w in self._aot_widths:
                zc, zm = zeros(b, w)
                self._aot[("grp", b, w)] = \
                    self._run_group.lower(sub, zc, zm).compile()
            self._scatter_group(scratch, idx, sub)    # primes the scatter
        # batched lane-init shapes: close resets exact group sizes
        # (1..1+secondary_slots); the storm lane-init pads its idx up to
        # the admission bucket
        for n in sorted({*range(1, 2 + self.secondary_slots),
                         *self._admit_buckets}):
            reset = self._reset_lanes(scratch, np.arange(n, dtype=np.int32))
            if self._sharded is not None:
                self._sharded.shard_states(reset)
        # remaining fixed-shape entry points (query/close/re-grant): a
        # plain execution populates their jit caches
        self._merge_lane(scratch, 0)
        self._reset_lane(scratch, 0)
        if self.secondary_slots and self.spec.merge is None:
            self._fold_lane(scratch, self.primary_slots, 0)
        self._res.merge_state(self._fresh)
        self.plan_secondary(np.zeros(self.primary_slots, np.float32))
        d = compilemon.since(before)
        self._aot_info = {
            "widths": [int(w) for w in self._aot_widths],
            "group_buckets": [int(b) for b in self._group_buckets],
            "admit_buckets": [int(b) for b in self._admit_buckets],
            "n_executables": len(self._aot),
            "warmup_ms": round((time.perf_counter() - t0) * 1e3, 3),
            "warmup_compiles": int(d.n_compiles),
            "warmup_compile_ms": float(d.stall_ms),
        }
        return self._aot_info

    def _gather_group(self, states, idx):
        """Lanes ``idx`` of ``states`` as one stacked ``ExecState`` for
        the per-session tier.  Under ``mesh=`` the group moves to one
        device: its scan holds a Pallas kernel, which cannot be
        partitioned across the mesh automatically."""
        sub = self._take_lanes(states, idx)
        return sub if self._sharded is None else \
            self._sharded.to_group_device(sub)

    def _scatter_group(self, states, idx, sub):
        """Inverse of ``_gather_group``: ``states`` with lanes ``idx``
        replaced by ``sub``, re-pinned to the lane sharding."""
        if self._sharded is None:
            return self._put_lanes(states, idx, sub)
        states = self._put_lanes(states, idx, self._sharded.replicate(sub))
        return self._sharded.shard_states(states)

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

        Returns HOST (numpy) arrays on purpose: jit and AOT executables
        take them directly, and the distributed flush path device_puts
        host memory straight to each shard -- resharding an
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

    @staticmethod
    def _pop_backlog(s: _Session, n: int) -> np.ndarray:
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
        for slot, sid in enumerate(self._slot_sid):
            if sid is not None:
                out[slot] = self.sessions[sid].backlog_tuples // self.chunk_size
        return out

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
                self._states = self._fold_lane(
                    self._states, self.primary_slots + j, old)
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

    def _fold_lane_impl(self, states, src, dst):
        contrib = self._res.merge_state(
            jax.tree.map(lambda x: x[src], states))
        bufs = states.buffers
        if self.spec.combine == "add":
            bufs = bufs.at[dst, :self.num_pri].add(contrib)
        else:
            bufs = bufs.at[dst, :self.num_pri].max(contrib)
        states = dataclasses.replace(states, buffers=bufs)
        return jax.tree.map(lambda x, f: x.at[src].set(f), states,
                            self._fresh)

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
                                  self._merge_lane(self._states, s.slot))
            for j in range(self.secondary_slots):
                if self._sec_assign[j] == s.slot:
                    contrib = jax.tree.map(np.asarray, self._merge_lane(
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
        active = sum(sid is not None for sid in self._slot_sid)
        backlog = sum(s.backlog_tuples for s in self.sessions.values()
                      if not s.closed)
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
        busy = {slot for slot, sid in enumerate(self._slot_sid)
                if sid is not None}
        busy |= {self.primary_slots + j
                 for j in range(self.secondary_slots)
                 if self._sec_assign[j] >= 0}
        m.lanes_busy.set(len(busy))
        if self.num_lanes <= m.MAX_LANE_SERIES:
            for ln in range(self.num_lanes):
                m.occupancy.set(1.0 if ln in busy else 0.0, lane=str(ln))
        depth: Dict[str, int] = {}
        for sid in self._slot_sid:
            if sid is not None:
                s = self.sessions[sid]
                depth[s.tenant] = depth.get(s.tenant, 0) + s.backlog_tuples
        tenants = sorted(depth, key=lambda t: (-depth[t], t))
        for tenant in tenants[:m.MAX_TENANT_SERIES]:
            m.backlog.set(depth[tenant], tenant=tenant)

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
                    "mesh_devices": (None if self._sharded is None
                                     else self.num_lanes
                                     // self.lanes_per_device),
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

"""End-to-end tracing: spans with contextvar propagation across every
concurrency seam of the data plane (ISSUE 12, docs/observability.md).

The reference's operators debug a stalled backup with a task log; this
build's job path crosses an asyncio jobs queue, thread pools (pipeline
hash workers, the backup writer thread, executor offloads), the aRPC
mux (server⇄agent), and the sync HTTP wire — a latency question is
unanswerable from any one layer's counters.  This module is the shared
measurement substrate:

- **Spans.**  ``with trace.span("job.queue_wait", kind=...):`` opens a
  timed span parented under the ambient context (a ``contextvar``), so
  nested spans form a tree per trace.  Span *names are a closed
  registry* (``SPANS`` below): every name maps to the histogram it
  feeds (or ``None``) and must be documented in
  ``docs/observability.md`` — pbslint's ``span-discipline`` and
  ``registry-consistency`` rules enforce both directions, the
  failpoint-catalog discipline applied to measurement points.
- **Propagation.**  Same-task nesting rides the contextvar.  Across
  threads: ``capture()``/``attached(ctx)``/``wrap(fn)`` (the pipeline
  pool, the backup writer thread, ``run_in_executor`` offloads).
  Across the aRPC mux: ``Session.call`` injects the context into the
  request headers (``TRACE_HEADER``) and the router re-attaches it
  around the handler, so agent-side work parents under the server's
  job span.  Across the sync wire: the same header on every HTTP
  request (``syncwire._WireClient`` → ``SyncWireServer``).
- **Ring buffer.**  Closed spans land in a bounded in-process ring
  (``PBS_PLUS_TRACE_RING`` entries, oldest evicted) served by
  ``GET /api2/json/d2d/traces`` and dumped into the pytest report on
  fleet chaos/soak failures (``tests/fleet/conftest.py``).
- **Histograms.**  Every span close (and the ``record()`` fast path
  for hot sites like mux frame writes) feeds a fixed-bucket log-spaced
  histogram in ``server/metrics.py`` — ``/metrics`` finally exports
  p50/p99-derivable latency for the whole path.
- **Device round trips.**  ``with trace.round_trip("device.scan", stats,
  ...) as rt:`` is a span whose body is cut into the five phases of a
  trip to the device (``with rt.phase("pack"):`` … ``h2d``, ``device``,
  ``d2h``, ``unpack``).  Phase seconds accumulate into the op's own
  ``stats`` dict (what ``/metrics`` and the benchmark read) and ride on
  the span as attrs; span and phases are also entered as
  ``jax.profiler.TraceAnnotation`` under the same names, so a profiler
  trace of the process shows them on the device's clock.  This module
  never imports jax: the annotation class is taken only when jax is
  already loaded.

- **Thread clocks.**  ``ThreadClock`` partitions a thread's life by
  state: ``spent(state)`` gives the time since the last call to
  ``state``, so the states sum to the thread's wall time, and the
  thread's own CPU seconds (``time.thread_time``) stand beside them —
  life less CPU is time blocked.  A thread attaches its clock with
  ``clocked(clock)``; code below it calls ``spent(state)`` or brackets a
  block with ``state(state)``, no-ops on a thread without one.  The
  device batcher's thread and every backup session's writer thread keep
  one (docs/observability.md "The session's clocks").  Counters, like a
  round trip's phase clocks: they run whether or not spans are enabled.
  A clock also carries the thread's own ``counts``: ``tally(...)`` adds
  to them from wherever the work happens on that thread (the dedup
  index's probes and inserts: docs/observability.md "The index"), so
  sessions running at once never share a count.
- **Job records.**  Closed ``backup.pump`` spans — one a backup job,
  carrying the session's clocks — are also kept in a table of their own
  (``job_records()``), which the ring's churn does not reach.

Tracing is ALWAYS ON.  The disabled path exists only for the bench's
tracing-on/off comparison (``disabled()``); the per-span cost without a
subscriber is gated < 5 µs (tests/test_bench_harness.py — the
failpoints disarmed-hit discipline applied here).
"""

from __future__ import annotations

import itertools
import os
import sys
import threading
import time
from collections import deque
from contextvars import ContextVar

from . import jaxenv
from .log import L

TRACE_HEADER = "x-pbs-trace"

# -- the span registry -------------------------------------------------------
# name -> histogram feed: None (span only), or (histogram_name, labels)
# where a "$attr" label value is resolved from the span's attrs at close
# time.  The set is CLOSED: span()/emit()/record() reject unknown names,
# pbslint's span-discipline requires literal names documented in
# docs/observability.md, and registry-consistency checks this dict
# against the call sites and the doc table in both directions.
SPANS = {
    # jobs plane (server/jobs.py)
    "job": None,
    "job.queue_wait": None,
    "job.enqueue_to_grant": ("pbs_plus_job_enqueue_to_grant_seconds",
                             {"kind": "$kind"}),
    "job.execute": ("pbs_plus_job_grant_to_publish_seconds",
                    {"kind": "$kind"}),
    "job.enqueue_to_publish": ("pbs_plus_job_enqueue_to_publish_seconds",
                               {"kind": "$kind"}),
    # backup data plane (server/backup_job.py, server/fleetsim.py)
    "backup.session_open": ("pbs_plus_session_open_seconds",
                            {"phase": "job"}),
    "backup.publish": None,
    # the pump's whole walk (RemoteTreeBackup.run); attrs: its files,
    # those of one call, and its agentfs calls
    "backup.pump": None,
    "session.open": ("pbs_plus_session_open_seconds",
                     {"phase": "connect"}),
    # batched ingest stages (pxar/transfer.py, pxar/pipeline.py)
    "ingest.cdc": ("pbs_plus_ingest_stage_seconds", {"stage": "cdc"}),
    "ingest.sha": ("pbs_plus_ingest_stage_seconds", {"stage": "sha"}),
    "ingest.probe": ("pbs_plus_ingest_stage_seconds", {"stage": "probe"}),
    "ingest.presketch": ("pbs_plus_ingest_stage_seconds",
                         {"stage": "presketch"}),
    "ingest.store": ("pbs_plus_ingest_stage_seconds", {"stage": "store"}),
    # the cross-session batcher (models/feeder.py): one dispatch per mask
    # group / hash round on the feeder's thread, and how long each
    # request queued for it
    "feeder.dispatch": ("pbs_plus_feeder_dispatch_seconds",
                        {"kind": "$kind"}),
    "feeder.queue_wait": ("pbs_plus_feeder_queue_wait_seconds",
                          {"kind": "$kind"}),
    # device round trips (ops/rolling_hash.py, ops/sha256.py,
    # ops/cuckoo.py), opened with round_trip() below
    "device.scan": ("pbs_plus_device_dispatch_seconds", {"op": "scan"}),
    "device.sha": ("pbs_plus_device_dispatch_seconds", {"op": "sha"}),
    "device.probe": ("pbs_plus_device_dispatch_seconds", {"op": "probe"}),
    # a hash batch on the host's SHA-256 (ops/sha256.py sha256_chunks),
    # on the caller's thread: child of the writer's ingest.sha
    "host.sha": None,
    # read path (pxar/chunkcache.py)
    "chunkcache.fetch": ("pbs_plus_chunk_cache_fetch_seconds", None),
    # spillable exact-confirm tier (pxar/digestlog.py)
    "digestlog.confirm": ("pbs_plus_digestlog_confirm_read_seconds",
                          None),
    # replication wire (pxar/syncwire.py)
    "sync.negotiate": ("pbs_plus_sync_batch_seconds",
                       {"phase": "negotiate"}),
    "sync.transfer": ("pbs_plus_sync_batch_seconds",
                      {"phase": "transfer"}),
    "sync.serve": None,
    # rpc layer (arpc/router.py, sidecar/client.py, arpc/mux.py)
    "rpc.serve": None,
    "sidecar.call": None,
    "mux.write_frame": ("pbs_plus_mux_frame_write_seconds", None),
    # per-service lock waits (server/services/, ISSUE 15): how long a
    # caller queued on a service's own lock — the histogram where the
    # old Server._prune_lock convoy would show up if the split ever
    # regressed into one big lock again
    "service.lock_wait": ("pbs_plus_service_lock_wait_seconds",
                          {"service": "$service"}),
}

_ctx: "ContextVar[tuple[str, str] | None]" = ContextVar(
    "pbs_plus_trace", default=None)

# ring capacity: enough that a fleet soak's LAST complete job traces
# survive the rpc.serve churn of earlier jobs (docs/observability.md)
_DEFAULT_RING = 8192


def _ring_capacity() -> int:
    try:
        return max(64, int(os.environ.get("PBS_PLUS_TRACE_RING",
                                          str(_DEFAULT_RING))))
    except ValueError:
        return _DEFAULT_RING


# closed spans, oldest evicted; deque append/snapshot are GIL-atomic so
# the hot path takes no lock
_ring: "deque[dict]" = deque(maxlen=_ring_capacity())
# closed backup.pump spans, one a backup job with its session's clocks
# (server/backup_job.py): a window of eight sessions closes thousands of
# rpc.serve and device spans, and a reader of a job's record must not
# depend on the ring's retention
_JOB_SPAN = "backup.pump"
_jobs: "deque[dict]" = deque(maxlen=256)
# open spans (orphan detection): span_id -> (name, wall-clock start)
_active: dict = {}
# per-close subscribers (test/chaos hooks); empty in production, and the
# close path skips the loop entirely when it is
_subs: list = []
_enabled = True          # bench-only kill switch (disabled() below)

# id generator: 64-bit counter seeded from urandom so two processes
# sharing a wire never collide; next() is GIL-atomic
_ids = itertools.count(int.from_bytes(os.urandom(8), "big") or 1)
_MASK = (1 << 64) - 1

_metrics = None          # lazy server.metrics binding (no import cycle)


def _new_id() -> str:
    return format(next(_ids) & _MASK, "016x")


def _feed_histogram(name: str, seconds: float, attrs: "dict | None") -> None:
    spec = SPANS[name]
    if spec is None:
        return
    global _metrics
    if _metrics is None:
        from ..server import metrics as _m      # light: stdlib + log only
        _metrics = _m
    hist, labels = spec
    if labels is not None:
        # $attr placeholders resolve even when the span carried no
        # attrs — a missing attr becomes the "" child, never the
        # literal "$kind" leaking into the exposition as a label value
        resolved = {}
        for k, v in labels.items():
            resolved[k] = str((attrs or {}).get(v[1:], "")) \
                if isinstance(v, str) and v.startswith("$") else v
        labels = resolved
    _metrics.observe_histogram(hist, seconds, labels)


def _close_record(rec: dict) -> None:
    _ring.append(rec)
    if rec["name"] == _JOB_SPAN:
        _jobs.append(rec)
    _feed_histogram(rec["name"], rec["dur_s"], rec.get("attrs"))
    if _subs:
        for fn in list(_subs):
            fn(rec)


class _Span:
    """One open span; use ONLY as a context manager (pbslint rule
    ``span-discipline``) — a begin without a guaranteed close would leak
    into ``active_spans()`` as an orphan."""

    __slots__ = ("name", "attrs", "trace_id", "span_id", "parent_id",
                 "_t0", "_wall", "_token")

    def __init__(self, name: str, attrs: "dict | None"):
        self.name = name
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        cur = _ctx.get()
        if cur is None:
            self.trace_id = _new_id()
            self.parent_id = ""
        else:
            self.trace_id, self.parent_id = cur
        self.span_id = _new_id()
        self._token = _ctx.set((self.trace_id, self.span_id))
        self._wall = time.time()
        _active[self.span_id] = (self.name, self._wall)
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        """Attrs known only once the block is under way."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur = time.perf_counter() - self._t0
        _ctx.reset(self._token)
        _active.pop(self.span_id, None)
        rec = {"name": self.name, "trace": self.trace_id,
               "span": self.span_id, "parent": self.parent_id,
               "start": self._wall, "dur_s": dur}
        if self.attrs:
            rec["attrs"] = self.attrs
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        _close_record(rec)
        return False


class _NoopSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def set(self, **attrs) -> None:
        pass

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoopSpan()


def span(name: str, **attrs) -> "_Span | _NoopSpan":
    """Open a timed span (context manager).  ``name`` must be in the
    ``SPANS`` registry; ``attrs`` ride into the ring record and resolve
    ``$attr`` histogram labels."""
    if name not in SPANS:
        raise ValueError(f"unregistered span name {name!r} "
                         "(add it to trace.SPANS + docs/observability.md)")
    if not _enabled:
        return _NOOP
    return _Span(name, attrs or None)


def emit(name: str, seconds: float, **attrs) -> None:
    """One-shot pre-measured span: records a span of duration
    ``seconds`` ending now, parented under the ambient context — for
    aggregated measurements a context manager cannot bracket (the
    sequential writer's per-chunk stage accumulators)."""
    if name not in SPANS:
        raise ValueError(f"unregistered span name {name!r}")
    if not _enabled:
        return
    cur = _ctx.get()
    if cur is None:
        trace_id, parent = _new_id(), ""
    else:
        trace_id, parent = cur
    rec = {"name": name, "trace": trace_id, "span": _new_id(),
           "parent": parent, "start": time.time() - seconds,
           "dur_s": seconds}
    if attrs:
        rec["attrs"] = attrs
    _close_record(rec)


def enabled() -> bool:
    """True unless inside ``disabled()`` — instrumentation that pays
    per-chunk measurement cost outside the span APIs (the ingest stage
    accumulators) gates on this so the bench's tracing-off mode really
    removes the whole cost."""
    return _enabled


def record(name: str, seconds: float, **attrs) -> None:
    """Histogram-only observation (no ring entry) for hot sites where a
    per-event span would dominate the work being measured (mux frame
    writes).  The name still comes from the ``SPANS`` registry."""
    if name not in SPANS:
        raise ValueError(f"unregistered span name {name!r}")
    if not _enabled:
        return
    _feed_histogram(name, seconds, attrs or None)


# -- device round trips ------------------------------------------------------
# A dispatch to the device is five steps on the calling thread
# (docs/observability.md "Device round trips"); each op times them where
# they happen, with this one helper.

PHASES = ("pack", "h2d", "device", "d2h", "unpack")

# op -> the counters dict its module keeps ("scan", "sha", "probe"; the
# process-wide feeder registers "feeder"), so that /metrics renders them
# without importing the modules, which import jax
DEVICE_STATS: "dict[str, dict]" = {}
_warned_compiles: set = set()


def device_stats(op: str, counters: dict) -> dict:
    """Register ``op``'s counters dict, with the five phase clocks
    (``pack_s`` … ``unpack_s``) added at zero, and return it."""
    for phase in PHASES:
        counters.setdefault(phase + "_s", 0.0)
    DEVICE_STATS[op] = counters
    return counters


def annotation(label: str):
    """``jax.profiler.TraceAnnotation(label)`` where jax is already
    loaded, else a no-op: in a profiler trace of this process the block
    then lies on its thread's line, on one clock with the device's.
    Outside a profiler session it costs well under a microsecond."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    return _NOOP if profiler is None else profiler.TraceAnnotation(label)


def name_os_thread(name: str) -> None:
    """Give the calling thread its name at the OS too (Linux
    ``PR_SET_NAME``, 15 bytes): CPython before 3.14 does not, and a
    profiler names a thread's line after the OS name — every Python
    thread's line would read ``python3``.  Elsewhere: nothing."""
    try:
        import ctypes
        ctypes.CDLL(None).prctl(15, name.encode()[:15], 0, 0, 0)
    except (OSError, AttributeError):
        pass


class _Phase:
    __slots__ = ("_trip", "_key", "_ann", "_t0")

    def __init__(self, trip: "_RoundTrip", phase: str):
        self._trip = trip
        self._key = phase + "_s"
        self._ann = annotation(f"{trip.name}/{phase}")

    def __enter__(self) -> "_Phase":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        dur = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        trip = self._trip
        trip.add(**{self._key: dur})
        if jaxenv.thread_compiles() != trip._compiles:
            trip._compiled(dur)
        return False


class _RoundTrip:
    """One trip to the device and back: a span of the registry, the
    profiler annotation of the same name, and the phase clocks.  Use
    only as a context manager, like a span."""

    __slots__ = ("name", "stats", "attrs", "shape", "_lock", "_span",
                 "_ann", "_compiles")

    def __init__(self, name: str, stats: dict, lock, shape: str,
                 attrs: dict):
        self.name = name
        self.stats = stats
        self.attrs = attrs
        self._lock = _NOOP if lock is None else lock
        # the compiled program's class, for the one warning when a
        # dispatch compiles; an op with several programs per trip (the
        # hash buckets) sets it before each
        self.shape = shape

    def __enter__(self) -> "_RoundTrip":
        self._compiles = jaxenv.thread_compiles()
        self._ann = annotation(self.name)
        self._ann.__enter__()
        self._span = _Span(self.name, self.attrs) if _enabled else _NOOP
        self._span.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._span.__exit__(*exc)
        self._ann.__exit__(*exc)
        return False

    def phase(self, phase: str) -> _Phase:
        """Time the block as one of ``PHASES``: into ``stats[phase_s]``,
        the span's attrs, and an annotation ``<span>/<phase>``."""
        return _Phase(self, phase)

    def add(self, **counts) -> None:
        """Add to the op's counters and to the span's attrs of the same
        names (``rows``, ``bytes``, ``padded_bytes`` …)."""
        with self._lock:
            for key, n in counts.items():
                self.stats[key] += n
        for key, n in counts.items():
            self.attrs[key] = self.attrs.get(key, 0) + n

    def _compiled(self, seconds: float) -> None:
        # a program was built or loaded inside the phase that just
        # closed, on this thread: a shape class met for the first time
        now = jaxenv.thread_compiles()
        self.attrs["compiled"] = self.attrs.get("compiled", 0) \
            + now - self._compiles
        self._compiles = now
        key = (self.name, self.shape)
        if key not in _warned_compiles:
            _warned_compiles.add(key)
            L.warning("%s %s compiled in %.1f s inside a dispatch",
                      self.name, self.shape, seconds)


def round_trip(name: str, stats: dict, *, lock=None, shape: str = "",
               **attrs) -> _RoundTrip:
    """Open the span ``name`` (a ``device.*`` name of ``SPANS``) around
    one trip to the device; ``stats`` is the op's counters dict
    (``device_stats``) and ``lock``, where the op is called from several
    threads at once, the op's own guard of it (the scan and the hash run
    on the feeder's one thread and pass none).  The phase clocks and
    counters run whether or not spans are enabled: they are counters,
    not tracing."""
    if name not in SPANS:
        raise ValueError(f"unregistered span name {name!r} "
                         "(add it to trace.SPANS + docs/observability.md)")
    return _RoundTrip(name, stats, lock, shape, attrs)


# -- thread clocks -----------------------------------------------------------
# Where a thread's own time goes (docs/observability.md "The session's
# clocks"): the device batcher's thread (models/feeder.py) and a backup
# session's writer thread (server/backup_job.py) each keep one.

REST = "other_s"        # what a clocked thread does between bracketed states
_thread = threading.local()


class ThreadClock:
    """One thread's life, partitioned by state.  ``spent(state)`` gives
    the time since the last call (or the start) to ``state``, so the
    states of ``seconds`` sum to the thread's wall time; ``stop`` closes
    the books with ``life_s`` and ``cpu_s``, the thread's own CPU seconds
    (``time.thread_time``): life less CPU is time blocked — on a queue,
    the device, a file, the interpreter lock.  ``seconds`` may be the
    owner's counters dict (``DeviceFeeder.stats``); ``label`` names the
    profiler annotations of ``state()`` blocks, ``<label>.<state>``;
    ``counts`` is what ``tally()`` adds to on this thread.
    Every call but the constructor is made on the clocked thread."""

    __slots__ = ("seconds", "counts", "label", "_t0", "_t", "_cpu")

    def __init__(self, seconds: "dict | None" = None, label: str = "",
                 counts: "dict | None" = None):
        self.seconds = {} if seconds is None else seconds
        self.counts = {} if counts is None else counts
        self.label = label
        self._t0 = self._t = self._cpu = 0

    def start(self) -> None:
        self._t0 = self._t = time.perf_counter_ns()
        self._cpu = time.thread_time()

    def spent(self, state: str, now_ns: "int | None" = None) -> None:
        """The thread's time since the last call goes to ``state``;
        ``now_ns`` is a ``perf_counter_ns`` reading the caller already
        has."""
        if now_ns is None:
            now_ns = time.perf_counter_ns()
        seconds = self.seconds
        seconds[state] = seconds.get(state, 0.0) + (now_ns - self._t) * 1e-9
        self._t = now_ns

    def cpu(self) -> None:
        """Bring ``cpu_s`` up to now (a thread that never stops: the
        batcher's, once a round)."""
        now = time.thread_time()
        seconds = self.seconds
        seconds["cpu_s"] = seconds.get("cpu_s", 0.0) + now - self._cpu
        self._cpu = now

    def stop(self) -> None:
        self.spent(REST)
        self.cpu()
        seconds = self.seconds
        seconds["life_s"] = seconds.get("life_s", 0.0) \
            + (self._t - self._t0) * 1e-9


class clocked:
    """Attach ``clock`` to the calling thread for the block and run it:
    ``spent()`` and ``state()`` below reach it with no argument."""

    __slots__ = ("_clock",)

    def __init__(self, clock: ThreadClock):
        self._clock = clock

    def __enter__(self) -> ThreadClock:
        _thread.clock = self._clock
        self._clock.start()
        return self._clock

    def __exit__(self, *exc) -> bool:
        _thread.clock = None
        self._clock.stop()
        return False


def spent(state: str, now_ns: "int | None" = None) -> None:
    """The calling thread's time since its clock's last reading goes to
    ``state``; nothing on a thread with no clock (pipelined hash
    workers, local and S3 backups, tests)."""
    clock = getattr(_thread, "clock", None)
    if clock is not None:
        clock.spent(state, now_ns)


def tally(**counts) -> None:
    """Add to the calling thread's own counts (its clock's ``counts``):
    work counted where it happens, on the thread that does it; nothing
    on a thread with no clock."""
    clock = getattr(_thread, "clock", None)
    if clock is not None:
        mine = clock.counts
        for key, n in counts.items():
            mine[key] = mine.get(key, 0) + n


class _State:
    """One stay of a clocked thread in a state: what came before goes to
    the residue (``REST``), the block to ``state``, and the block is a
    profiler annotation ``<label>.<state>``.  A context manager;
    ``begin``/``end`` return their ``perf_counter_ns`` readings for a
    caller that keeps an accumulator of its own on the same two reads
    (``_ChunkedStream.write``)."""

    __slots__ = ("_clock", "_state", "_ann")

    def __init__(self, clock: ThreadClock, state: str):
        self._clock = clock
        self._state = state
        self._ann = annotation(f"{clock.label}.{state[:-2]}")

    def begin(self) -> int:
        now = time.perf_counter_ns()
        self._clock.spent(REST, now)
        self._ann.__enter__()
        return now

    def end(self) -> int:
        self._ann.__exit__(None, None, None)
        now = time.perf_counter_ns()
        self._clock.spent(self._state, now)
        return now

    def __enter__(self) -> "_State":
        self.begin()
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


class _NoState:
    """``state()`` on a thread with no clock: the readings alone."""

    __slots__ = ()
    begin = end = staticmethod(time.perf_counter_ns)

    def __enter__(self) -> "_NoState":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NO_STATE = _NoState()


def state(state: str) -> "_State | _NoState":
    """Bracket a block of the calling thread as ``state`` (a key that
    ends in ``_s``) of its clock; a no-op on a thread with none."""
    clock = getattr(_thread, "clock", None)
    return _NO_STATE if clock is None else _State(clock, state)


def job_records(n: "int | None" = None) -> list:
    """Closed ``backup.pump`` spans, oldest first: one record a backup
    job, 256 deep, kept apart from the ring."""
    out = list(_jobs)
    return out[-n:] if n is not None and n > 0 else out


# -- propagation -------------------------------------------------------------

def capture() -> "tuple[str, str] | None":
    """The ambient (trace_id, span_id), for hand-off to another thread."""
    return _ctx.get()


class attached:
    """Attach a captured context in this thread/task for the block.
    ``attached(None)`` is a no-op (keeps whatever is ambient)."""

    __slots__ = ("_target", "_token")

    def __init__(self, ctx: "tuple[str, str] | None"):
        self._target = ctx
        self._token = None

    def __enter__(self) -> "attached":
        if self._target is not None:
            self._token = _ctx.set(self._target)
        return self

    def __exit__(self, *exc) -> bool:
        if self._token is not None:
            _ctx.reset(self._token)
        return False


def wrap(fn):
    """Capture the ambient context NOW and return a callable that runs
    ``fn`` under it — the ``run_in_executor`` seam (executor threads
    do not inherit the caller's contextvars)."""
    ctx = _ctx.get()

    def inner(*a, **kw):
        with attached(ctx):
            return fn(*a, **kw)
    return inner


def headers_out(headers: "dict | None" = None) -> dict:
    """Inject the ambient context into an outgoing header dict (aRPC
    call metadata, sync wire HTTP) — returns the dict unchanged-ish
    when no context is ambient."""
    cur = _ctx.get()
    if cur is None:
        return headers if headers is not None else {}
    out = dict(headers) if headers else {}
    out[TRACE_HEADER] = f"{cur[0]}-{cur[1]}"
    return out


def parse_header(value: "str | None") -> "tuple[str, str] | None":
    """Parse an incoming ``TRACE_HEADER`` value; None when absent or
    malformed (a bad peer header must never kill the request)."""
    if not value:
        return None
    trace_id, _, span_id = value.partition("-")
    if len(trace_id) == 16 and len(span_id) == 16:
        try:
            int(trace_id, 16), int(span_id, 16)
        except ValueError:
            return None
        return (trace_id, span_id)
    return None


# -- introspection / dump ----------------------------------------------------

def recent(n: "int | None" = None,
           trace_id: "str | None" = None) -> list:
    """Closed spans, oldest first (the ring's retention window)."""
    out = list(_ring)
    if trace_id is not None:
        out = [r for r in out if r["trace"] == trace_id]
    if n is not None and n > 0:
        out = out[-n:]
    return out


def active_spans() -> list:
    """Open (never-closed) spans: (name, span_id, age_s).  Non-empty
    after an operation completed = an orphan — the propagation tests
    fail on it."""
    now = time.time()
    return [(name, sid, now - t0)
            for sid, (name, t0) in list(_active.items())]


def clear() -> None:
    """Drop ring + orphan state (test isolation only)."""
    _ring.clear()
    _jobs.clear()
    _active.clear()


def subscribe(fn) -> None:
    _subs.append(fn)


def unsubscribe(fn) -> None:
    try:
        _subs.remove(fn)
    except ValueError:
        pass


def dump_text(n: int = 50) -> str:
    """The last ``n`` spans formatted one per line — the crash/chaos
    dump hook (tests/fleet/conftest.py appends this to failed fleet
    test reports; operators get the same view from the traces
    endpoint)."""
    lines = []
    for r in recent(n):
        attrs = r.get("attrs") or {}
        extra = " ".join(f"{k}={v}" for k, v in attrs.items())
        err = f" ERROR={r['error']}" if "error" in r else ""
        lines.append(
            f"{r['start']:.6f} {r['dur_s'] * 1e3:9.3f}ms "
            f"trace={r['trace']} span={r['span']} "
            f"parent={r['parent'] or '-':16s} {r['name']}"
            f"{' ' + extra if extra else ''}{err}")
    return "\n".join(lines)


class disabled:
    """Bench-only kill switch: spans/records become no-ops inside the
    block, so the tracing-on vs tracing-off ingest ratio is measurable
    (tests/test_bench_harness.py gates it ≥ 0.97).  NOT a production
    knob — tracing is always on."""

    __slots__ = ("_prev",)

    def __enter__(self) -> "disabled":
        global _enabled
        self._prev = _enabled
        _enabled = False
        return self

    def __exit__(self, *exc) -> bool:
        global _enabled
        _enabled = self._prev
        return False


_ring_lock = threading.Lock()


def configure_ring(capacity: int) -> None:
    """Resize the ring (server config / tests); keeps the newest
    entries."""
    global _ring
    with _ring_lock:
        _ring = deque(_ring, maxlen=max(64, int(capacity)))

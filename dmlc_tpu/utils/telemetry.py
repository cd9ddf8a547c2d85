"""Always-on pipeline telemetry: span tracer + labeled metrics registry.

tf.data's lesson (arXiv:2101.12127) is that AUTOTUNE and fleet-scale
debugging are both built on exactly one thing — a uniform, low-overhead
instrumentation layer over every pipeline stage — and the tf.data-service
paper (arXiv:2210.14826) adds that per-worker metrics must be aggregable
across hosts before a dispatcher can balance them. This module is that
layer for the ingest tier, and the sensor substrate the ROADMAP item 4
feedback controller will read. Two primitives:

**Span tracer** — fixed-size per-thread ring buffers recording
``(name, tid, start_ns, dur_ns, labels)`` spans. Recording is lock-free on
the hot path (each ring has exactly one writer: its thread) and bounded
(old spans overwrite, drops are counted), so it stays on in production.
Every pipeline stage emits spans at the SAME code sites that feed the
stage-seconds counters — read / parse in :mod:`dmlc_tpu.data.parsers`,
cache_read (and, under an epoch plan, plan_permute / plan_wait) there +
cache_write in :mod:`dmlc_tpu.io.block_cache`,
merge / convert / dispatch / transfer in :mod:`dmlc_tpu.data.device`
(the first three labeled with their batch's ``epoch`` / ``batch``), and the
data-service wire quartet (service_encode / service_send on parse
workers, service_recv / service_decode on clients,
:mod:`dmlc_tpu.service.frame`) — so a trace timeline and
``DeviceIter.stats()`` can never tell different stories. :class:`span` is
the one way a stage is spanned: besides the ring it runs the block inside
a ``jax.profiler.TraceAnnotation`` named ``dmlc_tpu:<stage>``, so a
profiler session shows the stages beside the device trace with nothing
set (:func:`record_span` is the ring-only form, for what is timed
without a block: the service tier's sends and RPCs).
Export as Chrome-trace/Perfetto JSON via ``DMLC_TPU_TRACE=chrome:<path>``
(dumped when the ``DeviceIter`` closes) or ``DeviceIter.dump_trace(path)``
/ :func:`export_chrome_trace`.

**Metrics registry** — named counters / gauges / info blobs
with label scoping. The single source of truth behind
``DeviceIter.stats()`` (its :class:`~dmlc_tpu.utils.timer.StageMeter`
stage counters are registry counters), the resilience counters
(:mod:`dmlc_tpu.io.resilience` keeps its public
``counters_snapshot/delta/reset`` API on top of it), the pipeline stall
diagnostics. ``make lint-metrics`` fails ad-hoc bookkeeping added
beside it.

**Pipeline scoping** — a thread-local label (:func:`scope`) stamped onto
every span and metric recorded while it is active. The pipeline thread
primitives (``ThreadedIter`` / ``OrderedWorkerPool`` / the native feed
threads / ``ManagedThread``) capture their creator's scope and install it
in the threads they spawn, so everything a ``DeviceIter`` causes — down
to filesystem retries on a producer thread — lands under that pipeline's
label. Two concurrent pipelines therefore keep disjoint books (the
cross-contamination fix for ``stats()['resilience']``).

**Pod aggregation** — :func:`pod_snapshot` serializes this process's
registry into a compact JSON-able dict; workers ship it to the rendezvous
tracker over the heartbeat path (``WorkerClient.report_metrics``) and the
tracker logs the merged per-rank × per-stage table
(:func:`format_pod_table`), so an 8-host run is debuggable from one
place. See docs/observability.md.

**Fleet observability plane** (schema v2) — four additions on top of
the substrate. *Distributed tracing*: a thread-local trace context
(:func:`trace` / :func:`current_trace`) stamps optional
``trace_id``/``parent_id``/``span_id`` fields onto spans, and its
compact wire form (:func:`trace_context_wire`) rides service RPCs so
one (job, part) is one trace from ``next_split`` to ``device_put``;
:func:`export_pod_trace` merges per-peer snapshots into ONE Perfetto
timeline with pid = role and per-peer clock offsets. *Prometheus
exposition*: :func:`render_prometheus` serializes the registry in text
exposition format (the ``metrics_text`` RPC), with a bounded
time-series ring (:func:`sample_metrics_history`,
``DMLC_TPU_METRICS_HISTORY``) behind the gauges. *Decision ledger*:
:func:`record_decision` is the one structured event shape every
controller (autotune / autoscaler / dispatcher / store / worker) emits.
*Bounded registry*: past ``DMLC_TPU_METRICS_MAX_PIPELINES`` pipeline
scopes the least-recently-touched one retires, its tallies folded into
process totals — the registry twin of span-ring retirement.
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

# bumped whenever the span schema, the pod-snapshot layout, or a
# registry metric name consumed across processes changes — the tracker
# refuses to merge snapshots from a different schema. v2: spans gained
# optional trace_id/parent_id/span_id distributed-tracing fields and
# snapshots a "decisions" summary (docs/observability.md Distributed
# tracing).
SCHEMA_VERSION = 2

# the canonical pipeline stages (benchmarks/_common.STAGE_ORDER mirrors
# this; DeviceIter.stats()['stages'] carries exactly these keys)
STAGES = ("read", "cache_read", "parse", "convert", "dispatch", "transfer")

# registry metric names (docs/observability.md has the full table)
STAGE_BUSY_METRIC = "stage_busy_seconds"
STAGE_WALL_METRIC = "stage_wall_seconds"
RESILIENCE_METRIC = "resilience_events"
STALL_METRIC = "pipeline_stall"
# consumer-side input-bound waiting: every second the consumer measurably
# waited for input (host-batch waits + sampled transfer landings) — the
# counter the autotuner trusts where stall_seconds alone under-reads a
# transfer-bound epoch
INPUT_WAIT_METRIC = "input_wait_seconds"
# what an OrderedWorkerPool's threads spend their time on, labeled (pool,
# state, pipeline): a worker is waiting for the max_ahead window
# (state="window_wait": the consumer is behind, back-pressure), waiting for
# the pull lock ("pull_wait": the serial stage is the queue), in the serial
# pull ("pull") or in work_fn ("work"); "ready_wait" is how long delivered
# items lay finished before the consumer took them, "merge" the seconds of
# DeviceIter's serial stage inside its own `merge` spans. The events twin
# counts items delivered and DeviceIter's staging-ring hits and misses
# (kind="items" / "ring_hits" / "ring_misses"). DeviceIter.stats()["pool"]
# reads both for its convert pool (docs/observability.md).
POOL_SECONDS_METRIC = "pool_seconds"
POOL_EVENTS_METRIC = "pool_events"
# autotuner mirrors (dmlc_tpu.data.autotune): per-knob current-value
# gauges + a steps counter, labeled by pipeline scope
AUTOTUNE_KNOB_METRIC = "autotune_knob"
AUTOTUNE_STEP_METRIC = "autotune_steps"
# tiered artifact store (dmlc_tpu.store): live on-disk bytes under
# management, gauge labeled (root, tier) — evictions/rebuilds ride the
# resilience counter like every other classified event (docs/store.md)
STORE_BYTES_METRIC = "store_bytes"
# multi-tenant data service (dmlc_tpu.service, docs/service.md): both
# labeled by `job`. The wait counter is the CLIENT-side per-job input
# starvation signal (every second a ServiceParser waits on the wire) the
# fleet autoscaler aggregates from the tracker pod table; the parts
# counter is the WORKER-side per-job parts-served tally. They ride
# pod_snapshot()['jobs'] so the pod table shows a per-job breakdown next
# to per-rank stages (docs/observability.md).
SERVICE_JOB_WAIT_METRIC = "service_job_input_wait_seconds"
SERVICE_JOB_PARTS_METRIC = "service_job_parts"
# per-job input-wait SLO target (register_job(slo_wait_frac=),
# docs/service.md Production QoS): a job-labeled gauge each
# ServiceParser publishes from its config reply, so the pod table shows
# every job's wait NEXT TO the target the autoscaler steers it under
SERVICE_JOB_SLO_METRIC = "service_job_slo_wait_frac"
# wire compression ledger (dmlc_tpu.service.frame, docs/service.md
# The stream): raw vs on-wire bytes for every served data frame, labeled by
# `job` — sent/raw is the live compression ratio the pod table reports;
# identity transports tick both equally so the ratio reads 1.0
SERVICE_WIRE_RAW_METRIC = "service_wire_bytes_raw"
SERVICE_WIRE_SENT_METRIC = "service_wire_bytes_sent"
# control-decision audit ledger (docs/observability.md Decision ledger):
# every autotuner step, fleet grow/drain, QoS throttle, store eviction,
# hedge and worker drain is one record_decision() event — this counter
# is its registry shadow, labeled (component, action), so decisions are
# countable next to the metrics that triggered them
DECISION_METRIC = "decision_events"


# ---------------- pipeline scoping ----------------

_tls = threading.local()
_scope_seq = itertools.count(1)


def new_pipeline_label(prefix: str = "pipeline") -> str:
    """A process-unique pipeline label (``pipeline-1``, ``pipeline-2``...)."""
    return f"{prefix}-{next(_scope_seq)}"


def current_scope() -> Optional[str]:
    """The pipeline label active on this thread, or None."""
    return getattr(_tls, "scope", None)


def set_scope(label: Optional[str]) -> None:
    """Install ``label`` as this thread's pipeline scope (thread primitives
    call this at thread start with the scope captured at construction)."""
    _tls.scope = label


@contextmanager
def scope(label: Optional[str]):
    """Run a block under a pipeline scope; restores the previous one."""
    prev = current_scope()
    set_scope(label)
    try:
        yield label
    finally:
        set_scope(prev)


# ---------------- distributed trace context ----------------
#
# A trace context is ``(trace_id, span_id)``: the trace a causal chain
# belongs to, plus the span id the NEXT hop parents under. It crosses
# processes as an optional ``{"trace": {"tid", "sid"}}`` JSON key on
# service control RPCs and stream requests (old peers ignore unknown
# keys, so wire framing and goldens are untouched — docs/service.md),
# and within a process a thread-local mirror stamps trace_id/parent_id
# onto every span recorded while it is installed.

# in-process override for the DMLC_TPU_TRACE_CONTEXT master switch: a
# caller flips propagation off for one epoch without touching the
# environment of spawned threads
_trace_propagation: Optional[bool] = None


def set_trace_propagation(enabled: Optional[bool]) -> None:
    """Force trace-context propagation on/off for this process
    (``None`` restores the ``DMLC_TPU_TRACE_CONTEXT`` env default)."""
    global _trace_propagation
    _trace_propagation = None if enabled is None else bool(enabled)


def trace_propagation_enabled() -> bool:
    """Master switch for cross-process trace context: on by default,
    ``DMLC_TPU_TRACE_CONTEXT=0`` (or :func:`set_trace_propagation`)
    turns the wire key + span stamping off."""
    if _trace_propagation is not None:
        return _trace_propagation
    return os.environ.get("DMLC_TPU_TRACE_CONTEXT", "").strip() != "0"


def new_trace_id() -> str:
    """A fresh 64-bit hex trace id (one per (job, part) causal chain)."""
    return os.urandom(8).hex()


def new_span_id() -> str:
    """A fresh 32-bit hex span id (for spans that hand a context on)."""
    return os.urandom(4).hex()


def current_trace() -> Optional[Tuple[str, str]]:
    """The ``(trace_id, parent span_id)`` context active on this thread,
    or None."""
    return getattr(_tls, "trace", None)


def set_trace(ctx: Optional[Tuple[str, str]]) -> None:
    """Install ``ctx`` as this thread's trace context."""
    _tls.trace = ctx


@contextmanager
def trace(trace_id: Optional[str], span_id: str = ""):
    """Run a block under a trace context — spans recorded inside inherit
    ``trace_id``/``parent_id`` automatically; restores the previous
    context. A falsy ``trace_id`` clears the context for the block."""
    prev = current_trace()
    set_trace((trace_id, span_id) if trace_id else None)
    try:
        yield
    finally:
        set_trace(prev)


def trace_context_wire(
        ctx: Optional[Tuple[str, str]] = None) -> Optional[dict]:
    """The compact wire form ``{"tid", "sid"}`` of ``ctx`` (default:
    this thread's context), or None when absent/disabled. Callers attach
    it under the ``"trace"`` request key only when non-None, so peers
    that predate tracing never see the key."""
    if not trace_propagation_enabled():
        return None
    if ctx is None:
        ctx = current_trace()
    if not ctx or not ctx[0]:
        return None
    return {"tid": ctx[0], "sid": ctx[1] or ""}


def trace_context_from_wire(obj: Any) -> Optional[Tuple[str, str]]:
    """Parse an incoming ``"trace"`` wire key back into a context.
    Malformed shapes yield None — observability must never fail an
    RPC."""
    if not trace_propagation_enabled() or not isinstance(obj, dict):
        return None
    tid = obj.get("tid")
    if not isinstance(tid, str) or not tid:
        return None
    sid = obj.get("sid")
    return (tid, sid if isinstance(sid, str) else "")


# ---------------- span tracer ----------------

def _ring_capacity() -> int:
    try:
        return max(64, int(os.environ.get(
            "DMLC_TPU_TRACE_RING_SPANS", "8192") or 8192))
    except ValueError:
        return 8192


def _max_rings() -> int:
    try:
        return max(8, int(os.environ.get(
            "DMLC_TPU_TRACE_MAX_RINGS", "512") or 512))
    except ValueError:
        return 512


class _SpanRing:
    """One thread's fixed-size span buffer. Single writer (the owning
    thread), so ``record`` takes no lock; readers (export) see a racy but
    structurally safe snapshot — every retained entry is a complete tuple
    because the list-slot store is atomic under the GIL."""

    __slots__ = ("tid", "thread_name", "thread", "capacity", "entries",
                 "idx", "total", "counts")

    def __init__(self, tid: int, thread_name: str, capacity: int,
                 thread: Optional[threading.Thread] = None):
        self.tid = tid
        self.thread_name = thread_name
        self.thread = thread  # liveness probe for ring retirement
        self.capacity = capacity
        self.entries: List[Optional[tuple]] = [None] * capacity
        self.idx = 0
        self.total = 0
        self.counts: Dict[str, int] = {}

    def record(self, name: str, start_ns: int, dur_ns: int,
               pipeline: Optional[str], labels: Optional[dict],
               trace_id: Optional[str] = None,
               parent_id: Optional[str] = None,
               span_id: Optional[str] = None) -> None:
        self.entries[self.idx] = (name, start_ns, dur_ns, pipeline, labels,
                                  trace_id, parent_id, span_id)
        self.idx = (self.idx + 1) % self.capacity
        self.total += 1
        self.counts[name] = self.counts.get(name, 0) + 1

    def snapshot(self) -> List[tuple]:
        # oldest-first: the wrapped segment precedes the head segment
        if self.total < self.capacity:
            ent = self.entries[: self.idx]
        else:
            ent = self.entries[self.idx:] + self.entries[: self.idx]
        return [e for e in ent if e is not None]

    def clear(self) -> None:
        self.entries = [None] * self.capacity
        self.idx = 0
        self.total = 0
        self.counts = {}


_rings_lock = threading.Lock()
_rings: List[_SpanRing] = []
# retired dead-thread rings fold their books here so span_counts() /
# spans_dropped() stay monotonic after retirement
_retired_counts: Dict[str, int] = {}
_retired_dropped = 0


def _retire_dead_ring_locked() -> None:
    """Memory bound for thread churn: pipelines create producer/worker
    threads per epoch, and each thread that ever recorded a span owns a
    ring. Past ``DMLC_TPU_TRACE_MAX_RINGS`` rings, drop the oldest ring
    whose thread has exited — its retained spans leave the trace (counted
    as dropped) but its totals are preserved."""
    global _retired_dropped
    if len(_rings) < _max_rings():
        return
    for i, ring in enumerate(_rings):
        if ring.thread is not None and not ring.thread.is_alive():
            dead = _rings.pop(i)
            for name, n in dead.counts.items():
                _retired_counts[name] = _retired_counts.get(name, 0) + n
            _retired_dropped += dead.total
            return


def _my_ring() -> _SpanRing:
    ring = getattr(_tls, "ring", None)
    if ring is None:
        t = threading.current_thread()
        ring = _SpanRing(t.ident or 0, t.name, _ring_capacity(), thread=t)
        with _rings_lock:
            _retire_dead_ring_locked()
            _rings.append(ring)
        _tls.ring = ring
    return ring


def record_span(name: str, start_s: float, dur_s: float,
                trace_id: Optional[str] = None,
                parent_id: Optional[str] = None,
                span_id: Optional[str] = None, **labels) -> None:
    """Record one stage span. ``start_s`` is a ``get_time()`` monotonic
    timestamp, ``dur_s`` its measured duration — the SAME values the
    caller feeds its stage-seconds counter, so per-stage span sums always
    reconcile with the attribution. The active pipeline scope rides along
    automatically, and so does the active trace context: explicit
    ``trace_id``/``parent_id`` win, otherwise this thread's installed
    context (:func:`trace`) links the span into its distributed trace.
    ``span_id`` names THIS span so a downstream hop can parent under it."""
    if trace_id is None:
        ctx = current_trace()
        if ctx is not None:
            trace_id = ctx[0]
            if parent_id is None:
                parent_id = ctx[1] or None
    _my_ring().record(name, int(start_s * 1e9), int(dur_s * 1e9),
                      current_scope(), labels or None,
                      trace_id, parent_id, span_id)


_PROFILER_PREFIX = "dmlc_tpu:"


class span:
    """Measure a block as one stage span, on two clocks at once: the
    thread's ring (exactly what :func:`record_span` writes, on the
    ``get_time`` clock) and a ``jax.profiler.TraceAnnotation`` named
    ``dmlc_tpu:<name>`` (the profiler's clock, beside the device plane).
    The annotation is unconditional — a TraceMe is inert while no
    profiler session is live — and is skipped only in a process that
    never imported jax, where no session can exist.

    ``with span("convert", book=add_busy) as sp:`` — on exit ``sp.t0`` /
    ``sp.dt`` are the start and duration the ring got, and ``book(dt)``
    (when given) feeds the SAME duration to the caller's stage counter,
    so spans and ``DeviceIter.stats()`` keep telling one story. Inside
    the block, ``sp.exclude(seconds)`` takes a nested stage's time out of
    the recorded duration, ``sp.labels[...] = ...`` adds a label known only
    by then, and ``sp.skip_ring()`` keeps this span out of the ring (a
    supply wait that the source's own spans already cover; a pull that
    ends the epoch): the profiler's timeline and ``book`` still get it.
    ``trace_id`` / ``parent_id`` / ``span_id`` pass through to
    :func:`record_span`."""

    __slots__ = ("name", "book", "labels", "t0", "dt", "_excluded", "_ring",
                 "_ann")

    def __init__(self, name: str, book: Optional[Callable[[float], None]]
                 = None, **labels):
        self.name = name
        self.book = book
        self.labels = labels
        self.t0 = self.dt = self._excluded = 0.0
        self._ring = True
        self._ann = None

    def exclude(self, seconds: float) -> None:
        self._excluded += seconds

    def skip_ring(self) -> None:
        self._ring = False

    def __enter__(self) -> "span":
        profiler = sys.modules.get("jax.profiler")
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(_PROFILER_PREFIX + self.name)
            self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.dt = time.monotonic() - self.t0 - self._excluded
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._ring:
            record_span(self.name, self.t0, self.dt, **self.labels)
        if self.book is not None:
            self.book(self.dt)


def spans_snapshot(pipeline: Optional[str] = None) -> List[dict]:
    """Retained spans across all threads, oldest-first per thread, as
    dicts; optionally filtered to one pipeline label."""
    with _rings_lock:
        rings = list(_rings)
    out = []
    for entry in rings:
        for (name, start_ns, dur_ns, pipe, labels,
             trace_id, parent_id, span_id) in entry.snapshot():
            if pipeline is not None and pipe != pipeline:
                continue
            row = {"name": name, "tid": entry.tid,
                   "thread": entry.thread_name, "start_ns": start_ns,
                   "dur_ns": dur_ns, "pipeline": pipe,
                   "labels": labels or {}}
            # optional distributed-tracing fields (schema v2): present
            # only on spans that belong to a trace, so v1-era consumers
            # of the row shape keep working untouched
            if trace_id:
                row["trace_id"] = trace_id
            if parent_id:
                row["parent_id"] = parent_id
            if span_id:
                row["span_id"] = span_id
            out.append(row)
    out.sort(key=lambda s: s["start_ns"])
    return out


def span_counts() -> Dict[str, int]:
    """Spans RECORDED per name since process start (not just retained —
    neither ring overwrites nor dead-ring retirement lower these)."""
    with _rings_lock:
        rings = list(_rings)
        out = dict(_retired_counts)
    for ring in rings:
        for name, n in list(ring.counts.items()):
            out[name] = out.get(name, 0) + n
    return out


def spans_dropped() -> int:
    """Spans recorded but no longer exportable (ring overwrites + rings
    retired with their thread)."""
    with _rings_lock:
        return _retired_dropped + sum(
            max(0, r.total - r.capacity) for r in _rings)


def reset_spans() -> None:
    """Clear every ring (tests; production rings just wrap)."""
    global _retired_dropped
    with _rings_lock:
        for ring in _rings:
            ring.clear()
        _retired_counts.clear()
        _retired_dropped = 0


def export_chrome_trace(path: str, pipeline: Optional[str] = None) -> int:
    """Write the retained spans as Chrome-trace/Perfetto JSON (object
    form: ``{"traceEvents": [...]}``, complete-event ``ph: "X"``, ts/dur
    in microseconds): the one-peer case of :func:`export_pod_trace`, this
    process under the name ``dmlc_tpu``. Returns the number of events
    written. The file is written to ``<path>.tmp`` then atomically
    published."""
    return export_pod_trace(path, [{
        "peer": "dmlc_tpu", "schema": SCHEMA_VERSION,
        "spans": spans_snapshot(pipeline)}])


# ---------------- trace-mode knob ----------------

def trace_mode() -> Tuple[str, Optional[str]]:
    """Parse ``DMLC_TPU_TRACE`` (docs/data.md):

    - ``chrome:<path>`` -> ``('chrome', path)`` — dump the span rings as a
      Chrome trace to ``path`` when the pipeline closes
    - anything else (including unset / ``0`` / ``1``) -> ``('off', None)``

    Profiler annotations need no switch: every :class:`span` carries a
    ``dmlc_tpu:<name>`` TraceMe, which a live ``jax.profiler`` session
    records and nothing else pays for.
    """
    value = os.environ.get("DMLC_TPU_TRACE", "").strip()
    if value.startswith("chrome:"):
        return "chrome", value[len("chrome:"):]
    return "off", None


# ---------------- metrics registry ----------------

class _Metric:
    __slots__ = ("lock", "labels")

    def __init__(self, labels: Dict[str, str]):
        self.lock = threading.Lock()
        self.labels = labels


class Counter(_Metric):
    """Monotonic float counter (stage seconds use float increments)."""

    __slots__ = ("_value",)
    kind = "counter"

    def __init__(self, labels):
        super().__init__(labels)
        self._value = 0.0

    def inc(self, n: float = 1.0) -> None:
        with self.lock:
            self._value += n

    @property
    def value(self) -> float:
        with self.lock:
            return self._value


class Gauge(_Metric):
    __slots__ = ("_value",)
    kind = "gauge"

    def __init__(self, labels):
        super().__init__(labels)
        self._value = 0.0

    def set(self, v: float) -> None:
        with self.lock:
            self._value = float(v)

    def add(self, delta: float) -> None:
        """One read-modify-write under the lock: threads that count up
        and down lose no update."""
        with self.lock:
            self._value += float(delta)

    @property
    def value(self) -> float:
        with self.lock:
            return self._value


class Info(_Metric):
    """A structured JSON-able dict (e.g. the pipeline stall diagnostic):
    last write wins, read back verbatim."""

    __slots__ = ("_value",)
    kind = "info"

    def __init__(self, labels):
        super().__init__(labels)
        self._value: Optional[dict] = None

    def set(self, value: dict) -> None:
        with self.lock:
            self._value = dict(value)

    @property
    def value(self) -> Optional[dict]:
        with self.lock:
            return dict(self._value) if self._value is not None else None


def _metrics_max_pipelines() -> int:
    """``DMLC_TPU_METRICS_MAX_PIPELINES`` knob-table row: how many
    distinct per-pipeline label scopes the registry retains before
    retiring the least-recently-touched one (docs/observability.md)."""
    from dmlc_tpu.utils import knobs as _knobs
    return _knobs.resolve("metrics_max_pipelines")


class MetricsRegistry:
    """Named, labeled metrics. ``counter/gauge/info`` get or
    create the handle for an exact (name, labels) pair — handles are
    cheap to cache at call sites (StageMeter does) so the hot path is one
    small per-metric lock, never the registry lock.

    **Bounded pipeline scopes** — a service constructing fresh pipelines
    forever (each ``DeviceIter``/``ServiceParser`` scope stamps a
    process-unique ``pipeline`` label on ~a dozen metrics) must not grow
    the registry without bound. Past ``DMLC_TPU_METRICS_MAX_PIPELINES``
    distinct pipeline scopes, the least-recently-touched scope is
    retired: its counters fold into the ``pipeline=""``
    process-total bucket (so ``sum``/``sum_by`` over every other label
    are unchanged — the same books-preserved pattern as span-ring
    retirement), its gauges and info blobs (stale per-instance state)
    are dropped."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[tuple, _Metric] = {}
        # pipeline-scope LRU: label -> logical touch stamp (a metric
        # creation under that scope); retirement tally for the pod table
        self._pipeline_touch: Dict[str, int] = {}
        self._touch_seq = itertools.count(1)
        self._retired_pipelines = 0

    def _retire_pipeline_locked(self, pipeline: str) -> None:
        self._pipeline_touch.pop(pipeline, None)
        self._retired_pipelines += 1
        tag = ("pipeline", pipeline)
        victims = [k for k in self._metrics if tag in k[2]]
        for key in victims:
            old = self._metrics.pop(key)
            if not isinstance(old, Counter):
                continue  # gauges/info are per-instance state, not tallies
            kind, name, label_items = key
            folded = tuple(sorted((lk, "" if lk == "pipeline" else lv)
                                  for lk, lv in label_items))
            tgt_key = (kind, name, folded)
            tgt = self._metrics.get(tgt_key)
            if tgt is None:
                tgt = Counter(dict(folded))
                self._metrics[tgt_key] = tgt
            tgt.inc(old.value)

    def _touch_pipeline_locked(self, pipeline: str) -> None:
        self._pipeline_touch[pipeline] = next(self._touch_seq)
        if len(self._pipeline_touch) <= _metrics_max_pipelines():
            return
        oldest = min(self._pipeline_touch, key=self._pipeline_touch.get)
        if oldest != pipeline:
            self._retire_pipeline_locked(oldest)

    def retired_pipelines(self) -> int:
        """Pipeline scopes retired (folded into process totals) so far."""
        with self._lock:
            return self._retired_pipelines

    def _get(self, cls, name: str, labels: Dict[str, str]) -> _Metric:
        key = (cls.kind, name, tuple(sorted(labels.items())))
        m = self._metrics.get(key)
        if m is None:
            with self._lock:
                m = self._metrics.get(key)
                if m is None:
                    m = cls(dict(labels))
                    self._metrics[key] = m
                    p = labels.get("pipeline")
                    if p:
                        self._touch_pipeline_locked(p)
        return m

    def counter(self, name: str, **labels) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(Gauge, name, labels)

    def info(self, name: str, **labels) -> Info:
        return self._get(Info, name, labels)

    # -------- read side --------

    def _rows(self, name: Optional[str], kind: Optional[str],
              label_filter: Dict[str, str]) -> Iterable[Tuple[tuple, _Metric]]:
        with self._lock:
            items = list(self._metrics.items())
        for key, m in items:
            k, n, _ = key
            if name is not None and n != name:
                continue
            if kind is not None and k != kind:
                continue
            if any(m.labels.get(fk) != fv for fk, fv in label_filter.items()):
                continue
            yield key, m

    def snapshot(self, name: Optional[str] = None, kind: Optional[str] = None,
                 **label_filter) -> List[dict]:
        """Matching metrics as ``{"kind", "name", "labels", "value"}`` rows."""
        return [{"kind": key[0], "name": key[1], "labels": dict(m.labels),
                 "value": m.value}
                for key, m in self._rows(name, kind, label_filter)]

    def sum(self, name: str, **label_filter) -> float:
        """Total over matching counters/gauges."""
        return sum(m.value for _, m in self._rows(name, None, label_filter)
                   if isinstance(m, (Counter, Gauge)))

    def sum_by(self, name: str, by: str, **label_filter) -> Dict[str, float]:
        """Per-``by``-label totals over matching counters/gauges."""
        out: Dict[str, float] = {}
        for _, m in self._rows(name, None, label_filter):
            if isinstance(m, (Counter, Gauge)):
                k = m.labels.get(by, "")
                out[k] = out.get(k, 0.0) + m.value
        return out

    def clear(self, name: Optional[str] = None) -> None:
        """Drop matching metrics entirely (tests / counter reset)."""
        with self._lock:
            if name is None:
                self._metrics.clear()
                self._pipeline_touch.clear()
                self._retired_pipelines = 0
            else:
                self._metrics = {k: v for k, v in self._metrics.items()
                                 if k[1] != name}


REGISTRY = MetricsRegistry()


# ---------------- compilation counters ----------------

# what jax.monitoring reports of XLA compilations, as registry counters:
# an operator of a real job sees a step recompiling (a new shape, a cold
# persistent cache) in render_prometheus() / pod_snapshot()['compile']
JIT_COMPILATIONS_METRIC = "jit_compilations"
JIT_COMPILE_SECONDS_METRIC = "jit_compile_seconds"
COMPILE_CACHE_HITS_METRIC = "compile_cache_hits"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_compile_counters_armed = False


def _on_compile_duration(event: str, duration: float, **kwargs) -> None:
    if event != _COMPILE_EVENT:
        return
    fn = str(kwargs.get("fun_name", ""))
    REGISTRY.counter(JIT_COMPILATIONS_METRIC, fn=fn).inc(1)
    REGISTRY.counter(JIT_COMPILE_SECONDS_METRIC, fn=fn).inc(duration)


def _on_compile_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        REGISTRY.counter(COMPILE_CACHE_HITS_METRIC).inc(1)


def arm_compile_counters() -> None:
    """Register (once per process) the ``jax.monitoring`` listeners behind
    ``jit_compilations`` / ``jit_compile_seconds`` (one backend compile
    each, labeled ``fn`` by the jitted function's name; a persistent-cache
    hit counts too, with the seconds its retrieval took) and
    ``compile_cache_hits``. :meth:`TrainLoopMixin._jit_step` arms it, so
    every learner's job has the counters from its first step on."""
    global _compile_counters_armed
    with _rings_lock:
        if _compile_counters_armed:
            return
        _compile_counters_armed = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_compile_duration)
    monitoring.register_event_listener(_on_compile_event)


# which way the ELL table gather's backward built its dense gradient, one
# count per traced backward (never inside the step): route="kernel" is the
# one-hot MXU kernel of ops/grad_scatter.py, route="xla" XLA's scatter-add;
# collective= is what crossed the chips of a mesh for it: "none" (one
# chip), "owned_rows" (a table dealt by rows: the cotangent rows of the
# slots a chip owns, through the exchange) or "all_slots" (tables laid in
# ranges: every chip's cotangent rows, all-gathered)
GRAD_SCATTER_ROUTE_METRIC = "grad_scatter_route"


def grad_scatter_routes() -> Dict[str, int]:
    """Process totals of ``grad_scatter_route`` by route and, for the
    backwards traced under a mesh, by collective
    (``collective_owned_rows``, ``collective_all_slots``)."""
    totals = REGISTRY.sum_by(GRAD_SCATTER_ROUTE_METRIC, "route")
    totals.update(
        (f"collective_{k}", v) for k, v in REGISTRY.sum_by(
            GRAD_SCATTER_ROUTE_METRIC, "collective").items()
        if k and k != "none")
    return {k: int(v) for k, v in sorted(totals.items()) if k}


# which way the ELL table gather's forward read its rows, one count per
# traced forward (never inside the step): route="kernel" is the sorted-walk
# one-hot MXU kernel of ops/table_gather.py, route="xla" XLA's gather;
# width= the columns of the tables together
TABLE_GATHER_ROUTE_METRIC = "table_gather_route"


def table_gather_routes() -> Dict[str, int]:
    """Process totals of ``table_gather_route`` by route."""
    totals = REGISTRY.sum_by(TABLE_GATHER_ROUTE_METRIC, "route")
    return {k: int(v) for k, v in sorted(totals.items()) if k}


# how a kernel on the sorted walk laid its slot side, one count per traced
# kernel-route op (never inside the step; the XLA routes have no slot
# side): op="gather" (ops/table_gather.py), "scatter" (the gradient's or
# the update's kernel, ops/grad_scatter.py) or "pair_grads" (the cotangent
# rows the field-aware FM's backward hands the scatter, ops/ffm_pairs.py);
# layout="columns" is lane-major [R, Np], layout="lines" row-major [Np,
# 128] float32, which XLA's gather permutes as it is and the kernel
# transposes in VMEM (ops/sorted_walk.py:slot_layout: by the payload's
# width)
TABLE_SLOT_LAYOUT_METRIC = "table_slot_layout"


def _totals_by_labels(metric: str, key: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for row in REGISTRY.snapshot(metric):
        name = key.format(**row["labels"])
        out[name] = out.get(name, 0) + int(row["value"])
    return dict(sorted(out.items()))


def table_slot_layouts() -> Dict[str, int]:
    """Process totals of ``table_slot_layout`` as ``<op>_<layout>``."""
    return _totals_by_labels(TABLE_SLOT_LAYOUT_METRIC, "{op}_{layout}")


# the equal runs a kernel-route op cut a permute's indices into, so that
# the runs behind the last real slot are not gathered
# (ops/sorted_walk.py:permute_live), one count per traced op whose caller
# said which slots are real: op="gather" (the forward's rows back to batch
# order) or "update" (the cotangent rows to sorted order, for the
# gradient's or the update's kernel); groups= the runs, 16 where the slots
# divide. On a table dealt by rows the four permutes of the road a step
# takes when its slots fit the exchange count under ops of their own: a
# worker's "rows_home" and "to_owners" (ops/table_exchange.py; on XLA's
# route too), an owner's "owner_gather" and "owner_update" (the one-chip
# ops on the slots it received); on tables laid in ranges a chip's two count
# as "shard_gather" and "shard_update". An op that is not counted here
# permutes with one gather
TABLE_SLOT_GROUPS_METRIC = "table_slot_groups"


def count_table_slot_groups(op: str, groups: int) -> None:
    """One traced ``op`` whose permute goes in ``groups`` runs."""
    REGISTRY.counter(TABLE_SLOT_GROUPS_METRIC, op=op,
                     groups=str(groups)).inc(1)


def table_slot_groups() -> Dict[str, int]:
    """Process totals of ``table_slot_groups`` as ``<op>_<groups>``."""
    return _totals_by_labels(TABLE_SLOT_GROUPS_METRIC, "{op}_{groups}")


# what the update's kernel walks for the last batch a learner's step took
# (ops/sorted_walk.py:walk_books; what= slots, real_slots, chunks, pairs,
# tile_products, whole_block_tile_products, blocks_touched; on a table dealt
# by rows the mean over the chips, with the largest chip's as
# what="<name>_largest_chip"), set outside any step by whoever asks:
# ``learner.walk_books()``, benchmarks/bench_grad_scatter.py --fused
WALK_BOOKS_METRIC = "walk_books"
# what XLA's compile of the learner's step says it holds a chip
# (``compiled.memory_analysis()``: kind= temp, argument, output, alias),
# kept by ``learner.hlo_scopes()`` from the compile it makes anyway
STEP_MEMORY_METRIC = "step_memory_bytes"


def _set_gauges(metric: str, label: str, values: Dict[str, float]) -> None:
    REGISTRY.clear(metric)     # one reading: no key of an earlier one stays
    for key, value in values.items():
        REGISTRY.gauge(metric, **{label: key}).set(value)


def _gauges_by(metric: str, label: str) -> Dict[str, float]:
    return {row["labels"][label]: row["value"]
            for row in REGISTRY.snapshot(metric, kind="gauge")}


def set_walk_books(books: Dict[str, float]) -> None:
    """``walk_books{what=}`` from one count of a batch."""
    _set_gauges(WALK_BOOKS_METRIC, "what", books)


def walk_books() -> Dict[str, float]:
    """The last ``walk_books`` set in this process, by ``what``."""
    return _gauges_by(WALK_BOOKS_METRIC, "what")


def set_step_memory(sizes: Dict[str, int]) -> None:
    """``step_memory_bytes{kind=}`` from one compile of a step."""
    _set_gauges(STEP_MEMORY_METRIC, "kind", sizes)


def step_memory() -> Dict[str, float]:
    """The last ``step_memory_bytes`` set in this process, by ``kind``."""
    return _gauges_by(STEP_MEMORY_METRIC, "kind")


# how FMLearner's or FFMLearner's step updated its tables, one count per
# traced step (never inside the step): route="fused" is the gradient kernel
# finishing Adam (the FFM: AdaGrad) on every block of the tables in VMEM,
# with no dense gradient (ops/grad_scatter.py:fused_table_update);
# route="dense" is a dense gradient handed to optax, reason= says why
# (layout, optimizer, l2, scatter_xla, collective_table, dealt; "adam" /
# "adagrad" on the fused route)
TABLE_UPDATE_ROUTE_METRIC = "table_update_route"


def table_update_routes() -> Dict[str, int]:
    """Process totals of ``table_update_route`` by route."""
    totals = REGISTRY.sum_by(TABLE_UPDATE_ROUTE_METRIC, "route")
    return {k: int(v) for k, v in sorted(totals.items()) if k}


# which way a ragged (bcoo) batch's per-slot terms were summed over each
# row's slots, and a row's cotangent taken back to its slots
# (ops/slot_rows.py), one count per traced op (never inside the step):
# route="kernel" is the sorted-walk one-hot kernels over the batch's rows,
# route="xla" segment_sum / take; op= "sum" or "take"; width= the columns
SLOT_ROWS_ROUTE_METRIC = "slot_rows_route"


def slot_rows_routes() -> Dict[str, int]:
    """Process totals of ``slot_rows_route`` by route."""
    totals = REGISTRY.sum_by(SLOT_ROWS_ROUTE_METRIC, "route")
    return {k: int(v) for k, v in sorted(totals.items()) if k}


# cells the CSV parser scanned, by the cell dtype it was asked for
# (``?dtype=float32|int32|int64``: integer cells are scanned as integers by
# both engines and never cross a float), one increment a parsed chunk. With
# ``?hash_bins=`` the cells that were hashed to an id count under
# dtype="hashed" (the label's and the weight's under the dtype asked for),
# and those of them that had no bytes under ``csv_empty_cells`` as well
CSV_CELLS_METRIC = "csv_cells"
CSV_EMPTY_CELLS_METRIC = "csv_empty_cells"


def csv_cells() -> Dict[str, int]:
    """Process totals of ``csv_cells`` by cell dtype."""
    totals = REGISTRY.sum_by(CSV_CELLS_METRIC, "dtype")
    return {k: int(v) for k, v in sorted(totals.items()) if k}


def csv_empty_cells() -> int:
    """Process total of ``csv_empty_cells``: hashed cells with no bytes."""
    return int(REGISTRY.sum(CSV_EMPTY_CELLS_METRIC))


# which way FFMLearner's step took its pair terms (ops/ffm_pairs.py), one
# count per traced step or forward (never inside the step): route="kernel"
# is the pair tensor selected once a block in VMEM, forward and backward;
# route="xla" the plain jax.numpy form, reason= says why (backend, dtype,
# rows; "none" on the kernel route); fields= says where a slot's field came
# from: "plane" (an ELL batch's, the general kernels select on it) or
# "position" (layout="dense": slot t is field t, the positional kernels)
FFM_INTERACTION_ROUTE_METRIC = "ffm_interaction_route"


def ffm_interaction_routes() -> Dict[str, int]:
    """Process totals of ``ffm_interaction_route`` by route."""
    totals = REGISTRY.sum_by(FFM_INTERACTION_ROUTE_METRIC, "route")
    return {k: int(v) for k, v in sorted(totals.items()) if k}


# how a learner's step reached a table dealt by rows over a mesh axis
# (parallel/mesh.py:RowDeal), one count per traced step (never inside the
# step): shards= the chips the rows are dealt over, deal= the rule
# ("cyclic"; "ranges" with learner="fm": FMLearner's tables in id order, a
# contiguous range a chip), collective= what carries the rows
# ("owned_slots": every slot's id goes to the chip that owns it and its row
# comes back, one all-to-all each way with a capacity, the cotangent rows
# likewise; a step that does not fit all-gathers every slot instead:
# ops/table_exchange.py; "all_slots": the road with no buckets, every
# chip's slots all-gathered, always; "xla": the one-device step on the
# row-sharded operands, partitioned by XLA)
TABLE_SHARD_ROUTE_METRIC = "table_shard_route"
# the steps that did not fit, as a learner last read them off the device
# (FFMLearner.fallback_steps(); a gauge: the count lives in the learner's
# state, not here)
TABLE_SHARD_FALLBACK_METRIC = "table_shard_fallback_steps"


def table_shard_routes() -> Dict[str, int]:
    """Process totals of ``table_shard_route`` by collective, and under
    ``fallback_steps`` the last reading of ``table_shard_fallback_steps``
    where a learner has taken one."""
    totals = REGISTRY.sum_by(TABLE_SHARD_ROUTE_METRIC, "collective")
    out = {k: int(v) for k, v in sorted(totals.items()) if k}
    if REGISTRY.snapshot(TABLE_SHARD_FALLBACK_METRIC):
        out["fallback_steps"] = int(REGISTRY.sum(TABLE_SHARD_FALLBACK_METRIC))
    return out


# a learner's saves (models/_checkpoint.py; docs/checkpoint.md): saves by
# how they ended (result= ok | failed | refused: no room for the device
# copy), payload bytes published, seconds a save waited for the save
# before it (durability first: none is dropped), and the saves in flight
CKPT_SAVES_METRIC = "ckpt_saves"       # exposed as ckpt_saves_total
CKPT_BYTES_METRIC = "ckpt_bytes"       # exposed as ckpt_bytes_total
CKPT_WAIT_PREVIOUS_METRIC = "ckpt_wait_previous_seconds"
CKPT_IN_FLIGHT_METRIC = "ckpt_saves_in_flight"


def checkpoint_counters() -> Dict[str, Any]:
    """Process totals of the checkpoint counters and the gauge."""
    saves = REGISTRY.sum_by(CKPT_SAVES_METRIC, "result")
    return {
        "ckpt_saves_total": {k: int(v) for k, v in sorted(saves.items())
                             if k},
        "ckpt_bytes_total": int(REGISTRY.sum(CKPT_BYTES_METRIC)),
        CKPT_WAIT_PREVIOUS_METRIC: REGISTRY.sum(CKPT_WAIT_PREVIOUS_METRIC),
        CKPT_IN_FLIGHT_METRIC: int(REGISTRY.sum(CKPT_IN_FLIGHT_METRIC)),
    }


def compile_counters() -> Dict[str, float]:
    """Process totals of the three compilation counters."""
    return {
        JIT_COMPILATIONS_METRIC: int(REGISTRY.sum(JIT_COMPILATIONS_METRIC)),
        JIT_COMPILE_SECONDS_METRIC: REGISTRY.sum(JIT_COMPILE_SECONDS_METRIC),
        COMPILE_CACHE_HITS_METRIC: int(
            REGISTRY.sum(COMPILE_CACHE_HITS_METRIC)),
    }


# ---------------- control-decision audit ledger ----------------

# retained decision events per process: the ledger is a bounded ring
# (old events drop, the DECISION_METRIC counters stay monotonic), sized
# for "why did the fleet do that" forensics, not for history
DECISION_HISTORY_LIMIT = 256

_decisions_lock = threading.Lock()
_decisions: List[dict] = []
_decisions_total = 0


def record_decision(component: str, action: str,
                    trigger: Optional[dict] = None,
                    outcome: Optional[Any] = None, **extra) -> dict:
    """Append one structured control-decision event to the audit ledger
    (docs/observability.md Decision ledger). One shape for every
    controller: ``component`` (autotune / autoscaler / dispatcher /
    store / worker), ``action`` (grow, drain, evict, hedge, throttle,
    ...), ``trigger`` (the metric deltas that fired it), ``outcome``
    (what changed). The event also bumps the ``decision_events``
    registry counter and inherits the active trace context so a
    decision shows up inside the trace it affected. Returns the event
    dict — fleet components journal exactly this via the dispatcher
    append-journal."""
    global _decisions_total
    event: Dict[str, Any] = {
        "ts": round(time.monotonic(), 6),
        "component": str(component),
        "action": str(action),
    }
    if trigger:
        event["trigger"] = dict(trigger)
    if outcome is not None:
        event["outcome"] = outcome
    ctx = current_trace()
    if ctx and ctx[0]:
        event["trace_id"] = ctx[0]
    for k, v in extra.items():
        if v is not None:
            event[k] = v
    with _decisions_lock:
        _decisions.append(event)
        _decisions_total += 1
        if len(_decisions) > DECISION_HISTORY_LIMIT:
            del _decisions[: len(_decisions) - DECISION_HISTORY_LIMIT]
    REGISTRY.counter(DECISION_METRIC, component=str(component),
                     action=str(action)).inc()
    return event


def decisions_snapshot(component: Optional[str] = None) -> List[dict]:
    """Retained decision events, oldest-first, optionally filtered to
    one component. Dicts are copies — callers may annotate freely."""
    with _decisions_lock:
        events = list(_decisions)
    return [dict(e) for e in events
            if component is None or e.get("component") == component]


def decisions_total() -> int:
    """Decisions RECORDED since process start (ring drops don't lower
    this)."""
    with _decisions_lock:
        return _decisions_total


def decision_counts() -> Dict[str, int]:
    """``component.action`` -> count since process start, from the
    registry shadow counter (monotonic across ring drops) — what
    ``pod_snapshot()['decisions']`` ships to the tracker."""
    out: Dict[str, int] = {}
    for row in REGISTRY.snapshot(DECISION_METRIC, "counter"):
        labels = row["labels"]
        key = f"{labels.get('component', '?')}.{labels.get('action', '?')}"
        out[key] = out.get(key, 0) + int(round(row["value"]))
    return out


def reset_decisions() -> None:
    """Clear the ledger (tests; production rings just wrap)."""
    global _decisions_total
    with _decisions_lock:
        _decisions.clear()
        _decisions_total = 0
    REGISTRY.clear(DECISION_METRIC)


# ---------------- bounded metrics time-series ring ----------------

def _metrics_history_limit() -> int:
    """``DMLC_TPU_METRICS_HISTORY`` knob-table row: samples retained in
    the bounded time-series ring behind the gauges."""
    from dmlc_tpu.utils import knobs as _knobs
    return _knobs.resolve("metrics_history")


_history_lock = threading.Lock()
_history: List[dict] = []


def sample_metrics_history(now: Optional[float] = None) -> dict:
    """Capture one bounded time-series sample of the hot fleet gauges —
    per-job input wait, wire bytes, store bytes, decision count — so
    post-hoc questions like "what did input_wait look like when the
    autoscaler grew" are answerable from the ring alone. The fleet
    autoscaler samples once per control tick; anything else may call it
    too (the ring just wraps)."""
    sample = {
        "ts": round(time.monotonic() if now is None else now, 6),
        "input_wait_seconds": round(REGISTRY.sum(INPUT_WAIT_METRIC), 4),
        "job_wait_seconds": {
            j: round(v, 4) for j, v in
            REGISTRY.sum_by(SERVICE_JOB_WAIT_METRIC, "job").items() if j},
        "wire_bytes_raw": int(REGISTRY.sum(SERVICE_WIRE_RAW_METRIC)),
        "wire_bytes_sent": int(REGISTRY.sum(SERVICE_WIRE_SENT_METRIC)),
        "store_bytes": int(REGISTRY.sum(STORE_BYTES_METRIC)),
        "decisions": decisions_total(),
    }
    limit = _metrics_history_limit()
    with _history_lock:
        _history.append(sample)
        if len(_history) > limit:
            del _history[: len(_history) - limit]
    return dict(sample)


def metrics_history() -> List[dict]:
    """The retained time-series samples, oldest-first (copies)."""
    with _history_lock:
        return [dict(s) for s in _history]


def reset_metrics_history() -> None:
    """Clear the ring (tests)."""
    with _history_lock:
        _history.clear()


# ---------------- Prometheus text-format exposition ----------------

_PROM_PREFIX = "dmlc_tpu_"


def _prom_name(name: str) -> str:
    out = []
    for ch in name:
        out.append(ch if (ch.isalnum() and ch.isascii()) or ch in "_:"
                   else "_")
    base = "".join(out)
    if base and base[0].isdigit():
        base = "_" + base
    return _PROM_PREFIX + base


def _prom_labels(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    parts = []
    for k in sorted(labels):
        v = str(labels[k]).replace("\\", "\\\\").replace("\n", "\\n") \
            .replace('"', '\\"')
        parts.append(f'{k}="{v}"')
    return "{" + ",".join(parts) + "}"


def render_prometheus(rows: Optional[List[dict]] = None) -> str:
    """Render registry snapshot rows as Prometheus text exposition
    format (docs/observability.md Prometheus exposition). Stable naming
    contract: every metric is prefixed ``dmlc_tpu_``, counters gain the
    conventional ``_total`` suffix, info blobs (structured JSON, not
    numeric) are skipped.
    Output is deterministically sorted; the ``metrics_text`` RPC on
    dispatcher and workers serves exactly this."""
    if rows is None:
        rows = REGISTRY.snapshot()
    typed: Dict[str, str] = {}
    samples: List[Tuple[str, str, float]] = []
    for row in rows:
        kind = row["kind"]
        if kind == "info":
            continue
        name = _prom_name(row["name"])
        labels = {k: v for k, v in (row["labels"] or {}).items()
                  if v not in (None, "")}
        if kind == "counter":
            typed.setdefault(name + "_total", "counter")
            samples.append((name + "_total", _prom_labels(labels),
                            float(row["value"])))
        elif kind == "gauge":
            typed.setdefault(name, "gauge")
            samples.append((name, _prom_labels(labels),
                            float(row["value"])))
    lines: List[str] = []
    last_name = None
    for name, label_str, value in sorted(samples):
        if name != last_name:
            lines.append(f"# TYPE {name} {typed[name]}")
            last_name = name
        try:
            text = str(int(value)) if value == int(value) else repr(value)
        except (OverflowError, ValueError):  # inf / nan
            text = repr(value)
        lines.append(f"{name}{label_str} {text}")
    return "\n".join(lines) + ("\n" if lines else "")


def parse_prometheus_text(text: str) -> List[Tuple[str, Dict[str, str],
                                                   float]]:
    """Minimal Prometheus text-format parser — the round-trip check
    behind the exposition tests. Returns
    ``(name, labels, value)`` samples; raises ValueError on any
    malformed sample line."""
    import re

    sample_re = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?\s+(\S+)$')
    label_re = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
    out: List[Tuple[str, Dict[str, str], float]] = []
    for raw in text.splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        m = sample_re.match(line)
        if m is None:
            raise ValueError(f"malformed exposition line: {raw!r}")
        name, _, label_blob, value_text = m.groups()
        labels: Dict[str, str] = {}
        if label_blob:
            pos = 0
            while pos < len(label_blob):
                lm = label_re.match(label_blob, pos)
                if lm is None:
                    raise ValueError(f"malformed labels: {raw!r}")
                labels[lm.group(1)] = (lm.group(2)
                                       .replace('\\"', '"')
                                       .replace("\\n", "\n")
                                       .replace("\\\\", "\\"))
                pos = lm.end()
                if pos < len(label_blob):
                    if label_blob[pos] != ",":
                        raise ValueError(f"malformed labels: {raw!r}")
                    pos += 1
        try:
            value = float(value_text)
        except ValueError:
            raise ValueError(f"malformed sample value: {raw!r}") from None
        out.append((name, labels, value))
    return out


# ---------------- pod-scale aggregation ----------------

def pod_snapshot() -> dict:
    """This process's registry as a compact JSON-able snapshot — what a
    worker ships to the tracker over the heartbeat path. Stage seconds and
    resilience events are summed ACROSS pipeline labels (the tracker's
    unit of balance is the host, not the pipeline instance)."""
    stages = REGISTRY.sum_by(STAGE_BUSY_METRIC, "stage")
    # 'transfer' lives on the wall meter only (it is a sampled consumer-
    # side probe, not a pipeline-thread busy counter) — merge it in so a
    # transfer-bound rank is visible in the pod table
    transfer = REGISTRY.sum_by(STAGE_WALL_METRIC, "stage").get("transfer")
    if transfer:
        stages["transfer"] = stages.get("transfer", 0.0) + transfer
    events = REGISTRY.sum_by(RESILIENCE_METRIC, "event")
    # per-job data-service breakdown (docs/service.md multi-tenant
    # service): client-side input wait + worker-side parts served,
    # keyed by job — the autoscaler's fleet-wide signal is the sum of
    # these across ranks (additive key; schema stays v1 because old
    # readers ignore it and every v1 field is unchanged)
    job_waits = REGISTRY.sum_by(SERVICE_JOB_WAIT_METRIC, "job")
    job_parts = REGISTRY.sum_by(SERVICE_JOB_PARTS_METRIC, "job")
    jobs = {j: {"input_wait_seconds": round(job_waits.get(j, 0.0), 4),
                "parts": int(round(job_parts.get(j, 0)))}
            for j in sorted(set(job_waits) | set(job_parts)) if j}
    # SLO targets ride beside the wait they bound (docs/service.md
    # Production QoS) — a gauge, identical across a job's ranks, so the
    # pod table can show wait-vs-target per job at a glance
    for j, slo in REGISTRY.sum_by(SERVICE_JOB_SLO_METRIC, "job").items():
        if j and slo and j in jobs:
            jobs[j]["slo_wait_frac"] = round(slo, 4)
    return {
        "telemetry_schema_version": SCHEMA_VERSION,
        "stages": {k: round(v, 4) for k, v in stages.items() if k},
        "resilience": {k: int(round(v)) for k, v in events.items() if k},
        "jobs": jobs,
        # tiered artifact store (docs/store.md): this host's live bytes
        # under management + its eviction/rebuild tallies, so the pod
        # table shows which rank's disk the budget is squeezing
        "store": {
            "store_bytes": int(REGISTRY.sum(STORE_BYTES_METRIC)),
            "store_evictions": int(round(
                events.get("store_evictions", 0))),
            "store_rebuilds_after_eviction": int(round(
                events.get("store_rebuilds_after_eviction", 0))),
        },
        "spans": span_counts(),
        "spans_dropped": spans_dropped(),
        # XLA compilations this process paid for (additive key)
        "compile": {k: round(v, 4) for k, v in compile_counters().items()},
        # traced ELL backwards by the route their gradient scatter took
        # and by what crossed the mesh for it
        "grad_scatter_routes": grad_scatter_routes(),
        # traced ELL forwards by the route their table gather took
        "table_gather_routes": table_gather_routes(),
        # traced kernel-route ops by how their slot side was laid
        "table_slot_layouts": table_slot_layouts(),
        # traced kernel-route ops that permute run by run, by their runs
        "table_slot_groups": table_slot_groups(),
        # what the update's kernel walked for the last batch counted
        "walk_books": walk_books(),
        # what the compile of the last step asked about holds a chip
        "step_memory_bytes": step_memory(),
        # traced FMLearner steps by how they updated the tables
        "table_update_routes": table_update_routes(),
        # traced steps on a table dealt by rows, by what carried the rows
        "table_shard_routes": table_shard_routes(),
        # traced FFMLearner steps and forwards by their pair terms' route
        "ffm_interaction_routes": ffm_interaction_routes(),
        # traced row sums / takes of ragged (bcoo) batches by their route
        "slot_rows_routes": slot_rows_routes(),
        # cells the CSV parser scanned, by the cell dtype asked for
        "csv_cells": csv_cells(),
        # of the hashed ones, those that had no bytes
        "csv_empty_cells": csv_empty_cells(),
        # control-decision ledger summary (schema v2): component.action
        # tallies, so the pod table shows every rank's control activity
        # next to the stage seconds it acted on
        "decisions": decision_counts(),
    }


def _format_jobs_cell(jobs: dict) -> str:
    """One rank's per-job breakdown cell: ``job=wait<seconds>s/parts<n>``
    per job (docs/observability.md per-job pod-table rows)."""
    cells = []
    for j in sorted(jobs):
        rec = jobs[j] or {}
        cell = (f"{j}=wait{float(rec.get('input_wait_seconds', 0.0)):.3f}s"
                f"/parts{int(rec.get('parts', 0))}")
        if rec.get("slo_wait_frac"):
            # the job's input-wait SLO target next to its wait — the
            # at-a-glance "is the autoscaler holding the contract" cell
            cell += f"/slo{float(rec['slo_wait_frac']):.2f}"
        cells.append(cell)
    return " ".join(cells) if cells else "-"


def format_pod_table(by_rank: Dict[int, dict]) -> str:
    """Merged per-rank × per-stage seconds table from worker snapshots
    (what the tracker logs), with a trailing per-job breakdown column
    (job-labeled input wait + parts served — the fleet autoscaler's
    operator-visible input signal). Ranks whose snapshot carries a
    different schema version are listed but not merged."""
    stage_cols = list(STAGES)
    extras = sorted({s for snap in by_rank.values()
                     for s in (snap.get("stages") or {})
                     if s not in STAGES})
    stage_cols += extras
    width = max([5] + [len(s) for s in stage_cols])
    header = "rank  " + "  ".join(f"{s:>{width}}" for s in stage_cols) \
        + "  resilience  jobs  decisions"
    lines = [header]
    totals = {s: 0.0 for s in stage_cols}
    job_totals: Dict[str, Dict[str, float]] = {}
    decision_totals: Dict[str, int] = {}
    for rank in sorted(by_rank):
        snap = by_rank[rank] or {}
        if snap.get("telemetry_schema_version") != SCHEMA_VERSION:
            lines.append(f"{rank:>4}  [schema "
                         f"{snap.get('telemetry_schema_version')!r} != "
                         f"{SCHEMA_VERSION}: not merged]")
            continue
        stages = snap.get("stages") or {}
        cells = []
        for s in stage_cols:
            v = float(stages.get(s, 0.0))
            totals[s] += v
            cells.append(f"{v:>{width}.3f}")
        res = snap.get("resilience") or {}
        hot = {k: v for k, v in sorted(res.items()) if v}
        # store_evictions/rebuilds already ride the resilience dict;
        # surface the rank's live store bytes next to them when nonzero
        store_bytes = (snap.get("store") or {}).get("store_bytes")
        if store_bytes:
            hot["store_bytes"] = int(store_bytes)
        jobs = snap.get("jobs") or {}
        for j, rec in jobs.items():
            tot = job_totals.setdefault(j, {"input_wait_seconds": 0.0,
                                            "parts": 0})
            tot["input_wait_seconds"] += float(
                (rec or {}).get("input_wait_seconds", 0.0))
            tot["parts"] += int((rec or {}).get("parts", 0))
            slo = (rec or {}).get("slo_wait_frac")
            if slo:
                # a target, not a tally: identical across ranks, so the
                # sum row carries it through max, never addition
                tot["slo_wait_frac"] = max(float(slo),
                                           float(tot.get("slo_wait_frac",
                                                         0.0)))
        # control-decision tallies (schema v2): every autoscale / evict /
        # hedge / throttle this rank performed, as component.action:n
        decisions = snap.get("decisions") or {}
        for d, n in decisions.items():
            decision_totals[d] = decision_totals.get(d, 0) + int(n)
        dec_cell = " ".join(f"{d}:{int(n)}" for d, n in
                            sorted(decisions.items()) if n) or "-"
        lines.append(f"{rank:>4}  " + "  ".join(cells)
                     + f"  {hot if hot else '-'}"
                     + f"  {_format_jobs_cell(jobs)}"
                     + f"  {dec_cell}")
    lines.append("-" * len(header))
    lines.append(" sum  " + "  ".join(
        f"{totals[s]:>{width}.3f}" for s in stage_cols)
        + (f"  jobs: {_format_jobs_cell(job_totals)}"
           if job_totals else "")
        + ("  decisions: " + " ".join(
            f"{d}:{n}" for d, n in sorted(decision_totals.items()) if n)
           if decision_totals else ""))
    return "\n".join(lines)


def component_snapshot(role: str, rings: bool = True) -> dict:
    """Everything ONE component ships for a merged pod timeline — the
    ``trace_dump`` RPC reply body on dispatcher and workers, and what
    ``LocalFleet.dump_trace`` collects locally. ``now`` is this
    process's monotonic clock at snapshot time: the puller pairs it with
    its own RPC request/reply midpoint to estimate the peer's clock
    offset (docs/observability.md Distributed tracing).
    ``process_cpu_seconds`` is this process's ``time.process_time()``:
    what the component has cost in cores, every thread of it.
    ``rings=False`` (the request's ``"spans": false``) leaves the span
    rings and the decision ledger empty: the reply a caller wants who
    reads the clock and the CPU seconds many times a run."""
    return {"peer": str(role), "pid": os.getpid(),
            "schema": SCHEMA_VERSION, "now": round(time.monotonic(), 6),
            "process_cpu_seconds": round(time.process_time(), 6),
            "spans": spans_snapshot() if rings else [],
            "decisions": decisions_snapshot() if rings else []}


def export_pod_trace(path: str, peers: List[dict]) -> int:
    """Merge per-peer span + decision snapshots into ONE Chrome-trace/
    Perfetto JSON — the fleet-wide timeline (docs/observability.md
    Distributed tracing). Each peer dict carries:

    - ``peer``: display name (``dispatcher``, ``worker-0``, ``client``,
      ``rank-3``...) — becomes the Perfetto process name, so pid = role
    - ``schema``: the peer's ``telemetry_schema_version``
    - ``clock_offset_s``: seconds to ADD to the peer's timestamps to
      land them on the caller's clock (estimated from RPC request/reply
      midpoints — see ``LocalFleet.dump_trace``); 0.0 for local spans
    - ``spans``: :func:`spans_snapshot` rows
    - ``decisions``: :func:`decisions_snapshot` events, rendered as
      instant events on the peer's timeline

    A peer at a DIFFERENT schema version is listed, never merged: its
    process shows up with one explicit ``schema-mismatch`` annotation
    instant event and none of its spans — the same refuse-to-merge
    contract as :func:`format_pod_table`, so a mixed-version fleet
    degrades loudly instead of rendering garbage. Returns the number of
    span events written; the file is written to ``<path>.tmp`` then
    atomically published."""
    events: List[dict] = []
    written = 0
    skipped_peers: List[str] = []
    for pid, peer in enumerate(peers, start=1):
        name = str(peer.get("peer") or f"peer-{pid}")
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": name}})
        schema = peer.get("schema")
        offset_us = float(peer.get("clock_offset_s") or 0.0) * 1e6
        if schema != SCHEMA_VERSION:
            # listed, not merged: one loud annotation, zero spans
            skipped_peers.append(name)
            events.append({
                "name": "schema-mismatch", "cat": "dmlc_tpu", "ph": "i",
                "pid": pid, "tid": 0, "ts": 0.0, "s": "p",
                "args": {"schema": schema, "expected": SCHEMA_VERSION,
                         "note": "peer listed, spans not merged"},
            })
            continue
        threads_named = set()
        for s in peer.get("spans") or []:
            tid = s.get("tid", 0)
            if tid not in threads_named:
                threads_named.add(tid)
                events.append({"name": "thread_name", "ph": "M",
                               "pid": pid, "tid": tid,
                               "args": {"name": s.get("thread", "")}})
            args = dict(s.get("labels") or {})
            if s.get("pipeline"):
                args["pipeline"] = s["pipeline"]
            for k in ("trace_id", "parent_id", "span_id"):
                if s.get(k):
                    args[k] = s[k]
            events.append({
                "name": s["name"], "cat": "dmlc_tpu", "ph": "X",
                "pid": pid, "tid": tid,
                "ts": s["start_ns"] / 1e3 + offset_us,
                "dur": s["dur_ns"] / 1e3,
                "args": args,
            })
            written += 1
        for d in peer.get("decisions") or []:
            events.append({
                "name": f"{d.get('component', '?')}.{d.get('action', '?')}",
                "cat": "dmlc_tpu_decision", "ph": "i", "pid": pid,
                "tid": 0, "ts": float(d.get("ts", 0.0)) * 1e6 + offset_us,
                "s": "p", "args": dict(d),
            })
    doc = {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "telemetry_schema_version": SCHEMA_VERSION,
            # of THIS process's rings (a peer's own drops stay with it)
            "spans_dropped": spans_dropped(),
            "peers": [str(p.get("peer") or "") for p in peers],
            "peers_not_merged": skipped_peers,
        },
    }
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return written


# ---------------- thread-scope inheritance helper ----------------

def scoped_target(fn: Callable[..., Any],
                  label: Optional[str] = None) -> Callable[..., Any]:
    """Wrap a thread target so it runs under ``label`` (default: the scope
    active where THIS call happens — i.e. the creator's scope). The
    pipeline thread primitives use this so spans/metrics recorded on their
    workers land under the right pipeline."""
    if label is None:
        label = current_scope()

    def run(*args, **kwargs):
        set_scope(label)
        return fn(*args, **kwargs)

    return run

"""Unified fault-tolerance layer for the I/O stack.

The reference dmlc-core hard-codes a 3x-per-part retry in its S3 writer
(s3_filesys.cc:789) and nothing else; this rebuild inherited that unevenly
(two ad-hoc fixed-retry loops, three filesystems with none), so one
transient 5xx mid-epoch killed the whole ``DeviceIter`` pipeline. Input
fault tolerance is a first-class property of a data plane that serves long
TPU runs (tf.data service, arXiv:2210.14826), so it lives HERE, once:

- :func:`classify` — the single error classifier: transient faults
  (5xx/429/408, connection reset, timeout, DNS/unreachable) are
  ``retryable``; everything else (4xx auth, malformed URI, logic errors)
  is ``fatal`` and must surface in one attempt. Walks ``__cause__`` so a
  wrapped DMLCError keeps its cause's class.
- :class:`RetryPolicy` — exponential backoff with FULL jitter (seedable),
  per-attempt timeout, overall deadline, and an ``Retry-After`` floor.
  Every retry loop in the package delegates here; ``make lint-retry``
  fails ad-hoc ``time.sleep``-in-retry-loop patterns anywhere else.
- :class:`ResilientStream` — resumable reads over any reopenable seekable
  stream: a mid-read transient fault reopens the source and resumes at
  the current byte offset (the Range/seek machinery the remote streams
  already have), consuming retry budget instead of failing the epoch.
- module counters (:func:`counters_snapshot`) — retry / resume / giveup
  totals, surfaced by ``DeviceIter.stats()['resilience']`` next to the
  stage attribution. The books live on the
  telemetry metrics registry (:mod:`dmlc_tpu.utils.telemetry`), with every
  event stamped by the recording thread's pipeline scope — so per-pipeline
  slices (``counters_snapshot(pipeline=...)``) stay disjoint between
  concurrent pipelines while the process-wide API stays byte-compatible.
  New events go through :func:`record_event` (``make lint-metrics`` bans
  direct counter mutation elsewhere). See docs/observability.md.

Deterministic fault injection for all of this lives in
:mod:`dmlc_tpu.io.faults`; every guarded attempt calls
``faults.maybe_fail`` so tier-1 tests exercise each retry/resume/give-up
path without a network. See docs/resilience.md.
"""

from __future__ import annotations

import http.client
import io as _pyio
import os
import random
import time
import urllib.error
from typing import Callable, Dict, Optional

from dmlc_tpu.io import faults
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import CacheCorruptionError, DMLCError
from dmlc_tpu.utils.timer import get_time

RETRYABLE = "retryable"
FATAL = "fatal"

# HTTP statuses that heal with retry: server-side faults, throttling, and
# request timeout. Everything else 4xx (auth, malformed request, not found)
# is deterministic — retrying it only burns budget and hides the bug.
_RETRYABLE_HTTP = frozenset({408, 429, 500, 502, 503, 504})


def classify(exc: BaseException) -> str:
    """``retryable`` or ``fatal`` for an I/O-stack exception.

    Follows the ``__cause__`` chain so a ``DMLCError`` raised ``from`` a
    transient urllib error stays retryable through wrapper layers (the
    stream-level giveup wraps, the pipeline level still wants the class).
    """
    import ssl

    seen = 0
    while exc is not None and seen < 8:
        if isinstance(exc, CacheCorruptionError):
            # cache faults heal: drop the bad cache, re-read/re-parse the
            # source, rewrite — retryable by construction (the retry IS
            # the rebuild), never a fatal structural error
            return RETRYABLE
        # HTTPError subclasses URLError and OSError: check it first
        if isinstance(exc, urllib.error.HTTPError):
            return (RETRYABLE if exc.code in _RETRYABLE_HTTP
                    or exc.code >= 500 else FATAL)
        if isinstance(exc, urllib.error.URLError):
            # urllib wraps transport failures as URLError(reason) where
            # reason is usually an OSError — gaierror for DNS, EHOSTUNREACH
            # / ECONNREFUSED for routing. All transient at this layer; the
            # one deterministic member is a certificate-verification
            # failure (retrying it only re-fails the handshake).
            if isinstance(exc.reason, ssl.SSLCertVerificationError):
                return FATAL
            return RETRYABLE
        if isinstance(exc, (ConnectionError, TimeoutError)):
            return RETRYABLE  # reset/aborted/refused, socket.timeout
        if isinstance(exc, http.client.HTTPException):
            return RETRYABLE  # IncompleteRead, BadStatusLine, ...
        if isinstance(exc, (DMLCError, OSError)) and exc.__cause__ is not None:
            exc = exc.__cause__
            seen += 1
            continue
        return FATAL
    return FATAL


def retry_after_seconds(exc: BaseException) -> float:
    """Backoff floor from a ``Retry-After`` response header, if any.

    Honors the delta-seconds form (the common throttling shape); an
    HTTP-date or garbage value is ignored rather than parsed — the jittered
    backoff still applies, the floor is just 0.
    """
    seen = 0
    while exc is not None and seen < 8:
        headers = getattr(exc, "headers", None)
        if headers is not None:
            try:
                value = headers.get("Retry-After")
            except AttributeError:
                value = None
            if value is not None:
                try:
                    return max(0.0, float(value))
                except (TypeError, ValueError):
                    return 0.0
        exc = exc.__cause__
        seen += 1
    return 0.0


# ---------------- counters ----------------
#
# Since the telemetry PR the books live in the metrics registry
# (dmlc_tpu.utils.telemetry.REGISTRY): every event is ONE registry
# counter under RESILIENCE_METRIC, labeled with the event key and the
# pipeline scope active on the recording thread. The public
# counters_snapshot / counters_delta / reset_counters API is
# byte-compatible (process-wide totals, same keys); the new
# ``pipeline=`` filter is what lets two concurrent DeviceIters keep
# disjoint books (docs/observability.md).

def record_event(key: str, n: int = 1) -> None:
    """Count one resilience event — the ONE sanctioned bump path
    (``make lint-metrics`` fails direct counter mutation elsewhere). The
    active pipeline scope is stamped on automatically, so the event shows
    up both process-wide and under its pipeline's label."""
    _telemetry.REGISTRY.counter(
        _telemetry.RESILIENCE_METRIC, event=key,
        pipeline=_telemetry.current_scope() or "").inc(n)


class _Counters:
    """Resilience event counters (registry facade, thread-safe).

    ``attempts``  guarded attempts issued
    ``retries``   failed attempts that were retried
    ``resumes``   of those, mid-stream reopen-at-offset events
    ``giveups``   operations abandoned with retry budget exhausted
    ``fatal``     operations failed on a non-retryable class (one attempt)
    ``producer_restarts`` / ``producer_giveups``
                  bounded producer restarts in ThreadedIter/OrderedWorkerPool
    ``parse_restarts`` / ``parse_giveups``
                  bounded chunk-source restarts inside the data-parallel
                  parse fan-out (ParallelTextParser's OrderedWorkerPool,
                  which labels its restart counters ``parse``)
    ``cache_corruptions``
                  cache integrity-check failures (CRC mismatch / torn
                  frame) detected while serving a warm cache
    ``cache_invalidations``
                  stale caches dropped at open time (signature mismatch,
                  unreadable/legacy format) — rebuilt from source
    ``cache_rebuilds``
                  healing rebuilds triggered by a mid-stream corruption:
                  the bad cache was dropped, the source re-read/re-parsed,
                  and a fresh cache rewritten
    ``service_retries``
                  data-service client streams interrupted (connection
                  loss, torn frame, worker error) and re-requested at the
                  exact block index
    ``service_failovers``
                  of those, resumes that landed on a DIFFERENT worker
                  after the dispatcher re-issued the dead worker's split
    ``service_giveups``
                  service streams abandoned with the failure budget
                  exhausted (no live worker took the part)
    ``dispatcher_restarts``
                  data-service control-plane restarts a client observed
                  (the dispatcher's generation token advanced mid-run)
    ``worker_reregistrations``
                  parse workers re-attaching to a restarted/recovered
                  dispatcher (generation change or declared-dead zombie)
    ``parts_reclaimed``
                  fully-parsed parts a restarted dispatcher adopted from
                  worker frame stores instead of re-issuing for re-parse
    ``control_plane_retries``
                  dispatcher round trips (register / locate / next_split
                  / reclaim ...) that failed transiently and were
                  retried under the shared policy
    ``worker_drains``
                  graceful worker drains begun (SIGTERM, preemption
                  notice, or operator drain): the dispatcher stopped
                  granting, re-issued the worker's unstarted parts, and
                  the worker served out its frame-store-complete parts
    ``drain_handoffs``
                  parts a client finished streaming off a draining
                  worker gracefully (drain END / moved-hint failover) —
                  handoffs, not socket-timeout failovers
    ``preemption_notices``
                  preemption signals workers observed
                  (``DMLC_TPU_PREEMPTION_NOTICE`` file/env, or the
                  ``preempt`` fault-plan op) — each triggers a drain
    ``speculative_reissues``
                  straggler parts the dispatcher speculatively re-issued
                  to a second worker (stuck past
                  ``DMLC_TPU_HEDGE_FACTOR`` x the fleet median)
    ``speculative_wins``
                  of those, races the speculative worker won
                  (first-complete-wins; the stuck primary's later
                  completion is deduped)
    ``worker_joins``
                  brand-new workers that joined a LIVE fleet mid-epoch
                  (registered after work had already been granted)
    ``service_parts_parsed``
                  parts a service worker supplied by ACTUALLY parsing
                  (cold pass — text ran through a parser somewhere in
                  the fleet)
    ``service_parts_shared``
                  parts a service worker supplied from an
                  already-published block-cache artifact instead of
                  parsing — the cross-job share-by-signature win (a
                  second job over the same corpus, or a relaunched
                  worker re-serving its own publication); the share of
                  parses avoided is shared / (parsed + shared)
    ``fleet_scale_ups`` / ``fleet_scale_downs``
                  fleet-autoscaler decisions: workers live-joined under
                  sustained per-job input wait / gracefully drained
                  under sustained idleness (docs/service.md fleet
                  autoscaling) — both zero on a clean run
    ``service_throttles``
                  locate requests the dispatcher shed with a retryable
                  ``throttled`` reply because admission control had the
                  job over its ``max_inflight`` budget or the fleet over
                  the ``DMLC_TPU_QOS_MAX_INFLIGHT`` ceiling
                  (docs/service.md Production QoS) — bounded queueing,
                  not failure: a throttled epoch still completes
                  byte-identically
    ``service_admission_waits``
                  client-side backoff sleeps taken on those throttled
                  replies (shared RetryPolicy schedule; each throttle
                  resets the locate deadline, so a deliberately-queued
                  batch tenant never burns toward ``service_giveups``)
    """

    _KEYS = ("attempts", "retries", "resumes", "giveups", "fatal",
             "producer_restarts", "producer_giveups",
             "parse_restarts", "parse_giveups",
             "cache_corruptions", "cache_invalidations", "cache_rebuilds",
             "service_retries", "service_failovers", "service_giveups",
             "dispatcher_restarts", "worker_reregistrations",
             "parts_reclaimed", "control_plane_retries",
             "worker_drains", "drain_handoffs", "preemption_notices",
             "speculative_reissues", "speculative_wins", "worker_joins",
             "service_parts_parsed", "service_parts_shared",
             "fleet_scale_ups", "fleet_scale_downs",
             "service_throttles", "service_admission_waits")

    def bump(self, key: str, n: int = 1) -> None:
        record_event(key, n)

    def snapshot(self, pipeline: Optional[str] = None) -> Dict[str, int]:
        """Totals per event key — process-wide by default, or one
        pipeline's slice with ``pipeline=`` (empty string selects events
        recorded outside any pipeline scope)."""
        label_filter = {} if pipeline is None else {"pipeline": pipeline}
        out = {k: 0 for k in self._KEYS}
        for key, v in _telemetry.REGISTRY.sum_by(
                _telemetry.RESILIENCE_METRIC, "event",
                **label_filter).items():
            if key:
                out[key] = int(round(v))
        return out

    def delta(self, base: Dict[str, int],
              pipeline: Optional[str] = None) -> Dict[str, int]:
        now = self.snapshot(pipeline)
        return {k: now.get(k, 0) - base.get(k, 0) for k in now}

    def reset(self) -> None:
        _telemetry.REGISTRY.clear(_telemetry.RESILIENCE_METRIC)


COUNTERS = _Counters()


def counters_snapshot(pipeline: Optional[str] = None) -> Dict[str, int]:
    return COUNTERS.snapshot(pipeline)


def counters_delta(base: Dict[str, int],
                   pipeline: Optional[str] = None) -> Dict[str, int]:
    return COUNTERS.delta(base, pipeline)


def reset_counters() -> None:
    COUNTERS.reset()


# ---------------- retry policy ----------------

class RetryPolicy:
    """Exponential backoff + full jitter, per-attempt timeout, deadline.

    One instance describes the budget for ONE logical operation (a request,
    a block fetch): ``max_attempts`` total tries, sleeping
    ``uniform(0, min(max_delay, base_delay * 2**retry))`` between them
    (full jitter — herd-safe), never less than a server-sent
    ``Retry-After``. ``deadline`` bounds the whole operation including
    sleeps; ``attempt_timeout`` is what callers should pass to their
    transport (urlopen timeout=).

    Env knobs (read by :func:`from_env` / :func:`default_policy`):

    ======================================  =======  ========================
    ``DMLC_RETRY_MAX_ATTEMPTS``             4        total attempts per op
    ``DMLC_RETRY_BASE_MS``                  50       first backoff cap (ms)
    ``DMLC_RETRY_MAX_MS``                   5000     backoff cap ceiling (ms)
    ``DMLC_RETRY_DEADLINE_S``               0 (off)  per-op wall deadline
    ``DMLC_RETRY_ATTEMPT_TIMEOUT_S``        60       transport timeout
    ``DMLC_RETRY_SEED``                     unset    seed the jitter rng
    ======================================  =======  ========================
    """

    def __init__(
        self,
        max_attempts: int = 4,
        base_delay: float = 0.05,
        max_delay: float = 5.0,
        deadline: Optional[float] = None,
        attempt_timeout: float = 60.0,
        seed: Optional[int] = None,
        sleep_fn: Optional[Callable[[float], None]] = None,
    ):
        self.max_attempts = max(1, int(max_attempts))
        self.base_delay = max(0.0, float(base_delay))
        self.max_delay = max(self.base_delay, float(max_delay))
        self.deadline = float(deadline) if deadline else None
        self.attempt_timeout = float(attempt_timeout)
        self._rng = random.Random(seed)
        self._sleep = sleep_fn or time.sleep

    @classmethod
    def from_env(cls) -> "RetryPolicy":
        env = os.environ
        seed = env.get("DMLC_RETRY_SEED")
        return cls(
            max_attempts=int(env.get("DMLC_RETRY_MAX_ATTEMPTS", "4") or 4),
            base_delay=float(env.get("DMLC_RETRY_BASE_MS", "50") or 50) / 1e3,
            max_delay=float(env.get("DMLC_RETRY_MAX_MS", "5000") or 5000) / 1e3,
            deadline=float(env.get("DMLC_RETRY_DEADLINE_S", "0") or 0) or None,
            attempt_timeout=float(
                env.get("DMLC_RETRY_ATTEMPT_TIMEOUT_S", "60") or 60),
            seed=int(seed) if seed else None,
        )

    @classmethod
    def none(cls) -> "RetryPolicy":
        """Single attempt, no sleeps — for inner layers whose caller owns
        the retry loop (stacked policies would multiply budgets)."""
        return cls(max_attempts=1)

    def backoff(self, retry_index: int, floor: float = 0.0) -> float:
        """Sleep for the (retry_index+1)-th retry: full-jitter exponential,
        floored by a server-sent Retry-After. The honored floor is capped
        at ``max(30s, max_delay)`` — a misbehaving server advertising
        ``Retry-After: 86400`` must not wedge a reader thread for a day."""
        floor = min(floor, max(30.0, self.max_delay))
        cap = min(self.max_delay, self.base_delay * (2 ** retry_index))
        return max(floor, self._rng.uniform(0.0, cap))

    def sleep(self, seconds: float) -> None:
        if seconds > 0:
            self._sleep(seconds)

    def call(
        self,
        fn: Callable[[], object],
        *,
        op: str = "request",
        what: str = "",
        resume_offset: int = 0,
        on_retry: Optional[Callable[[], None]] = None,
    ):
        """Run ``fn`` under this budget.

        Each attempt first passes through the fault-injection seam
        (``faults.maybe_fail`` for the generic ``connect`` op and for
        ``op``), so injected faults flow down the same classify/backoff
        paths as real ones. Fatal-class errors surface immediately (one
        attempt); retryable ones sleep and retry until the budget or
        deadline runs out, then raise a ``DMLCError`` chained to the last
        cause. ``resume_offset > 0`` marks retries as mid-stream resumes
        in the counters; ``on_retry`` runs before each re-attempt (e.g.
        drop a broken inner stream).
        """
        t0 = get_time()
        retries = 0
        while True:
            record_event("attempts")
            try:
                faults.maybe_fail("connect", what)
                faults.maybe_fail(op, what)
                return fn()
            except (KeyboardInterrupt, SystemExit, GeneratorExit):
                raise  # control-flow exceptions must never be rewrapped
            except BaseException as exc:  # noqa: BLE001 - classified below
                if classify(exc) != RETRYABLE:
                    record_event("fatal")
                    if isinstance(exc, DMLCError):
                        raise
                    raise DMLCError(
                        f"{op} {what} failed (non-retryable): {exc}") from exc
                delay = self.backoff(retries, floor=retry_after_seconds(exc))
                out_of_budget = retries + 1 >= self.max_attempts
                past_deadline = (
                    self.deadline is not None
                    and get_time() - t0 + delay > self.deadline)
                if out_of_budget or past_deadline:
                    record_event("giveups")
                    why = ("deadline exceeded" if past_deadline
                           else f"retry budget exhausted "
                                f"({self.max_attempts} attempts)")
                    raise DMLCError(
                        f"{op} {what} failed, {why}: {exc}") from exc
                retries += 1
                record_event("retries")
                if resume_offset > 0:
                    record_event("resumes")
                self.sleep(delay)
                if on_retry is not None:
                    on_retry()


def default_policy() -> RetryPolicy:
    """The env-configured policy (fresh read: knobs may change per test)."""
    return RetryPolicy.from_env()


def restart_verdict(policy: Optional[RetryPolicy], used: int,
                    exc: BaseException) -> str:
    """Shared gate for bounded producer/source/pipeline restarts.

    ``'restart'``   retryable class, budget left — consume one unit
    ``'giveup'``    retryable class, budget (``max_attempts - 1``) spent
    ``'propagate'`` fatal class or restarts disabled (``policy is None``)

    The caller owns its instance counters and the repositioning; pair a
    ``'restart'`` with :func:`restart_backoff` before re-arming.
    """
    if policy is None or classify(exc) != RETRYABLE:
        return "propagate"
    if used >= max(0, policy.max_attempts - 1):
        return "giveup"
    return "restart"


def restart_backoff(policy: RetryPolicy, used: int,
                    exc: BaseException) -> None:
    """Sleep the backoff for the (used+1)-th restart, honoring any
    Retry-After the triggering error carried."""
    policy.sleep(policy.backoff(used, floor=retry_after_seconds(exc)))


NO_RETRY = RetryPolicy.none()


# ---------------- resumable stream wrapper ----------------

class ResilientStream(_pyio.RawIOBase):
    """Resumable read-only stream over a reopenable source.

    ``open_fn()`` returns a fresh readable (and seekable, for mid-stream
    resume) binary stream. On a retryable mid-read failure the broken
    inner stream is dropped, a new one is opened and SEEKED to the current
    byte offset, and the read resumes — the consumer sees an unbroken byte
    sequence. Fatal errors and exhausted budgets surface as ``DMLCError``.

    The five remote filesystems implement the same contract natively (their
    range-GET machinery refetches at the failed offset, see
    ``HttpReadStream._fetch_retry``); this wrapper extends it to any other
    stream — local files on flaky network mounts, third-party filesystems
    registered via ``register_filesystem`` — through
    ``open_stream(uri, resilient=True)``.
    """

    def __init__(self, open_fn: Callable[[], object],
                 policy: Optional[RetryPolicy] = None, what: str = ""):
        super().__init__()
        self._open_fn = open_fn
        self._policy = policy or default_policy()
        self._what = what
        self._inner = None
        self._pos = 0
        self.reopens = 0  # resume events on THIS stream

    def readable(self) -> bool:
        return True

    def seekable(self) -> bool:
        return True

    def _ensure(self):
        if self._inner is None:
            self._inner = self._open_fn()
            if self._pos:
                self._inner.seek(self._pos)
                self.reopens += 1
        return self._inner

    def _drop_inner(self) -> None:
        inner, self._inner = self._inner, None
        if inner is not None:
            try:
                inner.close()
            except Exception:  # noqa: BLE001 - already broken
                pass

    def seek(self, offset: int, whence: int = 0) -> int:
        def attempt():
            inner = self._ensure()
            return inner.seek(offset, whence)

        self._pos = self._policy.call(
            attempt, op="read", what=self._what,
            resume_offset=self._pos, on_retry=self._drop_inner)
        return self._pos

    def tell(self) -> int:
        return self._pos

    def read(self, n: int = -1) -> bytes:
        def attempt():
            return self._ensure().read(n)

        data = self._policy.call(
            attempt, op="read", what=self._what,
            resume_offset=self._pos, on_retry=self._drop_inner)
        if data:
            self._pos += len(data)
        return data

    def readinto(self, b) -> int:
        data = self.read(len(b))
        b[: len(data)] = data
        return len(data)

    def close(self) -> None:
        self._drop_inner()
        super().close()

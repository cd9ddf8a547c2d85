"""Producer/consumer prefetch pipeline.

Behavioral equivalent of reference include/dmlc/threadediter.h: a single
producer thread fills a bounded queue ahead of the consumer, with

- cell recycling so buffers are reused instead of reallocated
  (Next/Recycle, threadediter.h:443-488),
- ``before_first`` epoch reset that interrupts and restarts the producer
  (signal kBeforeFirst, threadediter.h:210-235),
- exceptions in the producer captured and rethrown on the consumer side
  (threadediter.h:406-436, 490-505),
- clean destruction joining the thread (kDestroy + ScopedThread,
  threadediter.h:283-313),
- an OPT-IN bounded producer-restart path (``restart_policy``): a
  retryable-class source error (see :func:`dmlc_tpu.io.resilience.classify`)
  consumes restart budget — backoff, reposition via ``restart_fn``, keep
  producing — instead of poisoning the pipeline; fatal errors and exhausted
  budgets rethrow on the consumer as before.

The producer callback contract matches the reference's ``next(cell)``:
``produce_fn(cell) -> (ok, cell)`` where ``cell`` is a recycled buffer or
None, and ok=False signals end of stream. A simpler ``iterator`` front-end
(:func:`ThreadedIter.from_factory`) covers the common case.
"""

from __future__ import annotations

import os
import threading
from collections import deque
from typing import Any, Callable, Deque, Generic, Optional, Tuple, TypeVar

from dmlc_tpu.io import resilience as _resilience
from dmlc_tpu.utils import telemetry as _telemetry
from dmlc_tpu.utils.check import DMLCError
from dmlc_tpu.utils.timer import get_time

T = TypeVar("T")

# producer signals (threadediter.h:243-247)
_SIG_PRODUCE = 0
_SIG_BEFORE_FIRST = 1
_SIG_DESTROY = 2


def _fast_forward(it, n: int):
    """Skip the first ``n`` items of a freshly rebuilt source — the
    deterministic replay both restart paths use. A source that yields fewer
    items than already delivered surfaces loudly (a bare StopIteration
    leaking into the pipeline would read as silent truncation)."""
    for _ in range(n):
        try:
            next(it)
        except StopIteration:
            raise DMLCError(
                "producer restart: source yielded fewer items than already "
                "delivered — non-deterministic factory?") from None
    return it


def _stall_timeout() -> float:
    """Opt-in pipeline stall watchdog (seconds; 0 = off, the default).

    A wedged producer — most commonly a device backend whose transfer
    hangs — otherwise blocks the consumer silently and forever. With ``DMLC_PIPELINE_STALL_TIMEOUT=N`` the consumer raises a
    diagnosable error after waiting N seconds with a live but unproductive
    producer. Off by default: a legitimately slow first chunk (GB-scale
    remote reads) must never be killed by an arbitrary limit.
    """
    return float(os.environ.get("DMLC_PIPELINE_STALL_TIMEOUT", "0") or 0)


def _restart_budget_dict(policy, used: int) -> dict:
    """The restart budget as structured data — one schema for both
    pipeline primitives, published inside the stall diagnostic."""
    return {
        "enabled": policy is not None,
        "used": used,
        "limit": max(0, policy.max_attempts - 1) if policy is not None
        else 0,
    }


def _publish_stall_diagnostic(diag: dict) -> None:
    """Publish a stall diagnostic as a structured info metric on the
    telemetry registry, keyed by component, pool label, and pipeline
    scope (a pipeline runs several pools — parse fan-out + convert — and
    their diagnostics must not overwrite each other) — the
    machine-readable twin of the DMLCError message (tests and monitors
    assert on this dict, never on message text)."""
    _telemetry.REGISTRY.info(
        _telemetry.STALL_METRIC, component=diag.get("component", ""),
        label=diag.get("label", ""),
        pipeline=_telemetry.current_scope() or "").set(diag)


class ThreadedIter(Generic[T]):
    """Bounded-queue prefetch iterator with recycling + epoch reset."""

    def __init__(
        self,
        produce_fn: Callable[[Optional[T]], Tuple[bool, Optional[T]]],
        before_first_fn: Optional[Callable[[], None]] = None,
        max_capacity: int = 8,
        restart_fn: Optional[Callable[[int], None]] = None,
        restart_policy: Optional["_resilience.RetryPolicy"] = None,
    ):
        self._produce = produce_fn
        self._before_first = before_first_fn
        self._capacity = max_capacity
        self._lock = threading.Condition()
        self._queue: Deque[T] = deque()
        self._free: Deque[T] = deque()
        self._produce_end = False
        self._signal = _SIG_PRODUCE
        self._signal_processed = False
        self._exc: Optional[BaseException] = None
        self._destroyed = False
        self.stall_seconds = 0.0  # consumer time spent waiting on the producer
        # bounded producer restart (opt-in): on a retryable-class produce
        # error, back off and call restart_fn(items_produced_this_epoch) to
        # reposition the source, consuming one unit of the per-epoch budget
        # (restart_policy.max_attempts - 1). Without restart_fn the produce
        # callback is simply re-invoked — only correct for producers whose
        # state survives a failed call (NOT dead generators).
        self._restart_fn = restart_fn
        self._restart_policy = (
            restart_policy if restart_policy is not None
            else (_resilience.default_policy() if restart_fn else None))
        self._epoch_produced = 0   # items queued since epoch start
        self._epoch_restarts = 0   # budget consumed this epoch
        self.restarts = 0          # lifetime restart count
        self.restart_giveups = 0   # budget-exhausted poisonings
        self.last_producer_error: Optional[str] = None
        # the producer runs under the owning pipeline's telemetry scope so
        # spans/metrics it records land under the right label: captured at
        # construction, ADOPTED from the first consumer pull when built
        # outside any scope (a ThreadedInputSplit is constructed with the
        # parser, before the DeviceIter that owns it exists) — the loop
        # re-installs it each iteration, so adoption takes effect mid-run
        self._scope = _telemetry.current_scope()
        self._thread = threading.Thread(target=self._producer_loop,
                                        daemon=True)
        self._thread.start()

    def _budget_state(self) -> str:
        """Human retry-budget summary for diagnostics."""
        pol = self._restart_policy
        if pol is None:
            return "producer restart disabled"
        return (f"producer restarts {self._epoch_restarts}/"
                f"{max(0, pol.max_attempts - 1)} used this epoch")

    def _budget_dict(self) -> dict:
        return _restart_budget_dict(self._restart_policy,
                                    self._epoch_restarts)

    def _try_restart(self, exc: BaseException) -> bool:
        """Classify a producer error; on a retryable class with budget left,
        back off, reposition the source, and report True (keep producing)."""
        with self._lock:
            if self._signal != _SIG_PRODUCE:  # reset/destroy pending: bail
                return False
            used = self._epoch_restarts
            produced = self._epoch_produced
        verdict = _resilience.restart_verdict(self._restart_policy, used, exc)
        if verdict == "giveup":
            self.restart_giveups += 1
            _resilience.record_event("producer_giveups")
            return False
        if verdict != "restart":
            return False
        with self._lock:
            self._epoch_restarts += 1
            self.restarts += 1
        _resilience.record_event("producer_restarts")
        _resilience.restart_backoff(self._restart_policy, used, exc)
        if self._restart_fn is not None:
            # reposition failures propagate to the caller's except branch
            self._restart_fn(produced)
        return True

    # ---------------- producer side ----------------

    def _producer_loop(self) -> None:
        while True:
            _telemetry.set_scope(self._scope)  # one TLS store per item
            cell: Optional[T] = None
            with self._lock:
                # wait for: destroy/reset signal, or space to produce
                self._lock.wait_for(
                    lambda: self._signal != _SIG_PRODUCE
                    or (not self._produce_end and (len(self._queue) < self._capacity or self._free))
                )
                if self._signal == _SIG_DESTROY:
                    self._signal_processed = True
                    self._lock.notify_all()
                    return
                if self._signal == _SIG_BEFORE_FIRST:
                    # epoch reset: drop queued items into the free list
                    while self._queue:
                        self._free.append(self._queue.popleft())
                    try:
                        if self._before_first is not None:
                            self._before_first()
                        self._produce_end = False
                        self._epoch_produced = 0
                        self._epoch_restarts = 0  # fresh budget per epoch
                    except BaseException as exc:  # noqa: BLE001 - rethrown on consumer
                        self._exc = exc
                        self._produce_end = True
                    self._signal = _SIG_PRODUCE
                    self._signal_processed = True
                    self._lock.notify_all()
                    continue
                if self._free:
                    cell = self._free.popleft()
            # run the producer outside the lock (threadediter.h:365 next())
            try:
                ok, value = self._produce(cell)
            except BaseException as exc:  # noqa: BLE001 - captured for consumer
                self.last_producer_error = f"{type(exc).__name__}: {exc}"
                try:
                    restarted = self._try_restart(exc)
                except BaseException as exc2:  # noqa: BLE001 - reposition died
                    restarted = False
                    exc = exc2
                    self.last_producer_error = f"{type(exc2).__name__}: {exc2}"
                if restarted:
                    with self._lock:
                        if cell is not None:  # return the borrowed cell
                            self._free.append(cell)
                    continue
                with self._lock:
                    self._exc = exc
                    self._produce_end = True
                    self._lock.notify_all()
                continue
            with self._lock:
                if ok:
                    self._queue.append(value)  # type: ignore[arg-type]
                    self._epoch_produced += 1
                else:
                    self._produce_end = True
                    if cell is not None:
                        self._free.append(cell)
                self._lock.notify_all()

    # ---------------- consumer side ----------------

    def adopt_scope(self, label: Optional[str]) -> None:
        """Install ``label`` as this pipeline's scope if it was built
        outside any (monotonic None -> label, so benign if raced). The
        owning ``DeviceIter`` walks its source chain and calls this at
        construction, so prefetch work done BEFORE the first pull is
        already scoped (docs/observability.md)."""
        if self._scope is None and label is not None:
            self._scope = label

    def next(self) -> Optional[T]:
        """Pop the next item; None at end of stream. Rethrows producer errors."""
        if self._destroyed:
            raise DMLCError("ThreadedIter: already destroyed")
        if self._scope is None:
            # scope adoption (see __init__): the first scoped consumer owns
            # this pipeline — monotonic None -> label, so benign if raced
            self._scope = _telemetry.current_scope()
        t0 = get_time()
        timeout = _stall_timeout()
        with self._lock:
            if timeout > 0:
                if not self._lock.wait_for(
                    lambda: self._queue or self._produce_end, timeout=timeout
                ):
                    alive = self._thread.is_alive()
                    # the diagnostic is DATA first: published on the
                    # metrics registry so monitors/tests read structure,
                    # not message text (docs/observability.md)
                    _publish_stall_diagnostic({
                        "component": "ThreadedIter",
                        "timeout_seconds": timeout,
                        "producer_alive": alive,
                        "queue_len": len(self._queue),
                        "free_cells": len(self._free),
                        "last_producer_error": self.last_producer_error,
                        "restart_budget": self._budget_dict(),
                    })
                    raise DMLCError(
                        f"pipeline stalled: no item produced in {timeout:.0f}s "
                        f"(producer thread {'alive but blocked' if alive else 'dead'}, "
                        f"queue empty, free cells {len(self._free)}; "
                        f"last producer error: "
                        f"{self.last_producer_error or 'none'}; "
                        f"{self._budget_state()}). A hung "
                        f"device transfer or remote read is the usual cause; "
                        f"unset DMLC_PIPELINE_STALL_TIMEOUT to wait forever"
                    )
            else:
                self._lock.wait_for(lambda: self._queue or self._produce_end)
            self.stall_seconds += get_time() - t0
            if self._queue:
                item = self._queue.popleft()
                self._lock.notify_all()
                return item
            self._check_exc_locked()
            return None

    def set_capacity(self, max_capacity: int) -> None:
        """Live-resize the prefetch window (the autotuner's
        ``convert_ahead`` knob in natural-block mode): growing lets the
        producer run further ahead immediately; shrinking only gates NEW
        production — already-queued items still drain to the consumer,
        so delivery order and content are untouched."""
        with self._lock:
            self._capacity = max(1, int(max_capacity))
            self._lock.notify_all()

    def recycle(self, item: T) -> None:
        """Return a consumed cell for reuse (threadediter.h:476-488)."""
        with self._lock:
            self._free.append(item)
            self._lock.notify_all()
            self._check_exc_locked()

    def before_first(self) -> None:
        """Reset to the epoch start; blocks until the producer acknowledges."""
        with self._lock:
            self._check_exc_locked()
            self._signal = _SIG_BEFORE_FIRST
            self._signal_processed = False
            self._lock.notify_all()
            self._lock.wait_for(lambda: self._signal_processed)
            self._signal_processed = False
            self._check_exc_locked()

    def destroy(self) -> None:
        """Stop and join the producer thread."""
        if self._destroyed:
            return
        with self._lock:
            self._signal = _SIG_DESTROY
            self._signal_processed = False
            self._lock.notify_all()
        self._thread.join(timeout=30.0)
        self._destroyed = True

    def _check_exc_locked(self) -> None:
        if self._exc is not None:
            exc, self._exc = self._exc, None
            self._produce_end = True
            raise exc

    def __iter__(self):
        while True:
            item = self.next()
            if item is None:
                return
            yield item

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.destroy()
        except Exception:
            pass

    # ---------------- convenience front-end ----------------

    @staticmethod
    def from_factory(
        iterator_factory: Callable[[], Any], max_capacity: int = 8,
        restart_policy: Optional["_resilience.RetryPolicy"] = None,
    ) -> "ThreadedIter":
        """Prefetch over a restartable iterator factory.

        Each epoch calls ``iterator_factory()`` for a fresh iterator; this is
        the Pythonic face of the (next_fn, beforefirst_fn) pair.

        With ``restart_policy``, a retryable-class error from the iterator
        consumes restart budget: a FRESH iterator is built and fast-forwarded
        past the items already delivered (the factory must be deterministic),
        so the consumer sees an uninterrupted, in-order stream. Without it, a
        dead generator would otherwise surface the error and end the epoch.
        """
        state = {"it": iterator_factory()}

        def produce(cell):
            try:
                return True, next(state["it"])
            except StopIteration:
                return False, None

        def before_first():
            state["it"] = iterator_factory()

        def restart(produced: int) -> None:
            # skip what the consumer already has (deterministic factory)
            state["it"] = _fast_forward(iterator_factory(), produced)

        return ThreadedIter(
            produce, before_first, max_capacity=max_capacity,
            restart_fn=restart if restart_policy is not None else None,
            restart_policy=restart_policy)


class OrderedWorkerPool(Generic[T]):
    """Serial-pull, parallel-work, in-order-delivery prefetch pool.

    The pool form of :class:`ThreadedIter`'s producer machinery (same
    consumer contract: ``next() -> item | None`` at end of stream, worker
    exceptions rethrown on the consumer side, ``destroy()`` joins): items
    are pulled from ONE serial source iterator — the pull is serialized
    under a dedicated lock and each pulled item takes a sequence number,
    so source order is the law — then ``work_fn(item)`` runs CONCURRENTLY
    across ``num_workers`` threads, and results are handed to the
    consumer strictly in pull order.

    Built for pipeline stages whose per-item work releases the GIL (numpy
    packing, host layout conversion): work-for-item-N+1 overlaps whatever
    the consumer does with item N (DeviceIter's convert/dispatch overlap).
    ``max_ahead`` bounds pulled-but-undelivered items (backpressure); the
    instantaneous overshoot is at most ``num_workers`` items already past
    the window check when it closes.

    What the pool's threads wait for is counted, as registry counters
    ``pool_seconds{pool=counter_label, state=, pipeline=}`` under the scope
    the pool was built in: a worker's seconds waiting for the ``max_ahead``
    window (``window_wait``: the consumer is behind), for the pull lock
    (``pull_wait``: the serial stage is the queue), in the serial pull
    (``pull``) and in ``work_fn`` (``work``), which together are the
    workers' wall time; and per delivered item how long it lay finished
    before the consumer took it (``ready_wait``, with
    ``pool_events{kind="items"}``). The consumer's own wait stays
    ``stall_seconds``.
    """

    def __init__(
        self,
        source_factory: Callable[[], Any],
        work_fn: Callable[[Any], T],
        num_workers: int = 2,
        max_ahead: int = 4,
        restart_policy: Optional["_resilience.RetryPolicy"] = None,
        counter_label: str = "producer",
    ):
        self._source_factory = source_factory
        self._source = source_factory()
        self._work = work_fn
        self._ahead = max(1, int(max_ahead))
        self._lock = threading.Condition()
        self._pull_lock = threading.Lock()
        self._results: dict = {}
        self._seq = 0    # next sequence number to assign at pull time
        self._want = 0   # next sequence number the consumer delivers
        self._produce_end = False
        self._poisoned = False  # a work_fn error was delivered: terminal
        self._src_exc: Optional[BaseException] = None
        self._destroyed = False
        self.stall_seconds = 0.0  # consumer time waiting on the workers
        # which resilience counters this pool's restarts bump: the generic
        # "producer_*" pair by default; the parse fan-out labels its pool
        # "parse" so parse-source restarts are distinguishable in
        # DeviceIter.stats()['resilience']
        self._counter_label = counter_label
        # bounded source restart (opt-in, like ThreadedIter): a retryable
        # pull error rebuilds the source via source_factory() and
        # fast-forwards past the seq items already pulled, so sequence
        # numbers — and therefore delivery order — are preserved across a
        # mid-stream restart. The factory must be deterministic.
        self._restart_policy = restart_policy
        self.restarts = 0
        self.restart_giveups = 0
        self.last_producer_error: Optional[str] = None
        # workers run under the owning pipeline's scope: captured at
        # construction, adopted from the first consumer pull otherwise
        # (see ThreadedIter)
        self._scope = _telemetry.current_scope()
        # what the threads wait for (class docstring): summed over the
        # pools of successive epochs, since the handles are the registry's
        labels = {"pool": counter_label, "pipeline": self._scope or ""}
        self._seconds = {
            state: _telemetry.REGISTRY.counter(
                _telemetry.POOL_SECONDS_METRIC, state=state, **labels)
            for state in ("window_wait", "pull_wait", "pull", "work",
                          "ready_wait")}
        self._items = _telemetry.REGISTRY.counter(
            _telemetry.POOL_EVENTS_METRIC, kind="items", **labels)
        # live resize (docs/data.md autotune): _shrink holds exit credits
        # surplus workers consume at their next loop top; num_workers is
        # the current TARGET width (threads alive minus pending exits)
        self._shrink = 0
        self.num_workers = max(1, int(num_workers))
        self._threads = [
            threading.Thread(target=self._worker_loop, daemon=True)
            for _ in range(self.num_workers)
        ]
        for t in self._threads:
            t.start()

    def _budget_state(self) -> str:
        pol = self._restart_policy
        if pol is None:
            return "source restart disabled"
        return (f"source restarts {self.restarts}/"
                f"{max(0, pol.max_attempts - 1)} used")

    def _budget_dict(self) -> dict:
        return _restart_budget_dict(self._restart_policy, self.restarts)

    def _try_source_restart(self, exc: BaseException) -> bool:
        """Called under ``_pull_lock`` after a source pull raised. On a
        retryable class with budget left: back off, rebuild the source, and
        skip the ``seq`` items already pulled (order is law — the skip keeps
        every outstanding sequence number valid)."""
        verdict = _resilience.restart_verdict(self._restart_policy,
                                              self.restarts, exc)
        if verdict == "giveup":
            self.restart_giveups += 1
            _resilience.record_event(f"{self._counter_label}_giveups")
            return False
        if verdict != "restart":
            return False
        used = self.restarts
        self.restarts += 1
        _resilience.record_event(f"{self._counter_label}_restarts")
        _resilience.restart_backoff(self._restart_policy, used, exc)
        with self._lock:
            pulled = self._seq
        self._source = _fast_forward(self._source_factory(), pulled)
        return True

    # ---------------- worker side ----------------

    def _now(self, closes: str) -> float:
        """The pool's clock. Every reading closes an interval of the calling
        thread's, and ``closes`` names it: a worker's ``start`` and its four
        states, the consumer's ``asked`` (it is about to wait), ``stall``
        (its wait is over) and ``ready_wait`` (the hand-over). The books
        need the time alone; a clock that a test puts here also learns what
        every thread is doing as it reads."""
        return get_time()

    def _worker_loop(self) -> None:
        seconds = self._seconds
        t = self._now("start")

        def spent(state: str) -> None:
            # the time since the last call goes to ``state``: the four
            # states partition a worker's wall time
            nonlocal t
            now = self._now(state)
            seconds[state].inc(now - t)
            t = now

        while True:
            _telemetry.set_scope(self._scope)  # one TLS store per item
            with self._lock:
                self._lock.wait_for(
                    lambda: self._destroyed or self._produce_end
                    or self._shrink > 0
                    or (self._seq - self._want) < self._ahead
                )
                spent("window_wait")
                if self._destroyed or self._produce_end:
                    return
                if self._shrink > 0:
                    # live shrink: consume one exit credit and retire —
                    # between the wait and the pull lock, so a retiring
                    # worker never holds an undelivered item
                    self._shrink -= 1
                    return
            with self._pull_lock:
                spent("pull_wait")
                # re-check under the pull lock: another worker may have hit
                # end-of-stream (or destroy) while this one waited its turn
                if self._destroyed or self._produce_end:
                    return
                try:
                    item = next(self._source)
                except StopIteration:
                    spent("pull")
                    with self._lock:
                        self._produce_end = True
                        self._lock.notify_all()
                    return
                except BaseException as exc:  # noqa: BLE001 - rethrown on consumer
                    self.last_producer_error = f"{type(exc).__name__}: {exc}"
                    try:
                        restarted = self._try_source_restart(exc)
                    except BaseException as exc2:  # noqa: BLE001 - replay died
                        restarted = False
                        exc = exc2
                        self.last_producer_error = (
                            f"{type(exc2).__name__}: {exc2}")
                    spent("pull")
                    if restarted:
                        continue  # releases the pull lock, re-enters the wait
                    with self._lock:
                        self._src_exc = exc
                        self._produce_end = True
                        self._lock.notify_all()
                    return
                spent("pull")
                with self._lock:
                    seq = self._seq
                    self._seq += 1
            # the parallel stage: outside every lock
            try:
                out = ("ok", self._work(item))
            except BaseException as exc:  # noqa: BLE001 - rethrown in order
                out = ("exc", exc)
            spent("work")
            with self._lock:
                self._results[seq] = out + (t,)   # t: when it was finished
                self._lock.notify_all()

    # ---------------- consumer side ----------------

    def adopt_scope(self, label: Optional[str]) -> None:
        """See :meth:`ThreadedIter.adopt_scope` — same contract."""
        if self._scope is None and label is not None:
            self._scope = label

    def next(self) -> Optional[T]:
        """Pop the next result in source order; None at end of stream.

        A ``work_fn`` exception is rethrown at the position of the item
        that raised (earlier items still deliver) and POISONS the pool:
        later calls return None — items past a failure must never be
        handed out, or a consumer pairing deliveries with per-item
        bookkeeping (DeviceIter's resume-annotation fifo) would desync by
        one. A source-iterator exception is rethrown after all
        successfully pulled items drain.
        """
        if self._destroyed:
            raise DMLCError("OrderedWorkerPool: already destroyed")
        if self._poisoned:
            return None
        if self._scope is None:
            # scope adoption: the first scoped consumer owns this pool
            self._scope = _telemetry.current_scope()
        t0 = self._now("asked")
        timeout = _stall_timeout()
        with self._lock:
            ready = lambda: (  # noqa: E731
                self._want in self._results
                or (self._produce_end and self._want >= self._seq))
            if timeout > 0:
                if not self._lock.wait_for(ready, timeout=timeout):
                    alive = sum(t.is_alive() for t in self._threads)
                    _publish_stall_diagnostic({
                        "component": "OrderedWorkerPool",
                        "label": self._counter_label,
                        "timeout_seconds": timeout,
                        "workers_alive": alive,
                        "workers": self.num_workers,
                        "waiting_for": self._want,
                        "pulled": self._seq,
                        "last_producer_error": self.last_producer_error,
                        "restart_budget": self._budget_dict(),
                    })
                    raise DMLCError(
                        f"pipeline stalled: no item produced in {timeout:.0f}s "
                        f"({alive}/{len(self._threads)} workers alive, "
                        f"waiting for #{self._want} of {self._seq} pulled; "
                        f"last producer error: "
                        f"{self.last_producer_error or 'none'}; "
                        f"{self._budget_state()}). "
                        f"A hung device transfer or remote read is the usual "
                        f"cause; unset DMLC_PIPELINE_STALL_TIMEOUT to wait "
                        f"forever")
            else:
                self._lock.wait_for(ready)
            self.stall_seconds += self._now("stall") - t0
            if self._want in self._results:
                kind, value, done = self._results.pop(self._want)
                self._seconds["ready_wait"].inc(
                    max(0.0, self._now("ready_wait") - done))
                self._items.inc(1)
                self._want += 1
                self._lock.notify_all()  # window opened: let a worker pull
                if kind == "exc":
                    self._produce_end = True
                    self._poisoned = True
                    raise value
                return value
            if self._src_exc is not None:
                exc, self._src_exc = self._src_exc, None
                raise exc
            return None

    def resize(self, num_workers: int) -> int:
        """Live-resize the worker pool (the autotuner's pool-width
        knobs): growth spawns threads that join the same serial pull +
        in-order delivery machinery, shrink posts exit credits surplus
        workers consume at their next loop top. Sequence numbers — and
        therefore delivery order and content — are unaffected in both
        directions. Returns the new target width."""
        n = max(1, int(num_workers))
        spawn = []
        with self._lock:
            if self._destroyed:
                return self.num_workers
            # drop retired/dead threads so diagnostics count live ones
            self._threads = [t for t in self._threads if t.is_alive()]
            delta = n - self.num_workers
            self.num_workers = n
            if delta > 0:
                # cancel pending exits first, then top up with threads
                cancel = min(self._shrink, delta)
                self._shrink -= cancel
                for _ in range(delta - cancel):
                    t = threading.Thread(target=self._worker_loop,
                                         daemon=True)
                    self._threads.append(t)
                    spawn.append(t)
            elif delta < 0:
                self._shrink += -delta
            self._lock.notify_all()
        for t in spawn:
            t.start()
        return n

    def set_max_ahead(self, max_ahead: int) -> None:
        """Live-resize the pulled-but-undelivered window (the
        ``convert_ahead`` knob): growing opens the window immediately;
        shrinking only gates NEW pulls — items already in flight still
        deliver in order."""
        with self._lock:
            self._ahead = max(1, int(max_ahead))
            self._lock.notify_all()

    def destroy(self) -> None:
        """Stop and join the worker threads."""
        if self._destroyed:
            return
        with self._lock:
            self._destroyed = True
            self._lock.notify_all()
        for t in self._threads:
            t.join(timeout=30.0)

    def __iter__(self):
        while True:
            item = self.next()
            if item is None:
                return
            yield item

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.destroy()
        except Exception:
            pass



"""The task repository: one lock over the farm's task state.

The paper: *"Each control thread fetches tasks to be delivered to the remote
nodes from a centralized, synchronized task repository"* — pull-based
scheduling is what gives JJPF automatic load balancing, and keeping every
task on the client until its result arrives is what gives fault tolerance
("the task can be rescheduled as soon as the control thread understands that
the corresponding service node has been disconnected").

:class:`TaskRepository` is that centralized repository.  ONE re-entrant
lock guards the task records' states, the pending deque, the
:class:`~repro.core.leases.LeaseTable` (deadline heap included) and the
event-time counters.  Two conditions share the lock:

  * ``_work`` — leasers (control threads in ``get_task`` / ``get_batch``)
    park here; it is notified only by events that create leasable work or
    end the repository (new tasks, a failed or expired lease, a change of
    the straggler set, close, cancel, the last completion);
  * ``_progress`` — progress watchers (``wait_all``, ``wait_until``,
    streaming backpressure) park here; every completion notifies it.

Splitting them keeps a completion from waking N idle leasers who will
find nothing.  Aggregate reads (``stats()``, ``all_done``, the wait
predicates) read the counters lock-free: each is written only under the
lock, so a read is a valid (momentarily conservative) snapshot.  A
separate add lock, held only at add / cancel time, allocates task ids.

Extensions beyond the paper (see DESIGN.md): lease timeouts, speculative
re-execution of stragglers (idempotent ``complete``, first result wins —
across expiry re-enqueues and speculative duplicates alike), and batched
leasing (``get_batch`` hands a service up to N shape-compatible tasks in
one round-trip).

Every timestamp and every blocking wait goes through a
:class:`repro.core.clock.Clock` (wall clock by default), which is what
lets the ``sim://`` backend run this exact code under a deterministic
virtual clock; ``test_sim_lease_trace_identical_to_golden`` (in
``tests/test_scale.py``) pins the resulting sequence of waits, notifies
and lease timestamps.  A leaser's wait is capped at the next lease
deadline, so expiry is event-driven.  The lock is metered
(``lock_wait_s``, ``lock_hold_s``, ``lock_contentions``,
``lock_acquisitions`` in ``stats()``); the meters use
``time.perf_counter`` (never the clock seam): they profile *real*
contention, which a virtual clock serializes away by construction.
"""

from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass
from enum import Enum
from time import perf_counter
from typing import Any

from .clock import REAL_CLOCK
from .leases import LeaseTable


_UNSET = object()


class TaskState(Enum):
    PENDING = "pending"
    LEASED = "leased"
    DONE = "done"


@dataclass
class TaskRecord:
    task_id: int
    payload: Any
    state: TaskState = TaskState.PENDING
    result: Any = None
    attempts: int = 0
    completed_by: str | None = None
    group_key: Any = None  # memoized compatibility key (see get_batch)
    group_key_set: bool = False
    created_at: float = 0.0  # stamped only when telemetry is attached


class _LockMeter:
    """An RLock context manager that meters contention.

    Lock-*wait* time is measured only on the contended path (a failed
    non-blocking acquire), so the uncontended hot path pays one extra
    try-acquire and nothing else; lock-*hold* time costs two
    ``perf_counter`` reads per acquisition (~100 ns).  Both feed the
    repository's ``stats()``.  Counters are
    plain ints/floats written while the lock is held (hold/acquisitions)
    or by the single acquiring thread (wait/contentions), so lock-free
    readers see monotone, never-corrupt values.
    """

    __slots__ = ("lock", "wait_s", "hold_s", "contentions", "acquisitions",
                 "_t_acq")

    def __init__(self):
        self.lock = threading.RLock()
        self.wait_s = 0.0
        self.hold_s = 0.0
        self.contentions = 0
        self.acquisitions = 0
        self._t_acq = 0.0

    def __enter__(self) -> "_LockMeter":
        if not self.lock.acquire(blocking=False):
            t0 = perf_counter()
            self.lock.acquire()
            self.wait_s += perf_counter() - t0
            self.contentions += 1
        self.acquisitions += 1
        self._t_acq = perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.hold_s += perf_counter() - self._t_acq
        self.lock.release()

    def pause_hold(self) -> None:
        """Close the current hold window before a ``cond_wait`` releases
        the lock inside a metered section — a park is idle time, not lock
        hold (pair with :meth:`resume_hold` after the wait returns)."""
        self.hold_s += perf_counter() - self._t_acq

    def resume_hold(self) -> None:
        self._t_acq = perf_counter()


class TaskRepository:
    """Thread-safe pull queue with leases, rescheduling and speculation,
    under one metered lock."""

    def __init__(self, tasks: list, *, lease_s: float = 30.0,
                 speculation_factor: float = 3.0, on_complete=None,
                 streaming: bool = False, clock=None, on_lease=None,
                 straggler_rate_factor: float = 0.5,
                 reclaim_done: bool = False, obs=None):
        self._clock = clock if clock is not None else REAL_CLOCK
        self._obs = obs  # Observability bundle or None (no telemetry)
        self.on_complete = on_complete  # callable(task_id, result)
        self.streaming = streaming  # open-ended stream (futures / jobs)
        # drop payload+result from each record the moment it completes —
        # for unbounded streams whose results are consumed through
        # ``on_complete`` (farm jobs), so peak memory is the in-flight
        # window, not the whole stream.  ``results()`` is meaningless then.
        self.reclaim_done = reclaim_done
        self._closed = False
        self._cancelled = False
        self._meter = _LockMeter()
        self._progress = threading.Condition(self._meter.lock)
        self._work = threading.Condition(self._meter.lock)
        self.leases = LeaseTable(
            lease_s=lease_s, speculation_factor=speculation_factor,
            straggler_rate_factor=straggler_rate_factor, on_lease=on_lease)
        # serializes task-id allocation (and add-vs-cancel) — held only
        # at add/cancel time, never on the lease/complete hot path
        self._add_lock = threading.Lock()
        #: task-id -> record map; append-only under _add_lock, read
        #: lock-free (GIL-safe)
        self.records: dict[int, TaskRecord] = {}
        self._pending: deque[int] = deque()
        self.done_count = 0
        self.leased_count = 0
        self.reschedules = 0
        self._durations: list[float] = []
        self.completions_per_service: dict[str, int] = {}
        t_submit = 0.0 if obs is None else self._clock.monotonic()
        for i, t in enumerate(tasks):
            self.records[i] = TaskRecord(i, t, created_at=t_submit)
            self._pending.append(i)
        self._n_added = len(tasks)
        if obs is not None and tasks:
            obs.event("task-submit", t_submit, len(tasks), 0)
        # high-water mark of unfinished tasks — the streaming-submission
        # backpressure metric; tracked at add time under _add_lock so
        # submitters pay no repository-lock round-trip for it
        self.peak_unfinished = len(tasks)

    # ---------------- aggregate state ------------------------------- #
    def _exhausted(self) -> bool:
        """Every added task is done and no more can arrive.  Lock-free:
        the done counter is monotone and ``_n_added`` is frozen once the
        stream closes (the only time this can return True)."""
        if self.streaming and not self._closed:
            return False
        return self.done_count == self._n_added

    def __len__(self) -> int:
        return self._n_added

    @property
    def all_done(self) -> bool:
        return self._cancelled or self._exhausted()

    @property
    def cancelled(self) -> bool:
        return self._cancelled

    @property
    def closed(self) -> bool:
        """True once the stream can no longer grow (non-streaming
        repositories are born closed)."""
        return self._closed or not self.streaming

    def _notify_all_locked(self) -> None:
        """Wake leasers (``_work``) and progress watchers — for events
        that create leasable work or end the repository."""
        self._clock.cond_notify_all(self._work)
        self._clock.cond_notify_all(self._progress)

    # ---------------- stream lifecycle ------------------------------ #
    def close(self) -> None:
        """End a streaming repository: no more tasks will be added."""
        self._closed = True
        with self._meter:
            self._notify_all_locked()

    def cancel(self) -> int:
        """Terminal, idempotent: drop every pending task, stop handing out
        work, and make ``all_done`` True so pulling control threads (and
        anyone in ``wait_all``) unwind.  Tasks already leased keep their
        records but their results are dropped on arrival (``complete``
        returns False) and their leases can never re-enqueue — a cancelled
        repository cannot leak work back into the farm.  Returns how many
        pending tasks were dropped."""
        with self._add_lock:
            if self._cancelled:
                return 0
            self._cancelled = True
            self._closed = True
        with self._meter:
            dropped = len(self._pending)
            self._pending.clear()
            # clear outstanding leases up front: their results (if any
            # arrive) are dropped by the guards in complete/fail, and a
            # cancelled repository must never read as holding leases
            self.leases.clear()
            if self.leased_count:
                for rec in self.records.values():
                    if rec.state == TaskState.LEASED:
                        rec.state = TaskState.PENDING
            self.leased_count = 0
            self._notify_all_locked()
        if self._obs is not None:
            self._obs.event("cancel", None, dropped)
        return dropped

    def add_task(self, payload) -> int:
        """Streams can grow while the farm runs."""
        return self.add_tasks([payload])[0]

    def add_tasks(self, payloads: list) -> list[int]:
        """Register a whole batch of tasks under ONE repository-lock
        acquisition and ONE notify — streaming submitters
        (``FarmExecutor.map``, ``Job.add_tasks``) were paying a lock
        round-trip per task.  Ids are allocated under the add lock."""
        with self._add_lock:
            if self._cancelled:
                raise RuntimeError("cannot add tasks: repository cancelled")
            base = self._n_added
            obs = self._obs
            t_submit = 0.0 if obs is None else self._clock.monotonic()
            tids = list(range(base, base + len(payloads)))
            for tid, payload in zip(tids, payloads):
                self.records[tid] = TaskRecord(tid, payload,
                                               created_at=t_submit)
            self._n_added = base + len(tids)
            if obs is not None and tids:
                obs.event("task-submit", t_submit, len(tids), base)
            if tids:
                with self._meter:
                    self._pending.extend(tids)
                    self._notify_all_locked()
            unfinished = self._n_added - self.done_count
            if unfinished > self.peak_unfinished:
                self.peak_unfinished = unfinished
            return tids

    def unfinished(self) -> int:
        """Tasks added but not yet completed (pending + leased)."""
        return self._n_added - self.done_count

    def wait_unfinished_below(self, n: int, *, timeout: float | None = None
                              ) -> bool:
        """Block until fewer than ``n`` tasks are unfinished — the
        backpressure wait for streaming submitters (``Job.submit_stream``):
        a feeder sleeps here instead of materializing an unbounded task
        source.  Event-driven (completions notify the progress
        condition); returns False on timeout or if the repository is
        cancelled meanwhile."""
        deadline = (None if timeout is None
                    else self._clock.monotonic() + timeout)
        with self._progress:
            while self._n_added - self.done_count >= n:
                if self._cancelled:
                    return False
                remaining = (None if deadline is None
                             else deadline - self._clock.monotonic())
                if remaining is not None and remaining <= 0:
                    return False
                self._clock.cond_wait(
                    self._progress,
                    min(remaining, 0.5) if remaining is not None else 0.5)
            return not self._cancelled

    # ---------------- leasing --------------------------------------- #
    def _lease_locked(self, rec: TaskRecord, service_id: str,
                      now: float) -> None:
        rec.state = TaskState.LEASED
        rec.attempts += 1
        self.leased_count += 1
        self.leases.lease(rec.task_id, service_id, rec.attempts, now)

    def _expire_locked(self) -> None:
        """Re-enqueue leases past their deadline (the LeaseTable pops only
        the actually-expired heap prefix)."""
        obs = self._obs
        expired = None
        for tid in self.leases.expired(self._clock.monotonic()):
            rec = self.records[tid]
            if rec.state != TaskState.LEASED:
                continue
            rec.state = TaskState.PENDING
            self.leased_count -= 1
            self._pending.append(tid)
            self.reschedules += 1
            if obs is not None:
                if expired is None:
                    expired = []
                expired.append(tid)
        if expired:
            obs.event("expire", None, tuple(expired))

    def _try_lease_one(self, service_id: str):
        """Expire lapsed leases, then lease the next pending task.
        Returns ``(task_id, payload)`` or None if nothing is leasable."""
        with self._meter:
            self._expire_locked()
            while self._pending:
                tid = self._pending.popleft()
                rec = self.records[tid]
                if rec.state != TaskState.PENDING:
                    # stale queue entry: the task was re-enqueued by an
                    # expiry and then completed by its original owner
                    # before anyone re-leased it — leasing it again would
                    # re-run (and double-count) a DONE task
                    continue
                now = self._clock.monotonic()
                self._lease_locked(rec, service_id, now)
                obs = self._obs
                if obs is not None:
                    obs.queue_wait_s.observe(now - rec.created_at)
                    obs.event("lease", now, service_id,
                              ((tid, rec.attempts),))
                return tid, rec.payload
        return None

    def _fill_batch(self, service_id: str, max_batch: int,
                    compatible) -> list:
        """Expire, then lease up to ``max_batch`` compatible pending tasks
        under one lock hold; skipped tasks go back to the head in their
        original order."""
        batch: list = []
        with self._meter:
            self._expire_locked()
            if not self._pending:
                return batch
            now = self._clock.monotonic()
            obs = self._obs
            leased = None if obs is None else []
            oldest = now
            group_key: Any = _UNSET  # `compatible` may return None
            skipped: list[int] = []
            while self._pending and len(batch) < max_batch:
                tid = self._pending.popleft()
                rec = self.records[tid]
                if rec.state != TaskState.PENDING:
                    continue  # stale entry (see _try_lease_one)
                if compatible is None:
                    key = None
                elif rec.group_key_set:
                    key = rec.group_key
                else:  # computed once per task, under the lock
                    key = rec.group_key = compatible(rec.payload)
                    rec.group_key_set = True
                if group_key is _UNSET:
                    group_key = key
                elif key != group_key:
                    skipped.append(tid)
                    continue
                self._lease_locked(rec, service_id, now)
                batch.append((tid, rec.payload))
                if leased is not None:
                    leased.append((tid, rec.attempts))
                    if rec.created_at < oldest:
                        oldest = rec.created_at
            # skipped tasks go back to the head, original order
            self._pending.extendleft(reversed(skipped))
            if leased:
                # one queue-wait sample per dispatch (the oldest task's
                # wait): a per-task observe here doubles the recorder's
                # hot-path cost for no extra scheduling signal
                obs.queue_wait_s.observe(now - oldest)
                obs.event("lease", now, service_id, tuple(leased))
        return batch

    def _try_speculate(self, service_id: str):
        """Issue a speculative duplicate of a straggler task, or None."""
        with self._meter:
            tid = self.leases.speculation_candidate(
                service_id, self._durations, self._clock.monotonic())
            if tid is None:
                return None
            rec = self.records[tid]
            rec.attempts += 1
            now = self._clock.monotonic()
            self.leases.issue_speculative(tid, service_id, rec.attempts,
                                          now)
            if self._obs is not None:
                self._obs.event("speculate", now, service_id, tid,
                                rec.attempts)
            return tid, rec.payload

    def _park(self, remaining: float) -> None:
        """Block on the work condition until notified, but never past the
        next lease deadline — expiry stays event-driven (the waiter that
        wakes at the deadline re-enqueues the lapsed lease itself on its
        next scan)."""
        with self._meter:
            next_deadline = self.leases.next_deadline()
            if next_deadline is not None:
                # expired entries were popped on the last scan, so > 0
                remaining = min(
                    remaining,
                    max(next_deadline - self._clock.monotonic(), 1e-6))
            self._meter.pause_hold()
            try:
                self._clock.cond_wait(self._work, remaining)
            finally:
                self._meter.resume_hold()

    def get_task(self, service_id: str, *, timeout: float = 0.5,
                 allow_speculation: bool = True):
        """Lease the next pending task (or a speculative copy of a
        straggler).  Returns (task_id, payload) or None if the stream is
        exhausted (all tasks done) — a None with ``all_done`` False means
        "try again" (everything currently leased)."""
        deadline = self._clock.monotonic() + timeout
        while True:
            if self._cancelled:
                return None
            got = self._try_lease_one(service_id)
            if got is not None:
                return got
            if self._exhausted():
                return None
            if allow_speculation:
                got = self._try_speculate(service_id)
                if got is not None:
                    return got
            remaining = deadline - self._clock.monotonic()
            if remaining <= 0:
                return None
            self._park(remaining)

    def get_batch(self, service_id: str, max_batch: int, *,
                  timeout: float = 0.5, allow_speculation: bool = True,
                  compatible=None):
        """Lease up to ``max_batch`` *compatible* pending tasks at once.

        ``compatible`` maps a payload to a hashable group key (e.g.
        :func:`repro.core.batching.payload_signature`); only tasks sharing
        the key of the first pending task are leased together, the rest
        stay pending in their original order.  ``None`` treats every task
        as compatible.

        Returns a non-empty list of ``(task_id, payload)`` pairs, or
        ``None`` with the same contract as :meth:`get_task` (exhausted, or
        nothing leasable before the timeout).  When nothing is pending but
        a straggler qualifies, returns a singleton speculative batch."""
        if max_batch <= 1:
            got = self.get_task(service_id, timeout=timeout,
                                allow_speculation=allow_speculation)
            return None if got is None else [got]
        deadline = self._clock.monotonic() + timeout
        while True:
            if self._cancelled:
                return None
            batch = self._fill_batch(service_id, max_batch, compatible)
            if batch:
                return batch
            if self._exhausted():
                return None
            if allow_speculation:
                got = self._try_speculate(service_id)
                if got is not None:
                    return [got]
            remaining = deadline - self._clock.monotonic()
            if remaining <= 0:
                return None
            self._park(remaining)

    def report_rate(self, service_id: str, tasks_per_s: float | None) -> None:
        """Control threads report observed per-service throughput here
        (the AIMD controller's EWMA); it feeds straggler detection —
        the heterogeneity-aware arm of speculation."""
        if tasks_per_s is None:
            return
        with self._meter:
            # wake waiters only when the straggler set actually changed
            # (a service just crossed the cutoff, either way) — rates are
            # reported once per drained batch, and an unconditional
            # notify here would double every batch's wakeup storm
            if self.leases.report_rate(service_id, tasks_per_s):
                self._notify_all_locked()

    # ---------------- completion ------------------------------------ #
    def _record_done_locked(self, rec: TaskRecord, result, service_id: str,
                            now: float):
        if rec.state == TaskState.LEASED:
            self.leased_count -= 1
        rec.state = TaskState.DONE
        rec.result = None if self.reclaim_done else result
        if self.reclaim_done:
            rec.payload = None
        rec.completed_by = service_id
        self.done_count += 1
        lease = self.leases.finish(rec.task_id)
        if lease is not None:
            self._durations.append(now - lease.start)
        self.completions_per_service[service_id] = (
            self.completions_per_service.get(service_id, 0) + 1)
        return lease

    def _complete_some(self, results: list, service_id: str) -> list:
        """Record ``(task_id, result)`` pairs under ONE lock hold; returns
        the pairs actually recorded (idempotent: first result wins,
        late/speculative duplicates and post-cancel results are dropped).
        Completions wake progress watchers only — leasers parked in
        get_task/get_batch gain nothing from a task finishing, and waking
        all N of them per completion is the O(N²) herd.  The one
        completion they DO care about is the last one: it turns "wait for
        work" into "stream exhausted"."""
        recorded: list[tuple[int, Any]] = []
        obs = self._obs
        spans = None if obs is None else []
        with self._meter:
            now = self._clock.monotonic()
            for task_id, result in results:
                rec = self.records[task_id]
                if rec.state == TaskState.DONE or self._cancelled:
                    continue
                lease = self._record_done_locked(rec, result, service_id,
                                                 now)
                recorded.append((task_id, result))
                if spans is not None:
                    spans.append((task_id,
                                  now if lease is None else lease.start))
            if spans:
                # one lease-duration sample per completion batch: the
                # tasks of one drained dispatch were leased together, so
                # their starts coincide in the common case
                obs.lease_duration_s.observe(now - spans[0][1])
                obs.event("complete", now, service_id, tuple(spans))
            if recorded:
                self._clock.cond_notify_all(self._progress)
                if self._exhausted():
                    self._clock.cond_notify_all(self._work)
        return recorded

    def complete(self, task_id: int, result, service_id: str) -> bool:
        """Idempotent: the first result wins (speculative duplicates are
        dropped).  Returns True if this call recorded the result."""
        if not self._complete_some([(task_id, result)], service_id):
            return False
        if self.on_complete is not None:
            self.on_complete(task_id, result)
        return True

    def complete_batch(self, results: list, service_id: str) -> int:
        """Record a batch of ``(task_id, result)`` pairs under ONE lock
        acquisition and ONE notify — with batched dispatch, per-task
        ``complete`` calls made the repository lock the next bottleneck.
        Returns how many results were recorded (idempotent like
        ``complete``)."""
        recorded = self._complete_some(results, service_id)
        if self.on_complete is not None:
            for task_id, result in recorded:
                self.on_complete(task_id, result)
        return len(recorded)

    # ---------------- rescheduling ---------------------------------- #
    def fail(self, task_id: int, service_id: str) -> None:
        """A service died / errored mid-task: reschedule (the paper's natural
        descheduling point is the task start, so we simply re-enqueue)."""
        with self._meter:
            if self._cancelled:
                self.leases.fail(task_id, service_id)
                return  # a cancelled stream never re-enqueues work
            rec = self.records[task_id]
            if (self.leases.fail(task_id, service_id)
                    and rec.state == TaskState.LEASED):
                rec.state = TaskState.PENDING
                self.leased_count -= 1
                self._pending.append(task_id)
                self.reschedules += 1
                if self._obs is not None:
                    self._obs.event("task-fail", None, service_id, task_id)
                self._notify_all_locked()

    def expire_service(self, service_id: str) -> int:
        """Heartbeat-declared death: expire every lease held (solely) by
        ``service_id`` *now* instead of waiting out the lease deadline.
        This is the LivenessMonitor -> lease machinery hook; returns the
        number of tasks re-enqueued."""
        if self._cancelled:
            return 0
        expired = 0
        with self._meter:
            for tid in self.leases.expire_service(service_id):
                rec = self.records[tid]
                if rec.state != TaskState.LEASED:
                    continue
                rec.state = TaskState.PENDING
                self.leased_count -= 1
                self._pending.append(tid)
                self.reschedules += 1
                expired += 1
            if expired:
                if self._obs is not None:
                    self._obs.event("expire-service", None, service_id,
                                    expired)
                self._notify_all_locked()
        return expired

    # ---------------- waits ------------------------------------------ #
    def wait_all(self, timeout: float | None = None) -> bool:
        deadline = (None if timeout is None
                    else self._clock.monotonic() + timeout)
        with self._progress:
            while self.done_count < self._n_added:
                if self._cancelled:
                    return True  # terminal: nothing left to wait for
                remaining = (None if deadline is None
                             else deadline - self._clock.monotonic())
                if remaining is not None and remaining <= 0:
                    return False
                self._clock.cond_wait(
                    self._progress,
                    remaining if remaining is not None else 1.0)
            return True

    def wait_until(self, predicate, timeout: float | None = None) -> bool:
        """Event-driven wait for an arbitrary progress condition:
        ``predicate(stats_dict)`` is re-evaluated on every repository
        state change (completions, reschedules, leases expiring).  Tests
        use this instead of sleep-polling loops — under load the wait
        stretches, but it can never miss the event or flake."""
        deadline = (None if timeout is None
                    else self._clock.monotonic() + timeout)
        with self._progress:
            while not predicate(self.stats()):
                remaining = (None if deadline is None
                             else deadline - self._clock.monotonic())
                if remaining is not None and remaining <= 0:
                    return False
                self._clock.cond_wait(
                    self._progress,
                    min(remaining, 0.5) if remaining is not None else 0.5)
            return True

    def results(self) -> list:
        return [self.records[i].result for i in sorted(self.records)]

    # ---------------- introspection ---------------------------------- #
    def stats(self) -> dict:
        # every figure here is a counter maintained at event time under
        # the lock and read lock-free — this snapshot is O(services),
        # never O(tasks), and never blocks a lease/complete
        done = self.done_count
        leased = self.leased_count
        meter = self._meter
        return {
            "tasks": self._n_added,
            "done": done,
            "cancelled": self._cancelled,
            # derived, not len(_pending): the queue may briefly hold
            # stale entries for tasks that completed between expiry and
            # re-lease (a cancelled repository reads 0 — its queue is
            # dropped even though interrupted records sit in PENDING)
            "pending": (0 if self._cancelled
                        else self._n_added - done - leased),
            "leased": leased,
            "reschedules": self.reschedules,
            "peak_unfinished": self.peak_unfinished,
            "speculative_issues": self.leases.speculative_issues,
            "straggler_speculations": self.leases.straggler_speculations,
            "service_rates": dict(self.leases._service_rates),
            "per_service": dict(self.completions_per_service),
            # lock meters: real time, even under a virtual clock (see the
            # module docstring)
            "lock_wait_s": meter.wait_s,
            "lock_hold_s": meter.hold_s,
            "lock_contentions": meter.contentions,
            "lock_acquisitions": meter.acquisitions,
        }

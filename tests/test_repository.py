"""TaskRepository: leases, rescheduling, speculation, idempotent results."""

import threading
import time

import pytest

from repro.core import TaskRepository


def test_pull_order_and_results():
    repo = TaskRepository(list(range(5)))
    got = [repo.get_task("s1") for _ in range(5)]
    assert [g[0] for g in got] == list(range(5))
    for tid, payload in got:
        repo.complete(tid, payload * 10, "s1")
    assert repo.all_done
    assert repo.results() == [0, 10, 20, 30, 40]


def test_complete_is_idempotent_first_wins():
    repo = TaskRepository(["a"])
    tid, _ = repo.get_task("s1")
    assert repo.complete(tid, "r1", "s1") is True
    assert repo.complete(tid, "r2", "s2") is False
    assert repo.results() == ["r1"]
    assert repo.stats()["per_service"] == {"s1": 1}


def test_fail_reschedules():
    repo = TaskRepository(["a", "b"])
    tid, _ = repo.get_task("s1")
    repo.fail(tid, "s1")
    tid2, payload = repo.get_task("s2")
    # rescheduled task is available again (possibly after task b)
    seen = {tid2}
    nxt = repo.get_task("s2")
    if nxt:
        seen.add(nxt[0])
    assert tid in seen
    assert repo.stats()["reschedules"] == 1


def test_lease_expiry_reschedules():
    repo = TaskRepository(["a"], lease_s=0.05)
    tid, _ = repo.get_task("s1")
    time.sleep(0.1)
    got = repo.get_task("s2", timeout=1.0)
    assert got is not None and got[0] == tid
    assert repo.stats()["reschedules"] == 1


def test_speculation_issues_duplicate_of_straggler():
    repo = TaskRepository(list(range(5)), lease_s=60.0, speculation_factor=2.0)
    # build a completion-time history
    for _ in range(3):
        tid, p = repo.get_task("fast")
        repo.complete(tid, p, "fast")
    tid, _ = repo.get_task("slow")  # becomes the straggler
    time.sleep(0.05)
    # next puller gets the last pending task first, then a speculative copy
    t5 = repo.get_task("fast")
    assert t5 is not None
    repo.complete(t5[0], 0, "fast")
    spec = repo.get_task("fast", timeout=0.3)
    assert spec is not None and spec[0] == tid
    assert repo.stats()["speculative_issues"] == 1
    # both finish; first result wins
    repo.complete(tid, "fast-result", "fast")
    assert not repo.complete(tid, "slow-result", "slow")


def test_streaming_repo_waits_for_close():
    repo = TaskRepository([], streaming=True)
    assert not repo.all_done
    tid = repo.add_task("x")
    got = repo.get_task("s1")
    assert got == (tid, "x")
    repo.complete(tid, "y", "s1")
    assert not repo.all_done  # stream still open
    repo.close()
    assert repo.all_done


def test_stale_heap_entry_does_not_resurrect_completed_task():
    """The deadline heap deletes lazily: a task completed before its
    deadline must not be rescheduled when the stale entry pops."""
    repo = TaskRepository(["a"], lease_s=0.05)
    tid, p = repo.get_task("s1")
    repo.complete(tid, p, "s1")
    time.sleep(0.1)  # stale heap entry's deadline passes
    assert repo.get_task("s2", timeout=0.05) is None  # all done, no revival
    assert repo.stats()["reschedules"] == 0


def test_re_lease_gets_a_fresh_deadline():
    repo = TaskRepository(["a"], lease_s=0.15)
    t1, _ = repo.get_task("s1")
    time.sleep(0.2)
    t2 = repo.get_task("s2", timeout=1.0)  # expired -> re-leased
    assert t2 is not None and t2[0] == t1
    assert repo.stats()["reschedules"] == 1
    # the first lease's (now stale) heap entry must not expire the fresh
    # lease that s2 just took
    assert repo.get_task("s3", timeout=0.05) is None
    assert repo.stats()["reschedules"] == 1


def test_expire_service_requeues_immediately():
    """LivenessMonitor hook: a heartbeat-declared death frees the dead
    service's leases without waiting out lease_s."""
    repo = TaskRepository(["a", "b", "c"], lease_s=60.0)
    repo.get_task("dead")
    repo.get_task("dead")
    t3, _ = repo.get_task("alive")
    assert repo.expire_service("dead") == 2
    got = {repo.get_task("alive2")[0], repo.get_task("alive2")[0]}
    assert got == {0, 1}
    assert repo.stats()["reschedules"] == 2
    # the live service's lease was untouched
    assert repo.records[t3].state.value == "leased"


def test_get_batch_skipped_tasks_keep_fifo_order():
    repo = TaskRepository(["a1", "b1", "a2", "b2"])
    key = lambda payload: payload[0]  # noqa: E731 - group by first letter
    batch = repo.get_batch("s1", 4, compatible=key)
    assert [p for _, p in batch] == ["a1", "a2"]
    batch2 = repo.get_batch("s1", 4, compatible=key)
    assert [p for _, p in batch2] == ["b1", "b2"]


def test_concurrent_pullers_disjoint_tasks():
    repo = TaskRepository(list(range(50)))
    seen = []
    lock = threading.Lock()

    def worker(sid):
        while True:
            got = repo.get_task(sid, timeout=0.2, allow_speculation=False)
            if got is None:
                return
            with lock:
                seen.append(got[0])
            repo.complete(got[0], None, sid)

    threads = [threading.Thread(target=worker, args=(f"s{i}",)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(seen) == list(range(50))  # every task exactly once


def test_stale_pending_entry_does_not_re_lease_done_task():
    """Expiry re-enqueues a task; a late result from the expired holder
    then completes it (idempotent first-wins).  The dangling pending-
    queue entry must be skipped — never leased again as a DONE task,
    which would double-complete it."""
    repo = TaskRepository(["a", "b"])
    tid, _ = repo.get_task("s1")
    assert repo.expire_service("s1") == 1  # tid back in the queue
    assert repo.complete(tid, "late", "s1") is True  # stale but first
    nxt = repo.get_task("s2", allow_speculation=False)
    assert nxt is not None and nxt[0] != tid  # the DONE task stays done
    assert repo.stats()["leased"] == 1
    repo.complete(nxt[0], "r", "s2")
    assert repo.all_done
    assert repo.stats()["done"] == 2
    assert repo.results() == ["late", "r"]


# ------------------------------------------------------------------ #
# the one repository: exactly-once, fan-in and progress under churn
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("n_tasks", [3, 40])
def test_lone_service_drains_repository_exactly_once(n_tasks):
    """One service drains the whole repository, each task exactly once,
    and the stats aggregate agrees with what it was handed."""
    repo = TaskRepository(list(range(n_tasks)))
    got = []
    while True:
        g = repo.get_task("lone", timeout=0.1, allow_speculation=False)
        if g is None:
            break
        got.append(g[0])
        repo.complete(g[0], g[1] * 2, "lone")
    assert sorted(got) == list(range(n_tasks))
    assert repo.all_done
    assert repo.results() == [2 * t for t in range(n_tasks)]
    st = repo.stats()
    assert st["tasks"] == st["done"] == n_tasks
    assert st["leased"] == st["pending"] == st["reschedules"] == 0
    assert st["per_service"] == {"lone": n_tasks}


def test_exactly_once_under_churn_fuzz():
    """Real threads leasing while a churn thread expires their services:
    every task completes exactly once, no lease leaks."""
    import random

    n_tasks, n_workers = 400, 8
    repo = TaskRepository(list(range(n_tasks)), lease_s=60.0)
    completions: list[int] = []
    reclock = threading.Lock()
    stop = threading.Event()

    def worker(sid):
        while not repo.all_done:
            got = repo.get_task(sid, timeout=0.05,
                                allow_speculation=False)
            if got is None:
                continue
            if repo.complete(got[0], got[0], sid):
                with reclock:
                    completions.append(got[0])

    def churn():
        rng = random.Random(7)
        while not stop.is_set():
            repo.expire_service(f"w{rng.randrange(n_workers)}")
            time.sleep(0.002)

    workers = [threading.Thread(target=worker, args=(f"w{i}",))
               for i in range(n_workers)]
    churner = threading.Thread(target=churn)
    churner.start()
    for t in workers:
        t.start()
    for t in workers:
        t.join()
    stop.set()
    churner.join()
    assert sorted(completions) == list(range(n_tasks))  # exactly once
    st = repo.stats()
    assert st["done"] == n_tasks and st["leased"] == 0
    assert st["pending"] == 0
    assert len(repo.leases) == 0


def test_expire_service_requeues_all_its_leases_leak_free():
    """Dead leases interleaved with live ones: one expire_service call
    requeues exactly the dead service's leases and leaks nothing."""
    repo = TaskRepository(list(range(12)), lease_s=60.0)
    dead, alive = [], []
    for _ in range(6):
        dead.append(repo.get_task("dead", allow_speculation=False)[0])
        alive.append(repo.get_task("alive", allow_speculation=False)[0])
    assert repo.expire_service("dead") == 6
    assert repo.expire_service("dead") == 0  # nothing left to expire
    st = repo.stats()
    assert st["leased"] == 6 and st["reschedules"] == 6
    reclaimed = set()
    for _ in range(6):
        g = repo.get_task("rescuer", timeout=0.1, allow_speculation=False)
        reclaimed.add(g[0])
    assert reclaimed == set(dead)
    assert all(repo.records[t].state.value == "leased" for t in alive)
    assert repo.get_task("rescuer", timeout=0.05,
                         allow_speculation=False) is None


def test_cancel_drops_pending_and_late_results():
    """cancel() drops the pending queue and the lease table; nothing
    dispatches afterwards and late results are dropped."""
    repo = TaskRepository(list(range(20)), lease_s=60.0)
    leased = [repo.get_task("s1", allow_speculation=False)
              for _ in range(5)]
    assert repo.cancel() == 15  # 20 - 5 leased
    assert repo.cancel() == 0  # idempotent
    assert repo.all_done and repo.cancelled
    st = repo.stats()
    assert st["pending"] == 0 and st["leased"] == 0
    assert len(repo.leases) == 0
    assert repo.get_task("s2", timeout=0.05) is None
    for tid, payload in leased:
        assert repo.complete(tid, payload, "s1") is False
    assert repo.stats()["done"] == 0
    with pytest.raises(RuntimeError):
        repo.add_task("late")


def test_batch_keeps_groups_over_interleaved_payloads():
    """Interleaved payload groups: each batch holds one group only, and
    the skipped tasks stay pending in their original order."""
    repo = TaskRepository(["a1", "b1", "a2", "b2", "a3", "b3"])
    key = lambda p: p[0]  # noqa: E731
    # a full batch of 3 leaves b3 pending; b1 and b2 go back ahead of it
    batch = repo.get_batch("s1", 3, compatible=key)
    assert [p for _, p in batch] == ["a1", "a2", "a3"]
    batch2 = repo.get_batch("s1", 3, compatible=key)
    assert [p for _, p in batch2] == ["b1", "b2", "b3"]
    assert repo.stats()["leased"] == 6


def test_speculation_rescues_every_straggler():
    """Every stuck lease gets a speculative duplicate, first result wins,
    and the repository drains."""
    repo = TaskRepository(list(range(7)), lease_s=60.0,
                          speculation_factor=0.0)
    # the age arm needs >= 3 observed durations
    for _ in range(3):
        tid, p = repo.get_task("warm", allow_speculation=False)
        repo.complete(tid, p, "warm")
    stuck = {repo.get_task("slow", allow_speculation=False)[0]
             for _ in range(4)}
    rescued = set()
    for _ in range(4):
        g = repo.get_task("fast", timeout=0.5)
        assert g is not None
        rescued.add(g[0])
        repo.complete(g[0], None, "fast")
    assert rescued == stuck
    assert repo.stats()["speculative_issues"] == 4
    assert repo.all_done
    for tid in stuck:  # the stragglers' late results are dropped
        assert repo.complete(tid, "late", "slow") is False


def test_lock_meters_in_stats():
    """The contention instrumentation on the repository lock is always
    on."""
    repo = TaskRepository(list(range(10)))
    while True:
        g = repo.get_task("s1", timeout=0.05, allow_speculation=False)
        if g is None:
            break
        repo.complete(g[0], None, "s1")
    st = repo.stats()
    assert st["lock_acquisitions"] > 0
    assert st["lock_hold_s"] > 0.0
    assert st["lock_wait_s"] >= 0.0
    assert st["lock_contentions"] >= 0
    assert "shards" not in st


def test_streaming_backpressure_and_wait_all():
    """The progress condition: a feeder throttled by
    wait_unfinished_below and a watcher in wait_all both see the
    consumer's completions."""
    repo = TaskRepository([], streaming=True)
    done = threading.Event()

    def consumer():
        while not repo.all_done:
            g = repo.get_task("c", timeout=0.05, allow_speculation=False)
            if g is not None:
                repo.complete(g[0], None, "c")
        done.set()

    t = threading.Thread(target=consumer)
    t.start()
    for burst in range(10):
        assert repo.wait_unfinished_below(8, timeout=10.0)
        repo.add_tasks(list(range(burst * 4, burst * 4 + 4)))
    repo.close()
    assert repo.wait_all(timeout=10.0)
    t.join(timeout=10.0)
    assert done.is_set()
    assert repo.stats()["done"] == 40

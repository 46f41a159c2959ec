"""Scale fuzz: the farm under NoW-sized pools with seeded churn.

CI-sized companions to ``benchmarks/scale.py`` (which drives 1,000
services and a 1M-task stream): everything here runs the real scheduler
stack over the deterministic ``sim://`` backend with pools of ~100
services and streams of a few thousand tasks, pinning the invariants the
incremental rebalance work must preserve:

- **exactly-once under churn** — loud deaths, silent deaths and late
  joins (seeded ``FaultSpec`` schedules) over a streaming job deliver
  every task exactly once with the correct result;
- **trace determinism** — the same seed reproduces the identical lease
  trace and scheduler event trace, churn and all, and the incremental
  arbiter is byte-identical to the legacy full recompute;
- **bounded recomputes** — a join burst of N services costs O(1) arbiter
  recomputes (the coalescing window), never O(N), and the maintained
  service order is never re-sorted end-to-end;
- **O(1) bookkeeping regressions** — the streaming demand counter on a
  10k-task job, and the pool's cached membership snapshots staying
  identical objects until a membership event.
"""

import hashlib

import pytest

from repro.core import Program
from repro.sim import FaultSpec, SimCluster

PROG = Program(lambda x: x * 3.0 + 1.0, name="affine", jit=False)


def _churn_faults(n_services: int) -> dict[int, FaultSpec]:
    faults = {i: FaultSpec(die_at=0.2) for i in range(6)}
    faults.update({i: FaultSpec(die_at=0.3, silent=True, hang_s=2.0)
                   for i in range(6, 11)})
    faults.update({i: FaultSpec(register_at=0.15)
                   for i in range(n_services - 8, n_services)})
    return faults


def _run_churn(seed: int, *, incremental: bool = True,
               n_services: int = 96, n_tasks: int = 3000,
               window: int = 2048):
    """One streaming job over a churning pool; returns the delivered
    {tid: result} map, both event traces, and the rebalance counters."""
    faults = _churn_faults(n_services)
    base_cost_s = 0.6 * n_services / n_tasks
    with SimCluster(speed_factors=[1.0] * n_services, seed=seed,
                    base_cost_s=base_cost_s, latency_s=0.0,
                    faults=faults, stall_timeout_s=120.0) as cluster:
        sched = cluster.make_scheduler(
            max_batch=8, max_inflight=1, adaptive_batching=False,
            speculation=True, incremental_arbiter=incremental)
        with sched:
            job = sched.submit(PROG, None, collect_results=True)
            job.submit_stream((float(i) for i in range(n_tasks)),
                              window=window)
            got = {}
            for tid, result in job.as_completed():
                assert tid not in got, f"task {tid} delivered twice"
                got[tid] = result
            job.wait(timeout=300)
            counters = {
                "rebalances": sched.rebalances,
                "requests": sched.rebalance_requests,
                "resorts": (sched._arbiter.resorts if incremental
                            else None),
            }
            cluster.clock.sleep(5.0)  # drain silent-death hangs
            repo = job.repository.stats()
            counters["done"], counters["leased"] = repo["done"], repo["leased"]
            traces = (tuple(cluster.trace), tuple(sched.trace))
    return got, traces, counters


@pytest.mark.parametrize("n_services,n_tasks,window,seed",
                         [(96, 3000, 2048, 11), (64, 2000, 1024, 31)])
def test_churn_exactly_once_and_deterministic(n_services, n_tasks, window,
                                              seed):
    """Loud and silent deaths, late joins, batched leases, speculation:
    every task delivered exactly once, and the same seed reproduces the
    identical lease and scheduler traces."""
    knobs = dict(n_services=n_services, n_tasks=n_tasks, window=window)
    got, traces, counters = _run_churn(seed, **knobs)
    assert len(got) == n_tasks
    for tid, result in got.items():
        assert float(result) == tid * 3.0 + 1.0
    assert counters["done"] == n_tasks and counters["leased"] == 0
    # same seed, same everything — churn included
    got2, traces2, counters2 = _run_churn(seed, **knobs)
    assert got2 == got
    assert traces2 == traces
    assert counters2 == counters


def test_churn_incremental_matches_full_recompute():
    _, traces_inc, counters = _run_churn(23)
    _, traces_full, _ = _run_churn(23, incremental=False)
    assert traces_full == traces_inc
    # ~96 joins + 11 deaths + late joins never re-sort the maintained
    # order, and coalescing keeps actual recomputes far below requests
    assert counters["resorts"] == 0
    assert counters["requests"] >= 96
    assert counters["rebalances"] <= 25


def test_join_burst_coalesces_to_o1_recomputes():
    """40 services registering at the same virtual instant must collapse
    into a handful of arbiter recomputes, not 40."""
    n_late = 40
    faults = {4 + i: FaultSpec(register_at=0.3) for i in range(n_late)}
    with SimCluster(speed_factors=[1.0] * (4 + n_late), seed=5,
                    base_cost_s=4.0 / 2000, latency_s=0.0,
                    faults=faults, stall_timeout_s=120.0) as cluster:
        sched = cluster.make_scheduler(max_batch=8, max_inflight=1,
                                       adaptive_batching=False,
                                       speculation=False)
        with sched:
            job = sched.submit(PROG, [float(i) for i in range(2000)])
            job.wait(timeout=300)
            cluster.clock.sleep(2.0)
            assert job.stats()["done"] == 2000
            assert sched.n_services == 4 + n_late
            assert sched.rebalance_requests >= n_late
            assert sched.rebalances <= 10, (
                f"{sched.rebalances} recomputes for a {n_late}-join "
                "burst — coalescing regressed")


def test_stream_demand_counter_10k_tasks():
    """``Job._demand()`` is a counter, not a table walk: an open stream
    reports unbounded, and a closed 10k-task stream counts down to 0."""
    with SimCluster(speed_factors=[1.0] * 4, seed=3, base_cost_s=1e-4,
                    latency_s=0.0, stall_timeout_s=120.0) as cluster:
        sched = cluster.make_scheduler(max_batch=16, max_inflight=1,
                                       adaptive_batching=False,
                                       speculation=False)
        with sched:
            job = sched.submit(PROG, None, collect_results=False)
            assert job._demand() is None  # open stream: unbounded
            job.submit_stream((float(i) for i in range(10_000)),
                              window=1024)
            job.wait(timeout=300)
            stats = job.stats()
            assert stats["done"] == 10_000
            assert job._demand() == 0  # closed + drained


def test_pool_membership_snapshots_cached_until_change():
    """``ServicePool.ids()``/``capacities()`` return the same objects
    call-over-call (rebalances at 1k services must not copy the pool),
    and a membership event replaces them and bumps ``version()``."""
    faults = {0: FaultSpec(die_at=0.3)}
    with SimCluster(speed_factors=[1.0] * 4, seed=5, base_cost_s=0.05,
                    latency_s=0.0, faults=faults,
                    stall_timeout_s=120.0) as cluster:
        sched = cluster.make_scheduler(speculation=False)
        with sched:
            job = sched.submit(PROG, [float(i) for i in range(40)])
            pool = sched.pool
            v0 = pool.version()
            ids0 = pool.ids()
            caps0 = pool.capacities()
            assert pool.ids() is ids0
            assert pool.capacities() is caps0
            job.wait(timeout=300)
            cluster.clock.sleep(1.0)  # let the death land
            assert pool.version() > v0
            assert pool.ids() is not ids0
            assert "sim0" not in pool.ids()
            assert job.stats()["done"] == 40


# ------------------------------------------------------------------ #
# golden sim:// lease trace
# ------------------------------------------------------------------ #

# SHA-256 over the golden sim:// scenario's lease trace, captured on the
# single-lock engine.  Any change to lock scopes, wait sequences or lease
# timestamps moves it: a refactor of the repository must reproduce it
# byte for byte.
GOLDEN_SHA256 = (
    "272110425a85dabb62c84e5cd537dc298bee27c8993df7037af92d535ab4685e")
GOLDEN_EVENTS = 808


def golden_run(seed: int = 17, n_services: int = 24, n_tasks: int = 800,
               **job_knobs):
    """One churny streaming job on the sim backend (deaths + a late
    join, batched leases, speculation on); returns (results, trace hash,
    event count).  Runs the REAL engine under the virtual clock — any
    change to lock scopes, wait sequences, or lease timestamps shows up
    in the hash."""
    prog = Program(lambda x: x * 3.0 + 1.0, name="affine", jit=False)
    faults = {0: FaultSpec(die_at=0.2), 1: FaultSpec(die_at=0.25),
              n_services - 1: FaultSpec(register_at=0.15)}
    with SimCluster(speed_factors=[1.0] * n_services, seed=seed,
                    base_cost_s=0.5 * n_services / n_tasks, latency_s=0.0,
                    faults=faults, stall_timeout_s=300.0) as cluster:
        sched = cluster.make_scheduler(
            max_batch=4, max_inflight=1, adaptive_batching=False,
            speculation=True)
        with sched:
            job = sched.submit(prog, None, collect_results=True, **job_knobs)
            job.submit_stream((float(i) for i in range(n_tasks)),
                              window=256)
            got = {}
            for tid, result in job.as_completed():
                got[tid] = result
            job.wait(timeout=300)
            cluster.clock.sleep(3.0)
            trace = tuple(cluster.trace)
    h = hashlib.sha256()
    for item in trace:
        h.update(repr(item).encode())
    return got, h.hexdigest(), len(trace)


def test_sim_lease_trace_identical_to_golden():
    """The golden churny sim scenario's lease trace must match the hash
    pinned on the single-lock engine, byte for byte."""
    got, digest, n_events = golden_run()
    assert len(got) == 800
    assert (digest, n_events) == (GOLDEN_SHA256, GOLDEN_EVENTS), (
        "sim lease trace diverged from the pinned single-lock engine")

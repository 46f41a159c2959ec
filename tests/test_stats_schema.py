"""Every stats() surface matches its documented key set.

``repro.obs.schema`` is the single source of truth for the snapshot
shapes the benchmark JSON and dashboards consume.  This test runs a
small multi-tenant ``sim://`` farm (so every subtree is populated:
batching, jobs, arbiter, recorder) and walks the trees — a key rename
anywhere fails here naming the drifted surface, instead of silently
zeroing a downstream column.
"""

from __future__ import annotations

import pytest

from repro.core import Program
from repro.obs import Observability
from repro.obs.schema import (ENGINE_KEYS, ENGINE_OPTIONAL_KEYS,
                              EVENT_KINDS, VIRTUAL_CLOCK_KEYS, SchemaError,
                              _check, validate_engine_stats,
                              validate_job_stats,
                              validate_repository_stats)
from repro.sim import SimCluster

PROGRAM = Program(lambda x: x * 2.0, name="dbl", jit=False)


@pytest.fixture(scope="module")
def farm_snapshots():
    """One churny two-job run; returns every stats() tree we document."""
    obs = Observability()
    with SimCluster(speed_factors=[1.0, 1.0, 2.0], seed=5,
                    base_cost_s=0.002, obs=obs) as cluster:
        with cluster.make_scheduler(max_batch=4) as sched:
            jobs = [sched.submit(PROGRAM, [float(i) for i in range(30)],
                                 weight=w) for w in (1.0, 2.0)]
            for job in jobs:
                job.wait(timeout=600)
            engine = sched.stats()
            job_stats = [job.stats() for job in jobs]
            repo_stats = [job.repository.stats() for job in jobs]
        clock_stats = cluster.clock.stats()
    return {"engine": engine, "jobs": job_stats, "repos": repo_stats,
            "clock": clock_stats, "obs": obs}


def test_engine_tree_matches_schema(farm_snapshots):
    engine = farm_snapshots["engine"]
    validate_engine_stats(engine)  # walks batching/jobs/arbiter/trace
    assert set(engine) == ENGINE_KEYS | ENGINE_OPTIONAL_KEYS  # obs attached
    assert engine["arbiter"] is not None  # multi-tenant: arbiter ran


def test_job_and_repository_trees_match_schema(farm_snapshots):
    for js in farm_snapshots["jobs"]:
        validate_job_stats(js)
    for rs in farm_snapshots["repos"]:
        validate_repository_stats(rs)
        assert rs["done"] == rs["tasks"] == 30


def test_virtual_clock_stats_match_schema(farm_snapshots):
    _check("virtual_clock", farm_snapshots["clock"], VIRTUAL_CLOCK_KEYS)


def test_every_recorded_event_kind_is_documented(farm_snapshots):
    obs = farm_snapshots["obs"]
    kinds = {ev[1] for ev in obs.events()}
    undocumented = kinds - set(EVENT_KINDS)
    assert not undocumented, (
        f"events emitted outside the documented taxonomy: "
        f"{sorted(undocumented)} — add them to repro.obs.schema."
        f"EVENT_KINDS")
    assert {"lease", "complete", "dispatch", "drain", "recruit",
            "job-submit", "rebalance"} <= kinds


def test_metrics_snapshot_is_versioned(farm_snapshots):
    metrics = farm_snapshots["engine"]["metrics"]
    assert metrics["schema"] == "jjpf.metrics/v1"
    assert set(metrics) == {"schema", "counters", "gauges", "histograms"}
    assert {"queue_wait_s", "lease_duration_s", "dispatch_latency_s",
            "batch_size"} <= set(metrics["histograms"])


def test_schema_error_names_the_drifted_surface():
    with pytest.raises(SchemaError, match="repository"):
        validate_repository_stats({"tasks": 1})
    engine = {"schema": "jjpf.stats/v0"}
    with pytest.raises(SchemaError):
        validate_engine_stats(engine)

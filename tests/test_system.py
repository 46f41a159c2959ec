"""End-to-end behaviour of the JJPF system (the paper's workload)."""

import threading
import time

import jax.numpy as jnp
import pytest

from repro.core import (ApplicationManager, BasicClient, Farm, FarmExecutor,
                        LookupService, ParDegreeContract, Pipe, Program, Seq,
                        Service, interpret)


@pytest.fixture
def cluster():
    lookup = LookupService()
    services = [Service(lookup) for _ in range(3)]
    for s in services:
        s.start()
    return lookup, services


def test_two_line_api(cluster):
    lookup, _ = cluster
    out = []
    # the paper's two lines:
    cm = BasicClient(Program(lambda x: x * 2 + 1), None,
                     [jnp.asarray(i) for i in range(30)], out, lookup=lookup)
    cm.compute(timeout=120)
    assert [int(v) for v in out] == [2 * i + 1 for i in range(30)]


def test_join_waits_out_trailing_duplicate():
    # the slow service's copy of its task is overtaken by a speculative
    # duplicate on the fast one, so compute() returns while it still runs
    lookup = LookupService()
    fast = Service(lookup, service_id="fast")
    slow = Service(lookup, service_id="slow", task_delay_s=1.0)
    for s in (fast, slow):
        s.start()

    def inc(x):
        time.sleep(0.02)  # both services are leasing before the queue ends
        return x + 1

    out = []
    cm = BasicClient(Program(inc, jit=False), None, list(range(20)), out,
                     lookup=lookup)
    cm.compute(timeout=60)
    assert out == [i + 1 for i in range(20)]
    assert cm.stats()["speculative_issues"] == 1
    assert slow.tasks_executed == 0  # still asleep inside its task
    cm.join(timeout=10)
    assert slow.tasks_executed == 1
    assert not [t for t in threading.enumerate()
                if t.name.startswith("farm-slow")]


def test_skeleton_composition_runs_normalized(cluster):
    lookup, _ = cluster
    skel = Pipe(Farm(Seq(Program(lambda x: x + 1, name="inc"))),
                Seq(Program(lambda x: x * 3, name="tri")))
    tasks = [jnp.asarray(float(i)) for i in range(10)]
    expected = interpret(skel, tasks)
    out = []
    cm = BasicClient(skel, None, tasks, out, lookup=lookup)
    cm.compute(timeout=120)
    assert [float(v) for v in out] == [float(v) for v in expected]
    assert cm.fused_stages == 2


def test_fault_tolerance_mid_run(cluster):
    lookup, services = cluster
    services[0].fail_after(2)
    out = []
    prog = Program(lambda x: x + 100)
    cm = BasicClient(prog, None, [jnp.asarray(i) for i in range(40)], out,
                     lookup=lookup, lease_s=5.0)
    cm.compute(timeout=120)
    assert [int(v) for v in out] == [i + 100 for i in range(40)]


def test_all_services_die_then_replacement_arrives(cluster):
    lookup, services = cluster
    for s in services:
        s.kill()
    out = []
    cm = BasicClient(Program(lambda x: x * 2), None,
                     [jnp.asarray(i) for i in range(5)], out, lookup=lookup)

    def later():
        time.sleep(0.3)
        Service(lookup).start()  # fresh node joins the cluster

    threading.Thread(target=later, daemon=True).start()
    cm.compute(timeout=120)
    assert [int(v) for v in out] == [2 * i for i in range(5)]


def test_futures_streaming(cluster):
    lookup, _ = cluster
    with FarmExecutor(Program(lambda x: x - 1), lookup=lookup) as ex:
        futs = [ex.submit(jnp.asarray(i)) for i in range(12)]
        vals = [int(f.result(timeout=60)) for f in futs]
    assert vals == [i - 1 for i in range(12)]


def test_contract_limits_parallelism(cluster):
    lookup, services = cluster
    contract = ParDegreeContract(parallelism=1)
    out = []
    cm = BasicClient(Program(lambda x: x), contract,
                     [jnp.asarray(i) for i in range(10)], out, lookup=lookup)
    cm.compute(timeout=120)
    # only one service should have been recruited
    assert len(cm.stats()["per_service"]) == 1


def test_application_manager_recruits_replacements():
    lookup = LookupService()
    s1 = Service(lookup)
    s1.start()
    s1.fail_after(1)
    out = []
    tasks = [jnp.asarray(i) for i in range(6)]
    cm = BasicClient(Program(lambda x: x * 5), ParDegreeContract(2), tasks,
                     out, lookup=lookup, lease_s=5.0, elastic=False)
    mgr = ApplicationManager(cm, interval_s=0.02)
    mgr.start()

    def later():
        time.sleep(0.2)
        Service(lookup).start()

    threading.Thread(target=later, daemon=True).start()
    cm.compute(timeout=120)
    mgr.stop()
    assert [int(v) for v in out] == [5 * i for i in range(6)]


def test_load_balancing_pull_scheduling():
    """Heterogeneous services: the fast one completes more tasks."""
    lookup = LookupService()
    fast = Service(lookup, task_delay_s=0.001, service_id="fast")
    slow = Service(lookup, task_delay_s=0.05, service_id="slow")
    fast.start()
    slow.start()
    out = []
    cm = BasicClient(Program(lambda x: x), None,
                     [jnp.asarray(i) for i in range(40)], out, lookup=lookup,
                     speculation=False)
    cm.compute(timeout=120)
    per = cm.stats()["per_service"]
    assert per.get("fast", 0) > per.get("slow", 0)
    assert sum(per.values()) == 40

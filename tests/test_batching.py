"""The batched async execution engine: batch leasing, vmap execution,
shape-keyed compile cache, adaptive batch sizing, fault rescheduling."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AdaptiveBatchController, BasicClient, Farm,
                        LookupService, Pipe, Program, Seq, Service,
                        TaskRepository, interpret, payload_signature)
from repro.core.batching import (bucket_size, pad_stacked, pow2_floor,
                                 speed_capped_max_batch, stack_payloads,
                                 unstack_results)


# ------------------------------------------------------------------ #
# repository: batch leasing
# ------------------------------------------------------------------ #
def test_get_batch_leases_up_to_max():
    repo = TaskRepository(list(range(10)))
    batch = repo.get_batch("s1", 4)
    assert [tid for tid, _ in batch] == [0, 1, 2, 3]
    batch2 = repo.get_batch("s1", 100)
    assert [tid for tid, _ in batch2] == [4, 5, 6, 7, 8, 9]


def test_get_batch_groups_compatible_payloads():
    payloads = [jnp.zeros(2), jnp.zeros(2), jnp.zeros(3), jnp.zeros(2)]
    repo = TaskRepository(payloads)
    batch = repo.get_batch("s1", 4, compatible=payload_signature)
    # the shape-(3,) task must not be stacked with the shape-(2,) ones
    assert [tid for tid, _ in batch] == [0, 1, 3]
    batch2 = repo.get_batch("s1", 4, compatible=payload_signature)
    assert [tid for tid, _ in batch2] == [2]


def test_get_batch_max1_degenerates_to_get_task():
    repo = TaskRepository(["a", "b"])
    assert repo.get_batch("s1", 1) == [(0, "a")]


def test_complete_batch_idempotent():
    repo = TaskRepository(list(range(4)))
    batch = repo.get_batch("s1", 4)
    assert repo.complete_batch([(t, p * 10) for t, p in batch], "s1") == 4
    # duplicates (speculative copies) are dropped
    assert repo.complete_batch([(0, -1), (1, -1)], "s2") == 0
    assert repo.results() == [0, 10, 20, 30]
    assert repo.stats()["per_service"] == {"s1": 4}


def test_batch_release_on_failure_reschedules_all():
    repo = TaskRepository(list(range(6)))
    batch = repo.get_batch("dying", 4)
    for tid, _ in batch:
        repo.fail(tid, "dying")
    assert repo.stats()["reschedules"] == 4
    # every task is leasable again by a healthy service
    seen = set()
    while True:
        b = repo.get_batch("healthy", 6, timeout=0.1)
        if b is None:
            break
        for tid, p in b:
            seen.add(tid)
            repo.complete(tid, p, "healthy")
    assert seen == set(range(6))


# ------------------------------------------------------------------ #
# batching helpers
# ------------------------------------------------------------------ #
def test_bucket_size_powers_of_two():
    assert [bucket_size(n, 16) for n in (1, 2, 3, 5, 8, 9, 16)] == \
        [1, 2, 4, 8, 8, 16, 16]
    # beyond the cap: no padding (the lease itself never exceeds max_batch)
    assert bucket_size(12, 12) == 12


def test_bucket_padding_at_exact_power_of_two_boundary_is_noop():
    """A lease that already sits on a bucket boundary must not pad: the
    bucket is its own size, and pad_stacked returns the input untouched
    (no copy, no extra rows computed)."""
    for n in (1, 2, 4, 8):
        assert bucket_size(n, 8) == n
    stacked = stack_payloads([jnp.asarray([float(i)]) for i in range(8)])
    assert pad_stacked(stacked, 8, 8) is stacked
    svc = _service()
    prog = Program(lambda x: x + 1, name="incb")
    out = svc.execute_batch(prog, [jnp.asarray(float(i)) for i in range(8)],
                            pad_to=8)
    assert [float(v) for v in out] == [i + 1.0 for i in range(8)]
    assert svc.tasks_executed == 8


def test_unstack_results_on_scalar_leaf_pytrees():
    """A vmapped scalar program returns shape-(n,) leaves; unstacking must
    yield 0-d per-task results (not 1-element arrays), across arbitrary
    pytree structure."""
    batched = {"y": jnp.arange(3.0), "aux": (jnp.asarray([10, 20, 30]),)}
    rows = unstack_results(batched, 3)
    assert len(rows) == 3
    assert rows[1]["y"].shape == ()
    assert float(rows[1]["y"]) == 1.0
    assert int(rows[2]["aux"][0]) == 30
    # and a stack -> unstack roundtrip of 0-d payloads is the identity
    payloads = [{"x": jnp.asarray(float(i))} for i in range(4)]
    rows2 = unstack_results(stack_payloads(payloads), 4)
    assert [float(r["x"]) for r in rows2] == [0.0, 1.0, 2.0, 3.0]


def test_pad_stacked_repeats_last_row():
    stacked = stack_payloads([jnp.asarray([1.0, 2.0]), jnp.asarray([3.0, 4.0])])
    padded = pad_stacked(stacked, 2, 4)
    np.testing.assert_array_equal(
        np.asarray(padded), [[1.0, 2.0], [3.0, 4.0], [3.0, 4.0], [3.0, 4.0]])


def test_payload_signature_distinguishes_shape_dtype_tree():
    a = payload_signature(jnp.zeros((2, 3)))
    assert a == payload_signature(jnp.ones((2, 3)))
    assert a != payload_signature(jnp.zeros((3, 2)))
    assert a != payload_signature(jnp.zeros((2, 3), jnp.int32))
    assert (payload_signature({"x": jnp.zeros(2)})
            != payload_signature([jnp.zeros(2)]))


# ------------------------------------------------------------------ #
# service: vmap execution + shape-keyed compile cache
# ------------------------------------------------------------------ #
def _service():
    return Service(LookupService())


def test_execute_batch_matches_per_task_results():
    svc = _service()
    prog = Program(lambda x: jnp.sin(x) * 2 + 1, name="trig")
    payloads = [jnp.asarray(float(i)) for i in range(5)]
    batched = svc.execute_batch(prog, payloads)
    per_task = [svc.execute(prog, p) for p in payloads]
    for b, s in zip(batched, per_task):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(s))


def test_execute_batch_padded_results_match():
    svc = _service()
    prog = Program(lambda x: x * x, name="sq")
    payloads = [jnp.asarray(float(i)) for i in range(3)]
    out = svc.execute_batch(prog, payloads, pad_to=8)
    assert [float(v) for v in out] == [0.0, 1.0, 4.0]
    assert svc.tasks_executed == 3  # padding rows are not tasks


def test_compile_cache_keyed_by_shape_and_batch():
    svc = _service()
    prog = Program(lambda x: x + 1, name="inc")
    p2 = [jnp.zeros(2), jnp.zeros(2)]
    p3 = [jnp.zeros(3), jnp.zeros(3)]

    svc.execute_batch(prog, p2)
    assert (svc.cache_hits, svc.cache_misses) == (0, 1)
    svc.execute_batch(prog, p2)  # same (program, shape, batch) -> hit
    assert (svc.cache_hits, svc.cache_misses) == (1, 1)
    svc.execute_batch(prog, p3)  # new payload shape -> miss
    assert (svc.cache_hits, svc.cache_misses) == (1, 2)
    svc.execute_batch(prog, p3 + [jnp.zeros(3)])  # new batch size -> miss
    assert (svc.cache_hits, svc.cache_misses) == (1, 3)
    svc.execute(prog, jnp.zeros(2))  # per-task path has its own key
    assert (svc.cache_hits, svc.cache_misses) == (1, 4)
    svc.execute(prog, jnp.zeros(2))
    assert (svc.cache_hits, svc.cache_misses) == (2, 4)


def test_compile_cache_distinguishes_programs_not_ids():
    """Two programs must never share cache entries, even if one is GC'd
    and the other reuses its memory address (the old id() key bug)."""
    svc = _service()
    a = Program(lambda x: x + 1, name="p")
    b = Program(lambda x: x - 1, name="p")  # same NAME, different program
    assert float(svc.execute(a, jnp.asarray(1.0))) == 2.0
    assert float(svc.execute(b, jnp.asarray(1.0))) == 0.0
    assert svc.cache_misses == 2


# ------------------------------------------------------------------ #
# adaptive controller
# ------------------------------------------------------------------ #
def _converge(controller, latency_fn, rounds=30):
    sizes = []
    for _ in range(rounds):
        b = controller.next_batch()
        controller.record(b, latency_fn(b))
        sizes.append(controller.next_batch())
    return sizes


def test_controller_converges_and_holds():
    c = AdaptiveBatchController(max_batch=64, target_latency_s=0.1)
    # linear latency model: 1 ms fixed + 3 ms per task
    sizes = _converge(c, lambda b: 0.001 + 0.003 * b)
    # converged: last 10 suggestions identical, inside the latency band
    assert len(set(sizes[-10:])) == 1
    final = sizes[-1]
    assert 0.05 <= 0.001 + 0.003 * final <= 0.1


def test_controller_heterogeneous_speed_factors():
    """Services that differ only in per-task cost converge to batch sizes
    ordered opposite to their cost — the slow node never hoards a big
    lease, which is what keeps pull-scheduling balanced."""
    fast = AdaptiveBatchController(max_batch=64, target_latency_s=0.1)
    slow = AdaptiveBatchController(max_batch=64, target_latency_s=0.1)
    fast_sizes = _converge(fast, lambda b: 0.001 + 0.001 * b)
    slow_sizes = _converge(slow, lambda b: 0.001 + 0.02 * b)
    assert len(set(fast_sizes[-5:])) == 1 and len(set(slow_sizes[-5:])) == 1
    assert slow_sizes[-1] < fast_sizes[-1]
    assert fast_sizes[-1] == 64  # nearly-free tasks: grow to the cap


def test_controller_ignores_partial_tail_batches():
    c = AdaptiveBatchController(max_batch=8, initial=8, target_latency_s=0.1)
    c.record(2, 5.0)  # a tiny tail batch that took forever
    assert c.next_batch() == 8  # not evidence about full leases


def test_controller_pinned_when_min_equals_max():
    """min_batch == max_batch leaves no room to steer: whatever the
    latency says, the batch must stay pinned (and never crash)."""
    c = AdaptiveBatchController(min_batch=4, max_batch=4,
                                target_latency_s=0.1)
    assert c.next_batch() == 4
    for elapsed in (0.0, 0.001, 0.1, 50.0):
        c.record(4, elapsed)
        assert c.next_batch() == 4
    assert c.batches_recorded == 4


def test_controller_zero_elapsed_record_is_safe():
    """A batch observed at 0 elapsed (virtual clock tick, or clock
    granularity) must not divide by zero; it reads as infinitely fast and
    grows the batch."""
    c = AdaptiveBatchController(max_batch=16, initial=1,
                                target_latency_s=0.1)
    c.record(1, 0.0)
    assert c.next_batch() == 2
    assert c.throughput_ewma > 0
    c.record(0, 1.0)  # n_tasks=0 is a no-op, not a crash
    assert c.batches_recorded == 1


def test_controller_throughput_jump_skips_doubling_ladder():
    """Once the throughput EWMA is trusted (3 batches), a growth step
    jumps straight to the throughput-implied batch instead of doubling —
    O(1) convergence for fast services on short streams."""
    c = AdaptiveBatchController(max_batch=64, target_latency_s=0.1)
    for _ in range(3):  # establish the EWMA at ~1000 tasks/s
        c.record(c.next_batch(), c.next_batch() * 0.001)
    # growth step: ideal = ~1000 * 0.1 = ~100 -> pow2 floor capped at 64,
    # far beyond the plain doubling (4 -> 8)
    assert c.next_batch() > 8


@pytest.mark.parametrize("curve,check", [
    # cost flat in batch size: climbs to the ceiling
    (lambda b, i: 0.16 + 0.004 * (b - 1),
     lambda sizes: 16 in sizes[:10]),
    # cost linear in batch size: holds at one or two tasks
    (lambda b, i: 0.16 * b,
     lambda sizes: max(sizes) <= 2),
    # flat, every other batch waits 0.16 s behind a neighbour's
    (lambda b, i: 0.16 + 0.16 * (i % 2),
     lambda sizes: 16 in sizes
     and min(sizes[sizes.index(16):]) >= 8),
], ids=["flat", "linear", "flat-with-wait"])
def test_controller_steers_by_one_task_time(curve, check):
    """One task alone overruns half the 0.05 s target, so the controller
    steers by four times the observed one-task time instead."""
    c = AdaptiveBatchController(max_batch=16, target_latency_s=0.05)
    sizes = []
    for i in range(40):
        b = c.next_batch()
        c.record(b, curve(b, i))
        sizes.append(c.next_batch())
    assert check(sizes), sizes
    assert c.t1_s == pytest.approx(0.16)
    assert c.steer_target_s == pytest.approx(0.64)


def test_controller_fast_services_keep_their_sequences():
    """Services whose one task fits under half the target steer by the
    configured target, batch for batch as before the second regime."""
    cases = [(lambda b: 0.001 + 0.003 * b, [2, 4, 16] + [32] * 27),
             (lambda b: 0.001 + 0.001 * b, [2, 4, 32] + [64] * 27),
             (lambda b: 0.001 + 0.02 * b, [2] + [4] * 29)]
    for latency_fn, expected in cases:
        c = AdaptiveBatchController(max_batch=64, target_latency_s=0.1)
        assert _converge(c, latency_fn) == expected
        stats = c.stats()
        assert stats["t1_s"] == pytest.approx(latency_fn(1))
        assert stats["steer_target_s"] == 0.1


def test_controller_first_one_task_batch_may_hold_a_compile():
    """One slow one-task batch (its compile) does not switch a fast
    service to the second regime; a second, warm one decides."""
    c = AdaptiveBatchController(max_batch=16, target_latency_s=0.05)
    c.record(1, 2.0)
    assert c.steer_target_s == 0.05 and c.next_batch() == 1
    c.record(1, 0.002)
    assert c.t1_s == 0.002 and c.steer_target_s == 0.05
    assert c.next_batch() == 2


def test_controller_bad_bounds_rejected():
    with pytest.raises(ValueError):
        AdaptiveBatchController(min_batch=0)
    with pytest.raises(ValueError):
        AdaptiveBatchController(min_batch=8, max_batch=4)


def test_speed_capped_max_batch():
    # slower services get power-of-two-floored caps; baseline and faster
    # keep the full ceiling; the cap never drops below one task
    assert speed_capped_max_batch(16, 1.0) == 16
    assert speed_capped_max_batch(16, 0.5) == 16
    assert speed_capped_max_batch(16, 2.0) == 8
    assert speed_capped_max_batch(16, 3.0) == 4   # 16/3 = 5.33 -> 4
    assert speed_capped_max_batch(16, 40.0) == 1
    assert speed_capped_max_batch(1, 8.0) == 1


def test_pow2_floor():
    assert [pow2_floor(x) for x in (0.1, 1, 1.9, 2, 3, 8, 9, 1000)] == \
        [1, 1, 1, 2, 2, 8, 8, 512]


# ------------------------------------------------------------------ #
# end-to-end: batched farm == sequential reference
# ------------------------------------------------------------------ #
@pytest.fixture
def cluster():
    lookup = LookupService()
    services = [Service(lookup) for _ in range(3)]
    for s in services:
        s.start()
    return lookup, services


def _assert_identical(out, ref):
    assert len(out) == len(ref)
    for o, r in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


def test_batched_farm_identical_to_reference(cluster):
    lookup, _ = cluster
    skel = Farm(Seq(Program(lambda x: x * 3 + 1, name="w")))
    tasks = [jnp.asarray(float(i)) for i in range(25)]
    ref = interpret(skel, tasks)
    out: list = []
    cm = BasicClient(skel, None, tasks, out, lookup=lookup,
                     max_batch=4, max_inflight=2)
    cm.compute(timeout=120)
    _assert_identical(out, ref)
    assert cm.stats()["batching"]  # the batched path actually ran


def test_batched_farm_matches_per_task_farm_transcendental(cluster):
    """Batched vs per-task CLIENT paths with a transcendental op.  The
    dispatch machinery is exact (see the bit-identical arithmetic tests);
    XLA CPU's tanh itself differs by 1 ulp across vectorization widths
    (scalar vs vmapped codegen), so this comparison allows exactly that."""
    lookup, _ = cluster
    prog = Program(lambda x: jnp.tanh(x) * 3 + 1, name="w")
    tasks = [jnp.asarray(float(i)) for i in range(25)]
    ref: list = []
    BasicClient(prog, None, tasks, ref, lookup=lookup).compute(timeout=120)
    out: list = []
    cm = BasicClient(prog, None, tasks, out, lookup=lookup,
                     max_batch=4, max_inflight=2)
    cm.compute(timeout=120)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-7)


def test_batched_pipe_identical_to_reference(cluster):
    lookup, _ = cluster
    skel = Pipe(Farm(Seq(Program(lambda x: x + 10, name="shift"))),
                Seq(Program(lambda x: x * 2, name="scale")))
    tasks = [jnp.asarray(float(i)) for i in range(17)]
    ref = interpret(skel, tasks)
    out: list = []
    cm = BasicClient(skel, None, tasks, out, lookup=lookup,
                     max_batch=8, max_inflight=3)
    cm.compute(timeout=120)
    _assert_identical(out, ref)
    assert cm.fused_stages == 2


def test_batched_mixed_shapes_complete(cluster):
    """Tasks of several incompatible shapes all finish (leases group by
    signature; nothing is stacked across groups)."""
    lookup, _ = cluster
    prog = Program(lambda x: x.sum(), name="sum")
    tasks = ([jnp.ones(2)] * 5 + [jnp.ones((2, 2))] * 5 + [jnp.ones(3)] * 5)
    ref = [float(prog(t)) for t in tasks]
    out: list = []
    cm = BasicClient(prog, None, tasks, out, lookup=lookup, max_batch=4,
                     max_inflight=2)
    cm.compute(timeout=120)
    assert [float(v) for v in out] == ref


def test_batched_fault_tolerance_releases_batch(cluster):
    """A service dying mid-run forfeits its leased batch; the tasks are
    re-leased and the computation still completes exactly."""
    lookup, services = cluster
    services[0].fail_after(3)
    tasks = [jnp.asarray(i) for i in range(40)]
    out: list = []
    cm = BasicClient(Program(lambda x: x + 100), None, tasks, out,
                     lookup=lookup, lease_s=5.0, max_batch=4, max_inflight=2)
    cm.compute(timeout=120)
    assert [int(v) for v in out] == [i + 100 for i in range(40)]


def test_batched_load_balance_heterogeneous_speed(cluster):
    """Heterogeneous speed_factor cluster: batched run completes exactly
    and the fast service ends on a larger adaptive batch than the slow."""
    lookup = LookupService()
    fast = Service(lookup, service_id="fast", speed_factor=1.0)
    slow = Service(lookup, service_id="slow", speed_factor=40.0)
    fast.start()
    slow.start()
    tasks = [jnp.asarray(float(i)) for i in range(120)]
    out: list = []
    cm = BasicClient(Program(lambda x: x * 2, name="dbl"), None, tasks, out,
                     lookup=lookup, speculation=False, max_batch=16,
                     max_inflight=2, target_batch_latency_s=0.03)
    cm.compute(timeout=300)
    assert [float(v) for v in out] == [2.0 * i for i in range(120)]
    per = cm.stats()["per_service"]
    assert per.get("fast", 0) > per.get("slow", 0)


def test_batched_async_program_error_surfaces(cluster):
    """With block=False, runtime errors defer to materialization (the
    drain); they must fail the batch back and surface through compute()
    instead of silently killing the control thread."""
    lookup, _ = cluster

    def boom(x):
        def cb(v):
            if float(v) == 13.0:
                raise RuntimeError("boom@13")
            return np.asarray(v)
        return jax.pure_callback(cb, jax.ShapeDtypeStruct((), jnp.float32), x)

    tasks = [jnp.asarray(float(i)) for i in range(20)]
    out: list = []
    cm = BasicClient(Program(boom, name="boom"), None, tasks, out,
                     lookup=lookup, max_batch=4, max_inflight=2)
    with pytest.raises(Exception):
        cm.compute(timeout=60)


def test_futures_executor_batched(cluster):
    from repro.core import FarmExecutor
    lookup, _ = cluster
    with FarmExecutor(Program(lambda x: x - 1), lookup=lookup,
                      max_batch=4, max_inflight=2) as ex:
        futs = [ex.submit(jnp.asarray(i)) for i in range(12)]
        vals = [int(f.result(timeout=60)) for f in futs]
    assert vals == [i - 1 for i in range(12)]


def test_batched_farm_shares_executions_when_one_task_overruns_target():
    """Two services on one device run a program whose cost is flat in
    batch size (the per-batch round-trip stand-in) and over the 0.05 s
    target: a burst of queued tasks shares executions, exactly."""
    from repro.core import FarmExecutor
    from repro.obs import Observability
    lookup = LookupService()
    for _ in range(2):
        Service(lookup, task_delay_s=0.08).start()
    obs = Observability()
    with FarmExecutor(Program(lambda x: x * 3 + 1, name="flat"),
                      lookup=lookup, obs=obs, max_batch=16) as ex:
        futs = [ex.submit(jnp.asarray(float(i))) for i in range(8)]
        vals = [float(f.result(timeout=60)) for f in futs]
    assert vals == [3.0 * i + 1 for i in range(8)]
    sizes = [ev[3] for ev in obs.events() if ev[1] == "dispatch"]
    assert max(sizes) > 1, sizes

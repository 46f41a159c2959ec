"""Transport layer: wire protocol, endpoint resolution, and the proc
backend — real worker processes, real sockets, real SIGKILL."""

import gc
import os
import random
import socket
import struct
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BasicClient, Farm, LookupService, Program,
                        RemoteProgramError, Seq, Service, TaskRepository,
                        interpret, resolve_handle)
from repro.core.discovery import ServiceDescriptor
from repro.core.errors import TransportError
from repro.core.transport import LivenessMonitor
from repro.core.transport import wire
from repro.core.transport.wire import (MAX_FRAME_BYTES, dump_program,
                                       dump_pytree, load_program, load_pytree,
                                       pack_envelope, recv_frame, send_frame,
                                       unpack_envelope)
from repro.launch.now import NowPool


# --------------------------------------------------------------------- #
# wire protocol
# --------------------------------------------------------------------- #
def test_pytree_roundtrip_materializes_device_arrays():
    tree = {"a": jnp.arange(4.0), "b": [np.float32(2.0), 3], "c": None}
    out = load_pytree(dump_pytree(tree))
    assert isinstance(out["a"], np.ndarray)
    np.testing.assert_array_equal(out["a"], np.arange(4.0))
    assert out["b"] == [2.0, 3] and out["c"] is None


def test_frame_roundtrip_and_clean_eof():
    a, b = socket.socketpair()
    send_frame(a, {"op": "hello", "blob": b"\x00" * 4096})
    msg = recv_frame(b)
    assert msg["op"] == "hello" and len(msg["blob"]) == 4096
    a.close()
    assert recv_frame(b) is None  # EOF at a frame boundary, not an error
    b.close()


def test_program_ships_and_still_computes():
    p = Program(lambda x: x * 3.0, name="tri")
    q = load_program(dump_program(p))
    assert q.name == "tri"
    assert float(q(jnp.asarray(2.0))) == 6.0


def test_program_resident_state_ships_and_is_an_argument():
    p = Program(lambda w, x: x * w, name="scale", resident=jnp.asarray(3.0))
    q = load_program(dump_program(p))
    assert float(q(jnp.asarray(2.0))) == 6.0
    # the resident state is passed to the executable, not baked into it
    fn = q.prepare([jax.devices()[0]])
    assert float(fn(jnp.asarray(2.0))) == 6.0
    assert "constant(3" not in jax.jit(q.fn).lower(
        q.resident, jnp.asarray(2.0)).as_text()
    assert float(q.prepare_batched()(jnp.ones(4))[0]) == 3.0


def test_composed_program_keeps_stage_resident_state_an_argument():
    from repro.core import compose_programs

    scale = Program(lambda w, x: x * w, name="scale",
                    resident=jnp.asarray(3.0))
    fused = compose_programs([Program(lambda x: x + 1.0, name="inc"), scale])
    assert fused.resident[1] is scale.resident
    assert float(fused(jnp.asarray(2.0))) == 9.0
    assert float(fused.prepare([jax.devices()[0]])(jnp.asarray(2.0))) == 9.0
    assert "constant(3" not in jax.jit(fused.fn).lower(
        fused.resident, jnp.asarray(2.0)).as_text()
    assert fused.prepare_batched()(jnp.ones(4)).tolist() == [6.0] * 4


def test_pool_refuses_a_parent_that_holds_the_accelerator(monkeypatch):
    from jax._src import xla_bridge

    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one process at a time"):
        NowPool(1)


# --------------------------------------------------------------------- #
# wire protocol: malformed frames must fail as TransportError — cleanly,
# immediately, and without allocation (satellite regressions + fuzz)
# --------------------------------------------------------------------- #
def _feed(raw: bytes) -> socket.socket:
    """A socket whose peer wrote ``raw`` and hung up — every truncation
    and corruption scenario, without a worker process."""
    a, b = socket.socketpair()
    a.sendall(raw)
    a.close()
    b.settimeout(5.0)  # a hang is a test failure, not a CI timeout
    return b


def _expect_transport_error(raw: bytes, match: str) -> None:
    b = _feed(raw)
    try:
        with pytest.raises(TransportError, match=match):
            recv_frame(b)
    finally:
        b.close()


def test_zero_length_frame_is_a_clean_transport_error():
    """Satellite regression: a zero-length frame used to slip through to
    ``unpack_envelope(b"")`` and die with "unknown envelope tag b''" —
    sending people hunting a codec bug that never existed."""
    with pytest.raises(TransportError, match="zero-length frame"):
        unpack_envelope(b"")
    _expect_transport_error(struct.pack(">I", 0), "zero-length frame")


def test_truncated_header_is_a_transport_error():
    _expect_transport_error(b"\x00\x00", "mid-frame header")


def test_truncated_body_is_a_transport_error():
    _expect_transport_error(struct.pack(">I", 100) + b"M" + b"x" * 10,
                            "mid-frame body")


def test_corrupt_envelope_tag_is_a_transport_error():
    body = b"Xjunk"
    _expect_transport_error(struct.pack(">I", len(body)) + body,
                            "unknown envelope tag")


def test_corrupt_msgpack_body_is_a_transport_error():
    body = b"M" + b"\xc1\xc1\xc1"  # 0xc1 is reserved in msgpack
    _expect_transport_error(struct.pack(">I", len(body)) + body,
                            "corrupt msgpack envelope")


def test_non_dict_envelope_is_a_transport_error():
    msgpack = pytest.importorskip("msgpack")
    body = b"M" + msgpack.packb([1, 2, 3])
    _expect_transport_error(struct.pack(">I", len(body)) + body,
                            "expected dict")


def test_oversized_length_prefix_rejected_without_allocation():
    """A corrupt length prefix must be a protocol error, not a giant
    ``recv`` — the reader rejects it straight off the 4 header bytes."""
    t0 = time.monotonic()
    _expect_transport_error(struct.pack(">I", MAX_FRAME_BYTES + 1)
                            + b"M" + b"x" * 16, "announced")
    assert time.monotonic() - t0 < 1.0  # no body read, no buffer sizing


def test_pickle_fallback_roundtrip_without_msgpack(monkeypatch):
    """Bare installs (no msgpack) use pickle envelopes — same frames, tag
    ``P``; a peer that still sends msgpack gets a clean TransportError."""
    monkeypatch.setattr(wire, "_msgpack", None)
    data = pack_envelope({"op": "hello", "blob": b"\x01" * 64})
    assert data[:1] == b"P"
    msg = unpack_envelope(data)
    assert msg["op"] == "hello" and len(msg["blob"]) == 64
    a, b = socket.socketpair()
    try:
        send_frame(a, {"op": "ping"})
        assert recv_frame(b) == {"op": "ping"}
        with pytest.raises(TransportError, match="msgpack"):
            unpack_envelope(b"M\x81")
    finally:
        a.close()
        b.close()


def test_corrupt_pickle_body_is_a_transport_error():
    body = b"P" + b"\x80\x05junk-not-a-pickle"
    _expect_transport_error(struct.pack(">I", len(body)) + body,
                            "corrupt pickle envelope")


def test_fuzz_corrupted_frames_never_hang_and_fail_as_transport_error():
    """Property: for ANY corruption of a valid frame, recv_frame either
    returns a dict, reports clean EOF, or raises TransportError — it never
    hangs (5s socket timeout would surface as socket.timeout) and never
    raises anything else."""
    frame = pack_envelope({"op": "execute", "uid": 7,
                           "payload": b"\x00" * 50})
    raw = struct.pack(">I", len(frame)) + frame
    rng = random.Random(1306)  # fixed seed: reproducible trials
    for _ in range(200):
        corrupt = bytearray(raw)
        for _ in range(rng.randint(1, 3)):
            corrupt[rng.randrange(len(corrupt))] = rng.randrange(256)
        b = _feed(bytes(corrupt))
        try:
            msg = recv_frame(b)
            assert msg is None or isinstance(msg, dict)
        except TransportError:
            pass  # the only acceptable exception
        finally:
            b.close()


# --------------------------------------------------------------------- #
# endpoint resolution (inproc)
# --------------------------------------------------------------------- #
def test_lookup_registers_addresses_not_live_objects():
    lk = LookupService()
    Service(lk, service_id="sA").start()
    (desc,) = lk.query()
    assert isinstance(desc.endpoint, str)
    assert desc.endpoint.startswith("inproc://")
    handle = resolve_handle(desc, lookup=lk)
    assert handle.service_id == "sA"
    assert handle.recruit("c1") is True
    assert len(lk) == 0  # recruited service left the lookup
    handle.release()
    assert len(lk) == 1


def test_stale_inproc_address_resolves_to_none():
    desc = ServiceDescriptor("ghost", "inproc://ghost-deadbeef")
    assert resolve_handle(desc) is None


def test_legacy_live_object_endpoint_still_resolves():
    svc = Service(None, service_id="sB")
    handle = resolve_handle(ServiceDescriptor("sB", svc))
    assert handle.service_id == "sB"
    prog = Program(lambda x: x + 0.5, name="half")
    assert float(handle.execute(prog, jnp.asarray(1.0))) == 1.5


def test_inproc_farm_end_to_end_unchanged():
    lk = LookupService()
    for i in range(2):
        Service(lk, service_id=f"e{i}").start()
    prog = Program(lambda x: x * x, name="sq")
    tasks = [jnp.asarray(float(i)) for i in range(8)]
    out: list = []
    BasicClient(prog, None, tasks, out, lookup=lk).compute(timeout=120)
    assert [float(v) for v in out] == [float(i * i) for i in range(8)]


# --------------------------------------------------------------------- #
# liveness: heartbeat death feeds the lease machinery
# --------------------------------------------------------------------- #
class _FakeHandle:
    service_id = "flaky"
    needs_heartbeat = True

    def __init__(self):
        self.alive = True

    def ping(self):
        return self.alive


def test_liveness_monitor_expires_dead_services_leases():
    """Heartbeat death feeds the lease machinery — on a virtual clock, so
    the 'did the monitor beat the lease deadline' race is deterministic
    instead of a CI-load lottery."""
    from repro.sim import virtual_time

    with virtual_time() as clock:
        repo = TaskRepository(["x"], lease_s=60.0, clock=clock)
        tid, _ = repo.get_task("flaky")
        handle = _FakeHandle()
        monitor = LivenessMonitor(interval_s=0.05, timeout_s=0.2, clock=clock)
        monitor.watch(handle, repo.expire_service)
        try:
            handle.alive = False  # the node stops answering pings
            got = repo.get_task("survivor", timeout=5.0)
            assert got is not None and got[0] == tid
            assert repo.stats()["reschedules"] == 1
            assert monitor.deaths == 1
            assert clock.monotonic() < 1.0  # way before the 60s lease
        finally:
            monitor.stop()


class _ClosableFakeHandle:
    service_id = "leaky"
    needs_heartbeat = True

    def __init__(self):
        self.alive = True
        self.closed = 0

    def ping(self):
        return self.alive

    def close(self):
        self.closed += 1


def test_liveness_monitor_closes_dead_handle():
    """Satellite regression: on a declared death the monitor dropped the
    handle from its watch map but never ``close()``d it — one leaked
    socket fd per dead worker, forever.  The handle must be closed after
    ``on_dead`` fires."""
    monitor = LivenessMonitor(interval_s=0.02, timeout_s=0.08)
    handle = _ClosableFakeHandle()
    died = threading.Event()
    monitor.watch(handle, lambda sid: died.set())
    try:
        handle.alive = False
        assert died.wait(10.0)
        # close() happens right after on_dead in the same monitor sweep
        deadline = time.monotonic() + 5.0
        while handle.closed == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert handle.closed >= 1
        assert monitor.deaths == 1
    finally:
        monitor.stop()


# --------------------------------------------------------------------- #
# proc backend: worker processes on sockets
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def proc_cluster():
    lookup = LookupService()
    with NowPool(2, lookup, service_prefix="pw") as pool:
        yield lookup, pool


def test_proc_farm_per_task_and_batched_match_interpret(proc_cluster):
    lookup, _ = proc_cluster
    prog = Program(lambda x: x * x - 1.0, name="sqm1")
    tasks = [jnp.asarray(float(i)) for i in range(10)]
    reference = [float(v) for v in interpret(Farm(Seq(prog)), tasks)]
    for kwargs in ({}, {"max_batch": 4, "max_inflight": 2}):
        out: list = []
        cm = BasicClient(prog, None, tasks, out, lookup=lookup,
                         speculation=False, **kwargs)
        cm.compute(timeout=120)
        assert [float(v) for v in out] == reference
    # released workers re-register for the next client (Algorithm 2); the
    # release RPCs may still be in flight when compute() returns — wait
    # event-driven on the lookup itself, no sleep-polling
    assert lookup.wait_for_services(2, timeout_s=10.0)


def test_expiry_then_release_then_duplicate_completion(proc_cluster):
    """Satellite regression, proc flavor: a worker 'dies mid-batch' (its
    results never report back), the lease expires, the batch is re-leased
    to a second worker, and the dead worker's zombie results are dropped
    by idempotent completion."""
    _, pool = proc_cluster
    handle_a = resolve_handle(pool.workers[0].descriptor)
    handle_b = resolve_handle(pool.workers[1].descriptor)
    try:
        _die_mid_batch_scenario(handle_a, handle_b)
    finally:
        handle_a.close()
        handle_b.close()


def test_expiry_then_release_then_duplicate_completion_inproc():
    _die_mid_batch_scenario(
        resolve_handle(Service(None, service_id="ia").descriptor()),
        resolve_handle(Service(None, service_id="ib").descriptor()))


def _die_mid_batch_scenario(handle_a, handle_b):
    prog = Program(lambda x: x * 2.0, name="dbl")
    repo = TaskRepository([jnp.asarray(float(i)) for i in range(4)],
                          lease_s=0.2)
    batch_a = repo.get_batch("A", 4, compatible=None)
    assert len(batch_a) == 4
    # A computes the batch but dies before completing it back.  B's lease
    # request wakes AT A's lease deadline (repository waits are capped at
    # the next deadline — event-driven expiry, no sleep here).
    results_a = handle_a.execute_batch(prog, [p for _, p in batch_a])
    batch_b = repo.get_batch("B", 4, timeout=5.0)
    assert sorted(t for t, _ in batch_b) == sorted(t for t, _ in batch_a)
    assert repo.stats()["reschedules"] == 4
    results_b = handle_b.execute_batch(prog, [p for _, p in batch_b])
    recorded = repo.complete_batch(
        list(zip([t for t, _ in batch_b], results_b)), "B")
    assert recorded == 4
    # A's zombie results surface late: idempotent, first result wins
    zombie = repo.complete_batch(
        list(zip([t for t, _ in batch_a], results_a)), "A")
    assert zombie == 0
    assert repo.all_done
    assert [float(v) for v in repo.results()] == [0.0, 2.0, 4.0, 6.0]
    assert repo.stats()["per_service"] == {"B": 4}


def _open_fds() -> int | None:
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:  # no /proc (macOS): skip the fd-hygiene assertion
        return None


def test_proc_sigkill_mid_run_all_tasks_complete():
    lookup = LookupService()
    n_tasks = 40
    gc.collect()
    fds_before = _open_fds()
    with NowPool(2, lookup, task_delay_s=0.02, service_prefix="kw") as pool:
        victim = pool.workers[0].service_id
        prog = Program(lambda x: x + 1.0, name="inc")
        tasks = [jnp.asarray(float(i)) for i in range(n_tasks)]
        out: list = []
        cm = BasicClient(prog, None, tasks, out, lookup=lookup, lease_s=5.0,
                         speculation=False, max_batch=4, max_inflight=2)
        killed = threading.Event()

        def killer():
            # only kill once the victim demonstrably did work — an
            # event-driven wait on repository completions, not a poll loop
            if cm.repository.wait_until(
                    lambda s: s["per_service"].get(victim, 0) >= 1,
                    timeout=60.0):
                pool.kill(0)  # SIGKILL — no goodbye frames
                killed.set()

        threading.Thread(target=killer, daemon=True).start()
        cm.compute(timeout=120)
        assert killed.is_set(), "victim finished before the kill fired"
        assert not pool.workers[0].alive
        assert [float(v) for v in out] == [i + 1.0 for i in range(n_tasks)]
    # fd hygiene (the LivenessMonitor close fix): a declared death must
    # not leak the dead worker's socket — after pool teardown the process
    # is back to (about) its starting fd count
    if fds_before is not None:
        gc.collect()
        deadline = time.monotonic() + 5.0
        while _open_fds() > fds_before + 3 and time.monotonic() < deadline:
            time.sleep(0.05)  # kernel close is async-ish under load
        assert _open_fds() <= fds_before + 3, "socket fds leaked"


def test_proc_remote_program_error_surfaces(proc_cluster):
    lookup, _ = proc_cluster

    # nested on purpose: cloudpickle ships it by value (a module-level
    # function would be shipped by reference, unimportable in the worker)
    def raiser(x):
        raise ValueError("boom from worker")

    out: list = []
    cm = BasicClient(Program(raiser, jit=False, name="boom"), None,
                     [jnp.asarray(1.0)], out, lookup=lookup,
                     speculation=False)
    with pytest.raises(RemoteProgramError, match="boom from worker"):
        cm.compute(timeout=60)

"""The telemetry spine: recorder, metrics, exporters, determinism.

Covers the ``repro.obs`` contract ends-to-end:

- recorder unit behavior — per-thread rings (no shared lock on the hot
  path), bounded drop-oldest retention, deterministic merged order,
  sink-only mode (``ring_size=0``);
- metrics registry — counters, gauges, fixed-bucket histograms, the
  versioned snapshot shape;
- the Chrome trace-event exporter — valid schema, one track per
  service, task spans, ≥5 event types on a churny run;
- **same-seed determinism** — two churny ``sim://`` runs export
  byte-identical traces (SHA-256 pinned below: any change to event
  content, ordering, or serialization shows up as a diff of one
  constant);
- **tracing disabled is free** — a run without ``obs`` constructs no
  recorder and emits no events (the dispatch path carries `obs is
  None` checks only).
"""

from __future__ import annotations

import hashlib
import json
import threading

import pytest

from repro.obs import Observability
from repro.obs.export import (chrome_trace_events, dump_metrics_jsonl,
                              export_chrome_trace, farm_top,
                              validate_chrome_trace)
from repro.obs.metrics import (BATCH_BUCKETS, Counter, Gauge, Histogram,
                               MetricsRegistry)
from repro.obs.recorder import TraceRecorder
from repro.core import Program
from repro.sim import FaultSpec, SimCluster

PROGRAM = Program(lambda x: x * 3.0 + 1.0, name="affine", jit=False)

#: the golden churny scenario (same shape as the acceptance trace):
#: heterogeneous mix, one loud mid-run death, one late joiner,
#: speculation on — exercises lease/expire/speculate/recruit paths.
GOLDEN_SEED = 17
GOLDEN_SHA256 = \
    "9b081ddb9128014d21579a3f4a12426269516c03bbbafcd091a05e9c8561e77f"
GOLDEN_EVENTS = 162


def _golden_run() -> Observability:
    obs = Observability()
    with SimCluster(speed_factors=[1.0, 1.0, 2.0, 4.0], seed=GOLDEN_SEED,
                    base_cost_s=0.002, latency_s=0.0002,
                    faults={1: FaultSpec(die_at=0.08),
                            3: FaultSpec(register_at=0.05)},
                    obs=obs) as cluster:
        out, _client = cluster.run(PROGRAM, [float(i) for i in range(96)],
                                   max_batch=4, lease_s=0.3)
        assert sorted(float(v) for v in out) == \
            sorted(i * 3.0 + 1.0 for i in range(96))
    return obs


# ------------------------------------------------------------------ #
# recorder
# ------------------------------------------------------------------ #
def test_recorder_per_thread_rings_merge_deterministically():
    rec = TraceRecorder()
    barrier = threading.Barrier(3)

    def emit(k):
        barrier.wait()
        for i in range(5):
            rec.event("tick", float(i), k)  # explicit t: merge is by time

    threads = [threading.Thread(target=emit, args=(k,), name=f"ring-{k}")
               for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = rec.events()
    assert len(evs) == 15
    # sorted by (t, ring name, seq): per-t the three rings interleave in
    # name order — a total order independent of thread scheduling
    assert [e[0] for e in evs] == sorted(e[0] for e in evs)
    assert [e[2] for e in evs] == [0, 1, 2] * 5
    s = rec.stats()
    assert s["rings"] == 3 and s["events_recorded"] == 15
    assert s["events_dropped"] == 0


def test_recorder_bounded_ring_drops_oldest():
    rec = TraceRecorder(ring_size=4)
    for i in range(10):
        rec.event("tick", float(i))
    evs = rec.events()
    assert [e[0] for e in evs] == [6.0, 7.0, 8.0, 9.0]
    s = rec.stats()
    assert s["events_recorded"] == 10
    assert s["events_retained"] == 4
    assert s["events_dropped"] == 6
    rec.clear()
    assert rec.events() == []


def test_recorder_sink_only_mode_retains_nothing():
    got = []
    rec = TraceRecorder(ring_size=0, sink=lambda ring, ev: got.append(ev))
    for i in range(100):
        rec.event("tick", float(i), i * 2)
    assert len(got) == 100 and got[7] == (7.0, "tick", 14)
    assert rec.events() == []  # nothing retained: O(1) memory
    assert rec.stats()["events_recorded"] == 100
    assert rec.stats()["events_retained"] == 0


# ------------------------------------------------------------------ #
# metrics
# ------------------------------------------------------------------ #
def test_counter_gauge_histogram_and_snapshot_shape():
    reg = MetricsRegistry()
    c = reg.counter("tasks_done")
    c.inc()
    c.inc(4)
    g = reg.gauge("pool_size")
    g.set(7)
    h = reg.histogram("batch", boundaries=BATCH_BUCKETS)
    for v in (1, 3, 1000, 5000):
        h.observe(v)
    assert reg.counter("tasks_done") is c  # get-or-create
    snap = reg.snapshot()
    assert snap["schema"] == "jjpf.metrics/v1"
    assert snap["counters"]["tasks_done"] == 5
    assert snap["gauges"]["pool_size"] == 7
    hs = snap["histograms"]["batch"]
    assert hs["count"] == 4 and hs["sum"] == 6004
    # 1 -> first bucket (<=1), 3 -> (2,4], 1000 -> (512,1024], 5000 -> +inf
    assert hs["counts"][0] == 1 and hs["counts"][-1] == 1
    assert sum(hs["counts"]) == 4


def test_histogram_rejects_bad_boundaries():
    with pytest.raises(ValueError):
        Histogram("x", (1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        Histogram("x", ())


def test_instruments_are_thread_safe():
    c = Counter("n")
    g = Gauge("v")
    threads = [threading.Thread(target=lambda: [c.inc() for _ in range(1000)])
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    g.set(3.5)
    assert c.snapshot() == 4000 and g.snapshot() == 3.5


# ------------------------------------------------------------------ #
# exporters
# ------------------------------------------------------------------ #
def test_chrome_trace_export_loads_and_validates(tmp_path):
    obs = _golden_run()
    path = tmp_path / "trace.json"
    export_chrome_trace(obs, str(path))
    with open(path) as f:
        events = json.load(f)  # it IS plain trace-event JSON
    assert isinstance(events, list) and events
    info = validate_chrome_trace(str(path))
    # one track per service that did work, ≥5 event types, real spans
    assert info["service_tracks"] == 4
    assert len(info["event_types"]) >= 5
    assert info["spans"] > 0 and info["instants"] > 0
    assert {"lease", "complete", "recruit"} <= set(info["event_types"])


def test_chrome_trace_spans_nest_under_service_tracks():
    obs = _golden_run()
    events = chrome_trace_events(obs.events())
    tids = {e["args"]["name"]: e["tid"] for e in events
            if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tids["scheduler"] == 0
    svc_tids = {v for k, v in tids.items() if k.startswith("service ")}
    spans = [e for e in events if e["ph"] == "X"]
    assert spans and all(e["tid"] in svc_tids for e in spans
                         if e["cat"] == "complete")
    for e in spans:
        assert e["dur"] >= 0 and e["ts"] >= 0


def test_same_seed_runs_export_byte_identical_traces(tmp_path):
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    export_chrome_trace(_golden_run(), str(p1))
    export_chrome_trace(_golden_run(), str(p2))
    b1, b2 = p1.read_bytes(), p2.read_bytes()
    assert b1 == b2, "same seed produced different exported traces"
    assert hashlib.sha256(b1).hexdigest() == GOLDEN_SHA256, (
        "golden trace changed: if intentional, update GOLDEN_SHA256 "
        "(event content, ordering, or serialization drifted)")
    assert len(_golden_run().events()) == GOLDEN_EVENTS


def test_metrics_jsonl_dump_appends_lines(tmp_path):
    obs = _golden_run()
    path = tmp_path / "metrics.jsonl"
    dump_metrics_jsonl(obs.registry, str(path), t=1.0)
    dump_metrics_jsonl(obs.registry, str(path), t=2.0,
                       extra={"note": "second"})
    lines = path.read_text().splitlines()
    assert len(lines) == 2
    first, second = (json.loads(ln) for ln in lines)
    assert first["t"] == 1.0 and second["note"] == "second"
    assert second["histograms"]["queue_wait_s"]["count"] > 0


def test_farm_top_renders_jobs_and_services():
    obs = Observability()
    with SimCluster(speed_factors=[1.0, 2.0], seed=3, base_cost_s=0.002,
                    obs=obs) as cluster:
        with cluster.make_scheduler(max_batch=4) as sched:
            job = sched.submit(PROGRAM, [float(i) for i in range(24)])
            job.wait(timeout=600)
            text = farm_top(sched.stats())
    assert "job-0" in text and "sim0" in text
    assert "JOB" in text and "SERVICE" in text
    assert "jjpf.stats/v1" in text


# ------------------------------------------------------------------ #
# tracing disabled: zero events, no recorder on the dispatch path
# ------------------------------------------------------------------ #
def test_tracing_disabled_constructs_no_recorder(monkeypatch):
    def boom(self, *a, **kw):
        raise AssertionError("TraceRecorder constructed without obs")

    monkeypatch.setattr(TraceRecorder, "__init__", boom)
    monkeypatch.setattr(TraceRecorder, "event", boom)
    with SimCluster(speed_factors=[1.0, 1.0], seed=3,
                    base_cost_s=0.002) as cluster:
        out, client = cluster.run(PROGRAM, [float(i) for i in range(24)],
                                  max_batch=4)
        assert len(out) == 24
        stats = client.engine.stats()
    # no obs: the engine snapshot carries no metrics/trace subtree, and
    # every layer holds obs=None (the `if obs is not None` fast path)
    assert "metrics" not in stats and "trace" not in stats
    assert client.obs is None and client.engine.obs is None
    assert client.repository._obs is None
    # the deprecated on_lease hook still works without obs
    assert cluster.trace, "on_lease compatibility path stopped recording"


def test_obs_and_on_lease_lease_streams_agree():
    """The recorder's lease events carry the same assignments the
    deprecated on_lease hook reported (the generalization satellite)."""
    def run_hook():
        with SimCluster(speed_factors=[1.0, 2.0], seed=11,
                        base_cost_s=0.002) as cluster:
            cluster.run(PROGRAM, [float(i) for i in range(48)],
                        max_batch=4)
            return [(tid, sid, att) for (_t, tid, sid, att)
                    in cluster.trace]

    def run_obs():
        obs = Observability()
        with SimCluster(speed_factors=[1.0, 2.0], seed=11,
                        base_cost_s=0.002, obs=obs) as cluster:
            cluster.run(PROGRAM, [float(i) for i in range(48)],
                        max_batch=4)
        flat = []
        for ev in obs.events():
            if ev[1] == "lease":
                flat.extend((tid, ev[2], att) for tid, att in ev[3])
            elif ev[1] == "speculate":
                flat.append((ev[3], ev[2], ev[4]))
        return flat

    assert run_hook() == run_obs()

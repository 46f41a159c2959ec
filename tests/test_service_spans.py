"""Host spans of the service layer and the generate program's phases.

- ``Observability.span`` records ``(t_end, kind, *fields, t_start)`` and
  nothing for a body that raised;
- a farm without ``obs`` builds no span; ``sim://`` services emit none;
- under a CPU ``jax.profiler`` session, in-process services leave
  ``stack`` / ``launch`` / ``unstack`` spans on the host plane, each
  carrying its recorder time, and one fitted offset maps the plane's
  clock onto the recorder's;
- the Perfetto export draws the spans on the service's track;
- the generate program names its ``prefill`` and ``decode`` ops.
"""

from __future__ import annotations

import glob
import os
import re
import statistics

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as cfgs
from repro.core import FarmExecutor, LookupService, Program, Service
from repro.core import service as service_mod
from repro.models import build
from repro.obs import Observability
from repro.obs.export import chrome_trace_events, validate_chrome_trace
from repro.obs.schema import EVENT_KINDS
from repro.runtime.serve_loop import ServeConfig, make_generate_program
from repro.sim import SimCluster

SPANS = ("stack", "launch", "unstack")
DOUBLE = Program(lambda x: x * 2.0, name="double")


def _farm(obs, *, max_batch: int, tasks: int = 12):
    """Two in-process services fed ``tasks`` vectors; returns results."""
    lookup = LookupService()
    services = [Service(lookup) for _ in range(2)]
    for s in services:
        s.start()
    with FarmExecutor(DOUBLE, lookup=lookup, obs=obs,
                      max_batch=max_batch) as ex:
        futs = [ex.submit(np.full(4, float(i), np.float32))
                for i in range(tasks)]
        out = [np.asarray(f.result(timeout=60)) for f in futs]
    for s in services:
        s.kill()
    return out


def test_span_records_end_kind_fields_start():
    obs = Observability()
    with obs.span("launch", "s0", 3):
        pass
    (ev,) = obs.events()
    t_end, kind, *fields, t_start = ev
    assert kind == "launch" and fields == ["s0", 3]
    assert t_start <= t_end


def test_span_records_nothing_when_its_body_raises():
    obs = Observability()
    with pytest.raises(RuntimeError):
        with obs.span("launch", "s0", 1):
            raise RuntimeError("launch failed")
    assert obs.events() == []


def test_span_kinds_are_documented():
    assert set(SPANS) <= set(EVENT_KINDS)
    for kind in SPANS:
        assert EVENT_KINDS[kind][0].endswith("t_start")


def test_farm_without_obs_builds_no_span(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("a span was built without obs")

    monkeypatch.setattr(Observability, "span", boom)
    out = _farm(None, max_batch=4)
    assert [float(o[0]) for o in out] == [2.0 * i for i in range(12)]
    assert service_mod._span(None, "launch") is service_mod._NO_SPAN


@pytest.mark.parametrize("max_batch", [1, 4])
def test_inproc_services_record_their_spans(max_batch):
    obs = Observability()
    out = _farm(obs, max_batch=max_batch)
    assert [float(o[0]) for o in out] == [2.0 * i for i in range(12)]
    events = obs.events()
    drains = [ev for ev in events if ev[1] == "drain"]
    launches = [ev for ev in events if ev[1] == "launch"]
    # one launch per batch, speculative copies included
    assert sorted(ev[3] for ev in launches) == sorted(d[3] for d in drains)
    assert all(ev[-1] <= ev[0] for ev in launches)
    kinds = {ev[1] for ev in events}
    if max_batch == 1:  # the per-task path stacks nothing
        assert not kinds & {"stack", "unstack"}
    else:
        for kind in ("stack", "unstack"):
            assert sorted(ev[3] for ev in events if ev[1] == kind) == \
                sorted(d[3] for d in drains)
    # each launch nests in its batch's dispatch .. drain on its service
    for ev in launches:
        sid, t0, t1 = ev[2], ev[-1], ev[0]
        assert any(d[2] == sid and d[4] <= t0 and t1 <= d[0]
                   for d in drains)


def test_sim_services_emit_no_spans():
    obs = Observability()
    with SimCluster(speed_factors=[1.0, 2.0], seed=5, base_cost_s=0.002,
                    obs=obs) as cluster:
        cluster.run(DOUBLE, [float(i) for i in range(16)], max_batch=4)
    assert not {ev[1] for ev in obs.events()} & set(SPANS)


def _host_spans(log_dir: str) -> list:
    """(name, start_ns, t_start) of the host plane's service spans."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    stats = dict(ev.stats)
                    out.append((ev.name, int(ev.start_ns),
                                stats["t_start"]))
    return out


def test_profiler_host_plane_carries_the_recorder_clock(tmp_path):
    obs = Observability()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _farm(obs, max_batch=4, tasks=24)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    recorded = {ev[-1]: ev[1] for ev in obs.events() if ev[1] in SPANS}
    # every span on the plane is a recorded span, under its own name
    assert {"stack", "launch", "unstack"} <= {name for name, _, _ in spans}
    assert len(spans) == len(recorded)
    assert all(recorded[t] == name for name, _, t in spans)
    # one offset maps the plane's clock onto the recorder's: a span's
    # plane start lags its recorder time by the few microseconds between
    # the two clock reads, more when another thread takes the GIL there
    diffs = [s / 1e9 - t for _, s, t in spans]
    offset = statistics.median(diffs)
    residuals = sorted(abs(d - offset) for d in diffs)
    assert offset - min(diffs) < 100e-6
    assert residuals[int(0.9 * len(residuals))] < 100e-6


def test_export_draws_service_spans_on_the_service_track():
    events = [(1.0, "dispatch", "s0", 2),
              (1.0001, "stack", "s0", 2, 1.00001),
              (1.0005, "launch", "s0", 2, 1.0002),
              (1.0007, "unstack", "s0", 2, 1.0006),
              (1.2, "drain", "s0", 2, 1.0)]
    trace = chrome_trace_events(events)
    spans = {e["cat"]: e for e in trace if e["ph"] == "X"}
    assert set(spans) == {"dispatch", *SPANS}
    outer = spans["dispatch"]
    for kind in SPANS:
        e = spans[kind]
        assert e["name"] == kind and e["tid"] == outer["tid"]
        assert e["args"] == {"n": 2, "service": "s0"}
        assert outer["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"]
    assert spans["launch"]["ts"] == pytest.approx(1.0002e6)
    assert spans["launch"]["dur"] == pytest.approx(300.0)
    info = validate_chrome_trace(trace)
    assert info["spans"] == 4 and info["service_tracks"] == 1
    assert set(SPANS) <= set(info["event_types"])


def _generate_hlo(batched: bool) -> str:
    cfg = cfgs.reduced(cfgs.get("qwen3_1p7b"))
    api = build(cfg)
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    sc = ServeConfig(max_new_tokens=3, prompt_len=8, batch_per_task=2)
    program = make_generate_program(api, sc, params)
    tokens = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    fn = program.fn
    if batched:
        fn = jax.vmap(fn, in_axes=(None, 0))
        tokens = jax.ShapeDtypeStruct((2, 2, 8), jnp.int32)
    return jax.jit(fn).lower(params, {"tokens": tokens}).compile().as_text()


def _in_scope(op_name: str, scope: str) -> bool:
    """A path segment of ``op_name`` is ``scope``, bare or inside a
    transformation (``vmap(decode)``)."""
    return any(re.fullmatch(rf"(\w+\()*{scope}\)*", seg)
               for seg in op_name.split("/"))


@pytest.mark.parametrize("batched", [False, True])
def test_generate_program_names_its_phases(batched):
    names = re.findall(r'op_name="([^"]*)"', _generate_hlo(batched))
    prefill = [n for n in names if _in_scope(n, "prefill")]
    decode = [n for n in names if _in_scope(n, "decode")]
    assert prefill and decode and not set(prefill) & set(decode)
    # the decode loop is a while in the decode scope
    assert any(n.split("/")[2:3] == ["while"] for n in decode)

"""The readers of the program's spans and scopes (``bench.lib.spans``).

Unit cases on made-up events, then the chip trace that
``record_launches.py`` recorded on one TPU v5e: two services' threads
launching a jitted ``generate`` (scopes ``prefill`` and ``decode``) on
one device through the farm, in bursts."""

from __future__ import annotations

import os

import pytest

from bench.lib import spans
from bench.lib.spans import Execution, HostSpan
from bench.tests import record_launches

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(generate)/prefill/dot_general:", "prefill"),
    ("jit(generate)/decode/while/body/closed_call/tanh:", "decode"),
    ("jit(generate)/vmap(decode)/while/body/add:", "decode"),
    ("jit(generate)/vmap(prefill)/while:", "prefill"),
    ("jit(generate)/dot_general:", None),
    ("jit(prefill_cache)/add:", None),
    ("", None),
    (None, None),
])
def test_scope_of_a_name_stack(tf_op, scope):
    assert spans.scope_of(tf_op) == scope


def test_tf_op_stats_are_read_from_the_event_metadata():
    ops = spans.op_name_stacks(SMALL)
    assert list(ops) == ["/device:TPU:0"]
    tpu = ops["/device:TPU:0"]
    fusions = {k: v for k, v in tpu.items()
               if k.startswith("%convolution_tanh_fusion")}
    assert len(fusions) == 8
    assert set(fusions.values()) == {"jit(generate)/dot_general:"}


def test_offset_fit_ignores_a_span_held_up_between_its_clock_reads():
    offset = 1234.5
    spans_ = [HostSpan("launch", int((t + offset) * 1e9) + lag, 0, t)
              for t, lag in [(1.0, 3000), (2.0, 5000), (3.0, 4000),
                             (4.0, 2_000_000), (5.0, 4000)]]
    fitted, residuals = spans.fit_offset(spans_)
    assert fitted == pytest.approx(offset + 4e-6, abs=1e-9)
    assert max(residuals) == pytest.approx(2e-3 - 4e-6, abs=1e-9)
    assert spans.fit_offset([]) == (None, [])


def test_fifo_pairs_launches_with_later_executions():
    launches = [(0, HostSpan("launch", s, e, 0.0))
                for s, e in [(100, 110), (300, 305), (120, 130)]]
    execs = [Execution("/device:TPU:0", "jit_generate(1)", s, s + 50, None)
             for s in (40, 115, 140, 320)]
    pairs = spans.match_fifo(launches, execs)
    assert [(s.end_ns, x.start_ns) for s, x in pairs] == [
        (110, 115), (130, 140), (305, 320)]


def test_interval_overlap():
    assert spans.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert spans.overlap_ns([(0, 10), (5, 15)], [(0, 100)]) == 15
    assert spans.overlap_ns([(0, 10)], [(10, 20)]) == 0
    assert spans.overlap_ns([], [(0, 1)]) == 0


EVENTS = [
    (1.00, "task-submit", 2, 0),
    (1.10, "lease", "a", ((0, 0), (1, 0))),
    (1.11, "dispatch", "a", 2),
    (1.12, "stack", "a", 2, 1.115),
    (1.13, "launch", "a", 2, 1.125),
    (1.14, "unstack", "a", 2, 1.135),
    (1.50, "drain", "a", 2, 1.11),
    (1.20, "task-submit", 1, 2),
    (1.60, "lease", "b", ((2, 0),)),
    (1.61, "dispatch", "b", 1),
    (1.62, "stack", "b", 1, 1.615),
    (1.63, "launch", "b", 1, 1.625),
    (1.64, "unstack", "b", 1, 1.635),
    (1.90, "drain", "b", 1, 1.61),
]


def test_each_task_is_due_until_its_launch_ends():
    due = spans.first_launch_ends(EVENTS)
    assert due == {0: (1.00, 1.13), 1: (1.00, 1.13), 2: (1.20, 1.63)}


def test_service_batches_join_spans_and_drains():
    batches = sorted(spans.service_batches(EVENTS), key=lambda b: b["service"])
    assert [b["service"] for b in batches] == ["a", "b"]
    a = batches[0]
    assert a["stack"] == (1.115, 1.12) and a["launch"] == (1.125, 1.13)
    assert (a["dispatch"], a["drain"]) == (1.11, 1.50)


def test_readers_find_nothing_in_a_program_without_spans(tmp_path):
    """A program that predates the spans and scopes (the five readers on
    ``small.xplane.pb``, whose ``generate`` has neither): no value, no
    error."""
    import shutil
    from types import SimpleNamespace

    from bench.lib import trace

    shutil.copy(SMALL, tmp_path / "small.xplane.pb")
    events = [(0.1, "task-submit", 1, 0), (0.2, "lease", "a", ((0, 0),)),
              (0.3, "dispatch", "a", 1), (0.9, "drain", "a", 1, 0.3)]
    run = SimpleNamespace(
        cell=SimpleNamespace(name="small",
                             traffic=SimpleNamespace(new_tokens=8)),
        served=SimpleNamespace(events=events, t_open=0.0, t_close=1.0),
        trace=trace.reduce(str(tmp_path)), trace_dir=str(tmp_path),
        events_in_window=lambda: events)
    assert record_launches.read_all(run) == dict.fromkeys(
        record_launches.READERS)


# what record_launches.py printed when it recorded the fixture
PRINTED = {"prefill_ms.gen": 0.30740653333333334,
           "decode_step_ms.gen": 0.019206025,
           "device_wait_p90_s.chat": -0.0005261338000000001,
           "service_host_ms.chat": 10.80171893333386,
           "idle_queued_share.chat": 22.298332245784337}
PRINTED_OFFSET_S = -40.396930121


@pytest.fixture(scope="module")
def recorded():
    run = record_launches.fixture_run()
    return run, spans.profile(run)


def test_every_launch_finds_its_execution_by_run_id(recorded):
    run, prof = recorded
    launched = [ev for ev in run.events_in_window() if ev[1] == "launch"]
    assert prof.matched_by == "run_id"
    assert prof.launches == len(prof.launched) == len(launched) == 15
    pairs = sorted(prof.launched, key=lambda p: p[0].end_ns)
    assert all(x.name.startswith("jit_generate(") for _, x in pairs)
    # the device runs them in the order the two threads launched them
    runs = [x.run_id for _, x in pairs]
    assert runs == sorted(set(runs))


def test_one_offset_maps_the_recorder_onto_the_profile(recorded):
    run, prof = recorded
    assert prof.offset_s == pytest.approx(PRINTED_OFFSET_S, abs=1e-9)
    assert len(prof.spans) == 45
    assert max(map(abs, prof.residuals_s)) < 100e-6
    # each span on the plane is a recorded event, at its mapped time
    recorded_starts = {ev[-1] for ev in run.served.events
                       if ev[1] in spans.SPAN_KINDS}
    for s in prof.spans:
        assert s.t_start in recorded_starts
        assert abs(prof.to_profile_ns(s.t_start) - s.start_ns) < 100_000


def test_scopes_split_each_whole_execution(recorded):
    run, _ = recorded
    times = run.trace.module_times("generate")
    assert len(times) == 15
    prefill = spans.scope_ms_per_execution(run, "prefill")
    decode = spans.scope_ms_per_execution(run, "decode")
    whole_ms = 1000 * sum(times) / len(times)
    assert prefill + decode == pytest.approx(whole_ms, rel=0.01)
    assert decode / record_launches.DECODE_STEPS == pytest.approx(
        PRINTED["decode_step_ms.gen"], rel=1e-9)


def test_the_readers_read_what_the_recording_printed(recorded):
    run, _ = recorded
    got = record_launches.read_all(run)
    assert got == pytest.approx(PRINTED, rel=1e-9)
    # the device plane runs ahead of the host's: every execution here
    # reads as starting before its launch span ended (by up to 1.5 ms),
    # some before it began
    assert -2e-3 < got["device_wait_p90_s.chat"] < 0

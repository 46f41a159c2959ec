"""The trace reduction on a small trace recorded on one TPU v5e
(``record_trace.py``): five calls of a jitted ``generate`` of about
0.72 ms each, 50 ms host ``wait`` spans between them, all inside the
harness's ``window`` span of 0.2606 s (host clock)."""

from __future__ import annotations

import os

import pytest

from bench.lib import trace

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return trace.reduce(SMALL)


def test_window_is_the_host_span(small):
    assert small.window_s == pytest.approx(0.26060, abs=2e-4)
    assert [d.name for d in small.devices] == ["/device:TPU:0"]


def test_program_executions(small):
    # the first call's execution starts before the window's first
    # microsecond and is left out; the last is not followed by another,
    # so it is not known to have ended: three whole executions count
    assert len(small.devices[0].modules) == 4
    times = small.module_times("generate")
    assert len(times) == 3
    assert all(t == pytest.approx(0.000721, rel=0.01) for t in times)
    assert small.module_times("no-such-program") == []


def test_execution_cut_by_the_profiler_is_left_out():
    # two back-to-back executions of 6.54 s; the profiler stopped inside
    # the second, whose event then ends at the window's end
    s = int(1e9)
    dev = trace.Device("/device:TPU:0", busy_ns=15 * s, gaps=[], op_ns={},
                       modules=[("jit_generate(1)", 2 * s, 6540 * s // 1000),
                                ("jit_generate(1)", 8540 * s // 1000,
                                 6460 * s // 1000)])
    tr = trace.Trace(window=(0, 15 * s), devices=[dev])
    assert tr.module_times("generate") == [6.54]


def test_busy_is_the_union_of_ops(small):
    # ops inside each execution are back to back: the union equals the
    # executions' summed time, and everything else is idle
    whole = sum(dur for _, _, dur in small.devices[0].modules) / 1e9
    assert small.busy_s == pytest.approx(whole, rel=0.01)
    assert small.idle_share == pytest.approx(1 - small.busy_s / 0.26060,
                                             abs=1e-3)


def test_gaps_are_named_by_the_host_span(small):
    gaps = small.top_gaps(5)
    assert all(name.startswith("wait") for name, _ in gaps)
    assert all(s == pytest.approx(0.0515, abs=0.002) for _, s in gaps)


def test_top_ops_are_the_matmul_fusions(small):
    name, secs = small.top_ops(1)[0]
    assert name.startswith("convolution_tanh_fusion")
    assert 0 < secs < small.busy_s


def test_interval_arithmetic():
    spans = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 41)]
    assert trace.union_ns(spans) == 15 + 11 + 1
    assert trace.gaps_ns(spans, 0, 50) == [(31, 9), (41, 9), (15, 5)]
    assert trace.short_op_name("%fusion.12 = f32[8] fusion(x)") == "fusion.12"

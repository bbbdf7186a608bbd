"""The reduction from a profiler trace to busy time, program time and the
breakdown: on hand-made timelines, and on a small trace recorded on one
TPU v5e (``fixtures/tpu_small.xplane.pb``, made by
``fixtures/make_trace.py``: three runs of one jitted program, each inside
``bench.job``, inside ``bench.window``)."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace

FIXTURE = Path(__file__).parent / "fixtures" / "tpu_small.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(ops, modules, host):
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev(*e) for e in ops]),
        NS(name="XLA Modules", events=[ev(*e) for e in modules]),
    ])
    cpu = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(*e) for e in host])])
    return NS(planes=[cpu, device])


def test_union_and_gaps():
    assert trace.union_length([(0, 10), (5, 15), (20, 30)]) == 25
    assert trace.union_length([(0, 10), (2, 3)]) == 10
    assert trace.gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [
        (0, 2), (6, 8), (9, 10)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def test_reduce_hand_made_timeline():
    ops = [("fusion", 100, 200), ("scatter", 250, 100), ("fusion", 600, 300),
           ("outside", 5000, 100)]
    modules = [("jit_step(1)", 100, 250), ("jit_step(1)", 600, 300)]
    host = [("bench.window", 0, 1000), ("bench.job", 0, 500),
            ("bench.fetch", 350, 250), ("other", 0, 10)]
    s = trace.reduce_profile(profile(ops, modules, host))
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(550e-9)  # 100..350 and 600..900
    assert trace.module_time(s, r"jit_step") == (pytest.approx(550e-9), 2)
    with pytest.raises(LookupError, match="jit_step"):
        trace.module_time(s, r"_run_scan_ring")
    assert s["device_ops"][0] == ["fusion", pytest.approx(500e-9)]
    # Gaps: 0..100 (bench.job), 350..600 (bench.fetch), 900..1000 (window).
    assert s["idle_gaps"] == [["bench.fetch", pytest.approx(250e-9)],
                              ["bench.job", pytest.approx(100e-9)],
                              ["bench.window", pytest.approx(100e-9)]]


def test_no_device_plane_reads_nothing():
    s = trace.reduce_profile(profile([], [], [("bench.window", 0, 1000)]))
    assert s["device_events"] == 0
    assert trace.module_time(s, r"_run_scan_ring") == (0, 0)


def test_reduce_needs_a_window():
    with pytest.raises(ValueError):
        trace.reduce_profile(profile([], [], [("bench.job", 0, 5)]))


def test_reduce_recorded_tpu_trace():
    s = trace.reduce_file(FIXTURE)
    assert s["device_events"] > 0
    assert 0 < s["busy_s"] <= s["window_s"]
    seconds, runs = trace.module_time(s, r"^jit_")
    assert runs == 3 and 0 < seconds <= s["window_s"]
    assert s["device_ops"] and all(t > 0 for _, t in s["device_ops"])
    assert s["idle_gaps"] and {name for name, _ in s["idle_gaps"]} <= {
        "bench.window", "bench.job"}

"""Device time by scope, idle gaps by the program's spans and the program's
counters (``bench/scopes.py``): on hand-made timelines, on the small trace
``fixtures/tpu_small.xplane.pb`` (a program with no named scopes, as the
readers meet a program that names none), and on
``fixtures/tpu_scoped.xplane.pb`` (made by ``fixtures/make_scoped_trace.py``
on one TPU v5e: a jitted ``lax.scan`` with two named scopes in its body,
run three times between ``repro.*`` host spans)."""
from __future__ import annotations

import shutil
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import scopes

FIXTURES = Path(__file__).parent / "fixtures"
SMALL = FIXTURES / "tpu_small.xplane.pb"
SCOPED = FIXTURES / "tpu_scoped.xplane.pb"


def ev(name, start, dur, stats=()):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=list(stats))


def profile(ops, modules, main, worker=()):
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev(*e) for e in ops]),
        NS(name="XLA Modules", events=[ev(*e) for e in modules]),
    ])
    cpu = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(*e) for e in main]),
        NS(name="python", events=[ev(*e) for e in worker])])
    return NS(planes=[cpu, device])


PROGRAM = "jit__run_scan_ring(7)"
META = {"/device:TPU:0": {
    ("while", 7): "jit(_run_scan_ring)/vmap()/while:",
    ("sort", 7): "jit(_run_scan_ring)/vmap()/while/body/closed_call/"
                 "adwise.window/sort:",
    ("dot", 7): "jit(_run_scan_ring)/vmap()/while/body/closed_call/"
                "adwise.score/jit(f)/dot_general:",
    ("copy", 7): "jit(_run_scan_ring)/vmap()/while/body/copy:",
}}


def hand_made():
    ops = [("while", 100, 750), ("sort", 150, 100), ("dot", 300, 200),
           ("copy", 600, 100), ("tail", 860, 30), ("outside", 5000, 10)]
    modules = [(PROGRAM, 100, 800)]
    counters = [("host_serial_s", 0.25), ("host_syncs", 8), ("scan_calls", 2)]
    main = [("bench.window", 0, 2000),
            ("repro.partition_file", 10, 1980, counters),
            ("repro.init", 20, 80), ("repro.emit", 880, 5)]
    worker = [("repro.stage", 0, 2000)]
    return scopes.reduce_profile(profile(ops, modules, main, worker), META)


def test_self_times_leave_out_nested_ops():
    assert scopes.self_times([(0, 100), (10, 20), (30, 60), (35, 40),
                              (70, 80)]) == [50, 10, 25, 5, 10]
    # A child that ends past its parent (rounding) is cut at the parent.
    assert scopes.self_times([(0, 10), (5, 12)]) == [5, 7]


def test_scope_of_takes_the_innermost_phase():
    assert scopes.scope_of(META["/device:TPU:0"][("dot", 7)]) == "adwise.score"
    assert scopes.scope_of("jit(step)/engine.gather/vmap()/add:") == (
        "engine.gather")
    assert scopes.scope_of("a/engine.gather/b/engine.combine/psum:") == (
        "engine.combine")
    assert scopes.scope_of("jit(_run_scan_ring)/while:") is None
    assert scopes.scope_of(None) is None


def test_reduce_hand_made_timeline():
    s = hand_made()
    assert s["modules"] == {PROGRAM: pytest.approx(800e-9)}
    by = s["scopes"][PROGRAM]
    # The while keeps only its own 350 ns; the op with no metadata and the
    # copy count as unscoped; the op outside the program counts nowhere.
    assert by == {None: pytest.approx(480e-9),
                  "adwise.window": pytest.approx(100e-9),
                  "adwise.score": pytest.approx(200e-9)}
    assert sum(by.values()) <= s["modules"][PROGRAM]
    assert scopes.scope_time(s, r"_run_scan_ring", "adwise.score") == (
        pytest.approx(200e-9))
    assert scopes.named(s, r"_run_scan_ring", "adwise.")
    assert not scopes.named(s, r"_run_scan_ring", "engine.")
    # Gaps 890..2000, 0..100 and 850..860 are named on the window's thread
    # (the worker's stage span names none).
    assert s["idle_gaps"] == [
        ["repro.partition_file", pytest.approx(1110e-9)],
        ["repro.init", pytest.approx(100e-9)],
        ["repro.partition_file", pytest.approx(10e-9)]]
    assert s["counters"] == [{"host_serial_s": 0.25, "host_syncs": 8,
                              "scan_calls": 2}]


def test_scope_time_raises_on_a_missing_name():
    s = hand_made()
    with pytest.raises(LookupError, match="adwise.pick"):
        scopes.scope_time(s, r"_run_scan_ring", "adwise.pick")
    with pytest.raises(LookupError, match="jit_step"):
        scopes.scope_time(s, r"jit_step", "engine.gather")


def test_metadata_decoded_from_a_recorded_trace():
    meta = scopes.op_metadata(SMALL)
    assert set(meta) == {"/device:TPU:0"}
    tf_ops = {op.split(" = ")[0]: tf_op
              for (op, pid), tf_op in meta["/device:TPU:0"].items()}
    assert tf_ops["%fusion"] == "jit(<lambda>)/scatter-add:"
    assert tf_ops["%fusion.4"] == "jit(<lambda>)/dot_general:"
    assert {pid for op, pid in meta["/device:TPU:0"]} == {
        16859926762862546408}


def cell_root(tmp_path, fixture) -> Path:
    """A checkout root whose last traced run left ``fixture``."""
    dest = tmp_path / "runs" / "bench" / "cell" / "trace" / "plugins"
    dest.mkdir(parents=True)
    shutil.copy(fixture, dest / "host.xplane.pb")
    return tmp_path


def ctx_of(fixture, jobs: int, steps: int) -> dict:
    window = scopes.reduce_file(fixture)["window_s"]
    stats = dict(scan_calls=1, scan_steps_per_call=steps)
    return dict(trace=dict(window_s=window),
                results=[dict(stats=stats, work=1)] * jobs)


def test_a_program_without_names_reads_nothing(tmp_path, monkeypatch):
    """A program that names no phases and writes no counters, as the
    parent of this benchmark's readers: every new metric is left out."""
    monkeypatch.setattr(scopes, "ROOT", cell_root(tmp_path, SMALL))
    monkeypatch.setattr(scopes, "SCAN", r"^jit__lambda")
    monkeypatch.setattr(scopes, "SUPERSTEP", r"^jit__lambda")
    ctx = ctx_of(SMALL, jobs=3, steps=1)
    assert scopes.summary(ctx)["device_events"] > 0
    assert scopes.scan_us_per_step(ctx, "adwise.window") is None
    assert scopes.scan_us_per_step(ctx, None) is None
    assert scopes.superstep_ms(ctx, "engine.gather") is None
    assert scopes.counters(ctx) is None
    # Another run's trace is not read.
    assert scopes.summary(dict(trace=dict(window_s=1.0))) is None


def test_recorded_scopes_tile_the_program():
    s = scopes.reduce_file(SCOPED)
    (program,) = [n for n in s["modules"] if n.startswith("jit__lambda")]
    by = s["scopes"][program]
    assert set(by) == {None, "adwise.window", "adwise.score"}
    assert by["adwise.window"] > 0 and by["adwise.score"] > 0
    # Self times add up to the program's device time: the scan's while is
    # not counted again over its body's ops.
    assert sum(by.values()) == pytest.approx(s["modules"][program], rel=0.01)


def test_recorded_gaps_are_named_by_the_program(tmp_path):
    s = scopes.reduce_file(SCOPED)
    named = [name for name, t in s["idle_gaps"] if t > 1e-3]
    # Between two jobs the chip waits through one job's `repro.emit` sleep
    # and the next one's `repro.init` sleep, with its midpoint in the
    # first; before the first job and after the last, the window's 10 ms.
    assert sorted(named) == ["bench.window", "bench.window", "repro.emit",
                             "repro.emit"]
    assert s["counters"] == [{"host_serial_s": 0.006, "host_syncs": 4,
                              "scan_calls": 1}] * 3


def test_readers_on_a_recorded_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(scopes, "ROOT", cell_root(tmp_path, SCOPED))
    monkeypatch.setattr(scopes, "SCAN", r"^jit__lambda")
    ctx = ctx_of(SCOPED, jobs=3, steps=8)
    s = scopes.summary(ctx)
    (program,) = [n for n in s["modules"] if n.startswith("jit__lambda")]
    per_step = {scope: scopes.scan_us_per_step(ctx, scope)
                for scope in ("adwise.window", "adwise.score", None)}
    assert sum(per_step.values()) == pytest.approx(
        s["modules"][program] / 24 * 1e6, rel=0.01)
    with pytest.raises(LookupError):
        scopes.scan_us_per_step(ctx, "adwise.pick")
    assert [c["host_syncs"] for c in scopes.counters(ctx)] == [4, 4, 4]

"""``correct`` has to come out false when the answer is wrong.

The control (each cell's reference in the program's place, one precision
below the configuration's) is judged not correct, and a whole run of each
tiny cell, with the timed path broken underneath, prints ``correct: false``
for each fault the cell can have: a step that returns its state unchanged,
half of the job's work left out, and one answer altered where it is
produced; and for PageRank a superstep that ignores the state it is given.
(These cells run on one chip: no exchange between chips exists to leave
out.)
"""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from bench import control, run


def run_cell(root, cell, capsys) -> dict:
    jax.clear_caches()
    rc = run.main(["--workload", cell, "--seed", "7", "--seconds", "1",
                   "--trace", "0"], root=root, allow_cpu=True)
    jax.clear_caches()
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["tiny.adwise", "tiny.pagerank"])
def test_control_is_not_correct(tiny_root, cell, capsys):
    control.main(["--workload", cell, "--seeds", "1,2,3"], root=tiny_root)
    rows = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(rows) == 3 and not any(r["correct"] for r in rows)


def test_sound_run_is_correct(tiny_root, capsys):
    assert run_cell(tiny_root, "tiny.adwise", capsys)["correct"] is True


def frozen_vertex_cache(monkeypatch):
    """The ADWISE step returns its vertex cache unchanged."""
    from repro.core import driver

    make = driver._make_step

    def broken(*args, **kwargs):
        step = make(*args, **kwargs)

        def frozen(carry, x):
            new, out = step(carry, x)
            return new._replace(replicas=carry.replicas,
                                rep_version=carry.rep_version), out
        return frozen

    monkeypatch.setattr(driver, "_make_step", broken)


def wrap_partition_file(monkeypatch, alter):
    import repro.core
    from repro.core.types import PartitionResult

    real = repro.core.partition_file

    def broken(*args, **kwargs):
        res = real(*args, **kwargs)
        return PartitionResult(alter(np.array(res.assign), res.stats["k"]),
                               res.stats)

    monkeypatch.setattr(repro.core, "partition_file", broken)


def half_left_out(monkeypatch):
    def alter(a, k):
        a[len(a) // 2:] = -1
        return a
    wrap_partition_file(monkeypatch, alter)


def one_placement_altered(monkeypatch):
    def alter(a, k):
        a[len(a) // 2] = (a[len(a) // 2] + 1) % k
        return a
    wrap_partition_file(monkeypatch, alter)


def pagerank_state_unchanged(monkeypatch):
    import repro.engine

    real = repro.engine.pagerank_superstep

    def broken(g, **kwargs):
        _, x0 = real(g, **kwargs)
        return (lambda x: x), x0

    monkeypatch.setattr(repro.engine, "pagerank_superstep", broken)


def pagerank_input_ignored(monkeypatch):
    """Every superstep is taken from the first state, whatever it is given."""
    import repro.engine

    real = repro.engine.pagerank_superstep

    def broken(g, **kwargs):
        step, x0 = real(g, **kwargs)
        first = []

        def ignoring(x):
            if not first:
                first.append(x)
            return step(first[0])
        return ignoring, x0

    monkeypatch.setattr(repro.engine, "pagerank_superstep", broken)


def pagerank_half_edges(monkeypatch):
    import repro.engine

    real = repro.engine.build_partitioned_graph

    def broken(edges, assign, n, k, **kwargs):
        return real(edges[::2], assign[::2], n, k, **kwargs)

    monkeypatch.setattr(repro.engine, "build_partitioned_graph", broken)


def pagerank_rank_altered(monkeypatch):
    import repro.engine

    real = repro.engine.pagerank_superstep

    def broken(g, **kwargs):
        step, x0 = real(g, **kwargs)
        return (lambda x: step(x).at[0, 0].multiply(1.01)), x0

    monkeypatch.setattr(repro.engine, "pagerank_superstep", broken)


@pytest.mark.parametrize("cell,fault", [
    ("tiny.adwise", frozen_vertex_cache),
    ("tiny.adwise", half_left_out),
    ("tiny.adwise", one_placement_altered),
    ("tiny.pagerank", pagerank_state_unchanged),
    ("tiny.pagerank", pagerank_input_ignored),
    ("tiny.pagerank", pagerank_half_edges),
    ("tiny.pagerank", pagerank_rank_altered),
])
def test_fault_is_not_correct(tiny_root, cell, fault, monkeypatch, capsys):
    fault(monkeypatch)
    out = run_cell(tiny_root, cell, capsys)
    assert out["correct"] is False
    assert out["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in out["checks"].values())

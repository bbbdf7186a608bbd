"""The program's names on the profiler's clock: the ADWISE step's and the
engine superstep's phases as named scopes in the compiled programs' op
metadata, the ring loop's spans as ``repro.*`` profiler annotations, and
``partition_file``'s host-serial counters, reconciled with its spans."""
import glob
import re
import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import partition_file, run_partitioner
from repro.core.driver import FileSource, ScanDriver, _run_scan_ring
from repro.core.types import AdwiseConfig
from repro.engine import build_partitioned_graph, engine_mesh
from repro.engine.algorithms import pagerank_update
from repro.engine.gas import superstep_program
from repro.graph import rmat
from repro.graph.io import EdgeFileReader, write_edge_file
from repro.obs import NULL_TRACER, Tracer

K = 4


@pytest.fixture(scope="module")
def graph_file(tmp_path_factory):
    edges, n = rmat(8, 1200, seed=5)
    path = str(tmp_path_factory.mktemp("names") / "g.adw")
    write_edge_file(path, edges, n)
    return path, edges, n


def scopes_in(hlo_text: str, family: str) -> set:
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {s for name in names for s in re.findall(family + r"\.[a-z]+", name)}


def test_scan_program_names_the_adwise_phases(graph_file):
    path, _, n = graph_file
    cfg = AdwiseConfig(k=K, window_max=16)
    with EdgeFileReader(path) as r:
        src = FileSource([r], chunk_edges=256, cfg=cfg, prefetch=0)
        drv = ScanDriver(src, cfg, n)
        low = _run_scan_ring.lower(
            (drv.carry, src.alloc()), drv._m_real_j, drv._allowed_j,
            drv._caps_j, core=drv.core, n_steps=src.scan_steps, n_shards=0)
    assert scopes_in(low.compile().as_text(), "adwise") == {
        "adwise.window", "adwise.score", "adwise.pick", "adwise.apply"}


def test_superstep_program_names_the_engine_phases(graph_file):
    _, edges, n = graph_file
    assign = run_partitioner("hash", edges, n, K, seed=0).assign
    g = build_partitioned_graph(edges, assign, n, K)
    msg, apply = pagerank_update(n)
    program = superstep_program(engine_mesh(k=K), msg, apply, n)
    low = program.lower(jnp.full((n, 1), 1.0 / n, jnp.float32), g.edges,
                        g.evalid, jnp.asarray(np.asarray(g.replicas).T),
                        g.degrees)
    assert scopes_in(low.compile().as_text(), "engine") == {
        "engine.gather", "engine.combine", "engine.apply"}


def host_annotations(trace_dir) -> list:
    """Per host thread, the ``repro.*`` events of the trace under
    ``trace_dir``: (start ns, end ns, name, metadata)."""
    from jax.profiler import ProfileData

    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            events = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                       dict(ev.stats)) for ev in line.events
                      if ev.name.startswith("repro.")]
            if events:
                threads.append(events)
    return threads


def innermost_parent(ev, events):
    around = [o for o in events if o is not ev and o[0] <= ev[0]
              and ev[1] <= o[1] and (o[0], -o[1]) < (ev[0], -ev[1])]
    return min(around, key=lambda o: o[1] - o[0])[2] if around else None


def test_null_tracer_annotates_only_while_profiling(tmp_path):
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b")
    with jax.profiler.trace(str(tmp_path)):
        with NULL_TRACER.span("probe", cat="x", rows=3) as sp:
            sp.set(done=1)
        assert sp is not NULL_TRACER.span("c")
    assert NULL_TRACER.span("a") is NULL_TRACER.span("b")
    probes = [e for t in host_annotations(tmp_path) for e in t
              if e[2] == "repro.probe"]
    assert len(probes) == 1 and probes[0][3] == {"rows": 3, "done": 1}


def test_ring_loop_spans_reach_the_profiler(graph_file, tmp_path):
    path, _, _ = graph_file
    with EdgeFileReader(path) as r, jax.profiler.trace(str(tmp_path / "t")):
        res = partition_file(r, "adwise", K, chunk_edges=256, window_max=16,
                             spill_dir=str(tmp_path / "spill"), prefetch=2)
    threads = host_annotations(tmp_path / "t")
    main = [t for t in threads
            if any(e[2] == "repro.partition_file" for e in t)]
    assert len(main) == 1
    main = main[0]
    calls = res.stats["scan_calls"]
    names = Counter(e[2] for e in main)
    assert names["repro.partition_file"] == names["repro.init"] == 1
    assert names["repro.spill-verify"] == 1
    for name in ("scan-call", "refill", "dispatch", "refill-spec", "sync",
                 "emit"):
        assert names[f"repro.{name}"] == calls, name
    # Spans on one thread nest; none half-overlaps another.
    ordered = sorted(main, key=lambda e: (e[0], -e[1]))
    for i, a in enumerate(ordered):
        for b in ordered[i + 1:]:
            if b[0] >= a[1]:
                break
            assert b[1] <= a[1], (a[2], b[2])
    parents = {"repro.init": {"repro.partition_file"},
               "repro.scan-call": {"repro.partition_file"},
               "repro.spill-verify": {"repro.partition_file"},
               "repro.refill": {"repro.init", "repro.partition_file"},
               "repro.dispatch": {"repro.scan-call"},
               "repro.refill-spec": {"repro.scan-call"},
               "repro.sync": {"repro.scan-call"},
               "repro.emit": {"repro.scan-call"},
               "repro.fetch": {"repro.refill", "repro.refill-spec"}}
    for e in main:
        if e[2] in parents:
            assert innermost_parent(e, main) in parents[e[2]], e[2]
    # The read-ahead worker's spans are on its own thread.
    assert not names["repro.stage"]
    assert any(e[2] == "repro.stage" for t in threads if t is not main
               for e in t)
    # The counters ride on the partition_file annotation.
    (meta,) = [e[3] for e in main if e[2] == "repro.partition_file"]
    assert meta["host_syncs"] == res.stats["host_syncs"]
    assert meta["scan_calls"] == calls
    assert meta["scan_path"] == res.stats["scan_path"] == "single"
    assert meta["host_serial_s"] == pytest.approx(res.stats["host_serial_s"])


@pytest.mark.parametrize("strategy,cfg", [
    ("adwise", dict(window_max=16)),
    ("hdrf", {}),
    ("adwise-restream", dict(window_max=16, passes=2)),
])
def test_host_serial_counters_reconcile_with_spans(graph_file, tmp_path,
                                                   strategy, cfg):
    path, _, _ = graph_file
    tr = Tracer()
    with EdgeFileReader(path) as r:
        t = time.perf_counter()
        res = partition_file(r, strategy, K, chunk_edges=256,
                             spill_dir=str(tmp_path), trace=tr, **cfg)
        wall = time.perf_counter() - t
    st = res.stats
    assert st["host_syncs"] == 4 * st["scan_calls"]
    assert 0 < st["host_serial_s"] <= wall
    spans = {name: sorted((s for s in tr.spans if s.name == name),
                          key=lambda s: s.t0)
             for name in ("init", "dispatch", "emit", "refill",
                          "spill-verify")}
    (init,), (verify,) = spans["init"], spans["spill-verify"]
    dispatch, emit = spans["dispatch"], spans["emit"]
    assert len(dispatch) == len(emit) == st["scan_calls"]
    # The counter's stretches run on the spans' own floats: entry to the
    # first dispatch (`init`), each emit's start (the sync's return) to the
    # next dispatch, and the last emit's start to the end of the check.
    assert init.t1 == dispatch[0].t0
    serial = init.t1 - init.t0
    for e, d in zip(emit, dispatch[1:]):
        serial += d.t0 - e.t0
    serial += verify.t1 - emit[-1].t0
    assert serial == st["host_serial_s"]
    # Every blocking refill lies in a stretch: before the first dispatch,
    # or between an emit and the next dispatch.
    starts = [init.t0] + [e.t0 for e in emit]
    for refill in spans["refill"]:
        k = np.searchsorted([d.t0 for d in dispatch], refill.t1)
        assert starts[k] <= refill.t0 and refill.t1 <= dispatch[k].t0

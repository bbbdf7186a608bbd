"""Unified streaming-scan driver (`repro.core.driver`): ring-buffer
invariants, bit-parity between the device-resident ring (file) path and the
resident full-upload path, the host→device traffic accounting, and the
double-buffered refill pipeline (read-ahead worker determinism/teardown)."""
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    AdwiseConfig,
    partition_file,
    run_partitioner,
    spotlight_partition,
)
from repro.core import driver
from repro.core.adwise import partition_stream, partition_stream_batched
from repro.core.baselines import HdrfCore
from repro.core.driver import (
    AdwiseCore,
    FileSource,
    ResidentSource,
    ScanDriver,
    resolve_backend,
    resolve_prefetch,
    scan_path,
)
from repro.graph import rmat
from repro.graph.io import EdgeFileReader, write_edge_file

K = 8


@pytest.fixture(scope="module")
def rmat_file(tmp_path_factory):
    edges, n = rmat(8, 1100, seed=21)
    td = tmp_path_factory.mktemp("driver")
    path = str(td / "rmat.adw")
    write_edge_file(path, edges, n)
    return path, edges, n


# ----------------------------------------------------------------------------
# FileSource sizing / refill invariants
# ----------------------------------------------------------------------------


def test_file_source_sizing(rmat_file):
    path, edges, n = rmat_file
    for chunk, wmax, b in [(64, 8, 1), (400, 8, 2), (100, 16, 4), (7, 4, 1)]:
        cfg = AdwiseConfig(k=K, window_max=wmax, assign_batch=b)
        with EdgeFileReader(path) as r:
            src = FileSource([r], chunk_edges=chunk, cfg=cfg)
            f = wmax + src.scan_steps * b
            assert src.B % src.Rq == 0
            # Quantized refills always leave >= F consumable rows ahead.
            assert src.B >= f + src.Rq - 1
            assert src.Rq & (src.Rq - 1) == 0  # power of two
            # Single reads never exceed the caller's chunk bound.
            assert src.max_span <= max(chunk, wmax + b)
            assert src.max_span % src.Rq == 0 or src.max_span == src.Rq


def test_file_source_refill_overrun_guard(rmat_file):
    """A cursor past the uploaded high-water mark is a bug, not a refill."""
    path, _, n = rmat_file
    cfg = AdwiseConfig(k=K, window_max=8)
    with EdgeFileReader(path) as r:
        with FileSource([r], chunk_edges=100, cfg=cfg) as src:
            buf = src.alloc()
            buf = src.refill(buf, np.zeros(1, np.int64))
            with pytest.raises(AssertionError, match="overran"):
                src.refill(buf, np.array([int(src.hi[0]) + 1], np.int64))


def test_driver_direct_ring_run(rmat_file):
    """Drive ScanDriver over a FileSource by hand: parity with the resident
    path, cursors land exactly on the uploaded high-water mark, and every
    stream row ships to the device exactly once."""
    path, edges, n = rmat_file
    m = len(edges)
    cfg = AdwiseConfig(k=K, window_max=8)
    ref = partition_stream(edges, n, cfg)
    assign = np.full((m,), -1, np.int32)

    def on_assign(i, idx, p):
        assign[idx] = p

    with EdgeFileReader(path) as r:
        src = FileSource([r], chunk_edges=150, cfg=cfg)
        drv = ScanDriver(src, cfg, n)
        res = drv.run(on_assign=on_assign)
        assert (src.hi == m).all()  # no over- or under-upload
    assert (assign == ref.assign).all()
    assert int(res.assigned[0]) == m
    assert res.h2d_rows == m  # each row shipped exactly once
    assert res.h2d_bytes == m * 8  # no prev-pass buffer on a cold pass
    assert res.buffer_rows == src.B


# ----------------------------------------------------------------------------
# Property: ring path == full-upload path over random geometry
# ----------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(
    chunk=st.integers(min_value=48, max_value=500),
    wmax=st.sampled_from([4, 8]),
    b=st.sampled_from([1, 2]),
    z=st.sampled_from([1, 2, 4]),
)
def test_ring_parity_property(rmat_file, tmp_path_factory, chunk, wmax, b, z):
    """For random (chunk_edges, window_max, assign_batch, z): the ring-buffer
    file path assigns bit-identically to the in-memory path, never overruns
    the refill cursor (asserted inside FileSource), and ships each stream
    row once."""
    path, edges, n = rmat_file
    m = len(edges)
    cfg = dict(window_max=wmax, assign_batch=b)
    if z == 1:
        ref = run_partitioner("adwise", edges, n, K, seed=0, **cfg)
    else:
        ref = spotlight_partition(
            edges, n, K, z=z, spread=max(1, K // z), strategy="adwise",
            cfg=AdwiseConfig(k=K, seed=0, **cfg),
        )
    td = tmp_path_factory.mktemp("ringprop")
    with EdgeFileReader(path) as r:
        res = partition_file(
            r, "adwise", K, z=z, spread=max(1, K // z) if z > 1 else None,
            seed=0, chunk_edges=chunk, spill_dir=str(td), **cfg,
        )
    assert (np.asarray(res.assign) == ref.assign).all(), (
        f"ring diverged at chunk={chunk} wmax={wmax} b={b} z={z}"
    )
    assert res.stats["h2d_rows"] == m, "each row must ship exactly once"
    assert res.stats["h2d_bytes"] == m * 8
    if res.stats["scan_calls"] >= 2:
        # The point of the ring: per-call traffic is the refill, not the
        # full buffer re-upload (z * B rows per call).
        full_upload = res.stats["scan_calls"] * z * res.stats["buffer_rows"]
        assert res.stats["h2d_rows"] < full_upload


def test_restream_ring_h2d_accounting(rmat_file, tmp_path):
    """Re-streaming from disk: pass 1 ships (u, v) rows only; pass 2 also
    ships the prior pass's placements (4 more bytes per row) for buffered
    revocation — and still matches the in-memory restream bit for bit.

    With chunk_edges < m the ring wraps, so pass 2 must re-ship the uv rows
    (the cross-pass resume only adopts never-wrapped rings)."""
    path, edges, n = rmat_file
    m = len(edges)
    cfg = dict(window_max=8, passes=2)
    ref = run_partitioner("adwise-restream", edges, n, K, seed=0, **cfg)
    with EdgeFileReader(path) as r:
        res = partition_file(r, "adwise-restream", K, seed=0, chunk_edges=200,
                             spill_dir=str(tmp_path), **cfg)
    assert (np.asarray(res.assign) == ref.assign).all()
    assert res.stats["h2d_rows"] == 2 * m
    assert res.stats["h2d_bytes"] == m * 8 + m * 12
    # In-memory restream reuses the uploaded device stream across passes
    # (StreamResidency): one uv upload total; every resident pass still
    # ships its (m,) prev table (pass 1's is the all -1 cold table).
    assert ref.stats["h2d_rows"] == m
    assert ref.stats["h2d_bytes"] == m * 8 + 2 * m * 4


def test_restream_ring_cross_pass_resume(rmat_file, tmp_path):
    """chunk_edges >= m keeps the whole stream ring-resident, so pass 2
    adopts pass 1's donated ring (RingHandle) and ships ONLY the 4 B/row
    prev table: h2d drops from 8m + 12m to 8m + 4m — bit-identically."""
    path, edges, n = rmat_file
    m = len(edges)
    cfg = dict(window_max=8, passes=2)
    ref = run_partitioner("adwise-restream", edges, n, K, seed=0, **cfg)
    with EdgeFileReader(path) as r:
        res = partition_file(r, "adwise-restream", K, seed=0,
                             chunk_edges=2048, spill_dir=str(tmp_path), **cfg)
    assert (np.asarray(res.assign) == ref.assign).all()
    assert res.stats["h2d_rows"] == m  # uv shipped once, pass 2 prev-only
    assert res.stats["h2d_bytes"] == m * 8 + m * 4
    assert (res.stats["spans_prestaged"] + res.stats["spans_missed"]
            == res.stats["refill_spans"])


# ----------------------------------------------------------------------------
# Double-buffered refill pipeline (read-ahead worker)
# ----------------------------------------------------------------------------


@settings(max_examples=4, deadline=None)
@given(
    chunk=st.integers(min_value=48, max_value=500),
    wmax=st.sampled_from([4, 8]),
    b=st.sampled_from([1, 2]),
    z=st.sampled_from([1, 2]),
    depth=st.sampled_from([1, 3]),
)
def test_prefetch_determinism_property(
    rmat_file, tmp_path_factory, chunk, wmax, b, z, depth
):
    """The refill pipeline is a pure latency optimization: for random
    (chunk_edges, window_max, assign_batch, z, prefetch_depth) and jittered
    worker read timing, the pipelined run assigns bit-identically to the
    synchronous (prefetch=0) run and to the in-memory path, and every refill
    span is accounted exactly once (prestaged XOR missed)."""
    path, edges, n = rmat_file
    m = len(edges)
    cfg = dict(window_max=wmax, assign_batch=b)
    if z == 1:
        ref = run_partitioner("adwise", edges, n, K, seed=0, **cfg)
    else:
        ref = spotlight_partition(
            edges, n, K, z=z, spread=max(1, K // z), strategy="adwise",
            cfg=AdwiseConfig(k=K, seed=0, **cfg),
        )
    td = tmp_path_factory.mktemp("pfprop")
    outs = {}
    from repro.graph.io.format import EdgeFileReader as _R
    from repro.graph.io.format import EdgeFileSubReader as _SR
    for pf in (0, depth):
        jitter = {}
        if pf:  # delays land inside the read-ahead worker thread
            jitter = {
                _R: ("read", _R.read), _SR: ("read", _SR.read),
            }
            for klass, (name, orig) in jitter.items():
                def slow(self, start, count, _orig=orig):
                    time.sleep(((start // 64) % 3) * 5e-4)
                    return _orig(self, start, count)
                setattr(klass, name, slow)
        try:
            with EdgeFileReader(path) as r:
                res = partition_file(
                    r, "adwise", K, z=z,
                    spread=max(1, K // z) if z > 1 else None, seed=0,
                    chunk_edges=chunk, spill_dir=str(td), prefetch=pf, **cfg,
                )
        finally:
            for klass, (name, orig) in jitter.items():
                setattr(klass, name, orig)
        outs[pf] = res
        s = res.stats
        assert s["prefetch_depth"] == pf
        assert s["spans_prestaged"] + s["spans_missed"] == s["refill_spans"]
        if pf == 0:
            assert s["spans_prestaged"] == 0  # sync path never prestages
        assert s["h2d_rows"] == m  # pipeline never re-ships a row
        assert (np.asarray(res.assign) == ref.assign).all(), (
            f"prefetch={pf} diverged at chunk={chunk} wmax={wmax} b={b} z={z}"
        )
    assert (np.asarray(outs[0].assign) == np.asarray(outs[depth].assign)).all()


def test_prefetch_worker_prestages(rmat_file):
    """The read-ahead worker stages spans before the consumer asks: once it
    has provably read past the next refill target, that refill is a
    pipeline hit (spans_prestaged), not a miss."""
    path, _, n = rmat_file
    cfg = AdwiseConfig(k=K, window_max=8)
    with EdgeFileReader(path) as r:
        with FileSource([r], chunk_edges=150, cfg=cfg, prefetch=2) as src:
            buf = src.alloc()
            buf = src.refill(buf, np.zeros(1, np.int64))
            hi0 = int(src.hi[0])
            assert src._worker is not None  # pipeline actually engaged
            # Wait until the worker has staged at least one block past hi
            # (it may stage up to depth = 2 * max_span rows ahead).
            target = min(hi0 + src.Rq, int(src.m_per[0]))
            deadline = time.monotonic() + 10.0
            while int(src._worker._next[0]) < target:
                assert time.monotonic() < deadline, "worker never got ahead"
                time.sleep(0.005)
            buf = src.refill(buf, np.array([hi0], np.int64))
            assert int(src.hi[0]) > hi0
            assert src.spans_prestaged >= 1, "staged refill counted as miss"
            assert (src.spans_prestaged + src.spans_missed
                    == src.refill_spans)


def test_prefetch_worker_teardown_on_error(rmat_file):
    """A reader failure inside the worker thread surfaces as the consumer's
    exception, and FileSource teardown joins the thread — no leak."""
    path, _, n = rmat_file

    class _BoomReader:
        def __init__(self, inner):
            self.num_edges = inner.num_edges

        def read(self, start, count):
            raise IOError("disk pulled")

    cfg = AdwiseConfig(k=K, window_max=8)
    before = {t for t in threading.enumerate() if t.name == "adwise-readahead"}
    with EdgeFileReader(path) as r:
        with pytest.raises(RuntimeError, match="read-ahead worker failed"):
            with FileSource([_BoomReader(r)], chunk_edges=100, cfg=cfg,
                            prefetch=2) as src:
                src.refill(src.alloc(), np.zeros(1, np.int64))
    leaked = {
        t for t in threading.enumerate() if t.name == "adwise-readahead"
    } - before
    assert not leaked, f"read-ahead thread leaked: {leaked}"


def test_resolve_prefetch_env(monkeypatch):
    monkeypatch.delenv("ADWISE_PREFETCH", raising=False)
    assert resolve_prefetch(None) == 2  # pipeline on by default
    assert resolve_prefetch(0) == 0
    assert resolve_prefetch(5) == 5
    monkeypatch.setenv("ADWISE_PREFETCH", "0")
    assert resolve_prefetch(None) == 0
    monkeypatch.setenv("ADWISE_PREFETCH", "3")
    assert resolve_prefetch(None) == 3
    assert resolve_prefetch(1) == 1  # explicit argument beats the env


# ----------------------------------------------------------------------------
# Resident-source driving (the partition_stream / batched thin callers)
# ----------------------------------------------------------------------------


def test_partition_stream_reports_h2d(rmat_file):
    path, edges, n = rmat_file
    m = len(edges)
    res = partition_stream(edges, n, AdwiseConfig(k=K, window_max=8))
    # One resident upload: the (m, 2) stream plus the (m,) prev buffer.
    assert res.stats["h2d_rows"] == m
    assert res.stats["h2d_bytes"] == m * 8 + m * 4
    assert res.stats["scan_calls"] >= 1
    assert res.stats["unassigned"] == 0


def test_resident_source_validates_shapes():
    streams = np.zeros((2, 10, 2), np.int32)
    src = ResidentSource(streams, np.array([10, 7]))
    assert src.z == 2 and src.per == 10 and src.upload_rows == 20
    with pytest.raises(AssertionError):
        ResidentSource(streams, np.array([10, 11]))  # m_per > per


def test_resolve_backend():
    assert resolve_backend("vmap", 4) == ("vmap", 0)
    # Single-device hosts degrade shard_map to vmap.
    import jax

    if jax.device_count() == 1:
        assert resolve_backend("auto", 4) == ("vmap", 0)
        assert resolve_backend("shard_map", 4) == ("vmap", 0)
    with pytest.raises(ValueError, match="backend"):
        resolve_backend("loop", 2)


def test_driver_rejects_file_mode_without_sink(rmat_file):
    path, _, n = rmat_file
    cfg = AdwiseConfig(k=K, window_max=8)
    with EdgeFileReader(path) as r:
        src = FileSource([r], chunk_edges=100, cfg=cfg)
        drv = ScanDriver(src, cfg, n)
        with pytest.raises(AssertionError, match="on_assign"):
            drv.run()


# ----------------------------------------------------------------------------
# Executor paths: a lone instance stepped unbatched == the vmapped program
# ----------------------------------------------------------------------------


@pytest.fixture(scope="module")
def kilo_file(tmp_path_factory):
    edges, n = rmat(10, 2000, seed=14)
    path = str(tmp_path_factory.mktemp("paths") / "rmat10.adw")
    write_edge_file(path, edges, n)
    return path, edges, n


def _core(name, n):
    if name == "adwise":
        return AdwiseCore(cfg=AdwiseConfig(k=K, window_max=8), num_vertices=n)
    return HdrfCore(num_vertices=n, k=K, seed=3)


def _vmapped(one, core, n_steps):
    """The executors' batched path on its own: the program every instance
    count ran before a lone instance was stepped unbatched."""
    one = partial(one, core=core, n_steps=n_steps)
    return jax.jit(lambda *args: driver._batched(one, 0, *args))


def _assert_same(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("core_name", ["adwise", "hdrf"])
def test_single_instance_ring_scan_matches_vmap(kilo_file, core_name):
    """At z = 1 `_run_scan_ring` steps the bare instance; its carry, ring
    and step outputs are bit-identical to the vmapped program's, call
    after call of a file-backed ring scan."""
    path, _, n = kilo_file
    core = _core(core_name, n)
    with EdgeFileReader(path) as r, FileSource(
        [r], chunk_edges=256, core=core, prefetch=0
    ) as src:
        drv = ScanDriver(src, core, n)
        assert drv.scan_path == "single"
        steps = src.scan_steps
        batched = _vmapped(driver._scan_ring_one, core, steps)
        args = (drv._m_real_j, drv._allowed_j, drv._caps_j)
        carry, buf = drv.carry, src.alloc()
        cursors = np.zeros((1,), np.int64)
        for _ in range(3):
            buf = src.refill(buf, cursors)
            want = batched((carry, buf), *args)
            got = driver._run_scan_ring(
                (carry, buf), *args, core=core, n_steps=steps, n_shards=0
            )
            _assert_same(got, want)
            (carry, buf), _ = got
            cursors = np.asarray(carry.cursor).astype(np.int64)
        assert int(carry.assigned[0]) > 0


@pytest.mark.parametrize("core_name", ["adwise", "hdrf"])
def test_single_instance_resident_scan_matches_vmap(kilo_file, core_name):
    """The resident executor takes the same rule as the ring one."""
    _, edges, n = kilo_file
    core = _core(core_name, n)
    m = len(edges)
    src = ResidentSource(edges[None], np.array([m]))
    drv = ScanDriver(src, core, n)
    assert drv.scan_path == "single"
    steps = 300
    batched = _vmapped(driver._scan_resident_one, core, steps)
    args = (jnp.asarray(src.streams), drv._m_real_j, drv._allowed_j,
            drv._caps_j, jnp.full((1, m), -1, jnp.int32))
    carry = drv.carry
    for _ in range(3):
        want = batched(carry, *args)
        got = driver._run_scan_resident(
            carry, *args, core=core, n_steps=steps, n_shards=0
        )
        _assert_same(got, want)
        carry, _ = got
    assert int(carry.assigned[0]) > 0


def test_scan_path_follows_instance_count(kilo_file, tmp_path):
    path, edges, n = kilo_file
    assert scan_path(1, 0) == "single"
    assert scan_path(4, 0) == scan_path(4, 4) == "vmap"
    with EdgeFileReader(path) as r:
        res = partition_file(r, "adwise", K, chunk_edges=256, window_max=8,
                             spill_dir=str(tmp_path))
    assert res.stats["scan_path"] == "single"
    z, per = 4, len(edges) // 4
    streams = np.ascontiguousarray(edges[: z * per].reshape(z, per, 2))
    results = partition_stream_batched(
        streams, np.ones((z, per), bool), n, AdwiseConfig(k=K, window_max=8),
        backend="vmap",
    )
    assert [r.stats["scan_path"] for r in results] == ["vmap"] * z

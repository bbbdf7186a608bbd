"""Batched (vmapped / shard_mapped) spotlight path.

Covers the device-parallel refactor: z instance scans as one program
(`partition_stream_batched`), the spotlight rewrite on top of it, the
restream × spotlight composition (per-instance WarmState batches), and —
in a subprocess with 4 fake CPU devices — the padded `parts` engine mesh
plus the shard_map instance axis.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    AdwiseConfig,
    partition_stream,
    partition_stream_batched,
    restream_partition_batched,
    run_partitioner,
    spotlight_partition,
    spread_mask,
    warm_from_assignment,
)
from repro.graph import replica_sets_from_assignment, replication_degree
from repro.graph.stream import EdgeStream

N, M = 24, 60  # same adversarial-stream shapes as test_restream.py


def _adversarial_stream(kind: str, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "random":
        uv = rng.integers(0, N, (M, 2))
    elif kind == "self_loops":
        u = rng.integers(0, N, M)
        v = np.where(rng.random(M) < 0.5, u, rng.integers(0, N, M))
        uv = np.stack([u, v], axis=1)
    elif kind == "duplicates":
        base = rng.integers(0, N, (4, 2))
        uv = base[rng.integers(0, 4, M)]
    elif kind == "star":
        center = int(rng.integers(0, N))
        leaves = rng.integers(0, N, M)
        uv = np.stack([np.full(M, center), leaves], axis=1)
    else:  # pragma: no cover
        raise ValueError(kind)
    return uv.astype(np.int32)


def _random_edges(seed, n=50, m=300):
    rng = np.random.default_rng(seed)
    return np.stack(
        [rng.integers(0, n, m), rng.integers(0, n, m)], axis=1
    ).astype(np.int32)


# ----------------------------------------------------------------------------
# z=1 parity: the batched program is the same trace, vmapped
# ----------------------------------------------------------------------------

def test_batched_z1_bit_identical_to_partition_stream():
    edges = _random_edges(0)
    n, k = 50, 8
    cfg = AdwiseConfig(k=k, window_max=16, window_init=4)
    ref = partition_stream(edges, n, cfg)
    streams, valid = EdgeStream(edges, n).split_padded(1)
    got = partition_stream_batched(streams, valid, n, cfg)
    assert len(got) == 1
    np.testing.assert_array_equal(ref.assign, got[0].assign)
    # Same work was done, not just the same answer.
    assert ref.stats["score_rows"] == got[0].stats["score_rows"]
    assert ref.stats["final_w"] == got[0].stats["final_w"]
    np.testing.assert_array_equal(ref.stats["w_trace"], got[0].stats["w_trace"])


def test_batched_z1_warm_pass_bit_identical():
    """Warm-started (re-streaming) passes go through the same batched trace."""
    edges = _random_edges(3)
    n, k = 50, 6
    cfg = AdwiseConfig(k=k, window_max=16, window_init=4)
    base = partition_stream(edges, n, cfg)
    warm = warm_from_assignment(edges, base.assign, n, k)
    ref = partition_stream(edges, n, cfg, warm=warm)
    streams, valid = EdgeStream(edges, n).split_padded(1)
    got = partition_stream_batched(streams, valid, n, cfg, warm=[warm])
    np.testing.assert_array_equal(ref.assign, got[0].assign)


def test_batched_z1_allowed_mask_bit_identical():
    edges = _random_edges(7)
    n, k = 50, 8
    cfg = AdwiseConfig(k=k, window_max=16, window_init=4)
    allowed = spread_mask(k, 2, 0, 4)
    ref = partition_stream(edges, n, cfg, allowed=allowed)
    streams, valid = EdgeStream(edges, n).split_padded(1)
    got = partition_stream_batched(
        streams, valid, n, cfg, allowed=allowed[None, :]
    )
    np.testing.assert_array_equal(ref.assign, got[0].assign)


@pytest.mark.parametrize("m", [400, 250])  # z | m and z ∤ m
def test_spotlight_batched_matches_loop(m):
    """Both backends split the stream at the same instance boundaries
    (EdgeStream.split_bounds) — including ragged tails when z does not
    divide m — so the batched program must reproduce the sequential
    instances bit-for-bit."""
    edges = _random_edges(1, m=m)
    n, k, z = 50, 8, 4
    cfg = AdwiseConfig(k=k, window_max=16, window_init=4)
    loop = spotlight_partition(edges, n, k, z=z, spread=2, cfg=cfg,
                               backend="loop")
    batched = spotlight_partition(edges, n, k, z=z, spread=2, cfg=cfg,
                                  backend="batched")
    np.testing.assert_array_equal(loop.assign, batched.assign)
    assert batched.stats["backend"] in ("vmap", "shard_map")
    assert loop.stats["backend"] == "loop"


def test_length_bucketed_batch_bit_identical_to_per_instance():
    """Skewed per-instance lengths split the batch into several pow2 length
    buckets; every instance must still reproduce its stand-alone scan
    bit-for-bit — for ADWISE (stateless across instances) and for HDRF,
    whose tie-break seeds derive from the *global* instance id and would
    drift if bucketing's permutation leaked into `seed_instances`."""
    from repro.core.adwise import _ceil_pow2
    from repro.core.baselines import HdrfCore

    rng = np.random.default_rng(9)
    ms = [30, 70, 150, 290]
    z, per, n, k = len(ms), max(ms), 50, 8
    streams = np.zeros((z, per, 2), np.int32)
    valid = np.zeros((z, per), bool)
    for i, m in enumerate(ms):
        streams[i, :m] = np.stack(
            [rng.integers(0, n, m), rng.integers(0, n, m)], axis=1
        )
        valid[i, :m] = True
    assert len({_ceil_pow2(m) for m in ms}) == 4  # genuinely multi-bucket

    cfg = AdwiseConfig(k=k, window_max=8, window_init=2)
    got = partition_stream_batched(streams, valid, n, cfg)
    assert got[0].stats["n_buckets"] == 4
    for i, m in enumerate(ms):
        ref = partition_stream(streams[i, :m], n, cfg)
        np.testing.assert_array_equal(ref.assign, got[i].assign)

    # HDRF: the batch seeds instance i with seed + i (its global id), so
    # the stand-alone reference for instance i is a z=1 batch seeded seed+i.
    seed = 5
    got_h = partition_stream_batched(
        streams, valid, n, None, core=HdrfCore(num_vertices=n, k=k, seed=seed)
    )
    for i, m in enumerate(ms):
        ref_h = partition_stream_batched(
            streams[i : i + 1, :m], valid[i : i + 1, :m], n, None,
            core=HdrfCore(num_vertices=n, k=k, seed=seed + i),
        )
        np.testing.assert_array_equal(ref_h[0].assign, got_h[i].assign)


# ----------------------------------------------------------------------------
# Spread-mask property on adversarial streams
# ----------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    kind=st.sampled_from(["random", "self_loops", "duplicates", "star"]),
    z=st.sampled_from([2, 4]),
    spread=st.sampled_from([2, 4]),
)
def test_batched_spotlight_respects_spread_property(seed, kind, z, spread):
    """Every batched instance stays inside its spread block — adversarial
    streams (stars stall the top-b pick, duplicates/self-loops stress the
    window) included, and every edge is assigned."""
    edges = _adversarial_stream(kind, seed)
    k = 8
    cfg = AdwiseConfig(k=k, window_max=8, window_init=2)
    res = spotlight_partition(edges, N, k, z=z, spread=spread, cfg=cfg,
                              backend="batched")
    assert (res.assign >= 0).all() and (res.assign < k).all()
    per = -(-len(edges) // z)
    for i in range(z):
        allowed = set(np.flatnonzero(spread_mask(k, z, i, spread)))
        seg = res.assign[i * per : min((i + 1) * per, len(edges))]
        assert set(np.unique(seg)) <= allowed, (kind, i)


def test_batched_more_instances_than_edges():
    """z > m leaves some instances with empty streams — still valid."""
    edges = np.array([[0, 1], [1, 2]], np.int32)
    res = spotlight_partition(edges, 4, 8, z=4, spread=2,
                              cfg=AdwiseConfig(k=8, window_max=4),
                              backend="batched")
    assert res.assign.shape == (2,)
    assert (res.assign >= 0).all()


# ----------------------------------------------------------------------------
# restream × spotlight composition (per-instance WarmState batches)
# ----------------------------------------------------------------------------

def test_restream_batched_composes_with_spread(tiny_graph):
    edges, n = tiny_graph
    edges = edges[:400]
    k, z, spread = 8, 2, 4
    res = spotlight_partition(
        edges, n, k, z=z, spread=spread, strategy="adwise-restream",
        strategy_cfg=dict(passes=2, window_max=8, window_init=2),
        backend="batched",
    )
    assert (res.assign >= 0).all() and (res.assign < k).all()
    assert res.stats["passes_run"] == 2
    assert res.stats["stream_reads"] == 2
    per = -(-len(edges) // z)
    for i in range(z):
        allowed = set(np.flatnonzero(spread_mask(k, z, i, spread)))
        seg = res.assign[i * per : min((i + 1) * per, len(edges))]
        assert set(np.unique(seg)) <= allowed


def test_restream_batched_quality_monotone_per_instance(tiny_graph):
    """keep_best holds per instance: 2-pass batched restream is no worse
    than the 1-pass batched run on every instance's sub-stream."""
    edges, n = tiny_graph
    k, z = 8, 2
    streams, valid = EdgeStream(edges, n).split_padded(z)
    allowed = np.stack([spread_mask(k, z, i, 4) for i in range(z)])
    cfg = dict(window_max=8, window_init=2)
    one = restream_partition_batched(
        streams, valid, n, k, allowed=allowed, passes=1, **cfg)
    two = restream_partition_batched(
        streams, valid, n, k, allowed=allowed, passes=2, **cfg)
    for i in range(z):
        m_i = int(valid[i].sum())
        sub = streams[i, :m_i]
        rd1 = replication_degree(
            replica_sets_from_assignment(sub, one[i].assign, n, k))
        rd2 = replication_degree(
            replica_sets_from_assignment(sub, two[i].assign, n, k))
        assert rd2 <= rd1 + 1e-9
        assert two[i].stats["passes_run"] == 2


def test_restream_batched_eps_early_stop(tiny_graph):
    edges, n = tiny_graph
    streams, valid = EdgeStream(edges[:300], n).split_padded(2)
    res = restream_partition_batched(
        streams, valid, n, 8, passes=5, eps=10.0,
        window_max=8, window_init=2,
    )
    # A pass never improves RD by >= 10, so exactly one extra pass runs.
    assert res[0].stats["passes_run"] == 2
    assert res[0].stats["passes"] == 5
    assert res[0].stats["stream_reads"] == 2


# ----------------------------------------------------------------------------
# Backend validation
# ----------------------------------------------------------------------------

def test_batched_backend_rejects_custom_partitioner(tiny_graph):
    """Every *registry* strategy batches now; only a custom partitioner
    callable still needs the loop escape hatch."""
    edges, n = tiny_graph

    def custom(sub_edges, nv, k, seed=0, allowed=None):
        from repro.core.registry import run_partitioner
        return run_partitioner("hash", sub_edges, nv, k, seed=seed,
                               allowed=allowed)

    with pytest.raises(ValueError, match="loop"):
        spotlight_partition(edges, n, 8, z=2, spread=4, partitioner=custom,
                            backend="batched")


def test_unknown_backend_rejected(tiny_graph):
    edges, n = tiny_graph
    with pytest.raises(ValueError, match="backend"):
        spotlight_partition(edges, n, 8, z=2, spread=4, backend="tpu")


def test_baselines_auto_select_batched(tiny_graph):
    """auto resolves to the batched backend for every registry strategy —
    the baselines included — and matches the loop backend bit-for-bit."""
    edges, n = tiny_graph
    res = spotlight_partition(edges, n, 16, z=4, spread=4, strategy="dbh")
    assert res.stats["backend"] != "loop"
    assert (res.assign >= 0).all()
    loop = spotlight_partition(edges, n, 16, z=4, spread=4, strategy="dbh",
                               backend="loop")
    assert (res.assign == loop.assign).all()


# ----------------------------------------------------------------------------
# Multi-device: 4 fake CPU devices in a subprocess
# ----------------------------------------------------------------------------

@pytest.mark.slow
def test_multi_device_padding_and_instance_sharding():
    """On a forced 4-device CPU host: `engine_mesh` keeps all devices for a
    k (=6) not divisible by the device count (the parts axis pads 6 -> 8
    inside make_superstep), the engine still computes correct PageRank, and
    the batched partitioner's shard_map backend equals vmap exactly."""
    prog = textwrap.dedent("""
        import numpy as np, jax
        assert jax.device_count() == 4, jax.device_count()
        from repro.core import AdwiseConfig, run_partitioner, spotlight_partition
        from repro.core.adwise import partition_stream_batched
        from repro.engine import build_partitioned_graph, pagerank
        from repro.engine.gas import engine_mesh
        from repro.graph.stream import EdgeStream

        # Padding: k=6 on 4 devices keeps all 4 (6 pads to 8); k=2 caps at 2.
        assert engine_mesh(k=6).devices.size == 4
        assert engine_mesh(k=2).devices.size == 2

        rng = np.random.default_rng(0)
        u, v = rng.integers(0, 40, 300), rng.integers(0, 40, 300)
        keep = u != v
        edges = np.stack([u[keep], v[keep]], 1).astype(np.int32)
        n, k = 40, 6
        res = run_partitioner("hdrf", edges, n, k)
        g = build_partitioned_graph(edges, res.assign, n, k)
        pr, _ = pagerank(g, iters=5)
        deg = np.zeros(n)
        np.add.at(deg, edges[:, 0], 1); np.add.at(deg, edges[:, 1], 1)
        x = np.full(n, 1.0 / n)
        for _ in range(5):
            acc = np.zeros(n)
            np.add.at(acc, edges[:, 1], x[edges[:, 0]] / np.maximum(deg[edges[:, 0]], 1))
            np.add.at(acc, edges[:, 0], x[edges[:, 1]] / np.maximum(deg[edges[:, 1]], 1))
            x = 0.15 / n + 0.85 * acc
        np.testing.assert_allclose(pr, x, rtol=1e-4, atol=1e-7)

        # Slab-balanced placement: k=6 on 4 devices pads to 8 slabs, and
        # the pads are spread so real-slab counts differ by at most 1
        # (naive tail-padding would give (2, 2, 2, 0)). The PageRank check
        # above already proves the permuted layout computes identically.
        from repro.engine.gas import make_superstep
        step = make_superstep(
            g, lambda xu, xv, du, dv: (xu, xv), lambda s, a, d: s,
            engine_mesh(k=6),
        )
        assert step.slab_occupancy == (2, 2, 1, 1), step.slab_occupancy
        assert max(step.slab_occupancy) - min(step.slab_occupancy) <= 1

        # Instance axis on devices: shard_map backend == vmap backend.
        cfg = AdwiseConfig(k=6, window_max=8, window_init=2)
        streams, valid = EdgeStream(edges, n).split_padded(4)
        sm = partition_stream_batched(streams, valid, n, cfg, backend="shard_map")
        vm = partition_stream_batched(streams, valid, n, cfg, backend="vmap")
        assert sm[0].stats["n_shards"] == 4
        for a, b in zip(sm, vm):
            np.testing.assert_array_equal(a.assign, b.assign)

        # spotlight auto picks shard_map on a multi-device host.
        res = spotlight_partition(edges, n, 6, z=4, spread=2, cfg=cfg)
        assert res.stats["backend"] == "shard_map", res.stats["backend"]
        assert (res.assign >= 0).all()
        print("MULTIDEV_BATCHED_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.abspath("src"), env.get("PYTHONPATH")] if p
    )
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "MULTIDEV_BATCHED_OK" in out.stdout


# ----------------------------------------------------------------------------
# build_partitioned_graph unassigned guard (satellite)
# ----------------------------------------------------------------------------

def test_build_partitioned_graph_rejects_unassigned():
    from repro.engine import build_partitioned_graph

    edges = np.array([[0, 1], [1, 2], [2, 3]], np.int32)
    assign = np.array([0, -1, 1], np.int32)
    with pytest.raises(ValueError, match="unassigned|outside"):
        build_partitioned_graph(edges, assign, 4, 2)
    with pytest.raises(ValueError, match="outside"):
        build_partitioned_graph(edges, np.array([0, 2, 1], np.int32), 4, 2)

"""Multi-pass re-streaming (restream.py) + registry-wide assignment invariants.

Streams in the property tests are adversarial by construction: self-loops, duplicate
edges, star graphs (which stall the vertex-disjoint top-b pick), empty
streams and streams shorter than the assign batch.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    AdwiseConfig,
    available_strategies,
    partition_stream,
    restream_partition,
    run_partitioner,
    warm_from_assignment,
)
from repro.core import bitrows
from repro.core.adwise import Carry
from repro.core.restream import streaming_vertex_clustering
from repro.graph import (
    partition_balance,
    replica_sets_from_assignment,
    replication_degree,
)

N, M = 24, 60  # fixed shapes so the scan compiles once per (k, warm) pair


def _rd(edges, assign, n, k):
    return replication_degree(replica_sets_from_assignment(edges, assign, n, k))


def _adversarial_stream(kind: str, seed: int) -> np.ndarray:
    """(M, 2) int32 stream over N vertices; every kind is a worst case."""
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


# Shared strategy cfg: small windows so every adwise-family strategy reuses
# one compiled scan per (k, warm) combination.
def _cfg_for(name: str) -> dict:
    if name in ("adwise", "adwise-restream", "2ps"):
        cfg = dict(window_max=8, window_init=2)
        if name == "adwise-restream":
            cfg["passes"] = 2
        return cfg
    return {}


@settings(max_examples=8, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    kind=st.sampled_from(["random", "self_loops", "duplicates", "star"]),
    k=st.sampled_from([2, 5]),
)
def test_registry_wide_no_unassigned(seed, kind, k):
    """Every registered strategy returns assign in [0, k) — never -1."""
    edges = _adversarial_stream(kind, seed)
    for name in available_strategies():
        res = run_partitioner(name, edges, N, k, seed=seed, **_cfg_for(name))
        assert res.assign.shape == (M,), name
        assert res.assign.dtype == np.int32, name
        assert (res.assign >= 0).all() and (res.assign < k).all(), (
            f"{name} on {kind}: assign outside [0, {k})"
        )


@pytest.mark.parametrize("name", [
    "adwise", "adwise-restream", "2ps", "hdrf", "dbh", "greedy", "hash", "grid",
])
def test_registry_empty_stream(name):
    edges = np.zeros((0, 2), np.int32)
    res = run_partitioner(name, edges, 10, 4, **_cfg_for(name))
    assert res.assign.shape == (0,)


def test_registry_stream_shorter_than_assign_batch():
    edges = np.array([[0, 1], [2, 3]], np.int32)
    for name in ("adwise", "adwise-restream"):
        cfg = dict(_cfg_for(name), assign_batch=4)
        res = run_partitioner(name, edges, 5, 3, **cfg)
        assert (res.assign >= 0).all() and (res.assign < 3).all()


def test_star_graph_batched_drain_assigns_everything():
    """Regression: the static steps_total heuristic under-provisioned scan
    steps when the vertex-disjoint top-b pick stalls (star + assign_batch>1);
    edges were silently left at -1. The bounded drain loop must finish."""
    m = 100
    edges = np.stack(
        [np.zeros(m, np.int32), np.arange(1, m + 1, dtype=np.int32)], axis=1
    )
    for b in (2, 8):
        cfg = AdwiseConfig(k=4, window_max=16, assign_batch=b)
        res = partition_stream(edges, m + 1, cfg)
        assert res.stats["unassigned"] == 0
        assert (res.assign >= 0).all()


# ----------------------------------------------------------------------------
# Re-streaming semantics
# ----------------------------------------------------------------------------

def test_warm_start_carry_fields():
    cfg = AdwiseConfig(k=3, window_max=4)
    v = 6
    replicas = np.zeros((v, 3), bool)
    replicas[1, 2] = True
    deg = np.arange(v)
    sizes = np.array([5, 1, 2])
    carry = Carry.warm_start(cfg, v, 0.0, replicas=replicas, deg=deg, sizes=sizes)
    # One packed word per row (k <= 32), the scatter-dump row appended.
    assert carry.replicas.shape == (v + 1,) and carry.replicas.dtype == np.uint32
    rows = np.asarray(bitrows.unpack(jnp.asarray(carry.replicas), 3))
    assert rows[1, 2] and rows.sum() == 1 and not rows[v].any()
    assert carry.deg[:v].tolist() == deg.tolist()
    assert int(carry.max_deg) == v - 1
    assert carry.sizes.tolist() == sizes.tolist()
    assert float(carry.lam) == cfg.lam_init  # λ re-anneals each pass
    assert int(carry.assigned) == 0


def test_warm_from_assignment_round_trip(tiny_graph):
    edges, n = tiny_graph
    edges = edges[:400]
    k = 4
    res = run_partitioner("hdrf", edges, n, k)
    warm = warm_from_assignment(edges, res.assign, n, k)
    assert warm.replicas.shape == (n, k)
    assert warm.sizes.sum() == len(edges)
    assert (warm.deg >= 0).all() and warm.deg.sum() == 2 * len(edges)
    assert warm.prev_assign is not None
    # A warm pass over the same stream stays valid and balanced.
    res2 = partition_stream(edges, n, AdwiseConfig(k=k, window_max=16), warm=warm)
    assert (res2.assign >= 0).all() and (res2.assign < k).all()
    assert partition_balance(res2.assign, k) < 0.5


def test_restream_pass2_not_worse_fixed_seeds(tiny_graph):
    """Pass-2 replication degree <= pass 1 on a fixed seed set (keep_best
    guarantees the *returned* assignment; pass_rd records the trajectory)."""
    edges, n = tiny_graph
    edges = edges[:1000]
    k = 8
    for seed in (0, 1, 2):
        res = restream_partition(
            edges, n, k, passes=2, seed=seed, window_max=32, window_init=8
        )
        pass_rd = res.stats["pass_rd"]
        assert len(pass_rd) == 2
        rd_final = _rd(edges, res.assign, n, k)
        assert rd_final <= pass_rd[0] + 1e-9
        assert rd_final == pytest.approx(min(pass_rd), abs=1e-9)


def test_restream_matches_single_pass_at_passes_one(tiny_graph):
    edges, n = tiny_graph
    edges = edges[:600]
    k = 4
    cfg = dict(window_max=16, window_init=4)
    res1 = run_partitioner("adwise", edges, n, k, **cfg)
    resr = run_partitioner("adwise-restream", edges, n, k, passes=1, **cfg)
    np.testing.assert_array_equal(res1.assign, resr.assign)


def test_restream_base_strategy(tiny_graph):
    """Pass 1 may be any registered strategy; later passes are warm ADWISE."""
    edges, n = tiny_graph
    edges = edges[:600]
    k = 4
    res = restream_partition(
        edges, n, k, passes=2, base="hdrf", window_max=16, window_init=4
    )
    assert res.stats["base"] == "hdrf"
    assert (res.assign >= 0).all() and (res.assign < k).all()
    assert _rd(edges, res.assign, n, k) <= res.stats["pass_rd"][0] + 1e-9


def test_restream_stats_shape(tiny_graph):
    edges, n = tiny_graph
    edges = edges[:600]
    res = restream_partition(edges, n, 4, passes=3, window_max=16, window_init=4)
    st_ = res.stats
    assert st_["passes"] == 3
    assert len(st_["pass_rd"]) == len(st_["pass_wall_s"]) == 3
    assert len(st_["pass_score_rows"]) == 3
    assert st_["score_rows"] == sum(st_["pass_score_rows"])
    assert 1 <= st_["best_pass"] <= 3
    assert st_["unassigned"] == 0


def test_restream_rejects_bad_cfg():
    edges = np.array([[0, 1]], np.int32)
    with pytest.raises(TypeError, match="unknown config"):
        run_partitioner("adwise-restream", edges, 2, 2, windw_max=8)
    with pytest.raises(ValueError, match="passes"):
        restream_partition(edges, 2, 2, passes=0)


# ----------------------------------------------------------------------------
# 2PS
# ----------------------------------------------------------------------------

def test_2ps_round_trip(tiny_graph):
    edges, n = tiny_graph
    edges = edges[:800]
    k = 8
    res = run_partitioner("2ps", edges, n, k)
    assert (res.assign >= 0).all() and (res.assign < k).all()
    assert res.stats["name"] == "2ps"
    assert res.stats["n_clusters"] >= 1
    assert partition_balance(res.assign, k) < 0.5


def test_2ps_clustering_invariants(tiny_graph):
    edges, n = tiny_graph
    edges = edges[:800]
    k = 8
    cl, vols = streaming_vertex_clustering(edges, n, k)
    streamed = np.zeros(n, bool)
    streamed[edges.ravel()] = True
    assert (cl[streamed] >= 0).all()  # every streamed vertex is clustered
    assert (cl[~streamed] == -1).all()
    # Volumes are consistent with membership: vol[c] == sum deg over members.
    deg = np.zeros(n, np.int64)
    np.add.at(deg, edges[:, 0], 1)
    np.add.at(deg, edges[:, 1], 1)
    recomputed = np.zeros(len(vols))
    for v_id in np.flatnonzero(streamed):
        recomputed[cl[v_id]] += deg[v_id]
    np.testing.assert_allclose(recomputed, vols)


def test_2ps_cluster_affinity_lowers_replication(tiny_graph):
    """On a clustered graph, 2PS (phase-1 knowledge) beats single-edge
    streaming quality — the point of investing a clustering pass."""
    edges, n = tiny_graph
    k = 8
    rd_2ps = _rd(edges, run_partitioner("2ps", edges, n, k).assign, n, k)
    rd_hdrf = _rd(edges, run_partitioner("hdrf", edges, n, k).assign, n, k)
    assert rd_2ps < rd_hdrf


def test_spotlight_forwards_restream_cfg(tiny_graph):
    """Spotlight parallel loading composes with re-streaming strategies and
    forwards their cfg (regression: strategy_cfg used to be dropped)."""
    from repro.core import spotlight_partition, spread_mask

    edges, n = tiny_graph
    edges = edges[:400]
    k, z, spread = 8, 2, 4
    res = spotlight_partition(
        edges, n, k, z=z, spread=spread, strategy="adwise-restream",
        strategy_cfg=dict(passes=2, window_max=8, window_init=2),
    )
    assert (res.assign >= 0).all() and (res.assign < k).all()
    # Each instance stayed inside its spread block.
    from repro.graph.stream import EdgeStream
    bounds = EdgeStream.split_bounds(len(edges), z)
    for i in range(z):
        allowed = set(np.flatnonzero(spread_mask(k, z, i, spread)))
        assert set(np.unique(res.assign[bounds[i]:bounds[i + 1]])) <= allowed


def test_2ps_rejects_bad_cfg():
    edges = np.array([[0, 1]], np.int32)
    with pytest.raises(TypeError, match="unknown config"):
        run_partitioner("2ps", edges, 2, 2, cluster_slck=1.0)

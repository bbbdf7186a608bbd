"""repro.compat (JAX portability) and the partitioner registry."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core import available_strategies, get_partitioner, run_partitioner
from repro.core.registry import register
from repro.engine.gas import engine_mesh
from repro.kernels import ops

from conftest import random_edges

ALL_STRATEGIES = ["2ps", "2ps-l", "adwise", "adwise-restream", "dbh",
                  "greedy", "grid", "hash", "hdrf"]


# ----------------------------------------------------------------------------
# shard_map resolution / kwarg adaptation
# ----------------------------------------------------------------------------

def test_shard_map_resolves_on_installed_jax():
    """compat.shard_map is jax.shard_map, whose replication check is
    check_vma on the one JAX target."""
    import inspect

    assert "check_vma" in inspect.signature(jax.shard_map).parameters


def test_shard_map_runs_psum():
    mesh = engine_mesh(n_devices=1)
    f = compat.shard_map(
        lambda x: jax.lax.psum(x.sum(keepdims=True), "parts"),
        mesh=mesh, in_specs=P("parts"), out_specs=P(),
        check_replication=False,
    )
    out = f(jnp.arange(4, dtype=jnp.float32))
    assert float(out[0]) == 6.0


def test_shard_map_rejects_wrong_rep_kwarg_directly():
    """The installed shard_map does NOT accept the retired check_rep kwarg —
    the keyword compat maps check_replication onto is load-bearing."""
    mesh = engine_mesh(n_devices=1)
    with pytest.raises(TypeError):
        jax.shard_map(
            lambda x: x, mesh=mesh, in_specs=P(), out_specs=P(), check_rep=False
        )


# ----------------------------------------------------------------------------
# make_mesh / engine_mesh
# ----------------------------------------------------------------------------

def test_make_mesh_builds_auto_axes():
    """jax.make_mesh defaults to Explicit axes, where a gather from a
    sharded operand raises ShardingTypeError; compat's mesh is Auto."""
    from jax.sharding import AxisType, NamedSharding

    mesh = compat.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    table = jax.device_put(jnp.arange(12.0).reshape(6, 2),
                           NamedSharding(mesh, P("data", "model")))
    idx = jnp.array([5, 0, 3])
    with mesh:
        out = jax.jit(lambda t, i: t[i])(table, idx)
    np.testing.assert_array_equal(np.asarray(out), np.arange(12.0).reshape(6, 2)[[5, 0, 3]])


def test_engine_mesh_single_device():
    mesh = engine_mesh(n_devices=1)
    assert mesh.axis_names == ("parts",)
    assert mesh.devices.size == 1


def test_engine_mesh_k_exceeding_devices():
    """engine_mesh keeps every device for any k (make_superstep pads the
    parts axis); it only caps the mesh at k when devices outnumber parts."""
    import jax as _jax

    n_dev = _jax.device_count()
    for k in (3, 7, 8, 16):
        mesh = engine_mesh(k=k)
        assert mesh.devices.size == min(n_dev, k)
    assert engine_mesh(k=1).devices.size == 1


@pytest.mark.slow
def test_engine_multi_device_cpu_mesh():
    """Full engine correctness on a forced 6-device CPU host (subprocess so
    the device count does not leak into this process)."""
    prog = textwrap.dedent("""
        import numpy as np, jax
        assert jax.device_count() == 6, jax.device_count()
        from repro.engine.gas import engine_mesh
        from repro.engine import build_partitioned_graph, pagerank
        from repro.core import run_partitioner
        # All devices stay in the mesh; non-divisible k pads inside
        # make_superstep (k=9 on 6 devices -> parts axis pads 9 -> 12).
        assert engine_mesh(k=9).devices.size == 6
        assert engine_mesh(k=6).devices.size == 6
        assert engine_mesh(k=4).devices.size == 4  # capped at k
        rng = np.random.default_rng(0)
        u, v = rng.integers(0, 40, 300), rng.integers(0, 40, 300)
        keep = u != v
        edges = np.stack([u[keep], v[keep]], 1).astype(np.int32)
        n, k = 40, 9
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
        print("MULTIDEV_OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.abspath("src"), env.get("PYTHONPATH")] if p
    )
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "MULTIDEV_OK" in out.stdout


# ----------------------------------------------------------------------------
# Pallas probe
# ----------------------------------------------------------------------------

def test_pallas_probe_consistent_with_resolver(monkeypatch):
    monkeypatch.delenv(ops.KERNEL_TIER_ENV, raising=False)
    for op in ("window_score", "segment_sum", "flash_attention"):
        tiers = ops.available_tiers(op)
        assert tiers[-1] == "xla"
        resolved = ops.resolve_tier(op)
        assert resolved in tiers  # in particular: never 'interpret'
    if jax.default_backend() != "tpu":
        assert "pallas-tpu" not in ops.available_tiers("window_score")
        # pallas-cpu exists only where JAX can genuinely lower on CPU.
        if not compat.has_pallas_cpu_lowering():
            assert ops.available_tiers("window_score") == ("xla",)
            assert ops.resolve_tier("window_score") == "xla"
    # Legacy alias from the impl= era still resolves.
    assert ops.resolve_tier("window_score", "ref") == "xla"


def test_pallas_cpu_lowering_probe_is_cached_and_boolean():
    first = compat.has_pallas_cpu_lowering()
    assert isinstance(first, bool)
    assert compat.has_pallas_cpu_lowering() is first


# ----------------------------------------------------------------------------
# Partitioner registry
# ----------------------------------------------------------------------------

def test_registry_lists_all_builtin_strategies():
    assert available_strategies() == ALL_STRATEGIES


@pytest.mark.parametrize("strategy", ALL_STRATEGIES)
def test_registry_round_trip(strategy):
    rng = np.random.default_rng(7)
    edges = random_edges(rng, 60, 250)
    n, k = 60, 5
    cfg = (dict(window_max=16)
           if strategy in ("adwise", "adwise-restream", "2ps") else {})
    res = run_partitioner(strategy, edges, n, k, seed=3, **cfg)
    assert res.assign.shape == (len(edges),)
    assert res.assign.dtype == np.int32
    assert (res.assign >= 0).all() and (res.assign < k).all()
    assert res.stats.get("k") == k
    # Same name through get_partitioner is the same callable result.
    res2 = get_partitioner(strategy)(edges, n, k, seed=3, **cfg)
    np.testing.assert_array_equal(res.assign, res2.assign)


def test_registry_unknown_strategy_names_available():
    with pytest.raises(KeyError, match="hdrf"):
        get_partitioner("metis")


def test_registry_rejects_unknown_adwise_cfg():
    edges = np.array([[0, 1]], np.int32)
    with pytest.raises(TypeError, match="window_maxx"):
        run_partitioner("adwise", edges, 2, 2, window_maxx=8)


def test_registry_rejects_duplicate_name():
    with pytest.raises(ValueError, match="already registered"):
        register("hdrf")(lambda *a, **kw: None)


def test_partition_cli_accepts_every_registry_strategy():
    from repro.launch.partition import main

    for strategy in available_strategies():
        out = main(["--graph", "tiny_clustered", "--strategy", strategy,
                    "--k", "4", "--workload", "none", "--window-max", "16"])
        assert out["strategy"] == strategy
        assert out["replication_degree"] >= 1.0

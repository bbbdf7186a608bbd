"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real single CPU device; only launch/dryrun.py forces 512."""
import numpy as np
import pytest

from repro.graph import make_graph


@pytest.fixture(autouse=True)
def _hermetic_kernel_autotune(tmp_path, monkeypatch):
    """Point the kernel-tier autotune cache at a per-test path and drop the
    in-process memo, so a developer machine's accumulated table (or another
    test's recordings) can never leak measured walls into assertions — e.g.
    `partition_latency` expectations computed from SCORE_COST_S."""
    from repro.kernels import ops

    monkeypatch.setenv(ops.AUTOTUNE_CACHE_ENV,
                       str(tmp_path / "kernel_tiers.json"))
    ops.clear_tier_cache()
    yield
    ops.clear_tier_cache()


@pytest.fixture(scope="session")
def tiny_graph():
    edges, n = make_graph("tiny_clustered", seed=1)
    return edges, n


@pytest.fixture(scope="session")
def tiny_social():
    edges, n = make_graph("tiny_social", seed=2)
    return edges, n


def random_edges(rng: np.random.Generator, n: int, m: int) -> np.ndarray:
    u = rng.integers(0, n, m)
    v = rng.integers(0, n, m)
    keep = u != v
    return np.stack([u[keep], v[keep]], axis=1).astype(np.int32)

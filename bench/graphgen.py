"""Graph500 Kronecker edge lists, drawn on the device from a seed.

The Graph500 specification (graph500.org, "Graph 500 Benchmarks 1 and 2",
section 3, Kronecker generator): ``edgefactor * 2**SCALE`` edges, each
descending SCALE levels of the 2x2 initiator ``[[A, B], [C, D]]`` and taking
one bit of its source and one of its destination per level; then every
vertex id is relabelled by one random permutation. Self-loops and repeated
edges are kept, as in the specification's edge list. The file a
partitioning user streams is that list sorted (stably) by source.

One uniform draw per edge and level picks the quadrant: A below ``a``, B
below ``a + b``, C below ``a + b + c``, D above. The same seed gives the
same graph on every backend (the draws are counter-based).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def graph_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnames=("scale", "edge_factor"))
def _draw(key, a, b, c, *, scale: int, edge_factor: int):
    n = 1 << scale
    m = edge_factor * n
    k_perm, k_edge = jax.random.split(key)
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)

    def level(i, uv):
        u, v = uv
        r = jax.random.uniform(jax.random.fold_in(k_edge, i), (m,), jnp.float32)
        u_bit = r >= a + b
        v_bit = ((r >= a) & ~u_bit) | (r >= a + b + c)
        return (u << 1) | u_bit.astype(jnp.int32), (v << 1) | v_bit.astype(jnp.int32)

    zero = jnp.zeros((m,), jnp.int32)
    u, v = jax.lax.fori_loop(0, scale, level, (zero, zero))
    u, v = perm[u], perm[v]
    return jax.lax.sort((u, v), num_keys=1, is_stable=True)


def kronecker(graph: dict, seed: int, max_edges: int | None = None):
    """``(edges (m, 2) int32 numpy, num_vertices)``: the source-sorted edge
    file of the Graph500 graph ``graph`` (keys ``scale``, ``edge_factor``,
    ``a``, ``b``, ``c``), or its first ``max_edges`` rows."""
    scale, ef = int(graph["scale"]), int(graph["edge_factor"])
    u, v = _draw(graph_key(seed), jnp.float32(graph["a"]),
                 jnp.float32(graph["b"]), jnp.float32(graph["c"]),
                 scale=scale, edge_factor=ef)
    if max_edges is not None:
        u, v = u[:max_edges], v[:max_edges]
    edges = np.stack([np.asarray(u), np.asarray(v)], axis=1)
    return edges, 1 << scale

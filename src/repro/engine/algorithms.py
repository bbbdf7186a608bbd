"""Graph algorithms on the vertex-cut engine (the paper's §IV workloads).

  pagerank          — light compute/comm (paper Fig. 7a-c)
  coloring          — greedy conflict-resolution coloring (paper Fig. 7e, [4])
  label_propagation — connected components (min-label flooding)
  triangle_count    — heavy neighbourhood-intersection workload: the stand-in
                      for the paper's NP-complete subgraph-isomorphism /
                      clique searches (Fig. 7d/f) — compute- and
                      communication-heavy per superstep.

Each returns (result, info) where info carries superstep counts the latency
model converts into cluster processing latency.

When no mesh is passed, each workload builds one via `engine_mesh(k=g.k)`
(see `repro.compat` for the version-portable mesh/shard_map plumbing); the
partition axis is padded inside `make_superstep` so any device count shards
evenly (empty slabs are masked out of the gather and the replica sync).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.engine.gas import engine_mesh, make_superstep
from repro.engine.partitioned import PartitionedGraph

__all__ = [
    "pagerank", "pagerank_superstep", "label_propagation", "coloring",
    "triangle_count",
]


def pagerank_superstep(
    g: PartitionedGraph, damping: float = 0.85, mesh: Mesh | None = None,
    trace=None,
):
    """PageRank's jitted superstep and its initial state, ``(step, x0)``.

    ``x ← (1−d)/V + d·Σ_{nbrs} x_u / deg(u)`` with mass pushed both ways
    along every (undirected) edge. Exposed so a caller can time supersteps
    with the compile outside the window; :func:`pagerank` iterates it.
    """
    mesh = mesh or engine_mesh(k=g.k)
    v = g.num_vertices
    msg, apply = pagerank_update(v, damping)
    step = make_superstep(g, msg, apply, mesh, trace=trace)
    return step, jnp.full((v, 1), 1.0 / v, jnp.float32)


def pagerank_update(num_vertices: int, damping: float = 0.85):
    """PageRank's ``(msg_fn, apply_fn)`` pair for :func:`make_superstep`."""

    def msg(x_u, x_v, deg_u, deg_v):
        # Push current rank mass along both directions (undirected).
        return x_u / jnp.maximum(deg_u, 1)[:, None], x_v / jnp.maximum(deg_v, 1)[:, None]

    def apply(state, synced, degrees):
        return (1.0 - damping) / num_vertices + damping * synced

    return msg, apply


def pagerank(
    g: PartitionedGraph, iters: int = 20, damping: float = 0.85,
    mesh: Mesh | None = None, trace=None,
) -> Tuple[np.ndarray, dict]:
    step, state = pagerank_superstep(g, damping, mesh, trace)
    for _ in range(iters):
        state = step(state)
    return np.asarray(state[:, 0]), dict(supersteps=iters, msg_width=1)


def label_propagation(
    g: PartitionedGraph, max_iters: int = 64, mesh: Mesh | None = None,
    trace=None,
) -> Tuple[np.ndarray, dict]:
    """Connected components by min-label flooding; converged when stable."""
    mesh = mesh or engine_mesh(k=g.k)
    v = g.num_vertices

    def msg(x_u, x_v, deg_u, deg_v):
        return x_u, x_v  # forward the neighbour's current label

    def apply(state, synced, degrees):
        has_nbr = synced < 3.0e38
        return jnp.where(has_nbr, jnp.minimum(state, synced), state)

    step = make_superstep(g, msg, apply, mesh, combine="min", trace=trace)
    state = jnp.arange(v, dtype=jnp.float32)[:, None]
    it = 0
    for it in range(1, max_iters + 1):
        new = step(state)
        if bool(jnp.all(new == state)):
            state = new
            break
        state = new
    return np.asarray(state[:, 0]).astype(np.int64), dict(supersteps=it, msg_width=1)


def coloring(
    g: PartitionedGraph, max_colors: int = 64, max_iters: int = 256,
    mesh: Mesh | None = None, trace=None,
) -> Tuple[np.ndarray, dict]:
    """Largest-priority-first greedy coloring (Jones–Plassmann schedule).

    A vertex finalizes once every *unfinalized* neighbour has lower priority,
    taking the smallest color unused by finalized neighbours — exactly the
    sequential greedy order, so the result is always a proper coloring.

    State (min-combined) per vertex: [a | b_0..b_{C-1}] with
      a   = −(prio+1) while unfinalized, +BIG once finalized
      b_j = 0 if finalized with color j else 1
    so synced_a = −(max unfinalized neighbour prio+1) and synced_b_j = 0 iff
    some finalized neighbour holds color j.
    """
    mesh = mesh or engine_mesh(k=g.k)
    v, c = g.num_vertices, max_colors
    rng = np.random.default_rng(0)
    prio = jnp.asarray((rng.permutation(v) + 1).astype(np.float32))
    big = jnp.float32(3.0e38)

    def msg(x_u, x_v, deg_u, deg_v):
        return x_u, x_v

    def apply(state, synced, degrees):
        a = state[:, 0]
        finalized = a > 0
        # No unfinalized higher-priority neighbour (priorities are distinct).
        can = (~finalized) & (synced[:, 0] > -prio)
        free = jnp.argmax(synced[:, 1:] > 0.5, axis=1)  # smallest unused color
        b = jnp.where(
            can[:, None],
            1.0 - jax.nn.one_hot(free, c, dtype=jnp.float32),
            state[:, 1:],
        )
        a_new = jnp.where(can, big, a)
        return jnp.concatenate([a_new[:, None], b], axis=1)

    step = make_superstep(g, msg, apply, mesh, combine="min", trace=trace)
    state = jnp.concatenate([(-prio)[:, None], jnp.ones((v, c), jnp.float32)], axis=1)
    it = 0
    for it in range(1, max_iters + 1):
        new = step(state)
        if bool(jnp.all(new[:, 0] > 0)) or bool(jnp.all(new == state)):
            state = new
            break
        state = new
    colors = np.asarray(jnp.argmin(state[:, 1:], axis=1))
    return colors, dict(supersteps=it, msg_width=1 + c)


def triangle_count(
    g: PartitionedGraph, sketch_bits: int = 256, mesh: Mesh | None = None,
    trace=None,
) -> Tuple[int, dict]:
    """Heavy workload: approximate triangle counting via neighbourhood sketches.

    Each vertex carries a `sketch_bits`-wide simhash-style neighbourhood
    bitmap; one superstep broadcasts sketches to neighbours, a second
    accumulates |N(u) ∩ N(v)| estimates per edge. Exact for graphs with
    ≤ sketch_bits distinct neighbour hashes per vertex — tests use exact mode
    (sketch_bits ≥ V). Models the paper's SI/clique workloads: wide messages
    (msg_width = sketch_bits/32 words ≫ PageRank's 1) and heavy per-edge work.
    """
    mesh = mesh or engine_mesh(k=g.k)
    v, b = g.num_vertices, sketch_bits
    slot = np.arange(v) % b  # vertex -> sketch bit (exact when b >= V)

    def msg(x_u, x_v, deg_u, deg_v):
        return x_u, x_v

    def apply(state, synced, degrees):
        return jnp.minimum(synced, 1.0)  # OR of neighbour one-bit ids

    # Round 1: build neighbourhood bitmaps.
    step = make_superstep(g, msg, apply, mesh, trace=trace)
    ident = jax.nn.one_hot(jnp.asarray(slot), b, dtype=jnp.float32)
    bitmaps = step(ident)  # (V, b) — 1 iff some neighbour hashes to bit j

    # Round 2: per-edge intersection of endpoint bitmaps (local, heavy).
    edges, evalid = np.asarray(g.edges), np.asarray(g.evalid)
    bm = np.asarray(bitmaps) > 0
    ident_np = np.asarray(ident) > 0
    u, w = edges[..., 0], edges[..., 1]
    # |bits(N(u)) ∩ bits(N(w))| counts common neighbours exactly for b ≥ V
    # (u ∉ N(u): self-loops are removed at graph build, so the endpoints'
    # own bits never appear in the intersection).
    inter = (bm[u] & bm[w]).sum(axis=-1)
    del ident_np  # endpoints' own bits are excluded by construction
    per_edge = inter * evalid
    total = int(per_edge.sum()) // 3  # each triangle counted by 3 edges
    return total, dict(supersteps=2, msg_width=b // 32)

"""Gather-Apply-Scatter supersteps over `shard_map`.

The engine executes vertex programs on a vertex-cut partitioned graph. Each
device owns a slab of partitions (axis `parts`); one superstep is:

  gather : per-partition edge aggregation into local vertex accumulators
           (the `segment_sum` kernel's job on TPU; `.at[].add` under XLA)
  sync   : replica synchronisation — combine accumulators across the
           partitions a vertex is replicated on (lax.psum over `parts`)
  apply  : vertex update function on the synchronised accumulator

The dense psum is the XLA-friendly stand-in for the sparse point-to-point
replica sync a cluster engine (GrapH) performs; the *modeled* traffic —
what the paper's processing latency is driven by — is derived from the
replica table in `latency_model.py`. On a real TPU pod the psum itself also
shrinks with replication degree when the accumulator is masked to local
replicas, which we do (zeros compress under sparse collectives; on GPU/IB
clusters the mask is what a ragged all-to-all would send).

`shard_map` and the mesh come from `repro.compat` (Auto mesh axes), so the
engine runs unchanged on one device or many.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.engine.partitioned import PartitionedGraph
from repro.obs import resolve_tracer

__all__ = ["make_superstep", "superstep_program", "engine_mesh", "gather_local"]


def engine_mesh(n_devices: int | None = None, k: int | None = None) -> Mesh:
    """1-D engine mesh over the local devices.

    Args:
      n_devices: cap on the device count (default: all local devices).
      k: number of graph partitions about to be sharded over the mesh. Any
        device count works — `make_superstep` pads the partition axis up to
        a multiple of the mesh size with empty slabs (no edges, no replicas)
        that are masked out of the gather/sync — so the mesh keeps ALL
        devices instead of trimming to a divisor of k. Only when k is
        *smaller* than the device count is the mesh capped at k devices
        (extra devices would carry nothing but padding).
    """
    devs = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    if k is not None:
        devs = devs[: max(min(len(devs), int(k)), 1)]
    return compat.make_mesh((len(devs),), ("parts",), devices=np.array(devs))


BIG = jnp.float32(3.0e38)


def gather_local(
    edges: jax.Array,  # (kp, E, 2) — this shard's partitions
    evalid: jax.Array,  # (kp, E)
    vertex_data: jax.Array,  # (V, d) — replicated current state
    degrees: jax.Array,  # (V,)
    msg_fn: Callable,  # (x_u, x_v, deg_u, deg_v) -> (msg_to_v, msg_to_u)
    num_vertices: int,
    agg: str = "add",
) -> jax.Array:
    """Per-shard edge aggregation: (kp, V, d) local accumulators."""

    def one_partition(e, valid):
        u, v = e[:, 0], e[:, 1]
        mu, mv = msg_fn(vertex_data[u], vertex_data[v], degrees[u], degrees[v])
        if agg == "add":
            w = valid[:, None].astype(mu.dtype)
            acc = jnp.zeros((num_vertices, mu.shape[-1]), mu.dtype)
            acc = acc.at[v].add(mu * w)  # message flowing u -> v
            acc = acc.at[u].add(mv * w)  # message flowing v -> u (undirected)
        elif agg == "min":
            mu = jnp.where(valid[:, None], mu, BIG)
            mv = jnp.where(valid[:, None], mv, BIG)
            acc = jnp.full((num_vertices, mu.shape[-1]), BIG, mu.dtype)
            acc = acc.at[v].min(mu)
            acc = acc.at[u].min(mv)
        else:
            raise ValueError(agg)
        return acc

    return jax.vmap(one_partition)(edges, evalid)


def superstep_program(
    mesh: Mesh,
    msg_fn: Callable,
    apply_fn: Callable,  # (state, synced_acc, degrees) -> state
    num_vertices: int,
    combine: str = "add",
):
    """The jitted superstep over ``mesh``:
    ``(state, edges, evalid, replicas_t, degrees) -> state``.

    ``edges`` (kp, E, 2), ``evalid`` (kp, E) and ``replicas_t`` (kp, V) are
    sharded over ``parts``; ``state`` (V, d) and ``degrees`` (V,) are
    replicated. Gather runs per device, then the replica-masked accumulators
    are combined across ``parts`` (psum for ``add``, pmin for ``min``). The
    phases carry the scopes ``engine.gather``, ``engine.combine`` and
    ``engine.apply`` in the program's op metadata (the profiler's names).
    """
    if combine not in ("add", "min"):
        raise ValueError(combine)

    def step(state, edges, evalid, replicas_t, degrees):
        with jax.named_scope("engine.gather"):
            acc = gather_local(
                edges, evalid, state, degrees, msg_fn, num_vertices, agg=combine
            )
        with jax.named_scope("engine.combine"):
            if combine == "add":
                local = (acc * replicas_t[:, :, None]).sum(axis=0)  # mask to replicas
                synced = jax.lax.psum(local, "parts")
            else:
                local = jnp.where(replicas_t[:, :, None] > 0, acc, BIG).min(axis=0)
                synced = jax.lax.pmin(local, "parts")
        with jax.named_scope("engine.apply"):
            return apply_fn(state, synced, degrees)

    return jax.jit(compat.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), P("parts"), P("parts"), P("parts"), P()),
        out_specs=P(),
        check_replication=False,
    ))


def make_superstep(
    g: PartitionedGraph,
    msg_fn: Callable,
    apply_fn: Callable,  # (state, synced_acc, degrees) -> state
    mesh: Mesh,
    combine: str = "add",
    trace=None,
):
    """Build a jitted superstep: state (V, d) -> state (V, d).

    The partition axis of `g.edges` is sharded over the mesh's `parts` axis;
    vertex state is replicated (small next to edges, the usual vertex-cut
    regime). Accumulators are masked to each partition's replica set before
    the cross-partition combine — the masked entries are the engine's real
    traffic.

    When the mesh size does not divide k, the partition axis is padded up to
    the next multiple with empty slabs: no valid edges (`evalid` False ⇒
    zero / identity contributions in `gather_local`) and no replicas (the
    replica mask zeroes the slab out of the cross-partition combine). This
    is what lets `engine_mesh` keep every device for any k.

    Slab balance: pad slabs are interleaved so per-device REAL slab counts
    differ by at most one (appending them at the end would pile every pad
    onto the last devices — they idle while earlier devices carry full
    slabs, and the psum stalls on the stragglers). The cross-partition
    combine is permutation-invariant, so reordering slabs never changes
    results. The returned callable exposes the placement as
    ``.slab_occupancy`` (real slabs per device); its ``superstep`` span
    carries it for Perfetto visibility.
    """
    v, k = g.num_vertices, g.k
    n_shards = int(mesh.devices.size)
    k_pad = -(-k // n_shards) * n_shards
    edges_d, evalid_d = g.edges, g.evalid
    repl_t = jnp.asarray(np.asarray(g.replicas).T)  # (k, V)
    kp_per = k_pad // n_shards
    base, rem = divmod(k, n_shards)
    occupancy = np.full(n_shards, base, np.int64)
    occupancy[:rem] += 1
    if k_pad != k:
        pad = k_pad - k
        edges_d = jnp.concatenate(
            [edges_d, jnp.zeros((pad,) + edges_d.shape[1:], edges_d.dtype)]
        )
        evalid_d = jnp.concatenate(
            [evalid_d, jnp.zeros((pad,) + evalid_d.shape[1:], bool)]
        )
        repl_t = jnp.concatenate(
            [repl_t, jnp.zeros((pad, repl_t.shape[1]), repl_t.dtype)]
        )
        # Device d's contiguous shard_map slab holds occupancy[d] real
        # partitions followed by its share of the pads.
        perm = np.empty(k_pad, np.int64)
        next_real, next_pad, pos = 0, k, 0
        for d in range(n_shards):
            c = int(occupancy[d])
            perm[pos : pos + c] = np.arange(next_real, next_real + c)
            perm[pos + c : pos + kp_per] = np.arange(
                next_pad, next_pad + kp_per - c
            )
            next_real += c
            next_pad += kp_per - c
            pos += kp_per
        edges_d = edges_d[perm]
        evalid_d = evalid_d[perm]
        repl_t = repl_t[perm]

    program = superstep_program(mesh, msg_fn, apply_fn, v, combine)
    # The graph lives where the program reads it: each device holds its own
    # slab, and the arrays are arguments of the jitted step, not constants
    # embedded in it or resharded from one device on every call.
    parts = NamedSharding(mesh, P("parts"))
    edges_d, evalid_d, repl_t = (
        jax.device_put(x, parts) for x in (edges_d, evalid_d, repl_t)
    )
    degrees = jax.device_put(g.degrees, NamedSharding(mesh, P()))

    slab_occupancy = tuple(int(c) for c in occupancy)
    tr = resolve_tracer(trace)

    # The span wraps the jitted call from the host side: it covers dispatch
    # only (no block_until_ready, no added sync) and lives outside the
    # traced program, so the compiled superstep is unchanged.
    def superstep(state):
        with tr.span("superstep", cat="engine", k=k, combine=combine,
                     n_shards=n_shards, slab_occupancy=list(slab_occupancy)):
            return program(state, edges_d, evalid_d, repl_t, degrees)

    superstep.slab_occupancy = slab_occupancy
    return superstep

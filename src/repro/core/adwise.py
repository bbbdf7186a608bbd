"""ADWISE as a vectorized JAX streaming computation.

The paper's Algorithm 1 is a sequential loop: refill window → argmax over
(window × partitions) → assign → adapt. On accelerator hardware we express
one loop iteration as a fixed-shape masked update (see DESIGN.md §3) and run
the whole stream through `jax.lax.scan`:

  carry: vertex cache (replica table + versions), degree table, partition
         sizes, the window buffer (W_max slots + validity), lazy-traversal
         caches, λ, and the adaptive-window controller state.
  step : refill invalid slots from the stream, recompute the stale subset of
         window scores (lazy traversal budget R_sel), take the masked argmax
         over (W_max × k), emit the assignment, update the vertex cache and
         the controller.

This module owns the *per-step math* (the Carry / step function) and the
thin public entry points. The chunked stepping loop around the scan — carry
initialization, warm-state resume, r_sel/cap resolution, budget wiring and
recalibration, resident vs ring-buffer chunk sources — lives once in
:mod:`repro.core.driver`; `partition_stream`, `partition_stream_batched`,
the out-of-core path (`repro.core.oocore`) and every re-streaming pass are
all callers of the same :class:`~repro.core.driver.ScanDriver`.

Stream addressing: the step reads refill rows at ``src % m_pad``. For a
resident source ``m_pad`` is the (per-instance) stream length, so the mod is
the identity on every live index; for the out-of-core ring buffer it IS the
ring invariant (logical row ``s`` lives in slot ``s % B``). Padding reads
beyond the live range are masked by the ``fill`` mask, so both modes run the
very same trace with bit-identical outputs.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitrows, scoring
from repro.core.types import AdwiseConfig, PartitionResult, WarmState

__all__ = ["partition_stream", "partition_stream_batched", "WarmState"]

NEG_INF = scoring.NEG_INF
_BIG_I32 = np.int32(2**31 - 1)


class Carry(NamedTuple):
    # Vertex cache.
    # (V+1, ⌈K/32⌉) uint32, (V+1,) for K <= 32: partition p is bit p % 32
    # of word p // 32 (core.bitrows). Row V is a scatter dump, all zero.
    replicas: jax.Array
    rep_version: jax.Array  # (V+1,) int32
    deg: jax.Array  # (V+1,) int32
    max_deg: jax.Array  # () int32
    # Partition state.
    sizes: jax.Array  # (K,) int32
    lam: jax.Array  # () f32
    # Window.
    w_cap: jax.Array  # () int32 — logical window size w
    cursor: jax.Array  # () int32 — next stream index
    n_valid: jax.Array  # () int32
    win_uv: jax.Array  # (W, 2) int32
    win_sidx: jax.Array  # (W,) int32 — stream index per slot
    win_valid: jax.Array  # (W,) bool
    # Lazy traversal caches.
    cached_rcs: jax.Array  # (W, K) f32 — cached R + CS per slot
    cached_ver_u: jax.Array  # (W,) int32
    cached_ver_v: jax.Array  # (W,) int32
    theta: jax.Array  # () f32 — candidate threshold Θ from previous step
    # Counters / controller.
    assigned: jax.Array  # () int32
    score_rows: jax.Array  # () int32 — number of (edge × all-partitions) evals
    c: jax.Array  # () int32 — assignments since last window adaptation
    sum_g: jax.Array  # () f32
    avg_g_prev: jax.Array  # () f32
    last_grew: jax.Array  # () bool
    budget_left: jax.Array  # () f32 seconds
    lat_ema: jax.Array  # () f32 — per-edge modeled latency EMA
    # Calibrated latency model (dynamic so recalibration does not recompile).
    cost_per_score: jax.Array  # () f32
    base_cost: jax.Array  # () f32

    @classmethod
    def warm_start(
        cls,
        cfg: "AdwiseConfig",
        num_vertices: int,
        budget: float,
        *,
        replicas: np.ndarray,  # (V, K) bool — replica table of the prior pass
        deg: np.ndarray,  # (V,) int — streamed degrees of the prior pass
        sizes: np.ndarray,  # (K,) int — partition loads of the prior pass
    ) -> "Carry":
        """Carry warm-started from a previous pass's tables (re-streaming).

        λ restarts at ``cfg.lam_init`` and re-anneals over the new pass
        (``assigned`` resets, so the Eq. 4 tolerance schedule replays); the
        window controller likewise starts fresh. Only the *graph knowledge*
        — replica table, degree table, partition loads — carries over.

        The vertex tables are built on the host, the replica table packed
        to words there: they stay host arrays until the driver stacks its
        instances and uploads them, so a (V, K) bool never reaches the
        device.
        """
        base = _init_carry(cfg, num_vertices, budget)
        v1 = num_vertices + 1
        packed = bitrows.pack_np(replicas)
        rep = np.zeros((v1,) + packed.shape[1:], packed.dtype)
        rep[:num_vertices] = packed
        deg_np = np.zeros((v1,), np.int32)
        deg_np[:num_vertices] = deg
        return base._replace(
            replicas=rep,
            deg=deg_np,
            max_deg=np.int32(max(int(deg_np.max()), 1)),
            sizes=np.asarray(sizes, np.int32),
        )


class StepOut(NamedTuple):
    sidx: jax.Array  # (b,) int32 — stream index assigned this step (-1 = none)
    p: jax.Array  # (b,) int32
    w_cap: jax.Array  # () int32
    g_chosen: jax.Array  # () f32 — best score this step (diagnostics)


def _init_carry(cfg: AdwiseConfig, num_vertices: int, budget: float) -> Carry:
    v1 = num_vertices + 1
    w, k = cfg.window_max, cfg.k
    zi = jnp.zeros((), jnp.int32)
    zf = jnp.zeros((), jnp.float32)
    return Carry(
        replicas=bitrows.zeros(v1, k),
        rep_version=jnp.zeros((v1,), jnp.int32),
        deg=jnp.zeros((v1,), jnp.int32),
        max_deg=jnp.ones((), jnp.int32),
        sizes=jnp.zeros((k,), jnp.int32),
        lam=jnp.float32(cfg.lam_init),
        w_cap=jnp.int32(max(cfg.window_init, cfg.assign_batch)),
        cursor=zi,
        n_valid=zi,
        win_uv=jnp.zeros((w, 2), jnp.int32),
        win_sidx=jnp.full((w,), -1, jnp.int32),
        win_valid=jnp.zeros((w,), bool),
        cached_rcs=jnp.zeros((w, k), jnp.float32),
        cached_ver_u=jnp.full((w,), -1, jnp.int32),
        cached_ver_v=jnp.full((w,), -1, jnp.int32),
        theta=zf,
        assigned=zi,
        score_rows=zi,
        c=zi,
        sum_g=zf,
        avg_g_prev=jnp.float32(-jnp.inf),
        last_grew=jnp.asarray(True),
        budget_left=jnp.float32(budget),
        lat_ema=zf,
        cost_per_score=jnp.float32(1e-8),
        base_cost=jnp.float32(1e-7),
    )


def _make_step(
    cfg: AdwiseConfig,
    num_vertices: int,
    r_sel: int,
    stream: jax.Array,  # (m_pad, 2) int32 — full stream OR the ring buffer
    m_real: jax.Array,  # () int32
    allowed: jax.Array,  # (K,) bool
    cap: jax.Array,  # () int32 (BIG when disabled)
    has_budget: bool,
    prev_assign: jax.Array,  # (m_pad,) int32 — prior-pass partition, -1 = none
    update_deg: bool,  # False on warm-started passes (degrees already final)
):
    w_max, k, b = cfg.window_max, cfg.k, cfg.assign_batch
    v_dummy = num_vertices  # scatter dump row
    m_pad = stream.shape[0]
    slot_ids = jnp.arange(w_max, dtype=jnp.int32)

    # The phases carry named scopes (adwise.window: 1; adwise.score: 2-4;
    # adwise.pick: 5; adwise.apply: 6-7), which reach the compiled program
    # as op metadata only: the profiler attributes device time by them.
    def step(carry: Carry, _) -> tuple[Carry, StepOut]:
        with jax.named_scope("adwise.window"):
            # ---- 1) Refill invalid slots up to the logical window size w. ----
            need = jnp.clip(carry.w_cap - carry.n_valid, 0, w_max)
            avail = jnp.maximum(m_real - carry.cursor, 0)
            take = jnp.minimum(need, avail)
            inv = ~carry.win_valid
            rank = jnp.cumsum(inv.astype(jnp.int32)) - 1
            fill = inv & (rank < take)
            src = carry.cursor + rank
            # Ring addressing: logical row s lives at slot s % m_pad. For a
            # resident stream m_pad == m, so this is the identity on every live
            # index; reads past the live range are masked by `fill`.
            src_c = src % m_pad
            fill_uv = stream[src_c]
            win_uv = jnp.where(fill[:, None], fill_uv, carry.win_uv)
            win_sidx = jnp.where(fill, src, carry.win_sidx)
            win_valid = carry.win_valid | fill
            # Streamed degrees update on observation (first pass only — warm
            # passes inherit the final degree table and must not re-count).
            if update_deg:
                u_f = jnp.where(fill, fill_uv[:, 0], v_dummy)
                v_f = jnp.where(fill, fill_uv[:, 1], v_dummy)
                deg = carry.deg.at[u_f].add(1).at[v_f].add(1)
                seen = jnp.where(fill, jnp.maximum(deg[u_f], deg[v_f]), 0)
                max_deg = jnp.maximum(carry.max_deg, jnp.max(seen))
            else:
                deg = carry.deg
                max_deg = carry.max_deg
            # Buffered re-streaming revocation: the prior pass's assignment of an
            # edge is released when the edge enters the window, so balance/capacity
            # terms score against net loads while the pass re-places the stream.
            pa = prev_assign[src_c]
            dec = fill & (pa >= 0)
            sizes_net = carry.sizes.at[jnp.where(dec, pa, 0)].add(
                -dec.astype(jnp.int32)
            )
            cursor = carry.cursor + take
            n_valid = carry.n_valid + take

            u = win_uv[:, 0]
            v = win_uv[:, 1]

        with jax.named_scope("adwise.score"):
            # ---- 2) Lazy traversal: pick ≤ r_sel stale slots to rescore. ----
            ver_u = carry.rep_version[u]
            ver_v = carry.rep_version[v]
            if cfg.lazy:
                # A refilled slot's cache belongs to the previous occupant — always stale.
                stale = win_valid & (
                    (ver_u != carry.cached_ver_u) | (ver_v != carry.cached_ver_v) | fill
                )
            else:
                # Faithful mode: every valid window edge is rescored every step
                # (CS depends on *other* window edges, which version stamps on the
                # own endpoints cannot see).
                stale = win_valid
            # Priority classes: fresh window entries first, then stale candidates
            # (cached score above Θ), then stale secondary edges (§III-B).
            cand = carry.cached_rcs.max(axis=1) >= carry.theta
            cls = jnp.where(fill, 0, jnp.where(cand, 1, 2)).astype(jnp.int32)
            key = jnp.where(stale, cls * w_max + slot_ids, _BIG_I32)
            order = jnp.argsort(key)[:r_sel]
            sel_live = jnp.sort(key)[:r_sel] < _BIG_I32
            sel_idx = jnp.where(sel_live, order, w_max)  # dummy slot w_max
            sel_c = jnp.clip(sel_idx, 0, w_max - 1)

            # ---- 3) Fresh R (+ CS) for the selected rows. ----
            rep_u = bitrows.unpack(carry.replicas[u], k)  # (W, K) bool
            rep_v = bitrows.unpack(carry.replicas[v], k)
            r_all = scoring.replication_score(rep_u, rep_v, deg[u], deg[v], max_deg)
            rcs_rows = r_all[sel_c]
            if cfg.use_clustering:
                u_s, v_s = u[sel_c], v[sel_c]
                keep = win_valid[None, :] & (sel_c[:, None] != slot_ids[None, :])
                a = ((u[None, :] == u_s[:, None]) | (u[None, :] == v_s[:, None])) & keep
                bm = ((v[None, :] == u_s[:, None]) | (v[None, :] == v_s[:, None])) & keep
                af = a.astype(jnp.float32)
                bf = bm.astype(jnp.float32)
                num = af @ rep_v.astype(jnp.float32) + bf @ rep_u.astype(jnp.float32)
                den = af.sum(axis=1) + bf.sum(axis=1)
                rcs_rows = rcs_rows + num / jnp.maximum(den, 1.0)[:, None]
            cached_rcs = (
                jnp.zeros((w_max + 1, k), jnp.float32)
                .at[:w_max]
                .set(carry.cached_rcs)
                .at[sel_idx]
                .set(rcs_rows)[:w_max]
            )
            pad1 = lambda x, fillv: jnp.concatenate([x, jnp.full((1,), fillv, x.dtype)])
            cached_ver_u = pad1(carry.cached_ver_u, -1).at[sel_idx].set(ver_u[sel_c])[:w_max]
            cached_ver_v = pad1(carry.cached_ver_v, -1).at[sel_idx].set(ver_v[sel_c])[:w_max]
            n_scored = jnp.sum(sel_live.astype(jnp.int32))
            score_rows = carry.score_rows + n_scored

            # ---- 4) Score matrix g = cached RCS + λ·B, masked. ----
            bal = scoring.balance_score(sizes_net, allowed, cfg.eps)
            ok_p = allowed & (sizes_net < cap)
            g = cached_rcs + carry.lam * bal[None, :]
            g = jnp.where(win_valid[:, None] & ok_p[None, :], g, NEG_INF)
            # Candidate threshold Θ = g_avg + ε (§III-B) in RCS units — it gates
            # the cached R+CS values, so exclude the λ·B term common to a column.
            rcs_max = cached_rcs.max(axis=1)
            nv = jnp.maximum(jnp.sum(win_valid.astype(jnp.float32)), 1.0)
            theta = jnp.sum(jnp.where(win_valid, rcs_max, 0.0)) / nv + cfg.eps

        with jax.named_scope("adwise.pick"):
            # ---- 5) Assign the top-b vertex-disjoint window edges. ----
            def pick(i, st):
                g_m, ch_mask, ch_p, out_s, out_p, sum_gacc = st
                flat = jnp.argmax(g_m)
                slot = (flat // k).astype(jnp.int32)
                p = (flat % k).astype(jnp.int32)
                ok = g_m[slot, p] > NEG_INF / 2
                out_s = out_s.at[i].set(jnp.where(ok, win_sidx[slot], -1))
                out_p = out_p.at[i].set(jnp.where(ok, p, 0))
                share = (u == u[slot]) | (u == v[slot]) | (v == u[slot]) | (v == v[slot])
                g_m = jnp.where((share & ok)[:, None], NEG_INF, g_m)
                ch_mask = ch_mask.at[slot].max(ok)
                ch_p = ch_p.at[slot].set(jnp.where(ok, p, ch_p[slot]))
                sum_gacc = sum_gacc + jnp.where(ok, g[slot, p], 0.0)
                return (g_m, ch_mask, ch_p, out_s, out_p, sum_gacc)

            st0 = (
                g,
                jnp.zeros((w_max,), bool),
                jnp.zeros((w_max,), jnp.int32),
                jnp.full((b,), -1, jnp.int32),
                jnp.zeros((b,), jnp.int32),
                jnp.zeros((), jnp.float32),
            )
            if b == 1:
                st = pick(0, st0)
            else:
                st = jax.lax.fori_loop(0, b, pick, st0)
            _, ch, ch_p, out_s, out_p, g_sum = st
            n_ch = jnp.sum(ch.astype(jnp.int32))

        with jax.named_scope("adwise.apply"):
            # ---- 6) Apply assignments to the vertex cache / partition state. ----
            chi = ch.astype(jnp.int32)
            sizes = sizes_net.at[ch_p].add(chi)  # adds 0 where not chosen
            # The vertex tables take the (at most b) chosen slots only: a
            # scatter into a V-sized table costs per entry, not per table.
            (slots,) = jnp.nonzero(ch, size=b, fill_value=w_max)
            on = slots < w_max
            s_c = jnp.minimum(slots, w_max - 1)
            p_c = ch_p[s_c]
            u_c = jnp.where(on, u[s_c], v_dummy)
            v_c = jnp.where(on, v[s_c], v_dummy)
            # Chosen slots share no vertex, so two entries meet on one word
            # only as a self-loop (one bit) or in the dump row (no bit).
            uv_c = jnp.concatenate([u_c, v_c])
            on2 = jnp.concatenate([on, on])
            old, replicas = bitrows.set_bit(
                carry.replicas, uv_c, jnp.concatenate([p_c, p_c]), on2
            )
            # A self-loop's vertex gains two versions, as u and v each did.
            rep_version = carry.rep_version.at[uv_c].add(
                (on2 & ~old).astype(jnp.int32)
            )
            win_valid = win_valid & ~ch
            n_valid = n_valid - n_ch
            assigned = carry.assigned + n_ch

            lam = scoring.lambda_update(
                carry.lam, sizes, allowed, assigned, m_real, cfg.lam_lo, cfg.lam_hi
            )

            # ---- 7) Modeled latency + adaptive window controller (§III-A). ----
            step_cost = n_scored.astype(jnp.float32) * jnp.float32(k) * carry.cost_per_score + carry.base_cost
            budget_left = carry.budget_left - step_cost
            lat_edge = step_cost / jnp.maximum(n_ch.astype(jnp.float32), 1.0)
            lat_ema = jnp.where(
                carry.assigned == 0, lat_edge, 0.9 * carry.lat_ema + 0.1 * lat_edge
            )
            c = carry.c + n_ch
            sum_g = carry.sum_g + g_sum
            trigger = jnp.asarray(cfg.adapt) & (c >= carry.w_cap)
            avg_g = sum_g / jnp.maximum(c.astype(jnp.float32), 1.0)
            c1 = (~carry.last_grew) | (avg_g >= carry.avg_g_prev)
            if has_budget:
                edges_left = jnp.maximum(m_real - assigned, 1).astype(jnp.float32)
                c2 = lat_ema < budget_left / edges_left
            else:
                c2 = jnp.asarray(True)
            grow = trigger & c1 & c2 & (carry.w_cap < w_max)
            shrink = trigger & ~c2
            w_lo = jnp.int32(max(1, b))
            w_new = jnp.where(
                grow,
                jnp.minimum(2 * carry.w_cap, w_max),
                jnp.where(shrink, jnp.maximum((carry.w_cap + 1) // 2, w_lo), carry.w_cap),
            )
            out = StepOut(sidx=out_s, p=out_p, w_cap=carry.w_cap, g_chosen=g_sum)
            new_carry = Carry(
                replicas=replicas,
                rep_version=rep_version,
                deg=deg,
                max_deg=max_deg,
                sizes=sizes,
                lam=lam,
                w_cap=w_new,
                cursor=cursor,
                n_valid=n_valid,
                win_uv=win_uv,
                win_sidx=win_sidx,
                win_valid=win_valid,
                cached_rcs=cached_rcs,
                cached_ver_u=cached_ver_u,
                cached_ver_v=cached_ver_v,
                theta=theta,
                assigned=assigned,
                score_rows=score_rows,
                c=jnp.where(trigger, 0, c),
                sum_g=jnp.where(trigger, 0.0, sum_g),
                avg_g_prev=jnp.where(trigger, avg_g, carry.avg_g_prev),
                last_grew=jnp.where(trigger, grow, carry.last_grew),
                budget_left=budget_left,
                lat_ema=lat_ema,
                cost_per_score=carry.cost_per_score,
                base_cost=carry.base_cost,
            )
        return new_carry, out

    return step


def _ceil_pow2(x: int) -> int:
    """Smallest power of two >= max(x, 1) — the length-bucket key."""
    return 1 << (max(int(x), 1) - 1).bit_length()


def partition_stream(
    edges: np.ndarray,
    num_vertices: int,
    cfg: AdwiseConfig,
    *,
    allowed: Optional[np.ndarray] = None,
    n_chunks: int = 8,
    cost_per_score: Optional[float] = None,
    warm: Optional[WarmState] = None,
    residency=None,
    trace=None,
) -> PartitionResult:
    """Partition an edge stream with ADWISE (vectorized scan).

    Thin caller of :class:`repro.core.driver.ScanDriver` over a single
    resident instance (z == 1).

    Args:
      edges: (m, 2) int32 edge stream.
      num_vertices: |V|.
      cfg: AdwiseConfig.
      allowed: optional bool (k,) mask of partitions this instance may fill
        (spotlight spread). Default: all partitions.
      n_chunks: stream is processed in this many scan calls; wall-clock
        between chunks recalibrates the (C2) latency model.
      cost_per_score: optional fixed seconds per (edge,partition) score
        evaluation; overrides calibration (deterministic tests).
      warm: optional :class:`WarmState` from a previous pass (re-streaming):
        the replica/degree tables and partition loads carry over, degrees are
        not re-counted, and — when ``warm.prev_assign`` is given — each
        edge's prior placement is revoked as it re-enters the window.
      residency: optional :class:`repro.core.driver.StreamResidency` shared
        across re-streaming passes over the SAME edges — later passes reuse
        the resident device stream array and ship only their prev table.
      trace: optional :class:`repro.obs.Tracer` recording per-scan-call
        spans (host dispatch/wait only); stats gain a ``trace_summary``.

    Returns: PartitionResult with assign (int32[m]) and stats.
    """
    from repro.core.driver import ResidentSource, ScanDriver

    m = int(len(edges))
    k = cfg.k
    if m == 0:
        return PartitionResult(np.zeros((0,), np.int32), dict(k=k, unassigned=0))
    source = ResidentSource(
        np.ascontiguousarray(edges, np.int32).reshape(1, m, 2),
        np.array([m], np.int64),
        residency=residency,
    )
    drv = ScanDriver(
        source, cfg, num_vertices,
        allowed=None if allowed is None else np.asarray(allowed, bool)[None],
        warm=None if warm is None else [warm],
        cost_per_score=cost_per_score,
        backend="vmap",
        trace=trace,
    )
    res = drv.run(n_chunks=n_chunks)
    sidx, pout = res.sidx[0], res.p[0]
    assign = np.full((m,), -1, np.int32)
    live = sidx >= 0
    assign[sidx[live]] = pout[live]
    unassigned = int((assign < 0).sum())
    assert unassigned == 0 and int(res.assigned[0]) == m, (
        f"partition_stream left {unassigned} of {m} edges unassigned "
        f"(scan assigned counter: {int(res.assigned[0])}) — drain loop failed"
    )
    stats = dict(
        drv.stats_base(res, 0),
        w_trace=res.w_trace[0],
        unassigned=unassigned,
    )
    if trace is not None and trace.enabled:
        stats["trace_summary"] = trace.summary().as_dict()
    return PartitionResult(assign, stats)


def partition_stream_batched(
    streams: np.ndarray,
    valid: np.ndarray,
    num_vertices: int,
    cfg: Optional[AdwiseConfig],
    *,
    core=None,
    allowed: Optional[np.ndarray] = None,
    backend: str = "auto",
    n_chunks: int = 8,
    cost_per_score: Optional[float] = None,
    warm: Optional[Sequence[WarmState]] = None,
    residency=None,
    trace=None,
) -> list[PartitionResult]:
    """Run ``z`` independent instance scans as ONE batched program.

    This is the device-parallel spotlight entry point: the same step
    function `vmap`-ped over a leading instance axis — and, when multiple
    devices are visible, `shard_map`-ped over an ``("instances",)`` mesh
    axis so each device executes its slice of instances in parallel (the
    paper's z-machine parallel-loading model on real hardware). Thin caller
    of :class:`repro.core.driver.ScanDriver` over a z-instance resident
    source.

    Args:
      streams: (z, per, 2) int32 — per-instance padded edge chunks
        (:meth:`repro.graph.stream.EdgeStream.split_padded` layout).
      valid: (z, per) bool — per-row *prefix* mask; row i's real stream is
        ``streams[i, :valid[i].sum()]``.
      num_vertices: |V| (shared; instances keep independent vertex caches).
      cfg: AdwiseConfig (shared by all instances); may be None when ``core``
        is given.
      core: optional :class:`repro.core.driver.StepCore` — ANY step-core
        strategy (HdrfCore, GreedyCore, TpslCore, ...) vmaps over the z
        instance axis through the exact same driver path as ADWISE;
        per-instance state (e.g. HDRF's counter-based tie seeds ``seed+i``)
        comes from the core's ``seed_instances`` hook.
      allowed: optional (z, k) bool — per-instance spotlight spread masks.
        Default: every instance may fill every partition.
      backend: 'vmap' (single device), 'shard_map' (instances sharded over
        devices; z must have a divisor <= device_count > 1, else falls back
        to vmap), or 'auto' (shard_map iff multiple devices are visible).
      n_chunks / cost_per_score: as in :func:`partition_stream`.
      warm: optional length-z sequence of per-instance :class:`WarmState`
        (re-streaming composed with spotlight). All instances must agree on
        whether ``prev_assign`` is provided.
      residency: optional :class:`repro.core.driver.StreamResidency` shared
        across re-streaming passes over the SAME streams — later passes
        reuse the resident device array and ship only their prev table.

    Returns:
      A list of z :class:`PartitionResult`; entry i's ``assign`` covers
      instance i's real (un-padded) stream in local order. With z == 1 and
      identical inputs the assignment is bit-identical to
      :func:`partition_stream` — both run the same step function on the
      lone instance, unbatched.

    Length bucketing: instances are grouped by ``ceil_pow2(m_i)`` and each
    bucket runs as its own batched scan padded to
    ``min(ceil_pow2(max m_i in bucket), per)`` rows — the same
    bounded-kernel-shape discipline as the ring's pow2 ``Rq`` spans. Skewed
    per-instance lengths therefore compile at most
    ``ceil(log2(max_m / min_m)) + 1`` scan programs instead of padding
    every instance to the global maximum (and idling the short ones through
    the tail). When every instance lands in one bucket whose pow2 bound
    meets or exceeds ``per``, shapes — and thus programs, uploads, and
    assignments — are identical to the unbucketed layout. Results come back
    in the caller's instance order regardless of bucketing, and
    seed-deriving cores receive the *global* instance ids
    (:meth:`StepCore.seed_instances`), so assignments are bit-identical to
    the unbucketed program.
    """
    from repro.core.driver import ResidentSource, ScanDriver

    streams = np.ascontiguousarray(streams, np.int32)
    valid = np.asarray(valid, bool)
    assert streams.ndim == 3 and streams.shape[2] == 2, streams.shape
    z, per, _ = streams.shape
    assert valid.shape == (z, per), (valid.shape, streams.shape)
    # The refill logic consumes each instance stream sequentially from slot 0,
    # so validity must be a prefix per row.
    assert (valid[:, :-1] >= valid[:, 1:]).all() if per > 1 else True, (
        "valid must be a per-row prefix mask (padding only at the tail)"
    )
    assert core is not None or cfg is not None, "need a cfg or a step-core"
    k = core.k if core is not None else cfg.k
    m_per = valid.sum(axis=1).astype(np.int64)  # (z,)
    m_max = int(m_per.max()) if z else 0
    if allowed is not None:
        allowed = np.asarray(allowed, bool)
        assert allowed.shape == (z, k), (allowed.shape, (z, k))
    if warm is not None:
        warm = list(warm)
        assert len(warm) == z, f"need one WarmState per instance, got {len(warm)}"
    if m_max == 0:
        return [
            PartitionResult(np.zeros((0,), np.int32), dict(k=k, unassigned=0))
            for _ in range(z)
        ]

    # ---- pow2 length buckets --------------------------------------------
    # Bucket by the pow2 class of each instance's REAL length; the padded
    # width never exceeds the caller's layout, so a single-bucket batch is
    # shape-identical (same program, same h2d bytes) to the unbucketed one.
    buckets: dict[int, list[int]] = {}
    for i in range(z):
        buckets.setdefault(_ceil_pow2(int(m_per[i])), []).append(i)

    runs = []  # (global idx, driver, result, padded width) per bucket
    total_wall, total_h2d_rows, total_h2d_bytes = 0.0, 0, 0
    for key in sorted(buckets):
        idx = np.asarray(buckets[key], np.int64)
        width = min(key, per)
        drv = ScanDriver(
            ResidentSource(
                np.ascontiguousarray(streams[idx, :width]),
                m_per[idx],
                residency=residency,
            ),
            core if core is not None else cfg,
            num_vertices,
            allowed=None if allowed is None else allowed[idx],
            warm=None if warm is None else [warm[i] for i in idx],
            cost_per_score=cost_per_score,
            backend=backend,
            trace=trace,
            instance_ids=idx,
        )
        res_b = drv.run(n_chunks=n_chunks)
        total_wall += res_b.wall_time_s
        total_h2d_rows += res_b.h2d_rows
        total_h2d_bytes += res_b.h2d_bytes
        runs.append((idx, drv, res_b, width))
    tsum = (
        trace.summary().as_dict()
        if trace is not None and trace.enabled else None
    )
    results: list[Optional[PartitionResult]] = [None] * z
    for idx, drv, res_b, width in runs:
        for j, i in enumerate(int(g) for g in idx):
            m_i = int(m_per[i])
            assign = np.full((m_i,), -1, np.int32)
            live = res_b.sidx[j] >= 0
            assign[res_b.sidx[j][live]] = res_b.p[j][live]
            unassigned = int((assign < 0).sum())
            assert unassigned == 0 and int(res_b.assigned[j]) == m_i, (
                f"batched instance {i} left {unassigned} of {m_i} edges "
                f"unassigned (scan counter: {int(res_b.assigned[j])}) — "
                "drain failed"
            )
            stats = dict(
                drv.stats_base(res_b, j),
                batched=True,
                backend=res_b.backend,
                n_shards=res_b.n_shards,
                z=z,
                instance=i,
                # Buckets run back-to-back, so the batch's parallel-model
                # wall — and its upload bill — is the sum over buckets,
                # shared by every instance (one bucket degenerates to the
                # old single-program accounting).
                wall_time_s=total_wall,
                h2d_rows=total_h2d_rows,
                h2d_bytes=total_h2d_bytes,
                n_buckets=len(runs),
                bucket_rows=width,
                w_trace=res_b.w_trace[j],
                unassigned=unassigned,
            )
            if tsum is not None:
                stats["trace_summary"] = tsum
            results[i] = PartitionResult(assign, stats)
    assert all(r is not None for r in results)
    return results

"""Plain references that decide ``correct``.

Nothing here imports the program under test. Each function is a direct,
unoptimised statement of the semantics the program claims:

- :func:`adwise_reference`: ADWISE as the streaming scan runs it, one
  window step per assignment (Algorithm 1 of the ADWISE paper with the
  scan's lazy-traversal budget, window-local multiset clustering score,
  capacity cap and adaptive window and balance weight), in numpy, with the
  score arithmetic carried out in a chosen float type.
- :func:`quality`: replication degree (Eq. 1), imbalance and partition sizes
  of an assignment.
- :func:`pagerank_iterates`: the engine's PageRank update as a float64
  power iteration from a given state.
"""
from __future__ import annotations

import math

import ml_dtypes
import numpy as np

NEG_INF = -1e30
DTYPES = {"float64": np.float64, "float32": np.float32,
          "bfloat16": ml_dtypes.bfloat16}


def capacity(m: int, k: int, cap_slack: float) -> int:
    """Most edges one partition may hold: the Eq. 2 guarantee."""
    return int(math.ceil(cap_slack * m / k)) + 1


def adwise_reference(edges: np.ndarray, num_vertices: int, k: int, *,
                     window_max: int = 256, window_init: int = 1,
                     lam_init: float = 1.0, lam_lo: float = 0.4,
                     lam_hi: float = 5.0, eps: float = 0.01,
                     cap_slack: float = 1.15, lazy_budget: int | None = None,
                     dtype: str = "float64",
                     replay: tuple | None = None) -> dict:
    """ADWISE placements of ``edges`` (m, 2), one edge per step.

    Each step tops the window up to its logical size ``w`` from the stream,
    rescores at most ``r_sel`` stale window slots (fresh slots first, then
    those whose cached best score is above the threshold Θ, then the rest,
    by slot), scores every (slot, partition) as cached R + CS plus λ·B,
    assigns the first maximum among partitions below the cap, and updates
    λ (Eq. 4) and, every ``w`` assignments, the window size (C1; no latency
    budget). Scores are computed in ``dtype``; counts stay integers.

    With ``replay = (order, assign)`` (the edge each step placed, and the
    partition of every edge, from a partitioner that ran this algorithm)
    the scan replays those decisions instead of taking its own, and records
    by how much each replayed (edge, partition) scores below the step's best
    over every live (edge, partition). A sound partitioner chose a best
    pair at every step up to its own rounding, so the widest such gap
    (``max_gap``) is of the order of that rounding (float32 λ, which sums a
    term every step, drifts by about 1e-5); a decision taken on other
    scores shows as a gap of their size, and an edge placed before it
    entered the window as an infinite one. Replaying the order, not just
    the assignment, matters: the lazy caches make the state depend on the
    order, so two near-tied edges taken the other way round would change
    every later score.

    Returns ``assign`` (the placements made), ``order`` (the edge placed at
    each step) and ``max_gap`` (0 without ``replay``).
    """
    dt = DTYPES[dtype]
    m = len(edges)
    W = window_max
    r_sel = min(W, lazy_budget or max(8, W // 8))
    cap = capacity(m, k, cap_slack)
    rep = np.zeros((num_vertices + 1, k), bool)
    ver = np.zeros(num_vertices + 1, np.int64)
    deg = np.zeros(num_vertices + 1, np.int64)
    max_deg = 1
    sizes = np.zeros(k, np.int64)
    lam = dt(lam_init)
    w_cap, cursor, n_valid = window_init, 0, 0
    win_u = np.zeros(W, np.int64)
    win_v = np.zeros(W, np.int64)
    win_sidx = np.full(W, -1, np.int64)
    valid = np.zeros(W, bool)
    cached = np.zeros((W, k), dt)
    cver_u = np.full(W, -1, np.int64)
    cver_v = np.full(W, -1, np.int64)
    theta = dt(0.0)
    assigned, c = 0, 0
    sum_g, avg_prev, last_grew = dt(0.0), dt(-np.inf), True
    slots = np.arange(W)
    big = np.iinfo(np.int64).max
    assign = np.full(m, -1, np.int32)
    order = np.full(m, -1, np.int64)
    max_gap = 0.0
    one, two, eps_d = dt(1.0), dt(2.0), dt(eps)

    while assigned < m:
        # 1) Fill free slots, in slot order, up to the window size.
        take = min(max(w_cap - n_valid, 0), m - cursor)
        fill = np.zeros(W, bool)
        if take:
            free = np.flatnonzero(~valid)[:take]
            fill[free] = True
            rows = edges[cursor:cursor + take]
            win_u[free], win_v[free] = rows[:, 0], rows[:, 1]
            win_sidx[free] = np.arange(cursor, cursor + take)
            valid[free] = True
            np.add.at(deg, rows[:, 0], 1)
            np.add.at(deg, rows[:, 1], 1)
            max_deg = max(max_deg, int(deg[rows].max()))
            cursor += take
            n_valid += take
        u, v = win_u, win_v
        # 2) Lazy traversal: which stale slots to rescore this step.
        ver_u, ver_v = ver[u], ver[v]
        stale = valid & ((ver_u != cver_u) | (ver_v != cver_v) | fill)
        cand = cached.max(axis=1) >= theta
        cls = np.where(fill, 0, np.where(cand, 1, 2))
        key = np.where(stale, cls * W + slots, big)
        sel = np.sort(key)[:r_sel]
        sel = sel[sel < big] % W
        # 3) Fresh R + CS for the selected slots.
        if len(sel):
            rep_u, rep_v = rep[u], rep[v]
            denom = two * dt(max_deg)
            us, vs = u[sel], v[sel]
            psi_u = (deg[us].astype(dt) / denom).astype(dt)
            psi_v = (deg[vs].astype(dt) / denom).astype(dt)
            r = (rep_u[sel] * (two - psi_u)[:, None]).astype(dt) + \
                (rep_v[sel] * (two - psi_v)[:, None]).astype(dt)
            keep = valid[None, :] & (sel[:, None] != slots[None, :])
            a = ((u[None, :] == us[:, None]) | (u[None, :] == vs[:, None])) & keep
            b = ((v[None, :] == us[:, None]) | (v[None, :] == vs[:, None])) & keep
            num = a.astype(np.int64) @ rep_v + b.astype(np.int64) @ rep_u
            den = np.maximum(a.sum(axis=1) + b.sum(axis=1), 1)
            cs = (num.astype(dt) / den.astype(dt)[:, None]).astype(dt)
            cached[sel] = (r + cs).astype(dt)
            cver_u[sel], cver_v[sel] = ver_u[sel], ver_v[sel]
        # 4) Scores g = cached R+CS + λ·B over live slots and open partitions.
        mx, mn = sizes.max(), sizes.min()
        bal = ((mx - sizes).astype(dt) / (dt(mx - mn) + eps_d)).astype(dt)
        g = (cached + (lam * bal).astype(dt)[None, :]).astype(dt)
        ok = valid[:, None] & (sizes < cap)[None, :]
        g = np.where(ok, g, dt(NEG_INF))
        rcs_max = cached.max(axis=1)
        nv = max(int(valid.sum()), 1)
        theta = dt(dt(np.where(valid, rcs_max, dt(0.0)).astype(dt).sum(
            dtype=np.float64 if dt is np.float64 else np.float32)) / dt(nv)
            + eps_d)
        # 5) Assign the first best (slot, partition), or the replayed one.
        flat = int(np.argmax(g))
        s, p = divmod(flat, k)
        if not g[s, p] > NEG_INF / 2:
            raise RuntimeError("no open partition for a live window edge")
        if replay is not None:
            best = float(g[s, p])
            live = np.flatnonzero(valid & (win_sidx == replay[0][assigned]))
            if len(live) == 0:  # placed before it entered the window
                return dict(assign=assign, order=order, max_gap=float("inf"))
            s, p = int(live[0]), int(replay[1][replay[0][assigned]])
            max_gap = max(max_gap, best - float(g[s, p]))
        gu, gv = int(u[s]), int(v[s])
        assign[win_sidx[s]] = p
        order[assigned] = win_sidx[s]
        sizes[p] += 1
        new_u, new_v = not rep[gu, p], not rep[gv, p]
        rep[gu, p] = rep[gv, p] = True
        ver[gu] += new_u
        ver[gv] += new_v
        valid[s] = False
        n_valid -= 1
        assigned += 1
        # λ (Eq. 4).
        mx, mn = dt(sizes.max()), dt(sizes.min())
        iota = dt((mx - mn) / max(mx, one)) if mx > 0 else dt(0.0)
        tol = max(dt(0.0), dt(one - dt(dt(assigned) / dt(m))))
        lam = dt(min(max(dt(lam + dt(iota - tol)), dt(lam_lo)), dt(lam_hi)))
        # Window controller (C1 only: no latency budget).
        c += 1
        sum_g = dt(sum_g + g[s, p])
        if c >= w_cap:
            avg = dt(sum_g / dt(c))
            grow = ((not last_grew) or avg >= avg_prev) and w_cap < W
            w_cap = min(2 * w_cap, W) if grow else w_cap
            c, sum_g, avg_prev, last_grew = 0, dt(0.0), avg, grow
    return dict(assign=assign, order=order, max_gap=max_gap)


def quality(edges: np.ndarray, assign: np.ndarray, num_vertices: int,
            k: int) -> dict:
    """Replication degree, imbalance and sizes of a complete assignment."""
    assign = np.asarray(assign)
    if len(assign) != len(edges) or (assign < 0).any() or (assign >= k).any():
        raise ValueError("assignment is not one partition in [0, k) per edge")
    rep = np.zeros((num_vertices, k), bool)
    rep[edges[:, 0], assign] = True
    rep[edges[:, 1], assign] = True
    counts = rep.sum(axis=1)
    present = counts > 0
    sizes = np.bincount(assign, minlength=k)
    mx = sizes.max()
    return dict(replication_degree=float(counts.sum()) / int(present.sum()),
                imbalance=float((mx - sizes.min()) / mx),
                sizes=sizes)


def pagerank_iterates(edges: np.ndarray, n: int, x0: np.ndarray,
                      damping: float = 0.85):
    """Float64 power iteration of the engine's update from ``x0``: mass
    x/deg pushed both ways along every edge, then x = (1 - d)/V + d * sum.
    Yields the state after each superstep, without end."""
    u = edges[:, 0]
    v = edges[:, 1]
    deg = np.bincount(u, minlength=n) + np.bincount(v, minlength=n)
    inv = 1.0 / np.maximum(deg, 1)
    x = np.asarray(x0, np.float64)
    while True:
        y = x * inv
        acc = np.bincount(v, weights=y[u], minlength=n)
        acc += np.bincount(u, weights=y[v], minlength=n)
        x = (1.0 - damping) / n + damping * acc
        yield x

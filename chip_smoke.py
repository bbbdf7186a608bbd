#!/usr/bin/env python3
"""Smoke test on the chip: edge file -> ADWISE partition -> PageRank.

    python chip_smoke.py                 # one TPU chip, the main path
    python chip_smoke.py --edges 32768   # stream a shorter prefix of the file
    python chip_smoke.py --four-chip     # only the two cross-chip parities

The main path is the one a partitioning user runs: a Graph500 Kronecker
graph (scale 22: 4,194,304 vertices, edge factor 16) is written as a binary
edge file, and ``repro.launch.partition.main`` partitions it with ADWISE
out of core (``partition_file`` on the device ring), builds the engine graph
and runs PageRank supersteps. Only the first ``--edges`` rows of the file
are streamed (default: four ring scan calls' worth, 261,120 edges, a 257x
cut in edges, which keeps the run inside 20 minutes on one v5e at about a
millisecond per scan step; a ring call runs its full step count however
few edges remain, so the default fills every call); the vertex count and K
are not cut, so the (V+1, K) device tables have deployment size. Compile
time and persistent-cache traffic are read from ``jax.monitoring`` events
and reported apart from run time.

Every check must hold: no unassigned edge, replication degree and imbalance
equal to a host recomputation, PageRank equal (rtol 1e-4) to a numpy power
iteration, and on a small graph the HDRF/Greedy scans and the ADWISE file
path bit-identical to their references. Measured lines are labelled as
measured on the chip. The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; it is printed
only when every phase passed. Without a TPU the script exits non-zero
before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The deployment: Graph500 scale 22 (4,194,304 vertices) at k=32, streamed
# from the file in 65,536-edge chunks, then 10 PageRank supersteps.
SCALE, K, CHUNK_EDGES, ITERS, SEED = 22, 32, 1 << 16, 10, 0
DAMPING = 0.85
SCAN_CALLS = 4  # default stream length, in full ring scan calls


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    """A failed check fails the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def measured() -> str:
    """Label for a timed line: the platform the number was taken on."""
    import jax

    return f"measured on {jax.devices()[0].platform}"


class CompileLog:
    """XLA compile time and persistent-cache traffic, counted from
    ``jax.monitoring`` events while the process runs. ``compile_s`` is the
    backend-compile wall, which includes loading an executable from the
    persistent cache on a hit; ``lookups`` are compiles that consulted the
    cache, ``hits`` were served from it, ``written`` were stored to it."""

    DURATION = "/jax/core/compile/backend_compile_duration"
    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "lookups",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "written",
    }

    def __init__(self) -> None:
        import jax

        self.counts = dict(compile_s=0.0, programs=0, lookups=0, hits=0,
                           written=0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == self.DURATION:
            self.counts["compile_s"] += secs
            self.counts["programs"] += 1

    def _event(self, event: str, **_) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def since(self, mark: dict) -> str:
        d = {key: self.counts[key] - mark[key] for key in mark}
        return (f"XLA compile or cache load {d['compile_s']:.3f} s over "
                f"{d['programs']} programs (persistent cache: {d['lookups']} "
                f"lookups, {d['hits']} hits, {d['written']} written)")


def scan_steps(k: int, chunk_edges: int) -> int:
    """Steps in every ADWISE ring scan call at this geometry."""
    from repro.core import AdwiseConfig
    from repro.core.driver import FileSource

    return FileSource([types.SimpleNamespace(num_edges=0)],
                      chunk_edges=chunk_edges,
                      cfg=AdwiseConfig(k=k)).scan_steps


# ----------------------------------------------------------------------------
# Host references
# ----------------------------------------------------------------------------


def pagerank_reference(edges, n: int, iters: int, damping: float = DAMPING):
    """Plain numpy power iteration of the engine's update: mass x/deg pushed
    both ways along every edge, then x = (1-d)/V + d*sum."""
    import numpy as np

    u = edges[:, 0].astype(np.int64)
    v = edges[:, 1].astype(np.int64)
    deg = np.bincount(u, minlength=n) + np.bincount(v, minlength=n)
    inv = 1.0 / np.maximum(deg, 1)
    x = np.full(n, 1.0 / n)
    for _ in range(iters):
        acc = np.bincount(v, weights=x[u] * inv[u], minlength=n)
        acc += np.bincount(u, weights=x[v] * inv[v], minlength=n)
        x = (1.0 - damping) / n + damping * acc
    return x


# ----------------------------------------------------------------------------
# Phases (each raises on a failed check)
# ----------------------------------------------------------------------------


def make_graph_files(run_dir: Path, scale: int, edges: int, chunk_edges: int,
                     seed: int):
    """Graph500 Kronecker prefix -> ``graph.adw``, plus ``warm.adw`` (a
    shorter prefix with the same V) for compiling outside the timed run."""
    from repro.graph import kronecker
    from repro.graph.io import write_edge_file

    t0 = time.perf_counter()
    e, n = kronecker(scale, seed=seed, max_edges=edges)
    path, warm = run_dir / "graph.adw", run_dir / "warm.adw"
    write_edge_file(str(path), e, n)
    # Half a chunk: one scan call, which compiles the same ring programs.
    write_edge_file(str(warm), e[: min(len(e), chunk_edges // 2)], n)
    full = 16 * n
    log(f"graph: Graph500 Kronecker scale={scale} edgefactor=16 "
        f"A/B/C=0.57/0.19/0.19 seed={seed}: |V|={n} full |E|={full}")
    log(f"cut: streaming the first {len(e)} edges of the source-sorted file "
        f"({full / len(e):.1f}x fewer edges; |V| and K not cut)")
    log(f"host set-up: graph generated and written in "
        f"{time.perf_counter() - t0:.3f} s -> {path}")
    return e, n, path, warm


def partition_args(graph: Path, run_dir: Path, k: int, chunk_edges: int,
                   workload: str, iters: int, tag: str) -> list:
    return [
        "--graph", str(graph), "--strategy", "adwise", "--k", str(k),
        "--chunk-edges", str(chunk_edges), "--workload", workload,
        "--iters", str(iters), "--spill-dir", str(run_dir / f"spill_{tag}"),
        "--json", str(run_dir / f"{tag}.json"),
    ]


def run_main_path(run_dir: Path, *, scale: int, edges: int, k: int,
                  chunk_edges: int, iters: int, seed: int,
                  clog: CompileLog | None = None) -> dict:
    """Warm-up compile, the main partition -> PageRank run, and its checks."""
    import numpy as np

    from repro.engine import build_partitioned_graph, pagerank_superstep
    from repro.graph import (
        partition_balance,
        replica_sets_from_assignment,
        replication_degree,
    )
    from repro.launch.partition import main as partition_main

    e, n, path, warm = make_graph_files(run_dir, scale, edges, chunk_edges,
                                        seed)
    # Packed replica words + int32 versions (core/bitrows.py).
    table_mb = (n + 1) * (4 * -(-k // 32) + 4) / 1e6
    log(f"state: ADWISE (V+1, K)=({n + 1}, {k}) replica/version tables "
        f"~{table_mb:.0f} MB on the device; engine replica mask (K, V) "
        f"{n * k / 1e6:.0f} MB")

    clog = clog or CompileLog()
    steps = scan_steps(k, chunk_edges)
    mark, t0 = dict(clog.counts), time.perf_counter()
    out = partition_main(partition_args(warm, run_dir, k, chunk_edges, "none",
                                        0, "warm"))
    log(f"setup: compile warm-up on a {min(len(e), chunk_edges // 2)}-edge "
        f"prefix with the same V, K, chunk: {time.perf_counter() - t0:.3f} s "
        f"wall, its {out['stats']['scan_calls']} scan call(s) of {steps} "
        "steps included")
    log(f"setup: {clog.since(mark)}")

    mark, t0 = dict(clog.counts), time.perf_counter()
    out = partition_main(partition_args(path, run_dir, k, chunk_edges,
                                        "pagerank", iters, "main"))
    wall_main = time.perf_counter() - t0
    st = out["stats"]
    m = len(e)
    t_part = float(out["partition_latency_s"])
    log(f"{measured()}: partition wall {t_part:.4f} s, "
        f"{m / t_part:.1f} edges/s (adwise, k={k}); "
        f"main run incl. engine build + PageRank {wall_main:.3f} s")
    log(f"main run: {clog.since(mark)}")
    # Every ring scan call runs S steps whether or not edges remain.
    log(f"{measured()}: scan_calls={st['scan_calls']} x {steps} steps, "
        f"{t_part / (st['scan_calls'] * steps) * 1e6:.1f} us per step; "
        f"h2d_bytes={st['h2d_bytes']} h2d_wait_s={st['h2d_wait_s']:.4f}")

    check(out["unassigned"] == 0, f"unassigned={out['unassigned']}")
    assign = np.fromfile(st["spill_path"], np.int32)
    check(assign.shape == (m,) and (assign >= 0).all() and (assign < k).all(),
          "spill holds one partition id in [0, k) per edge")
    rd = replication_degree(replica_sets_from_assignment(e, assign, n, k))
    imb = partition_balance(assign, k)
    check(rd == out["replication_degree"],
          f"RD {out['replication_degree']} reported, {rd} recomputed")
    check(imb == out["imbalance"],
          f"imbalance {out['imbalance']} reported, {imb} recomputed")
    log(f"check: unassigned=0; RD={rd:.6f} imbalance={imb:.6f} equal to the "
        "host recomputation from the spill and the edge file")

    pr = np.asarray(out["result"], np.float64)
    ref = pagerank_reference(e, n, iters)
    check(pr.shape == (n,) and np.isfinite(pr).all(), "PageRank finite")
    np.testing.assert_allclose(pr, ref, rtol=1e-4, atol=0)
    err = float(np.max(np.abs(pr - ref) / ref))
    log(f"check: PageRank ({iters} supersteps) finite, max rel err vs numpy "
        f"power iteration {err:.3e} (rtol 1e-4)")

    g = build_partitioned_graph(e, assign, n, k)
    step, x = pagerank_superstep(g)
    x = step(x).block_until_ready()  # compile + one step, outside the window
    t0 = time.perf_counter()
    for _ in range(iters):
        x = step(x)
    x.block_until_ready()
    per = (time.perf_counter() - t0) / iters
    log(f"{measured()}: PageRank superstep {per * 1e3:.3f} ms "
        f"(mean of {iters}, to block_until_ready; V={n}, k={k}, "
        f"e_max={g.edges.shape[1]})")
    return dict(rd=rd, imbalance=imb, superstep_s=per, partition_s=t_part)


def run_small_parity(run_dir: Path, *, k: int, seed: int,
                     scale: float = 0.05) -> None:
    """Bit-parity on a small graph: integer-quantized HDRF/Greedy scans vs
    their numpy oracles, and the ADWISE file path vs the in-memory path."""
    import jax
    import numpy as np

    from repro.core import partition_file, run_partitioner
    from repro.graph import make_graph
    from repro.graph.io import EdgeFileReader, write_edge_file

    e, n = make_graph("brain_like", seed=seed, scale=scale)
    for strategy in ("hdrf", "greedy"):
        scan = run_partitioner(strategy, e, n, k, seed=seed).assign
        oracle = run_partitioner(strategy, e, n, k, seed=seed, scan=False).assign
        np.testing.assert_array_equal(scan, oracle, err_msg=strategy)
    log(f"check: brain_like scale={scale} (|V|={n} |E|={len(e)}, k={k}): "
        "hdrf and greedy scans bit-identical to their numpy oracles")

    path = run_dir / "brain.adw"
    write_edge_file(str(path), e, n)
    mem = run_partitioner("adwise", e, n, k, seed=seed).assign
    with EdgeFileReader(str(path)) as r:
        res = partition_file(r, "adwise", k, seed=seed, chunk_edges=4096,
                             spill_dir=str(run_dir / "spill_brain"))
        filed = np.array(res.assign)
    np.testing.assert_array_equal(filed, mem, err_msg="adwise file vs memory")
    log("check: adwise file path bit-identical to the in-memory path")

    if jax.default_backend() == "cpu":
        return
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError:
        log("finding: adwise on this device vs the host CPU: not measured "
            "(no CPU backend in this process)")
        return
    compare_adwise_backends(e, n, k, seed, mem, host)


def adwise_step_trace(e, n: int, k: int, seed: int) -> dict:
    """The in-memory ADWISE scan's decisions, one per step, on the default
    device: the stream index and partition taken, and the best and
    runner-up entries of that step's score matrix g. g is rebuilt from the
    carries before and after the step with the scan's own terms (rescored
    R+CS rows + λ·B over the live window slots); the step itself is the
    scan's, unchanged."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import AdwiseConfig, scoring
    from repro.core.driver import AdwiseCore

    cfg = AdwiseConfig(k=k, seed=seed)
    core = AdwiseCore(cfg, n)
    m = len(e)
    allowed = jnp.ones((k,), bool)
    cap = jnp.int32(core.cap_value(m, k))
    step = core.make_step(jnp.asarray(e, jnp.int32), jnp.int32(m), allowed,
                          cap, jnp.full((m,), -1, jnp.int32))

    def traced(carry, _):
        new, out = step(carry, None)
        s = out.sidx[0]
        # The taken slot left the window in this step; before it, it was live.
        live = new.win_valid | ((new.win_sidx == s) & (s >= 0))
        bal = scoring.balance_score(carry.sizes, allowed, cfg.eps)
        ok_p = allowed & (carry.sizes < cap)
        g = new.cached_rcs + carry.lam * bal[None, :]
        g = jnp.where(live[:, None] & ok_p[None, :], g, scoring.NEG_INF)
        return new, (s, out.p[0], jax.lax.top_k(g.reshape(-1), 2)[0])

    scan = jax.jit(lambda c: jax.lax.scan(traced, c, None, length=m)[1])
    sidx, p, top2 = (np.asarray(x) for x in scan(core.init_carry(0.0)))
    assign = np.full(m, -1, np.int32)
    assign[sidx[sidx >= 0]] = p[sidx >= 0]
    return dict(sidx=sidx, p=p, top=top2[:, 0], second=top2[:, 1],
                assign=assign)


def compare_adwise_backends(e, n: int, k: int, seed: int, dev_assign,
                            host) -> None:
    """Finding, not a check: ADWISE on this device vs the host CPU on the
    same stream -- agreement, quality of each, the first step where the two
    decide differently with its top-two score gap on each backend, and
    agreement again with float32 matmuls at the highest precision."""
    import jax
    import numpy as np

    from repro.core import run_partitioner
    from repro.graph import (
        partition_balance,
        replica_sets_from_assignment,
        replication_degree,
    )

    plat = jax.devices()[0].platform
    m = len(e)
    with jax.default_device(host):
        cpu_assign = run_partitioner("adwise", e, n, k, seed=seed).assign
        cpu = adwise_step_trace(e, n, k, seed)
    dev = adwise_step_trace(e, n, k, seed)
    with jax.default_matmul_precision("highest"):
        hi_assign = run_partitioner("adwise", e, n, k, seed=seed).assign

    def quality(a) -> str:
        rd = replication_degree(replica_sets_from_assignment(e, a, n, k))
        return f"RD={rd:.6f} imbalance={partition_balance(a, k):.6f}"

    log(f"finding: adwise on {plat} vs the host CPU: "
        f"{int((dev_assign == cpu_assign).sum())} of {m} assignments equal; "
        f"{plat} {quality(dev_assign)}, cpu {quality(cpu_assign)}")
    log(f"finding: the step trace reproduces run_partitioner on {plat}: "
        f"{bool((dev['assign'] == dev_assign).all())}, on cpu: "
        f"{bool((cpu['assign'] == cpu_assign).all())}")
    differ = (dev["sidx"] != cpu["sidx"]) | (dev["p"] != cpu["p"])
    if differ.any():
        t = int(np.argmax(differ))
        drift = int((dev["top"][:t] != cpu["top"][:t]).sum())
        gaps = ", ".join(
            f"{name} takes edge {int(tr['sidx'][t])} -> partition "
            f"{int(tr['p'][t])}, best {float(tr['top'][t]):.9g} runner-up "
            f"{float(tr['second'][t]):.9g} (gap "
            f"{float(tr['top'][t] - tr['second'][t]):.9g})"
            for name, tr in ((plat, dev), ("cpu", cpu)))
        log(f"finding: first differing decision at step {t} of {m}: {gaps}; "
            f"the best score differed bitwise in {drift} of the {t} earlier "
            "steps")
    else:
        log(f"finding: the step traces on {plat} and cpu take the same "
            "decisions at every step")
    log(f"finding: with jax_default_matmul_precision=highest on {plat}: "
        f"{int((hi_assign == cpu_assign).sum())} of {m} assignments equal to "
        f"the host CPU (default precision: "
        f"{int((dev_assign == cpu_assign).sum())})")


def run_four_chip(run_dir: Path, *, n_devices: int, k: int, seed: int,
                  scale: int, edges: int, chunk_edges: int) -> None:
    """The two cross-chip paths against their one-chip counterparts."""
    import numpy as np

    from repro.core import partition_file
    from repro.engine import build_partitioned_graph, engine_mesh, pagerank
    from repro.graph import kronecker
    from repro.graph.io import EdgeFileReader, write_edge_file

    e, n = kronecker(scale, seed=seed, max_edges=edges)
    path = run_dir / "four.adw"
    write_edge_file(str(path), e, n)
    log(f"graph: Graph500 Kronecker scale={scale}, first {len(e)} edges, "
        f"|V|={n}; z={n_devices} spotlight instances, k={k}")

    runs = {}
    for backend in ("shard_map", "vmap"):
        with EdgeFileReader(str(path)) as r:
            res = partition_file(
                r, "adwise", k, z=n_devices, seed=seed,
                chunk_edges=chunk_edges, backend=backend,
                spill_dir=str(run_dir / f"spill_{backend}"),
            )
            runs[backend] = (np.array(res.assign), res.stats)
    (a_sh, s_sh), (a_vm, s_vm) = runs["shard_map"], runs["vmap"]
    check(s_sh["backend"] == "shard_map" and s_sh["n_shards"] == n_devices,
          f"shard_map over {n_devices} devices, got {s_sh['backend']} "
          f"x{s_sh['n_shards']}")
    check(s_vm["backend"] == "vmap", f"vmap, got {s_vm['backend']}")
    np.testing.assert_array_equal(a_sh, a_vm, err_msg="shard_map vs vmap")
    log(f"check: adwise z={n_devices} partition_file, shard_map over "
        f"{n_devices} devices bit-identical to vmap on one "
        f"(walls {s_sh['wall_time_s']:.4f} s vs {s_vm['wall_time_s']:.4f} s)")

    g = build_partitioned_graph(e, a_sh, n, k)
    pr_n, _ = pagerank(g, iters=10, mesh=engine_mesh(n_devices=n_devices))
    pr_1, _ = pagerank(g, iters=10, mesh=engine_mesh(n_devices=1))
    check(np.isfinite(pr_n).all(), "PageRank finite")
    np.testing.assert_allclose(pr_n, pr_1, rtol=1e-5, atol=0)
    log(f"check: PageRank on a {n_devices}-device engine mesh allclose "
        f"(rtol 1e-5) to a 1-device mesh; max abs diff "
        f"{float(np.max(np.abs(pr_n - pr_1))):.3e}")


# ----------------------------------------------------------------------------
# Entry point
# ----------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--edges", type=int, default=None,
                    help="stream only the first N edges of the file "
                         f"(default: {SCAN_CALLS} full ring scan calls)")
    ap.add_argument("--run-dir", default=str(ROOT / "runs" / "chip_smoke"))
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the 4-device shard_map and engine-mesh "
                         "parities (needs 4 chips)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform!r}); "
              "this smoke test runs only on the chip", file=sys.stderr)
        return 1
    want = 4 if args.four_chip else 1
    if len(devices) < want:
        print(f"chip_smoke: --four-chip needs 4 devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
        f"jax {jax.__version__}")

    from repro import compat

    cache = Path(compat.enable_compile_cache())
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(f"compile cache: {cache} ({n_cached} entries at start)")
    clog = CompileLog()
    run_dir = Path(args.run_dir)
    run_dir.mkdir(parents=True, exist_ok=True)
    # A kernel-tier table from elsewhere must not steer what runs here.
    os.environ["ADWISE_AUTOTUNE_CACHE"] = str(run_dir / "kernel_tiers.json")

    if args.four_chip:
        run_four_chip(run_dir, n_devices=want, k=K, seed=SEED, scale=18,
                      edges=1 << 16, chunk_edges=1 << 14)
    else:
        edges = args.edges or SCAN_CALLS * scan_steps(K, CHUNK_EDGES)
        run_main_path(run_dir, scale=SCALE, edges=edges, k=K,
                      chunk_edges=CHUNK_EDGES, iters=ITERS, seed=SEED,
                      clog=clog)
        run_small_parity(run_dir, k=K, seed=SEED)
        stats = dev.memory_stats() or {}
        log(f"{measured()}: peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    n_cached = len(list(cache.glob("*"))) if cache.is_dir() else 0
    log(f"compile cache: {n_cached} entries at end; whole run: "
        f"{clog.since(dict.fromkeys(clog.counts, 0))}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

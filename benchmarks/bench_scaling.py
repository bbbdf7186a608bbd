"""Multi-device scaling harness: batched spotlight partitioning wall and
engine supersteps/s vs device count.

Strong scaling: every row partitions the same graph with the same ``--z``
spotlight instances; only the devices they run on change. Measured in this
process on the real device set: at N = 1 the z instances run as ONE vmapped
program on one device; at N > 1 the same program is shard_mapped over the
instance axis, next to the sequential ``backend="loop"`` path over the same
instances, and PageRank runs on an engine mesh over the first N devices
(the `parts` axis is padded inside `make_superstep`, so every N is valid for
every k). shard_map spreads the instances over the largest divisor of z
that fits the process's devices, so an N that this process cannot isolate
(N above ``jax.device_count()``, or fewer devices than shard_map would take)
is skipped with a note.

``--rehearse`` is the CPU rehearsal: each N runs in a child process under
``JAX_PLATFORMS=cpu`` with N virtual host devices (the flag is read at
process start-up). Its numbers are CPU numbers; never use it where a TPU is
present, since a parent holding the chip shares nothing with its children.

    PYTHONPATH=src python -m benchmarks.bench_scaling                  # N = 1,2,4,8
    PYTHONPATH=src python -m benchmarks.bench_scaling --smoke          # CI-size
    PYTHONPATH=src python -m benchmarks.bench_scaling --smoke --rehearse
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Optional

_JSON_MARK = "BENCH_SCALING_ROW:"


def _skip_reason(z: int, n_devices: int) -> Optional[str]:
    """Why this process cannot run z instances on exactly ``n_devices``
    devices (None when it can)."""
    import jax

    from repro.core.driver import resolve_backend

    have = jax.device_count()
    if n_devices > have:
        return f"this process has {have} device(s)"
    if n_devices == 1:
        return None
    backend, shards = resolve_backend("shard_map", z)
    if backend != "shard_map" or shards > n_devices:
        return (f"shard_map spreads z={z} instances over "
                f"{shards or 1} of this process's {have} devices")
    return None


def _measure(args, n_devices: int) -> dict:
    """Measure z instances on the first ``n_devices`` devices."""
    import jax
    import numpy as np

    from repro.core import AdwiseConfig, spotlight_partition
    from repro.engine import build_partitioned_graph, engine_mesh, pagerank
    from repro.graph import make_graph

    edges, n = make_graph(args.graph, seed=0, scale=args.scale)
    k, z = args.k, args.z
    spread = args.spread if args.spread else max(k // z, 1)
    cfg = AdwiseConfig(k=k, window_max=args.window,
                       window_init=max(1, args.window // 4))
    batched = "vmap" if n_devices == 1 else "shard_map"

    def run(backend):
        return spotlight_partition(edges, n, k, z=z, spread=spread,
                                   strategy="adwise", cfg=cfg, backend=backend)

    # Warm both paths (compile), then time a second run of each.
    res_b = run(batched)
    res_b = run(batched)
    t_batched = res_b.stats["wall_time_s"]  # measured batched-program wall
    res_l = run("loop")
    res_l = run("loop")
    t_loop = res_l.stats["wall_time_serial_s"]  # real serial host wall
    assert (res_b.assign >= 0).all() and (res_l.assign >= 0).all()

    g = build_partitioned_graph(edges, res_b.assign, n, k)
    iters = args.iters
    mesh = engine_mesh(n_devices=n_devices, k=k)
    pagerank(g, iters=2, mesh=mesh)  # compile
    t0 = time.perf_counter()
    pr, _ = pagerank(g, iters=iters, mesh=mesh)
    t_engine = time.perf_counter() - t0
    assert np.isfinite(pr).all()

    return dict(
        platform=jax.devices()[0].platform,
        devices=n_devices,
        m=len(edges),
        k=k,
        z=z,
        spread=spread,
        backend=res_b.stats["backend"],
        n_shards=res_b.stats["n_shards"],
        t_partition_batched_s=round(t_batched, 4),
        t_partition_loop_s=round(t_loop, 4),
        partition_speedup=round(t_loop / max(t_batched, 1e-9), 2),
        supersteps_per_s=round(iters / max(t_engine, 1e-9), 2),
    )


def _spawn(n_devices: int, args) -> dict:
    """CPU rehearsal: measure N in a child with N virtual host devices."""
    cmd = [
        sys.executable, "-m", "benchmarks.bench_scaling",
        "--devices", str(n_devices),
        "--graph", args.graph, "--scale", str(args.scale),
        "--k", str(args.k), "--z", str(args.z), "--spread", str(args.spread),
        "--window", str(args.window), "--iters", str(args.iters),
    ]
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.abspath("src"), env.get("PYTHONPATH")] if p
    )
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_scaling child (N={n_devices}) failed:\n{out.stderr[-2000:]}"
        )
    for line in out.stdout.splitlines():
        if line.startswith(_JSON_MARK):
            return json.loads(line[len(_JSON_MARK):])
    raise RuntimeError(f"child (N={n_devices}) printed no row:\n{out.stdout}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="brain_like")
    ap.add_argument("--scale", type=float, default=0.02)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--z", type=int, default=4,
                    help="spotlight instances, the same at every N")
    ap.add_argument("--spread", type=int, default=0,
                    help="partitions per instance (0 = k/z disjoint blocks)")
    ap.add_argument("--window", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10, help="engine supersteps")
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma-separated device counts to sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-size: tiny graph, N in {1,2}")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: one child process per N with N "
                         "virtual CPU devices (never on a TPU host)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        args.scale, args.k, args.z, args.window, args.iters = 0.008, 8, 4, 16, 4
        if args.devices == "1,2,4,8":
            args.devices = "1,2"
    sweep = [int(x) for x in args.devices.split(",") if x]

    if args.rehearse:
        rows = [_spawn(n_dev, args) for n_dev in sweep]
    else:
        rows = []
        for n_dev in sweep:
            why = _skip_reason(args.z, n_dev)
            if why:
                print(f"skip N={n_dev}: {why}")
            else:
                rows.append(_measure(args, n_dev))
    print("platform,devices,backend,n_shards,t_partition_batched_s,"
          "t_partition_loop_s,partition_speedup,supersteps_per_s")
    for r in rows:
        print(f"{r['platform']},{r['devices']},{r['backend']},{r['n_shards']},"
              f"{r['t_partition_batched_s']},{r['t_partition_loop_s']},"
              f"{r['partition_speedup']},{r['supersteps_per_s']}")
        if not args.rehearse:
            print(f"{_JSON_MARK}{json.dumps(r)}")
    if args.json:
        json.dump(rows, open(args.json, "w"), indent=1)
    return rows


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""The controls of ``correct``: each cell's plain reference put in the
program's place, computed one precision below what the configuration
states, and judged by the cell's own checks. A sound comparison reads them
as not correct.

    python3 bench/control.py --workload g500s22-k32.adwise --seeds 1,2,3

- partition jobs: the reference ADWISE with its scores in bfloat16 (the
  configuration states float32) places the job's edges;
- processing jobs: PageRank's power iteration in bfloat16 on the device
  (values, messages and sums), on the cell's graph from the seed's
  initial state.

Each seed prints one line with every check's value and limit; the last
line is a JSON list of them. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def pagerank_bf16(edges, n: int, x0, steps: int, damping: float):
    """The engine's PageRank update from ``x0``, every value in bfloat16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    bf = jnp.bfloat16
    u = jnp.asarray(edges[:, 0])
    v = jnp.asarray(edges[:, 1])

    @jax.jit
    def run(u, v, x):
        deg = jnp.zeros(n, jnp.int32).at[u].add(1).at[v].add(1)
        inv = (1.0 / jnp.maximum(deg, 1)).astype(bf)
        x = x.astype(bf)
        for _ in range(steps):
            y = x * inv
            acc = jnp.zeros(n, bf).at[v].add(y[u]).at[u].add(y[v])
            x = (bf((1.0 - damping) / n) + bf(damping) * acc).astype(bf)
        return x

    return np.asarray(run(u, v, jnp.asarray(x0)).astype(jnp.float32),
                      np.float64)


def control_result(job, traffic: dict) -> dict:
    """One job's result, made by the reference in lower precision."""
    from bench import reference

    t0 = time.perf_counter()
    if traffic["job"] == "partition":
        s = job.settings
        knobs = {key: s[key] for key in ("window_max", "window_init",
                                         "lam_init", "lam_lo", "lam_hi",
                                         "eps", "cap_slack")}
        res = reference.adwise_reference(job.edges, job.n, job.k,
                                         dtype="bfloat16", **knobs)
        return dict(wall_s=time.perf_counter() - t0, work=job.m,
                    assign=res["assign"], order=res["order"])
    ranks = pagerank_bf16(job.edges, job.n, job.x0, job.steps,
                          job.settings["damping"])
    return dict(wall_s=time.perf_counter() - t0, work=job.steps, start=0,
                ranks=ranks)


def main(argv=None, *, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 1,2,3")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(root / "src"))
    sys.path.insert(0, str(root))
    from bench.run import Cell, annotate

    cell = Cell(root, args.workload)
    workdir = root / "runs" / "bench" / f"{cell.name}.control"
    workdir.mkdir(parents=True, exist_ok=True)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        job = cell.job_module().Job(cell.config, cell.traffic, seed, workdir)
        if cell.traffic["job"] == "partition":
            job.setup(annotate)
        else:  # only the data: the control replaces the whole engine
            job.draw()
        result = control_result(job, cell.traffic)
        checks, _ = job.checks([result])
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        rows.append(dict(seed=seed, correct=correct, checks=checks,
                         control_s=result["wall_s"]))
        print(f"control {cell.name} seed {seed}: correct={correct} "
              + " ".join(f"{k}={c['value']!r}(limit {c['limit']!r})"
                         for k, c in checks.items()), flush=True)
    print(json.dumps(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

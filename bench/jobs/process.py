"""Processing jobs: supersteps of the vertex-cut engine on the whole graph.

Set-up draws the traffic mix's one graph instance (``traffic["graph_seed"]``:
every run processes the same graph, as a Graph500 run searches one graph
from many roots) and the run's initial state from ``--seed``: a positive
random vector summing to 1. It partitions the graph with the registry's
strategy named in the configuration (degrees passed in, from
``np.bincount``), builds the engine graph with the program's own slab
padding (``repro.engine.build_partitioned_graph``) and the algorithm's
jitted superstep. The jobs continue one chain of states: each runs
``traffic["supersteps"]`` supersteps from the state the previous job left
(the warm-up job from the initial state) and ends with the result on the
host, so every job reads a state no earlier job read.
"""
from __future__ import annotations

import time

import numpy as np

from bench import graphgen, reference


def initial_ranks(n: int, seed: int) -> np.ndarray:
    """The run's first PageRank state: ``n`` positive float32 values in
    [0.5, 1.5) scaled to sum to 1, drawn from ``seed``."""
    x = np.random.default_rng(int(seed)).random(n) + 0.5
    return (x / x.sum()).astype(np.float32)


class Job:
    work_unit = "supersteps"

    def __init__(self, config: dict, traffic: dict, seed: int, workdir) -> None:
        if traffic["algorithm"] != "pagerank":
            raise ValueError(f"no processing job for {traffic['algorithm']!r}")
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.k = int(config["k"])
        self.settings = config["pagerank"]
        self.steps = int(traffic["supersteps"])
        self.done = 0  # supersteps run so far in the chain

    def draw(self) -> None:
        """The graph and the initial state, on the host."""
        self.edges, self.n = graphgen.kronecker(self.config["graph"],
                                                self.traffic["graph_seed"])
        self.x0 = initial_ranks(self.n, self.seed)

    def setup(self, annotate) -> dict:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.core import run_partitioner
        from repro.engine import (build_partitioned_graph, engine_mesh,
                                  pagerank_superstep)

        phases = {}
        t0 = time.perf_counter()
        with annotate("bench.setup.draw"):
            self.draw()
        t1 = time.perf_counter()
        phases["draw_s"] = t1 - t0
        with annotate("bench.setup.partition"):
            deg = (np.bincount(self.edges[:, 0], minlength=self.n)
                   + np.bincount(self.edges[:, 1], minlength=self.n))
            assign = run_partitioner(self.settings["partitioner"], self.edges,
                                     self.n, self.k, seed=0,
                                     degrees=deg).assign
        t2 = time.perf_counter()
        phases["partition_s"] = t2 - t1
        with annotate("bench.setup.engine_build"):
            g = build_partitioned_graph(self.edges, assign, self.n, self.k)
            mesh = engine_mesh(k=self.k)
            self.step, _ = pagerank_superstep(
                g, damping=self.settings["damping"], mesh=mesh)
            # Vertex state is replicated over the engine's mesh, as the
            # superstep returns it: every job's input has one layout, so
            # the warm-up compiles the only program the window runs.
            self.state = jax.device_put(
                self.x0[:, None], NamedSharding(mesh, PartitionSpec()))
            sizes = np.bincount(assign, minlength=self.k)
            print(f"engine: {self.k} slabs of {g.edges.shape[1]} edges "
                  f"(fullest partition {sizes.max()}, mean "
                  f"{len(self.edges) / self.k:.0f})", flush=True)
            del g
        phases["build_s"] = time.perf_counter() - t2
        return phases

    def run(self, annotate) -> dict:
        with annotate("bench.job"):
            t0 = time.perf_counter()
            x = self.state
            for _ in range(self.steps):
                with annotate("bench.superstep"):
                    x = self.step(x)
            with annotate("bench.fetch"):
                ranks = np.asarray(x)[:, 0]
            wall = time.perf_counter() - t0
        self.state = x
        start, self.done = self.done, self.done + self.steps
        return dict(wall_s=wall, work=self.steps, start=start, ranks=ranks)

    def end_to_end(self, results: list) -> dict:
        return dict(superstep_ms=1e3 * sum(r["wall_s"] for r in results)
                    / sum(r["work"] for r in results))

    def release(self) -> None:
        self.step = self.state = None

    def checks(self, results: list) -> tuple:
        """Every job's ranks against the float64 power iteration from the
        same initial state, at the same count of supersteps: the widest
        relative error over all vertices and jobs."""
        limit = self.traffic["limits"]["rank_rel_err"]
        due = {}
        for i, r in enumerate(results):
            due.setdefault(r["start"] + r["work"], []).append(i)
        errs = [float("inf")] * len(results)
        iterates = reference.pagerank_iterates(
            self.edges, self.n, self.x0.astype(np.float64),
            self.settings["damping"])
        for count in range(1, max(due) + 1):
            ref = next(iterates)
            for i in due.get(count, ()):
                x = results[i]["ranks"]
                if x.shape == ref.shape and np.isfinite(x).all():
                    errs[i] = float(np.max(np.abs(x - ref) / ref))
        worst = max(errs)
        bad = sum(not e <= limit for e in errs)
        return dict(rank_rel_err=dict(value=worst, limit=limit)), bad

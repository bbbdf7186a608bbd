"""Partition jobs: one binary edge file through ``partition_file``.

Set-up draws the configuration's graph on the device, keeps the first
``config["partition_job_edges"]`` rows of its source-sorted file and writes
them with the program's edge-file writer. A job opens the file, partitions
it with ``repro.core.partition_file`` (the file ring, the read-ahead thread
and the ring scan) and reads the spilled assignment back; its wall runs from
the call to the assignment in host memory.

While the first job (the warm-up, outside the timed window) runs, the
placements the program writes to its spill are also noted in the order it
writes them: a scan call emits its steps' placements in step order, so this
is the order of the decisions, which the check replays. The timed jobs run
unhooked; each must give the warm-up's assignment exactly.
"""
from __future__ import annotations

import contextlib
import sys
import time

import numpy as np

from bench import graphgen, reference

# Settings of the configuration's strategy block that describe it but are
# not arguments of the program.
DESCRIPTIVE = {"score_dtype"}


@contextlib.contextmanager
def spill_order(written: list):
    """Note the edge indices of every placement the program writes to its
    spill (``repro.core.oocore._Spill.write``), in the order written."""
    from repro.core import oocore

    real = oocore._Spill.write

    def write(spill, idx, vals):
        written.append(np.array(idx, np.int64))
        return real(spill, idx, vals)

    oocore._Spill.write = write
    try:
        yield
    finally:
        oocore._Spill.write = real


class Job:
    work_unit = "edges"

    def __init__(self, config: dict, traffic: dict, seed: int, workdir) -> None:
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.workdir = workdir
        self.k = int(config["k"])
        self.m = int(config["partition_job_edges"])
        self.chunk = int(config["chunk_edges"])
        self.strategy = traffic["strategy"]
        self.settings = dict(config[self.strategy])
        self.path = workdir / "graph.adw"
        self.spill = workdir / "spill"
        self.first = None  # the first job's result: the warm-up's

    def setup(self, annotate) -> dict:
        from repro.graph.io import write_edge_file

        with annotate("bench.setup.draw"):
            t0 = time.perf_counter()
            self.edges, self.n = graphgen.kronecker(
                self.config["graph"], self.seed, max_edges=self.m)
            t1 = time.perf_counter()
        if len(self.edges) != self.m:
            raise ValueError(f"graph has {len(self.edges)} edges, the job "
                             f"streams {self.m}")
        write_edge_file(str(self.path), self.edges, self.n)
        return dict(draw_s=t1 - t0, write_s=time.perf_counter() - t1)

    def run(self, annotate) -> dict:
        from repro.core import partition_file
        from repro.graph.io import EdgeFileReader

        knobs = {key: val for key, val in self.settings.items()
                 if key not in DESCRIPTIVE}
        written: list = []
        hook = (spill_order(written) if self.first is None
                else contextlib.nullcontext())
        with annotate("bench.job"), hook:
            t0 = time.perf_counter()
            with EdgeFileReader(str(self.path)) as r:
                with annotate("bench.partition_file"):
                    res = partition_file(
                        r, self.strategy, self.k, chunk_edges=self.chunk,
                        spill_dir=str(self.spill), **knobs)
                assign = np.array(res.assign)
            wall = time.perf_counter() - t0
        keep = ("scan_calls", "scan_steps_per_call", "h2d_wait_s", "h2d_bytes",
                "prestage_wall_s", "io_wall_s", "wall_time_s")
        result = dict(wall_s=wall, work=self.m, assign=assign,
                      stats={key: res.stats.get(key) for key in keep})
        if self.first is None:
            result["order"] = (np.concatenate(written) if written
                               else np.zeros(0, np.int64))
            self.first = result
        return result

    def end_to_end(self, results: list) -> dict:
        q = reference.quality(self.edges, results[0]["assign"], self.n, self.k)
        return dict(
            partition_eps=sum(r["work"] for r in results)
            / sum(r["wall_s"] for r in results),
            replication_degree=q["replication_degree"],
        )

    def release(self) -> None:
        """The program keeps no device state between jobs."""

    def reported_quality(self, assign: np.ndarray) -> dict:
        """Replication degree and imbalance as the program reports them for
        a file-driven run: its chunked metrics over the file and the spill."""
        from repro.graph import quality_from_chunks
        from repro.graph.io import EdgeFileReader

        with EdgeFileReader(str(self.path)) as r:
            pairs = ((chunk, assign[s:s + len(chunk)]) for s, chunk in zip(
                range(0, self.m, self.chunk), r.chunks(self.chunk)))
            return quality_from_chunks(pairs, self.n, self.k)

    def checks(self, results: list) -> tuple:
        """Every job's assignment against the configuration's guarantees,
        the program's reported quality against the host recomputation, each
        job's assignment against the first (the warm-up's), and the first
        job's decisions, in the order it made them, against the reference
        ADWISE in float64: the widest gap between a step's best score and
        the score of the placement the program made."""
        limits = self.traffic["limits"]
        cap = reference.capacity(self.m, self.k, self.settings["cap_slack"])
        job = self.first if self.first is not None else results[0]
        first = job["assign"]
        unplaced = over_cap = report_gap = 0.0
        differ = 0
        bad = set()
        for i, r in enumerate(results):
            a = r["assign"]
            if a.shape != (self.m,):
                n_bad = self.m
            else:
                n_bad = int(((a < 0) | (a >= self.k)).sum())
            unplaced = max(unplaced, n_bad)
            if n_bad:
                bad.add(i)
                report_gap = over_cap = float("inf")
                continue
            sizes = np.bincount(a, minlength=self.k)
            over_cap = max(over_cap, float(max(0, sizes.max() - cap)))
            mine = reference.quality(self.edges, a, self.n, self.k)
            theirs = self.reported_quality(a)
            gap = max(abs(mine["replication_degree"]
                          - theirs["replication_degree"]),
                      abs(mine["imbalance"] - theirs["imbalance"]))
            report_gap = max(report_gap, gap)
            same = np.array_equal(a, first)
            differ += int(not same)
            if sizes.max() > cap or gap > 0 or not same:
                bad.add(i)
        fixed = dict(lazy=True, use_clustering=True, adapt=True,
                     latency_budget=None, assign_batch=1)
        if any(self.settings.get(key, val) != val for key, val in fixed.items()):
            raise ValueError(f"the reference ADWISE implements {fixed} only")
        order = job.get("order", np.zeros(0, np.int64))
        if len(order) != self.m or not np.array_equal(
                np.sort(order), np.arange(self.m)):
            print(f"decision order not recorded: the program's spill writes "
                  f"(repro.core.oocore._Spill.write) noted {len(order)} "
                  f"placements, the job has {self.m}", file=sys.stderr)
            decision_gap = float("inf")
        elif first.shape != (self.m,) or ((first < 0)
                                           | (first >= self.k)).any():
            decision_gap = float("inf")
        else:
            knobs = {key: self.settings[key] for key in (
                "window_max", "window_init", "lam_init", "lam_lo", "lam_hi",
                "eps", "cap_slack")}
            decision_gap = reference.adwise_reference(
                self.edges, self.n, self.k,
                replay=(order, first), **knobs)["max_gap"]
        if decision_gap > limits["decision_gap"]:
            bad.add(0)
        values = dict(unplaced=unplaced, over_cap=over_cap,
                      report_gap=report_gap, jobs_differ=float(differ),
                      decision_gap=decision_gap)
        return ({name: dict(value=v, limit=limits[name])
                 for name, v in values.items()}, len(bad))

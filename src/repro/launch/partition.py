"""Graph partition→process launcher — the paper's pipeline as a job type.

    PYTHONPATH=src python -m repro.launch.partition --graph brain_like --scale 0.1 \
        --strategy adwise --k 32 --z 8 --spread 4 --budget 2.0 \
        --workload pagerank --iters 100

    # out-of-core: partition a file-resident graph with bounded edge memory
    PYTHONPATH=src python -m repro.launch.partition --graph /data/orkut.adw \
        --strategy adwise-restream --passes 3 --k 32 --chunk-edges 262144
    # text edge list (SNAP format): ingest to binary first, then partition
    PYTHONPATH=src python -m repro.launch.partition --graph /data/orkut.txt \
        --ingest --relabel --strategy hdrf --k 32

Runs: stream partitioning (any strategy in the `repro.core.registry` —
adwise / adwise-restream / 2ps / 2ps-l / hdrf / dbh / greedy / hash / grid —
optionally under spotlight parallel loading) → vertex-cut engine build →
workload → total latency report (measured partitioning wall-clock + modeled
cluster processing latency, cf. DESIGN.md §3). New partitioners registered
in `repro/core/registry.py` show up in `--strategy` automatically;
`--passes` / `--eps` set the re-streaming pass count / early-stop for
adwise-restream. `2ps-l` is the linear-run-time 2PS variant (2PS phase-1
clustering, then a single windowless cluster-score pass as its own
step-core); it takes no AdwiseConfig knobs — its `cluster_slack=` / `lam=` /
`cap_slack=` defaults are the registry's. With `--z N` (alias `--parallel`)
the z spotlight instances run as ONE batched (vmapped / multi-device
shard_mapped) program for EVERY registry strategy — each strategy is a
device-resident step-core behind one scan driver — and `--backend loop`
forces the sequential per-instance path (bit-identical escape hatch).

`--graph` also takes a *path* instead of a preset name: a binary edge-stream
file (`repro.graph.io` format) is partitioned out-of-core through
`repro.core.oocore.partition_file` — resident edge memory stays bounded by
`--chunk-edges`, assignments spill to disk, quality metrics accumulate in
chunks, and the report includes the measured ingest wall / stream reads.
`--ingest` converts a SNAP-style text edge list to the binary format first
(one pass, O(chunk) memory; `--relabel` densifies sparse vertex ids).
`--prefetch N` sets the double-buffered ring-refill depth (0 = synchronous
escape hatch); the report then shows the measured h2d stall and the fraction
of refill spans the read-ahead worker had prestaged.

`--trace out.json` records a span timeline of the run with
`repro.obs.Tracer` and writes it as Chrome trace-event JSON — open it in
https://ui.perfetto.dev (or chrome://tracing). Tracks: the main stepping
loop (`scan`/`refill`/`phase` spans), the `adwise-readahead` worker
(`stage` spans + queue-depth counter), and one `restream-pass-<j>` lane per
re-streaming pass. The result's `stats["trace_summary"]` carries the
aggregate view (`events`, `wall_s`, per-category `{count, wall_s}`,
`tracks`); the same dict is printed at the end of a traced run. Tracing is
host-side only — spans wrap dispatch and host waits, never adding a device
sync — so `--trace` does not perturb the measured pipeline.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time

import numpy as np

from repro import compat
from repro.core import (
    AdwiseConfig,
    available_strategies,
    partition_file,
    run_partitioner,
    spotlight_partition,
)
from repro.engine import (
    PAPER_CLUSTER,
    build_partitioned_graph,
    coloring,
    label_propagation,
    pagerank,
    process_latency,
    triangle_count,
)
from repro.graph import (
    make_graph,
    partition_balance,
    replica_sets_from_assignment,
    replication_degree,
    unassigned_count,
)

# Strategies that take AdwiseConfig-style knobs from the CLI.
_ADWISE_LIKE = ("adwise", "adwise-restream", "2ps")


def adwise_cfg_kwargs(args) -> dict:
    return dict(
        window_max=args.window_max,
        latency_budget=args.budget,
        use_clustering=not args.no_cs,
    )


def strategy_cfg_kwargs(args) -> dict:
    """Registry-style **cfg for the active strategy (file-driven path)."""
    cfg = {}
    if args.strategy in _ADWISE_LIKE:
        cfg = adwise_cfg_kwargs(args)
    if args.strategy == "adwise-restream":
        cfg["passes"] = args.passes
        if args.eps is not None:
            cfg["eps"] = args.eps
    return cfg


def run_partition_file(path, args, trace=None):
    """Out-of-core path: ingest (optional) → partition_file → chunked metrics."""
    from repro.graph.io import EdgeFileReader, ingest_text

    if args.oracle:
        raise SystemExit(
            "--oracle (the sequential Algorithm-1 reference) has no "
            "out-of-core driver; run it on a generator preset instead"
        )
    if args.backend in ("batched", "loop"):
        print(f"note: --backend {args.backend} has no file-driven equivalent; "
              "using 'auto' (every scan-core strategy rides the batched ring "
              "buffer; only the stateless hashes run a per-instance loop)")
    ingest_tmp = None
    if args.ingest:
        # The cache name keys on --relabel: the two settings produce
        # different id spaces, so they must never reuse each other's binary.
        suffix = ".relabel.adw" if args.relabel else ".adw"
        binary = path + suffix
        if not os.access(os.path.dirname(os.path.abspath(path)) or ".", os.W_OK):
            # Read-only dataset mount: put the binary in the spill dir (kept)
            # or a temp dir the end of the run removes.
            if args.spill_dir is None:
                ingest_tmp = tempfile.mkdtemp(prefix="adwise-ingest-")
            else:
                os.makedirs(args.spill_dir, exist_ok=True)
            binary = os.path.join(
                args.spill_dir or ingest_tmp, os.path.basename(path) + suffix
            )
        if (os.path.exists(binary)
                and os.path.getmtime(binary) >= os.path.getmtime(path)):
            print(f"reusing up-to-date binary {binary} (delete it to re-ingest)")
        else:
            rep = ingest_text(path, binary, relabel=args.relabel)
            mb = rep.bytes_read / 1e6
            print(
                f"ingested {path}: {rep.num_edges} edges, {rep.num_vertices} "
                f"vertices, {rep.comment_lines} comments, {rep.blank_lines} "
                f"blanks in {rep.wall_s:.2f}s "
                f"({mb / max(rep.wall_s, 1e-9):.1f} MB/s) -> {binary}"
            )
        path = binary
    reader = EdgeFileReader(path)
    print(
        f"graph={path} |V|={reader.num_vertices} |E|={reader.num_edges} "
        f"k={args.k} (out-of-core, chunk={args.chunk_edges})"
    )
    backend = args.backend if args.backend not in ("batched", "loop") else "auto"
    spill_tmp = None if args.spill_dir else tempfile.mkdtemp(prefix="adwise-oocore-")
    res = partition_file(
        reader, args.strategy, args.k, z=args.parallel,
        spread=args.spread if args.parallel > 1 else None, seed=args.seed,
        chunk_edges=args.chunk_edges, backend=backend,
        spill_dir=args.spill_dir or spill_tmp, prefetch=args.prefetch,
        trace=trace, **strategy_cfg_kwargs(args),
    )
    return reader, res, spill_tmp, ingest_tmp


def run_partition(edges, n, args, trace=None):
    from repro.obs import resolve_tracer

    tr = resolve_tracer(trace)
    # In-memory paths get one coarse phase span (spotlight/registry routes
    # don't thread a tracer); the file-driven path traces the full pipeline.
    with tr.span("partition", cat="phase", strategy=args.strategy, k=args.k):
        return _run_partition(edges, n, args)


def _run_partition(edges, n, args):
    if args.parallel > 1:
        cfg = None
        strategy_cfg = None
        if args.strategy == "adwise":
            cfg = AdwiseConfig(k=args.k, **adwise_cfg_kwargs(args))
        elif args.strategy in _ADWISE_LIKE:
            strategy_cfg = adwise_cfg_kwargs(args)
            if args.strategy == "adwise-restream":
                strategy_cfg["passes"] = args.passes
                if args.eps is not None:
                    strategy_cfg["eps"] = args.eps
        return spotlight_partition(
            edges, n, args.k, z=args.parallel, spread=args.spread,
            strategy=args.strategy, cfg=cfg, seed=args.seed,
            strategy_cfg=strategy_cfg, backend=args.backend,
        )
    cfg = {}
    if args.strategy in _ADWISE_LIKE:
        cfg = adwise_cfg_kwargs(args)
    if args.strategy == "adwise":
        cfg["oracle"] = args.oracle
    elif args.strategy == "adwise-restream":
        cfg["passes"] = args.passes
        if args.eps is not None:
            cfg["eps"] = args.eps
    return run_partitioner(args.strategy, edges, n, args.k, seed=args.seed, **cfg)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graph", default="brain_like",
                    help="generator preset (brain_like/orkut_like/web_like/...)"
                         " OR a path to a graph file: a binary edge-stream "
                         "file (repro.graph.io format) is partitioned "
                         "out-of-core with bounded edge memory; with "
                         "--ingest, a SNAP-style text edge list is converted "
                         "to the binary format first")
    ap.add_argument("--ingest", action="store_true",
                    help="treat --graph as a text edge list (u v per line, "
                         "#/% comments, blank lines) and ingest it to "
                         "<graph>.adw before partitioning (one pass, "
                         "O(chunk) memory)")
    ap.add_argument("--relabel", action="store_true",
                    help="with --ingest: map vertex ids to a dense [0, n) "
                         "space in first-appearance order (required for "
                         "sparse or negative ids)")
    ap.add_argument("--chunk-edges", type=int, default=1 << 16,
                    help="out-of-core chunk size: resident edge rows are "
                         "bounded by ~2x this per spotlight instance "
                         "(file-driven path only)")
    ap.add_argument("--spill-dir", default=None,
                    help="directory for the assignment spill (file-driven "
                         "path). Default: a temp dir, removed when the run "
                         "finishes; pass a path to keep the spill")
    ap.add_argument("--prefetch", type=int, default=None,
                    help="read-ahead depth for the file-driven ring refill "
                         "pipeline: 0 = synchronous (bit-identical escape "
                         "hatch), N>=1 overlaps file read + h2d staging with "
                         "the running scan. Default: $ADWISE_PREFETCH or 2")
    ap.add_argument("--scale", type=float, default=0.05)
    ap.add_argument("--strategy", default="adwise",
                    choices=available_strategies())
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--parallel", "--z", type=int, default=1, dest="parallel",
                    help="z partitioner instances (spotlight parallel loading)")
    ap.add_argument("--spread", type=int, default=4)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "batched", "vmap", "shard_map", "loop"],
                    help="spotlight execution: one batched program for all z "
                         "instances (auto — every registry strategy batches) "
                         "or the sequential per-instance loop")
    ap.add_argument("--budget", type=float, default=None, help="latency preference L (s)")
    ap.add_argument("--window-max", type=int, default=256)
    ap.add_argument("--passes", type=int, default=2,
                    help="re-streaming passes (adwise-restream)")
    ap.add_argument("--eps", type=float, default=None,
                    help="early-stop re-streaming when a pass improves RD by "
                         "less than this (adwise-restream)")
    ap.add_argument("--no-cs", action="store_true", help="disable clustering score")
    ap.add_argument("--oracle", action="store_true", help="sequential reference impl")
    ap.add_argument("--workload", default="pagerank",
                    choices=["pagerank", "coloring", "wcc", "triangles", "none"])
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default=None)
    ap.add_argument("--trace", default=None, metavar="OUT_JSON",
                    help="record a span timeline of the run (repro.obs) and "
                         "write Chrome trace-event JSON here — open in "
                         "https://ui.perfetto.dev. Host-side only: no added "
                         "device syncs")
    args = ap.parse_args(argv)
    compat.enable_compile_cache()

    tracer = None
    if args.trace:
        from repro.obs import Tracer

        tracer = Tracer()

    from_file = args.ingest or os.path.exists(args.graph)
    reader = None
    spill_tmp = ingest_tmp = None
    if from_file:
        reader, res, spill_tmp, ingest_tmp = run_partition_file(
            args.graph, args, trace=tracer)
        n = reader.num_vertices
        edges = None  # never resident during partitioning
    else:
        edges, n = make_graph(args.graph, seed=args.seed, scale=args.scale)
        print(f"graph={args.graph} |V|={n} |E|={len(edges)} k={args.k}")
        res = run_partition(edges, n, args, trace=tracer)
    # The unassigned count is reported explicitly, so quality metrics run
    # under the 'drop' policy: a partial assignment yields numbers over the
    # assigned subset *plus* a nonzero unassigned= field — never a silent
    # mis-count (and never a crash before the count is printed).
    n_unassigned = unassigned_count(res.assign)
    if from_file:
        # Chunked metric accumulation: the quality numbers for a file-driven
        # run never materialize the edge array either.
        from repro.graph import quality_from_chunks

        assign = res.assign
        pairs = (
            (chunk, assign[s : s + len(chunk)])
            for s, chunk in zip(
                range(0, reader.num_edges, args.chunk_edges),
                reader.chunks(args.chunk_edges),
            )
        )
        q = quality_from_chunks(pairs, n, args.k, unassigned="drop")
        rd, imb = q["replication_degree"], q["imbalance"]
    else:
        rep = replica_sets_from_assignment(edges, res.assign, n, args.k,
                                           unassigned="drop")
        rd = replication_degree(rep)
        imb = partition_balance(res.assign, args.k, unassigned="drop")
    t_part = res.stats.get("wall_time_s", 0.0)
    print(f"partitioner={args.strategy} RD={rd:.3f} imbalance={imb:.4f} "
          f"unassigned={n_unassigned} partition_latency={t_part:.2f}s")
    if from_file:
        print(
            f"io: {res.stats['rows_read']} rows read "
            f"({res.stats['stream_reads_measured']} stream reads, billed "
            f"{res.stats['stream_reads']}), io_wall={res.stats['io_wall_s']:.2f}s, "
            f"resident edges <= {res.stats['peak_resident_edges']}, "
            f"h2d={res.stats.get('h2d_bytes', 0) / 1e6:.2f} MB "
            f"({res.stats.get('h2d_rows', 0)} rows over "
            f"{res.stats.get('scan_calls', 0)} scan calls, "
            f"ring={res.stats.get('buffer_rows', 0)} rows), "
            f"spill={res.stats['spill_path']}"
        )
        spans = int(res.stats.get("refill_spans", 0) or 0)
        if spans:
            pre = int(res.stats.get("spans_prestaged", 0) or 0)
            wait = float(res.stats.get("h2d_wait_s", 0.0) or 0.0)
            prestage = float(res.stats.get("prestage_wall_s", 0.0) or 0.0)
            # Measured overlap: fraction of the worker's staging wall hidden
            # from the driver's critical path (1 - stall/staging).
            overlap = max(0.0, 1.0 - wait / prestage) if prestage > 0 else 0.0
            print(
                f"pipeline: prefetch={res.stats.get('prefetch_depth', 0)}, "
                f"h2d_wait={wait:.3f}s, prestage_wall={prestage:.3f}s, "
                f"spans={spans} ({pre} prestaged / "
                f"{int(res.stats.get('spans_missed', 0) or 0)} missed), "
                f"overlap={overlap:.0%}"
            )

    out = dict(
        graph=args.graph, strategy=args.strategy, k=args.k,
        replication_degree=rd, imbalance=imb, unassigned=n_unassigned,
        partition_latency_s=t_part,
        stats={k: v for k, v in res.stats.items()
               if isinstance(v, (int, float, str))
               or (isinstance(v, list)
                   and all(isinstance(x, (int, float)) for x in v))},
    )
    if args.workload != "none":
        if from_file:
            # Partitioning ran out-of-core; the *processing* engine builds a
            # resident partitioned graph, so the edges are loaded only now.
            print("loading edges for the processing engine (partitioning "
                  "itself ran out-of-core)")
            edges = reader.read_all()
        g = build_partitioned_graph(edges, res.assign, n, args.k)
        t0 = time.perf_counter()
        if args.workload == "pagerank":
            result, info = pagerank(g, iters=min(args.iters, 30), trace=tracer)
            info["supersteps"] = args.iters
        elif args.workload == "coloring":
            result, info = coloring(g, trace=tracer)
        elif args.workload == "wcc":
            result, info = label_propagation(g, trace=tracer)
        else:
            result, info = triangle_count(g, trace=tracer)
        t_proc_local = time.perf_counter() - t0
        model = process_latency(g, info["supersteps"], info["msg_width"], PAPER_CLUSTER)
        total = t_part + model["t_total_s"]
        print(
            f"workload={args.workload} supersteps={info['supersteps']} "
            f"modeled_processing={model['t_total_s']:.2f}s (cluster: {model['profile']}) "
            f"local_exec={t_proc_local:.2f}s\n"
            f"TOTAL latency (partition + modeled processing) = {total:.2f}s"
        )
        out.update(
            workload=args.workload,
            processing_model=model,
            total_latency_s=total,
        )
        # In-process callers get the workload's answer; the JSON report
        # keeps to the scalars.
        out["result"] = result
    if tracer is not None:
        n_events = tracer.export(args.trace)
        summ = tracer.summary()
        cats = ", ".join(
            f"{c}:{d['count']}x/{d['wall_s']:.3f}s"
            for c, d in sorted(summ.categories.items())
        )
        print(f"trace: {n_events} events -> {args.trace} "
              f"(wall={summ.wall_s:.3f}s; {cats})")
        out["trace"] = dict(path=args.trace, **summ.as_dict())
    if args.json:
        with open(args.json, "w") as f:
            json.dump({k: v for k, v in out.items() if k != "result"}, f,
                      indent=1)
    if from_file:
        # The temp spill (|E|*4 bytes) dies with the run; metrics and the
        # workload are done with it (POSIX keeps the live mapping valid past
        # the unlink). --spill-dir keeps it instead. The reader FD always
        # closes (in-process callers — benches, tests — must not leak one
        # per run); the ingest temp dir follows the spill's lifetime.
        reader.close()
        if spill_tmp is not None:
            shutil.rmtree(spill_tmp, ignore_errors=True)
        if ingest_tmp is not None:
            shutil.rmtree(ingest_tmp, ignore_errors=True)
    return out


if __name__ == "__main__":
    main()

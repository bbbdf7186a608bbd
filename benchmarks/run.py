"""Run every benchmark at smoke scale. One section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run --smoke   # every entrypoint, seconds
    PYTHONPATH=src python -m benchmarks.run           # smoke scale (CI)
    PYTHONPATH=src python -m benchmarks.run --full    # paper-scale proxies

--smoke exists so CI (and the test suite) can prove every bench entrypoint
still *runs* — tiny graphs, k=8, minimal steps — without paying benchmark
wall-clock.

--json-dir DIR additionally writes a machine-readable ``BENCH_<n>.json``
summary (n auto-increments over the files already in DIR, so a kept
directory accumulates the perf trajectory run over run): partition walls,
host→device stream traffic, ingest MB/s, engine supersteps/s, and the raw
per-bench rows. tools/ci.sh passes ``bench_logs/`` and keeps the file.

``--trace out.json`` wraps every bench section in a ``bench``-category span
(repro.obs) and writes a Perfetto-loadable Chrome trace-event timeline.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import re
import sys
import time


def _next_bench_path(json_dir: str) -> str:
    os.makedirs(json_dir, exist_ok=True)
    taken = [
        int(m.group(1))
        for f in os.listdir(json_dir)
        if (m := re.fullmatch(r"BENCH_(\d+)\.json", f))
    ]
    return os.path.join(json_dir, f"BENCH_{max(taken, default=-1) + 1}.json")


def _summarize(results: dict) -> dict:
    """The headline numbers the perf trajectory tracks, pulled from the raw
    bench returns (absent benches simply contribute nothing)."""
    head: dict = {}
    io = results.get("io") or {}
    if io:
        head["ingest_mb_s"] = io.get("ingest_mb_s")
        head["ingest_python_mb_s"] = io.get("ingest_python_mb_s")
        head["ingest_speedup"] = io.get("ingest_speedup")
        head["read_mb_s"] = io.get("read_mb_s")
        for row in io.get("rows", []):
            if row.get("strategy") == "adwise":
                head["partition_file_wall_s"] = row.get("t_file_s")
                head["partition_memory_wall_s"] = row.get("t_memory_s")
                head["h2d_bytes"] = row.get("h2d_bytes")
                head["h2d_rows_per_call"] = (
                    row["h2d_rows"] / row["scan_calls"]
                    if row.get("scan_calls") else None
                )
                head["ring_rows"] = row.get("ring_rows")
                head["partition_file_sync_wall_s"] = row.get("t_file_sync_s")
                head["h2d_wait_s"] = row.get("h2d_wait_s")
                head["prestage_wall_s"] = row.get("prestage_wall_s")
                head["prefetch_depth"] = row.get("prefetch_depth")
                head["overlap_efficiency"] = row.get("overlap_efficiency")
        head["restream_h2d_bytes"] = io.get("restream_h2d_bytes")
    for row in io.get("scan_vs_oracle", []):
        head.setdefault("scan_core_speedup", {})[row["strategy"]] = (
            row.get("speedup")
        )
    for row in results.get("scaling") or []:
        head.setdefault("supersteps_per_s", {})[str(row.get("devices"))] = (
            row.get("supersteps_per_s")
        )
        head.setdefault("partition_batched_s", {})[str(row.get("devices"))] = (
            row.get("t_partition_batched_s")
        )
    kernels = results.get("kernels") or {}
    if kernels:
        # Chosen dispatch tier + hot-kernel walls at that tier (the tier
        # ladder replaced unconditional interpret mode; a flip back to a
        # slower tier shows up here and in bench_compare).
        head["kernel_tier"] = kernels.get("kernel_tier")
        head["window_score_wall_s"] = kernels.get("window_score_wall_s")
        head["segment_sum_wall_s"] = kernels.get("segment_sum_wall_s")
    return head


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="fastest possible pass over every bench entrypoint")
    ap.add_argument("--json-dir", default=None,
                    help="write a BENCH_<n>.json machine-readable summary "
                         "into this directory (auto-incrementing n)")
    ap.add_argument("--trace", default=None, metavar="OUT_JSON",
                    help="record a section-level span timeline (repro.obs) "
                         "and write Chrome trace-event JSON here (open in "
                         "https://ui.perfetto.dev)")
    args = ap.parse_args(argv)
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    scale = 0.08 if args.full else 0.012

    import jax

    from repro import compat
    from repro.obs import Tracer, resolve_tracer

    compat.enable_compile_cache()
    # Only a CPU host rehearses device scaling in child processes; on a TPU
    # host this process holds the chip and every bench measures in it.
    rehearse = [] if jax.default_backend() == "tpu" else ["--rehearse"]

    tr = resolve_tracer(Tracer() if args.trace else None)

    def sec(title, name, fn):
        """One bench section: banner + a `bench`-category span around it."""
        print(title)
        with tr.span(name, cat="bench"):
            return fn()

    t0 = time.time()

    from benchmarks import (
        bench_io,
        bench_kernels,
        bench_moe_balance,
        bench_replication,
        bench_restream,
        bench_scaling,
        bench_spotlight,
        bench_total_latency,
        bench_window,
        roofline,
    )

    results: dict = {}
    if args.smoke:
        k = ["--k", "8"]
        results["total_latency"] = sec(
            "=== Fig.7a-f: total latency (smoke) ===", "total_latency",
            lambda: bench_total_latency.main(
                ["--scale", "0.006", *k, "--graphs", "brain_like",
                 "--windows", "8", "--baselines", "dbh"]))
        sec("\n=== Fig.7g-i: replication degree (smoke) ===", "replication",
            lambda: bench_replication.main(
                ["--scale", "0.006", *k, "--graphs", "brain_like"]))
        sec("\n=== re-streaming pass sweep (smoke) ===", "restream",
            lambda: bench_restream.main(
                ["--scale", "0.006", *k, "--graphs", "brain_like",
                 "--passes", "2", "--window", "8"]))
        sec("\n=== Fig.8: spotlight spread sweep (smoke) ===", "spotlight",
            lambda: bench_spotlight.main(["--scale", "0.01", *k, "--z", "4"]))
        results["scaling"] = sec(
            "\n=== multi-device scaling (smoke: N in {1,2}) ===", "scaling",
            lambda: bench_scaling.main(["--smoke", *rehearse]))
        results["io"] = sec(
            "\n=== out-of-core I/O: ingest + ring-buffer partitioning (smoke) ===",
            "io", lambda: bench_io.main(["--smoke"]))
        sec("\n=== §III ablations (smoke) ===", "window",
            lambda: bench_window.main(["--scale", "0.004", *k]))
        sec("\n=== ADWISE-balance MoE routing (smoke) ===", "moe_balance",
            lambda: bench_moe_balance.main(
                ["--steps", "3", "--tokens", "128", "--d", "16"]))
        results["kernels"] = sec(
            "\n=== kernels (smoke) ===", "kernels",
            lambda: bench_kernels.main(["--quick"]))
        sec("\n=== roofline table ===", "roofline", lambda: roofline.main([]))
        print(f"\nsmoke pass over all bench entrypoints done in {time.time()-t0:.0f}s")
    else:
        results["total_latency"] = sec(
            "=== Fig.7a-f: total latency (partition + modeled processing) ===",
            "total_latency",
            lambda: bench_total_latency.main(["--scale", str(scale)]))
        sec("\n=== Fig.7g-i: replication degree per strategy and L ===",
            "replication",
            lambda: bench_replication.main(["--scale", str(scale)]))
        sec("\n=== re-streaming: RD vs pass count (adwise-restream / 2ps) ===",
            "restream",
            lambda: bench_restream.main(["--scale", str(scale / 2)]))
        sec("\n=== Fig.8: spotlight spread sweep ===", "spotlight",
            lambda: bench_spotlight.main(["--scale", str(scale * 1.5)]))
        results["scaling"] = sec(
            "\n=== multi-device scaling: batched spotlight + engine vs N ===",
            "scaling",
            lambda: bench_scaling.main(
                ["--scale", str(scale / 2), "--devices", "1,2,4,8", *rehearse]))
        results["io"] = sec(
            "\n=== out-of-core I/O: ingest MB/s + file vs in-memory wall ===",
            "io", lambda: bench_io.main(["--scale", str(scale)]))
        sec("\n=== §III ablations: window / lazy / clustering / lambda ===",
            "window", lambda: bench_window.main(["--scale", str(scale / 2)]))
        sec("\n=== beyond-paper: ADWISE-balance MoE routing ===", "moe_balance",
            lambda: bench_moe_balance.main(
                ["--steps", "12" if not args.full else "40"]))
        results["kernels"] = sec(
            "\n=== kernels (per-tier wall times, CPU-indicative) ===",
            "kernels",
            lambda: bench_kernels.main(["--quick"] if not args.full else []))
        sec("\n=== roofline table (from dry-run artifact, if present) ===",
            "roofline", lambda: roofline.main([]))
        print(f"\nall benchmarks done in {time.time()-t0:.0f}s")

    if args.trace:
        n_events = tr.export(args.trace)
        print(f"trace: {n_events} events -> {args.trace}")

    if args.json_dir:
        path = _next_bench_path(args.json_dir)
        # Retrace budget over the whole bench pass: how many distinct
        # programs the driver kernels compiled. A jump here without a
        # geometry change is a recompilation regression (the pow2-Rq
        # contract tests/test_compile_budget.py enforces per-run).
        from repro.core.driver import scan_compile_counts

        compiles = scan_compile_counts()
        doc = dict(
            mode="full" if args.full else ("smoke" if args.smoke else "default"),
            wall_s=round(time.time() - t0, 2),
            platform=platform.platform(),
            python=platform.python_version(),
            summary=dict(_summarize(results), jit_scan_compiles=compiles),
            jit_scan_compiles=compiles,
            io=results.get("io"),
            kernels=results.get("kernels"),
            scaling=results.get("scaling"),
            total_latency=results.get("total_latency"),
        )
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, default=str)
        print(f"bench summary -> {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

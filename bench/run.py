#!/usr/bin/env python3
"""On-chip benchmark: one cell of ``BENCHMARK.json`` per process.

    python3 bench/run.py --workload g500s22-k32.adwise --seed 7 \
        --seconds 30 --trace 0

A cell names a configuration (``bench/configs/<name>.json``: the graph, k,
the strategy's settings) and a traffic mix (``bench/traffic/<name>.json``:
which job runs back to back, and the limits of its checks). The mix's
``job`` names the module ``bench/jobs/<job>.py`` that drives the program;
each per-layer metric is read by ``bench/metrics/<metric>.py``. All of them
are found by name, so a cell, a configuration, a mix or a metric is added as
files and entries, never by editing this one.

A run: (1) make the cell's data from ``--seed`` on the device, (2) build
and warm up every program the window will run, (3) run whole jobs for at
most ``--seconds`` (a job starts only while the mean job time so far still
fits), (4) read the device's peak memory, free the program's state and
compare every job's answer with the plain references in
``bench/reference.py``. ``--trace 1`` records a ``jax.profiler`` trace of
the window and reports the cell's per-layer metrics instead of its
end-to-end ones. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: every number compared,
with its limit); the same checks are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the run exits 2
and prints no result. JAX's persistent compilation cache lives in
``<checkout>/runs/bench/jax_cache`` (every program, however quick to
compile, so that only a checkout's first run compiles); generated files and
traces in ``<checkout>/runs/bench/<cell>/``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------------------
# Finding a cell's parts by name
# ----------------------------------------------------------------------------


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything it names, read from the
    files under ``root``."""

    def __init__(self, root: Path, name: str) -> None:
        self.root = Path(root)
        spec = json.loads((self.root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {', '.join(sorted(cells))})")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in spec["configs"]}
        self.config = json.loads(
            (self.root / configs[self.entry["config"]]["file"]).read_text())
        bench = self.root / "bench"
        self.traffic = json.loads(
            (bench / "traffic" / f"{self.entry['traffic']}.json").read_text())
        self.job_path = bench / "jobs" / f"{self.traffic['job']}.py"
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m["workloads"]]
        self.metric_paths = {m["name"]: bench / "metrics" / f"{m['name']}.py"
                             for m in self.per_layer}

    def job_module(self):
        return load_module(self.job_path, f"bench_job_{self.traffic['job']}")

    def reader(self, metric: str):
        return load_module(self.metric_paths[metric],
                           "bench_metric_" + metric.replace(".", "_"))


# ----------------------------------------------------------------------------
# Compile accounting
# ----------------------------------------------------------------------------


class CompileLog:
    """XLA compiles and persistent-cache traffic, counted from
    ``jax.monitoring`` events. ``compile_s`` is the backend-compile wall,
    which includes loading an executable from the persistent cache."""

    DURATION = "/jax/core/compile/backend_compile_duration"
    EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "lookups",
        "/jax/compilation_cache/cache_hits": "hits",
    }

    def __init__(self) -> None:
        import jax

        self.counts = dict(compile_s=0.0, programs=0, lookups=0, hits=0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == self.DURATION:
            self.counts["compile_s"] += secs
            self.counts["programs"] += 1

    def _event(self, event: str, **_) -> None:
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def mark(self) -> dict:
        return dict(self.counts)

    def since(self, mark: dict) -> dict:
        return {key: self.counts[key] - mark[key] for key in mark}


def annotate(name: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# ----------------------------------------------------------------------------
# One run
# ----------------------------------------------------------------------------


def finite(x: float) -> float:
    """JSON has no infinity: a check that could not be computed reads as
    the largest float."""
    return float(x) if math.isfinite(x) else sys.float_info.max


def run_window(job, seconds: float, max_jobs: int | None) -> tuple:
    """Whole jobs back to back: one starts while the mean job time so far
    still fits in ``seconds``. Returns (results, window seconds, error)."""
    results: list = []
    error = None
    with annotate("bench.window"):
        t0 = time.perf_counter()
        try:
            while True:
                elapsed = time.perf_counter() - t0
                if results and (elapsed + elapsed / len(results) > seconds
                                or len(results) == max_jobs):
                    break
                results.append(job.run(annotate))
        except Exception as e:  # a job that fails is counted, not fatal
            error = e
            traceback.print_exc()
        window = time.perf_counter() - t0
    return results, window, error


def peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def main(argv=None, *, root: Path = ROOT, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(root).resolve()
    cell = Cell(root, args.workload)

    # The benchmark's own compile cache, at a fixed path that only it writes.
    cache_dir = root / "runs" / "bench" / "jax_cache"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    workdir = root / "runs" / "bench" / cell.name
    workdir.mkdir(parents=True, exist_ok=True)
    # A kernel-tier table from elsewhere must not steer what runs here.
    os.environ["ADWISE_AUTOTUNE_CACHE"] = str(workdir / "kernel_tiers.json")
    sys.path.insert(0, str(root / "src"))
    sys.path.insert(0, str(root))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        print(f"bench: JAX found no TPU (platform={devices[0].platform!r}); "
              "the benchmark runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    used = devices[: cell.chips]
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clog = CompileLog()
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}, jax {jax.__version__}; cell {cell.name} "
        f"seed {args.seed}")

    job = cell.job_module().Job(cell.config, cell.traffic, args.seed, workdir)
    mark = clog.mark()
    phases = job.setup(annotate)
    t0 = time.perf_counter()
    job.run(annotate)  # one whole job compiles and warms every program
    phases["warmup_s"] = time.perf_counter() - t0
    comp = clog.since(mark)
    setup_s = time.perf_counter() - T_START
    log("setup: " + ", ".join(f"{k} {v:.4f} s" for k, v in phases.items())
        + f"; XLA compile or cache load {comp['compile_s']:.4f} s over "
        f"{comp['programs']} programs ({comp['hits']} of {comp['lookups']} "
        f"from the persistent cache); setup_s {setup_s:.4f}")

    trace_dir = workdir / "trace"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    mark = clog.mark()
    max_jobs = cell.traffic.get("trace_jobs") if args.trace else None
    results, window_s, error = run_window(job, args.seconds, max_jobs)
    if args.trace:
        jax.profiler.stop_trace()
    in_window = clog.since(mark)["programs"]
    log(f"window: {len(results)} jobs in {window_s:.4f} s; "
        f"compiles in the window: {in_window}")
    for i, r in enumerate(results):
        log(f"job {i}: wall {r['wall_s']:.6f} s, {r['work']} {job.work_unit}")

    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices), memory_peak_bytes=peak_bytes(used))
    correct = error is None and bool(results)
    e2e = {}
    if results:
        try:
            e2e = job.end_to_end(results)
        except Exception:  # an answer that cannot be measured is not correct
            traceback.print_exc()
            correct = False
    e2e["setup_s"] = setup_s
    job.release()

    checks: dict = {}
    failed = int(error is not None)
    if results:
        try:
            checks, bad_jobs = job.checks(results)
            failed += bad_jobs
        except Exception:  # a comparison that cannot run is not correct
            traceback.print_exc()
            correct = False
            failed = len(results)
    correct = correct and all(c["value"] <= c["limit"] for c in checks.values())

    out = dict(correct=correct, attempted=len(results) + int(error is not None),
               failed=failed)
    if args.trace:
        from bench import trace as trace_mod

        path = trace_mod.find_xplane(trace_dir)
        t0 = time.perf_counter()
        summary = trace_mod.reduce_file(path, len(used))
        log(f"trace: {os.path.getsize(path)} bytes, {summary['device_events']} "
            f"device events, read in {time.perf_counter() - t0:.3f} s; busy "
            f"{summary['busy_s']:.6f} s of {summary['window_s']:.6f} s; "
            f"programs: " + ", ".join(
                f"{n} {t:.6f} s x{summary['module_runs'][n]}"
                for n, t in sorted(summary["modules"].items(),
                                   key=lambda kv: -kv[1])[:8]))
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        ctx = dict(results=results, trace=summary, config=cell.config,
                   device=device)
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
        out.update(metrics=metrics, device=device,
                   breakdown=dict(device_ops=summary["device_ops"],
                                  idle_gaps=summary["idle_gaps"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        out.update(metrics={name: dict(value=e2e[name], unit=units[name])
                            for name in units if name in e2e},
                   device=device)
    out["checks"] = {name: dict(value=finite(c["value"]), limit=c["limit"])
                     for name, c in checks.items()}
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

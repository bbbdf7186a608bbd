#!/usr/bin/env bash
# CI gate: static trace-contract checks, type check, tier-1 quick test
# profile, and the smoke pass over every benchmark entrypoint (proves each
# bench still *runs*; regressions in launch/bench wiring fail here, not in
# a nightly).
#
#   tools/ci.sh          # what the workflow runs
#   tools/ci.sh --full   # also run the slow-marked tests
#
# Runs under `set -euo pipefail` end-to-end: every step below must succeed
# or the script dies there — no failing checker/bench can be masked by a
# later successful command (note the nullglob arrays for BENCH counting:
# `ls ... | wc -l` would abort the script on an empty dir under pipefail).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

MARK='not slow'
if [[ "${1:-}" == "--full" ]]; then
  MARK=''
fi

# Trace-contract checker. Self-test FIRST: every fixture must trip its
# rule, so a silently-broken checker fails CI before it can wave the repo
# through. Then the repo gate: zero unsuppressed findings over src/ and
# tools/ (fixtures excluded by the engine; the shipped baseline is empty —
# intentional violations carry inline justifications instead).
python -m tools.staticcheck --selftest
python -m tools.staticcheck src tools --baseline tools/staticcheck/baseline.json

# Strict type check on the trace-contract surface (core/types.py +
# core/driver.py, per mypy.ini). The workflow installs mypy; bare
# containers without it skip rather than mask the rest of the gate.
if python -c "import mypy" >/dev/null 2>&1; then
  python -m mypy --config-file mypy.ini
else
  echo "mypy not installed: skipping type check (workflow installs it)"
fi

if [[ -n "$MARK" ]]; then
  python -m pytest -x -q -m "$MARK"
else
  python -m pytest -x -q
fi

# Refill-pipeline matrix: the driver/ring suite must hold bit-parity and
# its h2d accounting at BOTH ends of the prefetch knob — 0 (synchronous
# escape hatch) and 2 (the double-buffered default) — whatever the
# environment's ADWISE_PREFETCH happens to be.
ADWISE_PREFETCH=0 python -m pytest -x -q tests/test_driver.py
ADWISE_PREFETCH=2 python -m pytest -x -q tests/test_driver.py

# Kernel-tier matrix: the kernel suite must hold numeric parity at BOTH a
# pinned xla tier (the env override escape hatch, bit-stable everywhere)
# and the autotuned default this host resolves — whichever tier that is,
# it is never interpret (asserted inside the suite).
ADWISE_KERNEL_TIER=xla python -m pytest -x -q tests/test_kernels.py
python -m pytest -x -q tests/test_kernels.py

# The smoke pass also writes a machine-readable BENCH_<n>.json into
# bench_logs/ (kept / uploaded as a CI artifact), so the perf trajectory —
# partition walls, h2d stream traffic, ingest MB/s, scan-core speedups,
# supersteps/s, jit compile counts — is tracked run over run instead of
# scrolling away in logs.
shopt -s nullglob
BENCH_BEFORE=(bench_logs/BENCH_*.json)
python -m benchmarks.run --smoke --json-dir bench_logs
BENCH_AFTER=(bench_logs/BENCH_*.json)
shopt -u nullglob
if (( ${#BENCH_AFTER[@]} <= ${#BENCH_BEFORE[@]} )); then
  echo "FATAL: benchmarks.run --json-dir bench_logs produced no new" \
       "BENCH_<n>.json (before=${#BENCH_BEFORE[@]} after=${#BENCH_AFTER[@]})" >&2
  exit 1
fi

# Bench regression gate: diff the two newest BENCH summaries. Warn-only on
# this shared box — smoke-scale walls are noisy — but the report lands in
# the log and a pinned perf runner can make it binding by dropping the ||.
python -m tools.bench_compare bench_logs \
  || echo "WARN: bench_compare flagged a wall regression (warn-only here)"

# Multi-device path: batched spotlight (shard_map over instances) + padded
# engine mesh on 2 fake CPU devices, every run (bench_scaling measures in
# this process, over N in {1,2} of its devices).
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
  python -m benchmarks.bench_scaling --smoke

# Ring-buffer smoke: text ingest (bytes vs python parser parity) -> binary
# -> file-driven partitioning in a tmpdir. Asserted inside: bit-parity with
# the in-memory path, h2d_rows == m (each stream row ships to the device
# once), and per-scan-call h2d below a full ring re-upload.
python -m benchmarks.bench_io --smoke

# Step-core spotlight smoke on 2 fake CPU devices: hdrf z=4 through the
# file-driven ring buffer (one batched program over the instances), asserted
# bit-identical to the in-memory spotlight — mirrors the bench_scaling
# spotlight smoke for the baseline step-cores.
XLA_FLAGS="--xla_force_host_platform_device_count=2" python - <<'PY'
import os, tempfile
import numpy as np
import jax
assert jax.device_count() >= 2, jax.devices()
from repro.core import partition_file
from repro.core.spotlight import spotlight_partition
from repro.graph import rmat
from repro.graph.io import EdgeFileReader, write_edge_file

edges, n = rmat(10, 4000, seed=0)
with tempfile.TemporaryDirectory() as td:
    path = os.path.join(td, "g.adw")
    write_edge_file(path, edges, n)
    with EdgeFileReader(path) as r:
        res = partition_file(r, "hdrf", 8, z=4, spread=2, seed=0,
                             chunk_edges=1024, spill_dir=td)
    ref = spotlight_partition(edges, n, 8, z=4, spread=2, seed=0,
                              strategy="hdrf")
    assert (np.asarray(res.assign) == ref.assign).all(), (
        "2-device file-driven hdrf spotlight diverged from in-memory")
    print("2-device hdrf z=4 partition_file smoke OK "
          f"({res.stats['name']}, backend={res.stats.get('backend')}, "
          f"devices={jax.device_count()})")

# Slab-balanced engine placement: k=7 on 2 devices pads to 8 slabs, and
# make_superstep spreads the pad so per-device REAL slab counts differ by
# at most one ((4, 3), not tail-padded (4, 4-with-1-pad-heavy)).
from repro.engine import build_partitioned_graph
from repro.engine.gas import engine_mesh, make_superstep
g = build_partitioned_graph(edges, ref.assign % 7, n, 7)
step = make_superstep(g, lambda xu, xv, du, dv: (xu, xv),
                      lambda s, a, d: s, engine_mesh(k=7))
occ = step.slab_occupancy
assert sum(occ) == 7 and max(occ) - min(occ) <= 1, occ
print(f"2-device slab placement OK: occupancy={occ}")
PY

# Traced pipeline smoke: drive the real launcher CLI with --trace over a
# file-driven hdrf z=2 run, then validate the emitted Chrome trace-event
# JSON (schema + globally monotonic ts) and the contract that makes the
# timeline trustworthy: scan-span count == scan_calls, and both the main
# stepping track and the adwise-readahead worker track are present. The
# trace is kept in bench_logs/ and uploaded as a CI artifact next to the
# BENCH summaries.
python - <<'PY'
import json, os, tempfile
import numpy as np
from repro.graph import rmat
from repro.graph.io import write_edge_file
from repro.launch import partition as launch
from repro.obs import validate_chrome_trace

os.makedirs("bench_logs", exist_ok=True)
trace_path = "bench_logs/trace_smoke.json"
edges, n = rmat(10, 4000, seed=0)
with tempfile.TemporaryDirectory() as td:
    path = os.path.join(td, "g.adw")
    write_edge_file(path, edges, n)
    out = launch.main([
        "--graph", path, "--strategy", "hdrf", "--k", "8",
        "--z", "2", "--spread", "4", "--chunk-edges", "1024",
        "--prefetch", "2", "--workload", "none",
        "--trace", trace_path,
    ])
doc = json.load(open(trace_path))
errs = validate_chrome_trace(doc)
assert not errs, f"invalid chrome trace: {errs[:5]}"
scan_spans = [e for e in doc["traceEvents"]
              if e.get("ph") == "X" and e.get("cat") == "scan"]
scan_calls = int(out["stats"].get("scan_calls", 0))
assert scan_calls and len(scan_spans) == scan_calls, (
    f"scan span count {len(scan_spans)} != scan_calls {scan_calls}")
tracks = {e["args"]["name"] for e in doc["traceEvents"]
          if e.get("ph") == "M" and e.get("name") == "thread_name"}
assert "main" in tracks and "adwise-readahead" in tracks, tracks
print(f"traced smoke OK: {len(doc['traceEvents'])} events, "
      f"{scan_calls} scan spans, tracks={sorted(tracks)} -> {trace_path}")
PY

echo "bench summaries kept:"
ls -l bench_logs/ 2>/dev/null || true

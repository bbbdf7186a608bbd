"""The harness on the CPU: cells found by name from their files, a new cell
or metric picked up from new files alone, no run without a TPU, a
well-formed last line from a tiny rehearsal, and the roofline's byte count
a lower bound of what the engine moves."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import REPO

from bench.run import Cell


def spec() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in spec()["workloads"]])
def test_cell_found_by_name(name):
    cell = Cell(REPO, name)
    assert cell.job_path.is_file()
    assert cell.config["k"] in (32, 128)
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for metric in cell.per_layer:
        assert callable(cell.reader(metric["name"]).read)


def test_new_cell_and_metric_are_files_only(tmp_path):
    """A cell with its own traffic mix and a per-layer metric with its own
    reader join the benchmark as new files plus entries in BENCHMARK.json;
    no file of the harness changes."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*.py")}
    s = spec()
    mix = json.loads((REPO / "bench/traffic/adwise.json").read_text())
    mix["trace_jobs"] = 2
    (tmp_path / "bench/traffic/adwise-trace2.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/scan.calls_per_job.py").write_text(
        "def read(ctx):\n"
        "    r = ctx['results']\n"
        "    return sum(x['stats']['scan_calls'] for x in r) / len(r)\n")
    s["workloads"].append(dict(name="g500s22-k32.adwise-trace2",
                               config="g500s22-k32", traffic="adwise-trace2",
                               chips=1, why="test"))
    s["per_layer"].append(dict(name="scan.calls_per_job", unit="calls",
                               better="lower", source="program_counter",
                               layer="ring scan", moves="partition_eps",
                               workloads=["g500s22-k32.adwise-trace2"]))
    for m in s["end_to_end"]:
        if m["name"] in ("partition_eps", "replication_degree"):
            m["workloads"].append("g500s22-k32.adwise-trace2")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))

    cell = Cell(tmp_path, "g500s22-k32.adwise-trace2")
    assert cell.traffic["trace_jobs"] == 2
    assert [m["name"] for m in cell.per_layer] == ["scan.calls_per_job"]
    ctx = dict(results=[dict(stats=dict(scan_calls=2))] * 3)
    assert cell.reader("scan.calls_per_job").read(ctx) == 2
    assert {m["name"] for m in cell.end_to_end} == {
        "partition_eps", "replication_degree", "setup_s"}
    assert "scan.calls_per_job" not in {
        m["name"] for m in Cell(tmp_path, "g500s22-k32.adwise").per_layer}
    assert before == {p: p.read_bytes()
                      for p in (tmp_path / "bench").rglob("*.py")
                      if p in before}


def last_json_line(stdout: str):
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    return json.loads(lines[-1]) if lines else None


def cpu_env() -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def test_run_without_tpu_exits_nonzero():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500s22-k32.adwise",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    code = ("import sys; from pathlib import Path; sys.path.insert(0, '.'); "
            "from bench import run; sys.exit(run.main(sys.argv[1:], "
            "root=Path('.'), allow_cpu=True))")
    proc = subprocess.run(
        [sys.executable, "-c", code, "--workload", "g500s22-k32.adwise",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def rehearse(root: Path, cell: str, trace: int, seed: int = 2**31 + 11):
    code = ("import sys; from pathlib import Path; sys.path.insert(0, '.'); "
            "from bench import run; sys.exit(run.main(sys.argv[1:], "
            "root=Path('.'), allow_cpu=True))")
    return subprocess.run(
        [sys.executable, "-c", code, "--workload", cell, "--seed", str(seed),
         "--seconds", "1", "--trace", str(trace)],
        cwd=root, env=cpu_env(), capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,e2e", [
    ("tiny.adwise", {"partition_eps", "replication_degree", "setup_s"}),
    ("tiny.pagerank", {"superstep_ms", "setup_s"}),
])
def test_cpu_rehearsal_last_line(tiny_root, cell, e2e, trace):
    proc = rehearse(tiny_root, cell, trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = last_json_line(proc.stdout)
    assert list(out)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name
        assert f"check {name}: " in proc.stderr
    assert proc.stderr.rstrip().splitlines()[-1].startswith("check ")
    if trace:
        # No device plane on the CPU: device metrics are left out, and
        # only the host counter of the file ring can be read.
        assert set(out["metrics"]) <= {"ring.h2d_wait_ms"}
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
        assert out["device"]["window_s"] > 0
    else:
        assert set(out["metrics"]) == e2e
        assert all(m["value"] > 0 for m in out["metrics"].values())
    assert "compiles in the window: 0" in proc.stdout


def test_pagerank_bytes_are_a_lower_bound():
    """The roofline's least bytes per superstep are no more than what the
    engine's own arrays hold: the edge slabs it reads and the rank vector it
    reads and writes."""
    import numpy as np

    from bench import graphgen
    from bench.roofline import pagerank_superstep_bytes
    from repro.core import run_partitioner
    from repro.engine import build_partitioned_graph

    graph = dict(scale=10, edge_factor=16, a=0.57, b=0.19, c=0.19)
    edges, n = graphgen.kronecker(graph, seed=3)
    for k in (4, 32):
        assign = run_partitioner("dbh", edges, n, k, seed=0).assign
        g = build_partitioned_graph(edges, assign, n, k)
        engine = g.edges.nbytes + 2 * n * np.dtype(np.float32).itemsize
        assert pagerank_superstep_bytes(len(edges), n) <= engine


def test_unknown_device_kind_has_no_peak():
    from bench.roofline import peak

    assert peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peak("TPU v9 imaginary", "hbm_bytes_per_s")

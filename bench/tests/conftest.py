"""Shared fixtures: a copy of the benchmark with tiny configurations, which
runs on the CPU in seconds."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "src"))
sys.path.insert(0, str(REPO))

TINY = dict(scale=10, k=4, chunk_edges=512)


def make_tiny_root(dest: Path) -> Path:
    """``dest`` with ``bench/`` copied, ``src`` linked, and a
    ``BENCHMARK.json`` whose cells run the real configurations' settings on
    a 2**10-vertex graph at k=4."""
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (dest / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "bench/configs/g500s22-k32.json").read_text())
    n = 1 << TINY["scale"]
    cfg.update(name="tiny", k=TINY["k"], chunk_edges=TINY["chunk_edges"],
               partition_job_edges=2 * (TINY["chunk_edges"]
                                        - cfg["adwise"]["window_max"]))
    cfg["graph"].update(scale=TINY["scale"], num_vertices=n,
                        num_edges=16 * n)
    (dest / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    spec["configs"] = [dict(spec["configs"][0], name="tiny",
                            file="bench/configs/tiny.json")]
    spec["workloads"] = [
        dict(name=f"tiny.{t}", config="tiny", traffic=t, chips=1, why=t)
        for t in ("adwise", "pagerank")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [f"tiny.{w.split('.', 1)[1]}"
                              for w in m["workloads"] if "k32" in w]
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)

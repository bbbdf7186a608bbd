"""Work counts from shapes, and the chip peaks they are held against."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peak(device_kind: str, key: str) -> float:
    """A peak of ``device_kind`` from ``peaks.json``; a kind that is not in
    the table is an error, not a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}")
    return float(table[device_kind][key])


def pagerank_superstep_bytes(num_edges: int, num_vertices: int) -> int:
    """Least HBM traffic of one PageRank superstep: every edge read once
    (two int32 ids) and the float32 rank vector read and written once. It
    counts the graph, not any engine's arrays, so an engine with sparser
    arrays is held to the same work."""
    return 8 * num_edges + 8 * num_vertices

"""Vertex-cut graph processing engine (shard_map GAS + workloads + cost model)."""
from repro.engine.partitioned import PartitionedGraph, build_partitioned_graph
from repro.engine.gas import engine_mesh, make_superstep
from repro.engine.algorithms import (
    pagerank,
    pagerank_superstep,
    label_propagation,
    coloring,
    triangle_count,
)
from repro.engine.latency_model import (
    ClusterProfile,
    PAPER_CLUSTER,
    TPU_POD,
    partition_latency,
    process_latency,
)

__all__ = [
    "PartitionedGraph",
    "build_partitioned_graph",
    "engine_mesh",
    "make_superstep",
    "pagerank",
    "pagerank_superstep",
    "label_propagation",
    "coloring",
    "triangle_count",
    "ClusterProfile",
    "PAPER_CLUSTER",
    "TPU_POD",
    "partition_latency",
    "process_latency",
]

"""Graph substrate: synthetic generators, edge streams, storage, metrics."""
from repro.graph.generate import (
    barabasi_albert,
    erdos_renyi,
    kronecker,
    rmat,
    watts_strogatz,
    make_graph,
    GRAPH_PRESETS,
)
from repro.graph.stream import EdgeStream
from repro.graph.metrics import (
    replication_degree,
    partition_balance,
    partition_sizes,
    quality_from_chunks,
    replica_sets_from_assignment,
    replica_sets_from_chunks,
    sync_volume,
    unassigned_count,
)

__all__ = [
    "barabasi_albert",
    "erdos_renyi",
    "kronecker",
    "rmat",
    "watts_strogatz",
    "make_graph",
    "GRAPH_PRESETS",
    "EdgeStream",
    "replication_degree",
    "partition_balance",
    "partition_sizes",
    "quality_from_chunks",
    "replica_sets_from_assignment",
    "replica_sets_from_chunks",
    "sync_volume",
    "unassigned_count",
]

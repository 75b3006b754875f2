"""Graph substrate: device COO/CSR structures, segment-op message passing,
stream generators, neighbor sampling, and mesh partitioning."""

from .generators import DATASET_STATS, TxStream, make_power_law_graph, make_transaction_stream
from .sampler import CSRNeighbors, SampledBlock, build_csr_neighbors, sample_fanout
from .segment_ops import (
    PaddedCSR,
    build_padded_csr,
    embedding_bag,
    gather_scatter_sum,
    segment_max,
    segment_mean,
    segment_softmax,
    segment_sum,
)
from .structs import (
    DeviceGraph,
    WindowExpiry,
    append_edges,
    csr_sort,
    device_graph_from_coo,
    expire_window,
    expiring_edges,
    remove_edges,
)

__all__ = [
    "DeviceGraph",
    "device_graph_from_coo",
    "append_edges",
    "remove_edges",
    "WindowExpiry",
    "expiring_edges",
    "expire_window",
    "csr_sort",
    "segment_sum",
    "segment_mean",
    "segment_max",
    "segment_softmax",
    "gather_scatter_sum",
    "embedding_bag",
    "PaddedCSR",
    "build_padded_csr",
    "TxStream",
    "make_transaction_stream",
    "make_power_law_graph",
    "DATASET_STATS",
    "CSRNeighbors",
    "SampledBlock",
    "build_csr_neighbors",
    "sample_fanout",
]

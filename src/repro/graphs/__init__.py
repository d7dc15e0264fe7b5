from .containers import (  # noqa: F401
    ArrayEdgeSource,
    ChunkedEdgeSource,
    CompressedEdgeBlocks,
    Graph,
    build_graph,
    components_oracle,
    compress_edges,
    compress_graph,
    edges_oracle,
    graph_spec,
    open_edge_file,
    sort_dedup_edges,
    write_edge_file,
)
from .ingest import IngestResult, ingest_chunks  # noqa: F401
from . import generators  # noqa: F401

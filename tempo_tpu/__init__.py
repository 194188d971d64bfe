"""tempo_tpu — a TPU-native distributed tracing backend.

A brand-new framework with the capabilities of Grafana Tempo (reference:
/root/reference), re-architected for JAX/XLA on TPU rather than ported from Go:

- multi-tenant OTLP ingest (distributor → ingester / metrics-generator)
- object-storage columnar trace blocks (parquet, vparquet4-inspired schema)
- TraceQL query language: search and metrics (`quantile_over_time` etc.)
- streaming metrics-generator: span RED metrics, service graphs, local blocks,
  Prometheus remote write
- compaction, blocklist polling, scatter-gather query federation

The numeric planes — metric aggregation registries, latency-quantile /
cardinality / heavy-hitter sketches, and TraceQL metrics aggregation — run as
fused XLA programs over padded span-attribute tensors (structure-of-arrays
`SpanBatch`), sharded over `jax.sharding.Mesh` device meshes with collective
merges (psum / pmax). CPU-side services retain protocol, sharding, and storage
orchestration roles.

Layer map (mirrors SURVEY.md §1 for the reference):

    ops/        sketch + hash kernels (JAX/XLA)              <- TPU compute
    model/      wire model, SpanBatch span tensors, interning
    registry/   metric series state on device (counter/gauge/histogram)
    generator/  metrics-generator service + processors
    traceql/    TraceQL lexer/parser/engines
    storage/    backends, block encodings, WAL, blocklist, compaction
    parallel/   mesh construction, sharded pipelines, collectives
    distributor/ ingester/ querier/ frontend/ compactor/  CPU service modules
    api/ app/ cli/  HTTP surface, module wiring, operator tools
"""

__version__ = "0.1.0"

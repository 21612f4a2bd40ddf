"""greptimedb_tpu — a TPU-native time-series / analytics database framework.

A ground-up rebuild of the capabilities of GreptimeDB v0.2.0 (reference:
iamazy/greptimedb, surveyed in SURVEY.md), designed TPU-first:

- columnar LSM storage engine: WAL + SoA memtable buffers + Parquet SSTs
  (reference: src/storage)
- scan / filter / group-by-tag / time-bucket aggregation, window functions
  (rate, *_over_time), merge+dedup, and compaction downsampling execute as
  JAX/XLA kernels (pjit/vmap/shard_map over device meshes)
- SQL and PromQL front ends, HTTP/MySQL/gRPC protocol servers
- standalone-to-distributed frontend/datanode/meta architecture

The compute path is JAX (jit/XLA); the host path (WAL, catalog, routing,
object-store I/O) is Python/C++ and never touches the accelerator.
"""

__version__ = "0.1.0"

DEFAULT_CATALOG_NAME = "greptime"
DEFAULT_SCHEMA_NAME = "public"
MITO_ENGINE = "mito"

"""tpu-skyline: a TPU-native framework for distributed streaming skyline queries.

Re-implements the capability surface of the Flink/Kafka reference system
(Asterinos1/Flink-Skyline-QoS — see SURVEY.md) as an idiomatic JAX/XLA/Pallas
design: windowed micro-batches become ``(N, d)`` tensors, per-partition
dominance testing runs as tiled dominance-bitmask kernels, and local skylines
are merged into the global skyline by on-chip collectives over a
``jax.sharding.Mesh``.

Subpackage map (reference parity noted per SURVEY.md §2):

- ``ops``       — dominance predicate + skyline kernels (replaces the JVM BNL
                  hot loop, FlinkSkyline.java:417-444 / ServiceTuple.java:67-77)
- ``parallel``  — MR-Dim / MR-Grid / MR-Angle partitioners (FlinkSkyline.java:669-877)
                  and the sharded two-phase local/global skyline over a TPU mesh
                  (replaces keyBy shuffle + GlobalSkylineAggregator)
- ``stream``    — windowing, record-id query barrier, streaming engine
                  (SkylineLocalProcessor semantics, FlinkSkyline.java:214-445)
- ``bridge``    — Kafka/in-memory transport plane + the skyline worker
                  (FlinkSkyline.java:84-97,177-183 Kafka I/O)
- ``workload``  — synthetic generators + producer/trigger CLIs
                  (python/unified_producer.py, kafka_producer.py, query_trigger.py)
- ``metrics``   — result-JSON → CSV collector + phase tracing
                  (python/metrics_collector.py; FlinkSkyline.java timing fields)
- ``plots``     — figure tools (python/graph_*.py)
- ``utils``     — config, padding/bucketing, checkpointing
"""

__version__ = "0.1.0"


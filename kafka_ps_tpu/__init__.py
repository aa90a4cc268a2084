"""kafka_ps_tpu — a TPU-native streaming parameter-server framework.

A from-scratch JAX/XLA re-design of the capability set of
Parameter-Server-Architecture-On-Apache-Kafka (HPI research prototype,
reference at /root/reference): streaming ingestion with rate pacing,
per-worker dynamic sliding data buffers, k-step local training with
delta exchange, central aggregation under three consistency models
(sequential/BSP, bounded-delay/SSP, eventual/ASP) gated by per-worker
vector clocks, and continuous test-set evaluation with CSV metric logs.

The Kafka fabric of the reference (three topics: INPUT_DATA,
WEIGHTS_TOPIC, GRADIENTS_TOPIC — reference BaseKafkaApp.java:27-33) is
replaced by TPU-native transports: `shard_map` + `psum` collectives over
an ICI device mesh for the synchronous path, and host-orchestrated
async dispatch with per-device `device_put` for the stale paths.

Package layout:
  models/    LR model family, metrics (the reference's ml/ package)
  parallel/  mesh, collectives, consistency gating, vector-clock tracker
  data/      paced stream producer + dynamic sliding buffers (producer/)
  runtime/   server/worker processors, in-process fabric, apps (processors/, apps/)
  utils/     config, CSV logging, checkpointing (improvement over reference)
  cli/       runner entry points mirroring ServerAppRunner/WorkerAppRunner
"""

__version__ = "0.1.0"

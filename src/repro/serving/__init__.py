"""High-throughput serving: the request-driven execution path.

``Workload.predict`` (core/mlalgos) is the forward pass;
:class:`PredictRunner` compiles it once per (workload, bucket,
precision) behind a pad-to-bucket ladder with double-buffered staging; :class:`ModelRegistry` versions checkpointed states behind an
atomic hot-swap; :class:`MicroBatchQueue` coalesces single-row requests
into bucket-sized micro-batches under a max-wait deadline with
backpressure and per-request latency accounting.  See
docs/ARCHITECTURE.md §Serving.
"""

from repro.serving.queue import Backpressure, MicroBatchQueue
from repro.serving.registry import ModelRegistry
from repro.serving.runner import DEFAULT_BUCKETS, PredictRunner

__all__ = ["Backpressure", "DEFAULT_BUCKETS", "MicroBatchQueue",
           "ModelRegistry", "PredictRunner"]

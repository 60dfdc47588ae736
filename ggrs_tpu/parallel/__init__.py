"""TPU parallelism strategies.

The reference is a single-threaded Rust library with no parallelism at all
(SURVEY §2, parallelism note).  The TPU build's first-class axes are new
design, not ports:

- **temporal** — the rollback replay as ``lax.scan`` (ggrs_tpu.ops.replay);
- **session** — ``shard_map`` batching of many independent sessions across a
  device mesh with ICI collectives for global health counters (``batch``),
  plus massed request fulfillment for LIVE heterogeneous sessions — B
  networked sessions' per-tick request lists executed as one predicated
  device program (``session_pool``), with the HOST half of the same tick —
  protocol + sync mechanism for all B sessions — stepped in one native
  crossing (``host_bank``; ``HostedPool`` pairs the two);
- **player/entity** — vectorization inside one state pytree (the games do
  this by construction, e.g. BoxGame's (P, ...) arrays).
"""

from .batch import (
    BatchedSessions,
    HOST_AXIS,
    SESSION_AXIS,
    make_distributed_mesh,
    make_mesh,
    make_mesh2d,
)
from .session_pool import BatchedRequestExecutor, HostedPool
from .host_bank import HostSessionPool

__all__ = [
    "BatchedRequestExecutor",
    "HostSessionPool",
    "HostedPool",
    "BatchedSessions",
    "HOST_AXIS",
    "SESSION_AXIS",
    "make_distributed_mesh",
    "make_mesh",
    "make_mesh2d",
]

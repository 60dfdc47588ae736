"""Cross-cutting utilities: tracing/profiling, logging, durable checkpoints.

The reference uses the ``tracing`` crate for protocol/session debug output
(SURVEY §5; /root/reference/src/network/protocol.rs, tracing calls
throughout).  The TPU equivalents here are Python ``logging`` for the host
path plus ``jax.profiler`` trace annotations around device dispatches so the
fused replay shows up as named spans in TensorBoard/Perfetto profiles.
``checkpoint`` adds the disk persistence the reference's in-memory
save/load ring lacks (device sessions expose it as
``save_checkpoint``/``load_checkpoint``).
"""

from .checkpoint import load_pytree, save_pytree
from .tracing import enable_tracing, get_logger

__all__ = [
    "enable_tracing",
    "get_logger",
    "load_pytree",
    "save_pytree",
]

"""Tracing and logging.

Host-side events (protocol state changes, rollback decisions, oversized
packets) log through the ``ggrs_tpu`` logger hierarchy — the analog of the
reference's ``tracing`` crate spans (e.g. rollback decisions at
/root/reference/src/sessions/p2p_session.rs:679-682, packet warnings at
/root/reference/src/network/udp_socket.rs:54-59).  Timed spans, and their
place in ``jax.profiler`` traces, are ``ggrs_tpu.obs.trace``'s.
"""

from __future__ import annotations

import logging

_ROOT = "ggrs_tpu"


def get_logger(name: str = "") -> logging.Logger:
    """Logger under the ``ggrs_tpu`` hierarchy (e.g. ``get_logger("net")``)."""
    return logging.getLogger(f"{_ROOT}.{name}" if name else _ROOT)


def enable_tracing(level: int = logging.DEBUG) -> None:
    """Opt-in console tracing, the analog of installing the reference
    examples' FmtSubscriber (/root/reference/examples/ex_game/ex_game_p2p.rs:37-44)."""
    logger = get_logger()
    if not logger.handlers:
        handler = logging.StreamHandler()
        handler.setFormatter(
            logging.Formatter("%(asctime)s %(name)s %(levelname)s %(message)s")
        )
        logger.addHandler(handler)
    logger.setLevel(level)

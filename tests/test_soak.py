"""Soak tier: the failure modes this hunts — ring /
watermark drift, unbounded queue growth under asymmetric loss, checksum-
history aliasing after frame wrap — only surface at 10^5+ frames, a horizon
the reference's tests never reach (/root/reference/tests/test_p2p_session.rs
runs hundreds of frames).

The harnesses (``p2p_soak`` / ``pool_soak``) live in tests/soak_harness.py and
assert convergence themselves.  Tiers:

  - test_p2p_soak_100k_frames: two peers over the seeded fault net for 1e5
    frames with desync detection on; bit-exact convergence at every settled
    frame, bounded send queues / event queues / checksum history / digest
    backlog, bounded RSS growth.  Crosses the 128-slot input-queue ring
    ~780x and the 32-entry checksum history cap ~60x.
  - test_pool_soak_wraparound: 8 pooled sessions (4 matches) for 2e4 device
    ticks — ~156 input-ring wraps per queue.

Both are marked ``soak`` — deselect with ``-m "not soak"`` when iterating.
"""

from __future__ import annotations

import pytest

from soak_harness import p2p_soak, pool_soak

pytestmark = pytest.mark.soak


def _bounded_growth_invariants(sessions, digests) -> None:
    for s in sessions:
        for ep in s._remote_endpoints:
            assert ep._core.pending_len() <= 128 + 16, "send queue grew"
            assert len(ep.pending_checksums) <= 32, (
                "checksum history grew past its cap"
            )
        assert len(s._event_queue) <= 100, "session event queue grew"
    for d in digests:
        assert len(d) < 1200, "digest backlog grew (stalled peer?)"


def test_p2p_soak_100k_frames():
    stats = p2p_soak(100_000, periodic=_bounded_growth_invariants)
    # convergence and horizon asserts live inside the harness; pin the
    # test-tier extras here
    assert stats["desyncs"] == 0
    assert stats["compared"] > 50_000
    assert stats["rss_drift_mb"] < 64.0, (
        f"RSS grew {stats['rss_drift_mb']:.0f} MiB in the second half"
    )


def test_pool_soak_wraparound():
    stats = pool_soak(20_000)
    assert stats["sessions"] == 8
    assert stats["ring_wraps"] >= 156

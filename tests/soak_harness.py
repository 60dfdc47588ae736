"""The soak harnesses: long-horizon drives shared by ``tests/test_soak.py``.

``p2p_soak`` is the two-peer harness over the seeded fault net;
``pool_soak`` the pooled-hosting one (one ``BatchedRequestExecutor`` for
2·n sessions).  Both assert convergence themselves and return their
statistics; the tests pin the tier's extras on top.
"""

from __future__ import annotations

import random
import time
import zlib

import numpy as np

from ggrs_tpu.games import BoxGame, boxgame_config


def _match_population(n_matches: int):
    """The soak's match population: yields ``(builder, socket, schedule)``
    per session — names, rng seeds and input schedules."""
    import random

    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.sessions import SessionBuilder

    net = InMemoryNetwork()
    for m in range(n_matches):
        names = (f"A{m}", f"B{m}")
        for me in (0, 1):
            b = (
                SessionBuilder(boxgame_config())
                .with_clock(lambda: 0)
                .with_rng(random.Random(3 + 5 * m + me))
                .add_player(Local(), me)
                .add_player(Remote(names[1 - me]), 1 - me)
            )
            yield (
                b,
                net.socket(names[me]),
                lambda i, m=m, me=me: ((i + 2 * m + me) // (2 + m % 3)) % 16,
            )


def _build_matches(n_matches: int):
    """The per-session form of ``_match_population``: started P2PSessions."""
    sessions, schedules = [], []
    for b, sock, sched in _match_population(n_matches):
        sessions.append(b.start_p2p_session(sock))
        schedules.append(sched)
    return sessions, schedules


def _pooled_matches_setup(n_matches: int):
    """n_matches 2-peer BoxGame matches over one in-memory net with ONE
    BatchedRequestExecutor fulfilling all 2·n sessions.  Returns
    (sessions, schedules, pool)."""
    from ggrs_tpu.parallel import BatchedRequestExecutor

    game = BoxGame(2)

    def to_arr(pairs):
        return np.asarray([p[0] for p in pairs], np.uint8)

    sessions, schedules = _build_matches(n_matches)
    pool = BatchedRequestExecutor(
        game.advance, game.init_state(), to_arr,
        batch_size=len(sessions), ring_length=10, max_burst=9,
        with_checksums=False,
    )
    pool.warmup(np.zeros((2,), np.uint8))
    return sessions, schedules, pool


def p2p_soak(frames: int, periodic=None) -> dict:
    """The long-horizon two-peer harness of tests/test_soak.py: 2 peers
    over the seeded fault net, desync detection on, rolling bit-exact
    comparison of every settled frame (a frame's first save may be
    speculative — the LAST save wins, compared once both peers are
    max_prediction+1 past it, then forgotten so memory stays bounded).

    Which tier it soaks: the sessions are started with
    ``start_p2p_session``, not added to a ``HostSessionPool``, so detection
    at interval 100 runs in the per-session Python ``P2PSession`` here: the
    protocol's plain reference.  Since PR 35 a POOL of such builders is
    served by the native bank with detection inside the crossing
    (docs/DESIGN.md §4); that tier is held to this one by
    ``tests/test_bank_desync_detection.py`` and driven on the chip by
    ``chip_smoke.py``'s ``pool`` leg, not by this soak.

    ``periodic(sessions, digests)`` runs every 10k frames for extra
    invariants (the test asserts queue bounds there).  Returns
    ``{"fps", "compared", "desyncs", "rss_drift_mb"}`` after asserting
    convergence itself."""
    import resource

    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.core.types import DesyncDetection
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.sessions import SessionBuilder

    game = BoxGame(2)
    net = InMemoryNetwork(seed=1234, loss=0.08, duplicate=0.04, reorder=0.04)
    clock_now = [0]
    sessions = []
    for me in (0, 1):
        b = (
            SessionBuilder(boxgame_config())
            .with_desync_detection_mode(DesyncDetection.on(interval=100))
            .with_clock(lambda: clock_now[0])
            .with_rng(random.Random(77 + me))
            .add_player(Local(), me)
            .add_player(Remote(("peer", 1 - me)), 1 - me)
        )
        sessions.append(b.start_p2p_session(net.socket(("peer", me))))

    # settled = both peers advanced past the frame by the whole prediction
    # window, so no speculative save can still be pending for it
    horizon_slack = sessions[0]._max_prediction + 1
    states = [game.init_state_np(), game.init_state_np()]
    digests: list = [{}, {}]
    compared = [0]

    def digest(st) -> int:
        return zlib.crc32(
            b"".join(np.ascontiguousarray(v).tobytes() for v in st.values())
        )

    def compare_settled() -> None:
        horizon = min(s.current_frame for s in sessions) - horizon_slack
        for f in [f for f in digests[0] if f <= horizon]:
            if f in digests[1]:
                assert digests[0][f] == digests[1][f], (
                    f"state divergence at frame {f}"
                )
                del digests[1][f]
                compared[0] += 1
            del digests[0][f]

    def rss_mb() -> float:
        # CURRENT resident set, not ru_maxrss: the rusage value is a
        # process-lifetime high-water mark, so a pytest run whose earlier
        # device tests peaked higher would make the drift identically 0.0
        # and the leak certification vacuous
        try:
            with open("/proc/self/status") as f:
                for line in f:
                    if line.startswith("VmRSS:"):
                        return int(line.split()[1]) / 1024.0
        except OSError:
            pass
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    desyncs = 0
    rss_half = 0.0
    t0 = time.perf_counter()
    for i in range(frames):
        clock_now[0] += 16
        for me, s in enumerate(sessions):
            s.add_local_input(me, (i * 7 + me * 3) % 16)
            for r in s.advance_frame():
                k = type(r).__name__
                if k == "SaveGameState":
                    snap = {k2: v.copy() for k2, v in states[me].items()}
                    d = digest(snap)
                    r.cell.save(r.frame, snap, d)
                    digests[me][r.frame] = d  # last save wins
                elif k == "LoadGameState":
                    states[me] = {
                        k2: v.copy() for k2, v in r.cell.data().items()
                    }
                elif k == "AdvanceFrame":
                    inp = np.asarray([v for v, _ in r.inputs], np.uint8)
                    states[me] = game.advance_np(states[me], inp)
            desyncs += sum(
                1 for e in s.events()
                if type(e).__name__ == "DesyncDetected"
            )
        if i % 500 == 0:
            compare_settled()
        if i == frames // 2:
            rss_half = rss_mb()
        if periodic is not None and i % 10_000 == 0:
            periodic(sessions, digests)
    compare_settled()
    dt = time.perf_counter() - t0
    assert desyncs == 0, f"{desyncs} desync events over the soak"
    assert compared[0] > frames // 2, f"only {compared[0]} frames compared"
    assert all(s.current_frame >= frames - 64 for s in sessions), (
        "a peer stalled short of the horizon"
    )
    return {
        "fps": frames / dt,
        "compared": compared[0],
        "desyncs": desyncs,
        "rss_drift_mb": rss_mb() - rss_half,
    }


def pool_soak(ticks: int, n_matches: int = 4) -> dict:
    """Long-horizon pooled-hosting harness: one
    BatchedRequestExecutor fulfilling 2·n_matches sessions for ``ticks``
    ticks (periodic fences), asserting every session reaches the horizon.
    Returns ``{"session_ticks_per_sec", "sessions", "ring_wraps"}``."""
    sessions, schedules, pool = _pooled_matches_setup(n_matches)
    n_sessions = len(sessions)
    t0 = time.perf_counter()
    for i in range(ticks):
        reqs = []
        for h, (s, sched) in enumerate(zip(sessions, schedules)):
            s.add_local_input(h % 2, sched(i))
            reqs.append(s.advance_frame())
        pool.run(reqs)
        if i % 2_000 == 0:
            pool.block_until_ready()
    pool.block_until_ready()
    dt = time.perf_counter() - t0
    assert all(s.current_frame >= ticks - 64 for s in sessions), (
        "a pooled session stalled short of the horizon"
    )
    for m in range(n_matches):
        fa = sessions[2 * m].current_frame
        fb = sessions[2 * m + 1].current_frame
        assert abs(fa - fb) <= sessions[0]._max_prediction
    return {
        "session_ticks_per_sec": n_sessions * ticks / dt,
        "sessions": n_sessions,
        "ring_wraps": ticks // 128,
    }

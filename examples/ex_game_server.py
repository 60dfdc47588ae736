"""Massed-hosting example: N live P2P BoxGame matches on one chip.

The reference binds one session to one process; a matchmaking service
hosting hundreds of games runs hundreds of processes.  Here ONE process
drives N matches (2 peers each, in-memory transport — the shape of a game
server simulating authoritatively for its clients) and fulfills all 2N
sessions' per-tick request lists with a single batched device dispatch
(``parallel.BatchedRequestExecutor``).  Per-session rollback depths differ
every tick; the pool normalizes them into one predicated program.

  python examples/ex_game_server.py --matches 16 --frames 300
"""

from __future__ import annotations

import argparse
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--matches", type=int, default=8)
    ap.add_argument("--frames", type=int, default=240)
    ap.add_argument("--max-prediction", type=int, default=8)
    args = ap.parse_args()

    import numpy as np

    from ex_game import box_config
    from ggrs_tpu.core import Local, Remote
    from ggrs_tpu.games import BoxGame
    from ggrs_tpu.net import InMemoryNetwork
    from ggrs_tpu.parallel import BatchedRequestExecutor
    from ggrs_tpu.sessions import SessionBuilder
    from ggrs_tpu.utils.device import place_compile_cache

    place_compile_cache()
    game = BoxGame(2)
    n_sessions = 2 * args.matches

    # compile the pool BEFORE any session exists (see ops executor warmup)
    pool = BatchedRequestExecutor(
        game.advance,
        game.init_state(),
        lambda pairs: np.asarray([p[0] for p in pairs], np.uint8),
        batch_size=n_sessions,
        ring_length=args.max_prediction + 2,
        max_burst=args.max_prediction + 1,
    )
    pool.warmup(np.zeros((2,), np.uint8))

    net = InMemoryNetwork()
    sessions, schedules = [], []
    for m in range(args.matches):
        names = (f"A{m}", f"B{m}")
        for me in (0, 1):
            b = (
                SessionBuilder(box_config())
                .with_rng(random.Random(1000 + 3 * m + me))
                .with_max_prediction_window(args.max_prediction)
                .add_player(Local(), me)
                .add_player(Remote(names[1 - me]), 1 - me)
            )
            sessions.append(b.start_p2p_session(net.socket(names[me])))
            schedules.append(
                lambda i, m=m, me=me: ((i + 2 * m + me) // (2 + m % 3)) % 16
            )

    # inputs hold constant over the final frames so repeat-last predictions
    # become correct and every peer's live state converges to the true
    # simulation (predicted tails otherwise legitimately differ at the
    # moment we stop and compare)
    drain_from = max(0, args.frames - 3 * args.max_prediction)

    t0 = time.perf_counter()
    for i in range(args.frames):
        for s in sessions:
            s.poll_remote_clients()
        reqs = []
        for h, (s, sched) in enumerate(zip(sessions, schedules)):
            s.add_local_input(h % 2, sched(min(i, drain_from)))
            reqs.append(s.advance_frame())
        pool.run(reqs)  # ONE dispatch for all matches
    pool.block_until_ready()
    dt = time.perf_counter() - t0

    # verify every match's two peers agree bit-exactly
    desyncs = 0
    for m in range(args.matches):
        a, b = pool.live_state(2 * m), pool.live_state(2 * m + 1)
        for k in a:
            if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
                desyncs += 1
                break
    rate = n_sessions * args.frames / dt
    print(
        f"hosted {args.matches} matches ({n_sessions} sessions) for "
        f"{args.frames} frames: {rate:,.0f} session-ticks/sec, "
        f"{desyncs} desynced matches"
    )
    print("SERVER-EXAMPLE-OK" if desyncs == 0 else "SERVER-EXAMPLE-DESYNC")
    return 0 if desyncs == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())

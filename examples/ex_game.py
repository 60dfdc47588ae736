"""Shared example-game harness: BoxGame fulfilled on device, driven from a
fixed-timestep loop.

Mirrors the reference's example scaffolding (state/checksum handling, request
dispatch, desync-on-demand — /root/reference/examples/ex_game/ex_game.rs) with
a terminal renderer instead of a window: each ship is a letter on an 80x24
grid.  Keyboard input is replaced by a deterministic per-player bot (seeded),
so the examples run headless; pass --render to watch.
"""

from __future__ import annotations

import sys
import time
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ggrs_tpu.games import BoxGame, boxgame_config
from ggrs_tpu.games.boxgame import WINDOW_H, WINDOW_W, _FP  # fixed-point consts
from ggrs_tpu.ops import DeviceRequestExecutor
from ggrs_tpu.utils.device import place_compile_cache

FPS = 60
# prediction window shared by the example sessions and the jit warmup —
# sessions built by the drivers leave the builder default (8) untouched
MAX_PREDICTION = 8

box_config = boxgame_config


class Game:
    """Owns the device executor and renders / reports state."""

    def __init__(
        self,
        num_players: int,
        render: bool = False,
        rollbacks: bool = True,
        max_prediction: int = MAX_PREDICTION,
    ) -> None:
        place_compile_cache()  # before the first compile
        self.box = BoxGame(num_players)
        self.num_players = num_players
        self.render = render
        self.executor = DeviceRequestExecutor(
            self.box.advance,
            self.box.init_state(),
            lambda pairs: jnp.asarray([p[0] for p in pairs], jnp.uint8),
        )
        # compile ALL programs the session can dispatch before its loop
        # starts: a mid-session compile pause stalls the poll/ack pump long
        # enough to trip peers' disconnect timers.  Spectators never roll
        # back (rollbacks=False skips the burst-depth compiles).  The deepest
        # burst is max_prediction resim pairs + the trailing live advance.
        self.executor.warmup(
            jnp.zeros((num_players,), jnp.uint8),
            burst_depths=range(2, max_prediction + 2) if rollbacks else (),
        )
        self.frames_run = 0

    def handle_requests(self, requests: List) -> None:
        self.executor.run(requests)
        self.frames_run += 1

    def bot_input(self, handle: int, frame: int) -> int:
        """Deterministic per-player 'AI': thrust always, turn in a pattern."""
        phase = (frame // 30 + handle * 7) % 4
        return 0b0001 | (0b0100 if phase in (1, 3) else 0b1000 if phase == 2 else 0)

    def draw(self) -> None:
        if not self.render:
            return
        state = self.executor.state
        pos = np.asarray(state["pos"]) / _FP if state["pos"].dtype == np.int32 else np.asarray(state["pos"])
        cols, rows = 78, 22
        grid = [[" "] * cols for _ in range(rows)]
        for p in range(self.num_players):
            x = int(pos[p, 0] / (WINDOW_W / _FP) * cols) % cols
            y = int(pos[p, 1] / (WINDOW_H / _FP) * rows) % rows
            grid[y][x] = chr(ord("A") + p)
        sys.stdout.write("\x1b[H\x1b[2J")
        for row in grid:
            sys.stdout.write("".join(row) + "\n")
        sys.stdout.write(f"frame {self.frames_run}\n")
        sys.stdout.flush()


class FrameClock:
    """Fixed-timestep accumulator with skip support (the reference's loop,
    /root/reference/examples/ex_game/ex_game_p2p.rs:110-136)."""

    def __init__(self, fps: int = FPS) -> None:
        self.dt = 1.0 / fps
        self.acc = 0.0
        self.last = time.perf_counter()
        self.skip_until = 0.0

    def ready_frames(self, max_frames: int = 5) -> int:
        now = time.perf_counter()
        self.acc += now - self.last
        self.last = now
        # drop backlog beyond one burst: after a long pause (e.g. a jit
        # compile) a game resumes at real-time cadence rather than fast-
        # forwarding hundreds of frames — which would outrun remote peers'
        # input rings (a spectator follows at most 60 frames behind)
        self.acc = min(self.acc, max_frames * self.dt)
        n = 0
        while self.acc >= self.dt and n < max_frames:
            self.acc -= self.dt
            if now >= self.skip_until:
                n += 1
        return n

    def skip(self, frames: int) -> None:
        """Honor a WaitRecommendation by sitting out ``frames`` frames."""
        self.skip_until = time.perf_counter() + frames * self.dt

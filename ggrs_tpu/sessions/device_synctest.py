"""DeviceSyncTestSession: the determinism harness with HBM-resident state.

Semantics mirror ``SyncTestSession`` (forced rollback of ``check_distance``
frames every tick with first-seen checksum comparison,
/root/reference/src/sessions/sync_test_session.rs:85-150) — but the whole tick
is a fused XLA program (`ggrs_tpu.ops.replay`) and ``run_ticks`` dispatches
hundreds of ticks per device call.  The observable contract differs in one
documented way: checksum mismatches surface at the end of a ``run_ticks``
batch (as ``MismatchedChecksum`` carrying every divergent frame still in the
ring window plus the earliest offender overall), not at the exact tick — the
price of never syncing the device per frame, and the reason this session is
the benchmark harness (BASELINE configs 1-2).

Use the host ``SyncTestSession`` when you need per-tick request lists or
arbitrary Python state; use this one when the game is a JAX pytree.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core.errors import InvalidRequest, MismatchedChecksum
from ..obs.trace import default_tracer
from ..ops.checksum import checksum_device
from ..ops.replay import ReplayPrograms, build_replay_programs

_I32_MAX = np.iinfo(np.int32).max


class DeviceSyncTestSession:
    """Determinism harness over a pure JAX ``advance``; states live on device.

    Arguments mirror the builder's synctest knobs
    (/root/reference/src/sessions/builder.rs:346-358): ``check_distance`` is
    the forced-rollback depth; ``max_prediction`` only sizes the state ring
    (``max(max_prediction, check_distance) + 1`` slots).
    """

    def __init__(
        self,
        advance: Callable[[Any, Any], Any],
        init_state: Any,
        input_template: Any,
        check_distance: int = 2,
        max_prediction: int = 8,
        checksum: Callable[[Any], jax.Array] = checksum_device,
    ) -> None:
        if check_distance < 1:
            raise InvalidRequest(
                "DeviceSyncTestSession requires check_distance >= 1; with 0 "
                "there is no rollback to fuse — use the host SyncTestSession."
            )
        ring_length = max(max_prediction, check_distance) + 1
        self._programs: ReplayPrograms = build_replay_programs(
            advance, ring_length, check_distance, checksum=checksum
        )
        self._carry = self._programs.init_carry(init_state, input_template)
        self._ticks_run = 0
        self.check_distance = check_distance

    # ------------------------------------------------------------------
    # durable checkpoints (beyond the reference, whose save/load machinery
    # is in-memory only — SURVEY §5 checkpoint note)
    # ------------------------------------------------------------------

    def save_checkpoint(self, path: str) -> None:
        """Write the full session carry (state/input/checksum rings, live
        state, desync counters) plus the tick counter to ``path``; a fresh
        session with the same game/config resumes bit-exactly via
        ``load_checkpoint``."""
        from ..utils.checkpoint import save_pytree

        save_pytree(
            path,
            self._carry,
            {"ticks_run": self._ticks_run, "check_distance": self.check_distance},
        )

    def load_checkpoint(self, path: str) -> None:
        """Restore a checkpoint written by ``save_checkpoint``.  The session
        must have been constructed with the same game and config (leaf
        shapes/dtypes and check_distance are validated)."""
        from ..utils.checkpoint import load_pytree

        carry, meta = load_pytree(path, self._carry)
        if meta["check_distance"] != self.check_distance:
            raise InvalidRequest(
                f"checkpoint was taken at check_distance="
                f"{meta['check_distance']}, session uses {self.check_distance}"
            )
        self._carry = jax.tree_util.tree_map(jnp.asarray, carry)
        self._ticks_run = int(meta["ticks_run"])

    # ------------------------------------------------------------------

    @property
    def current_frame(self) -> int:
        return self._ticks_run

    @property
    def resim_frames_per_tick(self) -> int:
        """Resimulated (rolled-back) frames per steady tick."""
        return self.check_distance

    @property
    def requests_per_tick(self) -> int:
        """Request-list equivalents fused per steady tick (2d+2, the
        reference's per-tick workload — SURVEY §3.3)."""
        return 2 * self.check_distance + 2

    def run_ticks(self, inputs: Any, check: bool = True) -> None:
        """Advance ``n`` frames with ``inputs`` (leading axis = ticks, then the
        per-frame input shape, e.g. ``(n, P)`` u8 for BoxGame).

        Splits the batch across the warmup boundary automatically, then raises
        ``MismatchedChecksum`` if any resimulated frame diverged from its
        first-seen checksum.

        ``check=False`` defers the desync check: the call stays fully async
        (no device→host read, which would wait for the queued work),
        accumulating mismatch counters on device until ``verify()``.
        Pre-stage inputs with ``jnp.asarray`` to keep the submit path free of
        host→device transfers too."""
        inputs = jax.tree_util.tree_map(jnp.asarray, inputs)
        n = jax.tree_util.tree_leaves(inputs)[0].shape[0]
        if n == 0:
            return
        n_warm = self._programs.split_at_warmup(self._ticks_run, n)
        if n_warm:
            head = jax.tree_util.tree_map(lambda a: a[:n_warm], inputs)
            with default_tracer().root_span("synctest.warmup", ticks=n_warm):
                self._carry = self._programs.run_warmup(self._carry, head)
        if n > n_warm:
            # avoid a per-call device slice when the whole batch is steady
            tail = (
                inputs
                if n_warm == 0
                else jax.tree_util.tree_map(lambda a: a[n_warm:], inputs)
            )
            with default_tracer().root_span("synctest.steady", ticks=n - n_warm):
                self._carry = self._programs.run_steady(self._carry, tail)
        self._ticks_run += n
        if check:
            self._raise_on_mismatch()

    def verify(self) -> None:
        """Raise ``MismatchedChecksum`` if any deferred ``run_ticks`` batch
        saw a resimulation diverge."""
        self._raise_on_mismatch()

    def live_state(self) -> Any:
        """The current (frame ``current_frame``) game state, fetched to host."""
        return jax.device_get(self._carry["live"])

    def block_until_ready(self) -> None:
        jax.block_until_ready(self._carry)

    # ------------------------------------------------------------------

    def _raise_on_mismatch(self) -> None:
        # one fetch for both scalars: each device_get is a full round-trip
        mismatches, first_bad = jax.device_get(
            (self._carry["mismatches"], self._carry["first_bad"])
        )
        if int(mismatches):
            raise MismatchedChecksum(
                self._ticks_run, self._window_mismatched_frames(int(first_bad))
            )

    def _window_mismatched_frames(self, first_bad: int) -> list:
        """Every frame still in the ring whose saved (resimulated) digest
        differs from its first-seen history digest, plus the earliest bad
        frame overall — the full-report analog of the reference's mismatched
        frame list (/root/reference/src/sessions/sync_test_session.rs:93-102).

        Only runs on the failure path (one extra device fetch); per-slot
        digests are already resident, so the hot loop pays nothing for this.
        A slot is comparable when it still holds the newest frame for both
        arrays: ring saves lag the history by one frame (the history entry for
        the live frame lands before its resim save), so the slot of the
        current frame is history-only and excluded."""
        ring_frames, ring_cs, hist = jax.device_get(
            (
                self._carry["ring"]["frames"],
                self._carry["ring"]["checksums"],
                self._carry["hist"],
            )
        )
        t = self._ticks_run
        r = len(ring_frames)
        frames = set()
        if first_bad != _I32_MAX:
            frames.add(first_bad)
        for i in range(r):
            f = int(ring_frames[i])
            if f < 0 or f + r <= t or i == t % r:
                continue  # never saved / stale slot / history is one ahead
            if np.any(ring_cs[i] != hist[i]):
                frames.add(f)
        return sorted(frames)

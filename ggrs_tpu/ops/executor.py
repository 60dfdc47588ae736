"""DeviceRequestExecutor: fulfill a host session's command list on device.

The host sessions (P2P / Spectator / SyncTest) keep the reference's contract —
they emit an ordered list of Save/Load/Advance requests and never touch game
state (/root/reference/src/lib.rs:170-195).  This executor is the device-side
fulfillment: game state is a JAX pytree held on HBM, Save stores the *device
handle* (zero-copy) plus a lazily-fetched on-device checksum into the
request's ``GameStateCell``, Load swaps the handle back, and Advance
dispatches the jitted user ``advance``.

The live path performs ZERO device→host reads: checksums ride in
``DeviceChecksum`` handles that materialize only when the session actually
reports one over the wire (every DesyncDetection interval), and rollback
bursts — a Load followed by a run of Save/Advance pairs — are one fused scan
dispatch whose per-step states come back as jit outputs (no post-hoc device
slicing).  A device→host read makes the host wait for everything the
device has queued — a pipeline stall on any transport — so "no reads on
the live path" keeps host and device overlapped.  (What a read costs on a
directly attached chip is not measured yet.)
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.types import (
    AdvanceFrame,
    GgrsRequest,
    InputStatus,
    LoadGameState,
    SaveGameState,
)
from .checksum import DeviceChecksum, checksum_device

InputsToArray = Callable[[Sequence[Tuple[Any, InputStatus]]], Any]


def _stack_pytrees(trees: Sequence[Any]) -> Any:
    """Stack pytrees on a new leading (time) axis, staying on the host when
    every leaf is NumPy — the single H2D transfer then happens inside the
    consuming jit instead of as eager device ops."""

    def stack(*leaves: Any) -> Any:
        if all(isinstance(l, np.ndarray) for l in leaves):
            return np.stack(leaves)
        return jnp.stack([jnp.asarray(l) for l in leaves])

    return jax.tree_util.tree_map(stack, *trees)


class ExecutorPrograms:
    """The compiled device programs for one ``advance`` function — the jitted
    single advance, the fused rollback burst, and the checksum — shareable
    across every ``DeviceRequestExecutor`` driving the same game.

    jit caches hang off the wrapped callables, so N peers in one process that
    each build their own executor would otherwise compile every program N
    times.  Build one of these and pass it to each executor's ``programs``
    argument to compile once.
    """

    def __init__(
        self, advance: Callable[[Any, Any], Any], with_checksums: bool = True
    ) -> None:
        self.with_checksums = with_checksums
        self.raw_advance = advance  # for executor-side identity validation
        self.advance = jax.jit(advance)
        self.checksum = jax.jit(checksum_device)

        def _burst(state: Any, inputs: Any):
            def body(st: Any, inp: Any):
                nxt = advance(st, inp)
                return nxt, nxt

            final, post = jax.lax.scan(body, state, inputs)
            n = jax.tree_util.tree_leaves(inputs)[0].shape[0]
            # unstack inside the jit: per-step states (and digests) come back
            # as program outputs, so fulfilling N Save cells costs zero
            # additional dispatches or transfers
            steps = [
                jax.tree_util.tree_map(lambda l, _k=k: l[_k], post)
                for k in range(n)
            ]
            sums = (
                [checksum_device(s) for s in steps] if with_checksums else None
            )
            return final, steps, sums

        self.burst = jax.jit(_burst)


class DeviceRequestExecutor:
    """Executes GgrsRequest lists with device-resident state.

    ``advance``        pure JAX ``(state, inputs_array) -> state``.
    ``init_state``     initial pytree (device arrays).
    ``inputs_to_array`` maps the request's ``[(input, status), ...]`` list to
                       the array ``advance`` consumes (e.g. u8 bitmask vector
                       for BoxGame).  Disconnected players already arrive as
                       default inputs, matching the reference's dummy inputs.
    ``programs``       optional shared ``ExecutorPrograms`` (same ``advance``
                       and ``with_checksums``): lets N executors in one
                       process reuse one set of compiled programs.
    """

    def __init__(
        self,
        advance: Callable[[Any, Any], Any],
        init_state: Any,
        inputs_to_array: InputsToArray,
        with_checksums: bool = True,
        programs: Optional[ExecutorPrograms] = None,
    ) -> None:
        if programs is None:
            programs = ExecutorPrograms(advance, with_checksums)
        assert programs.with_checksums == with_checksums, (
            "shared ExecutorPrograms was built with a different "
            "with_checksums setting"
        )
        # == (not `is`): bound methods compare equal when they bind the same
        # function on the same object, but a fresh object is created per
        # attribute access, so identity would always fail for `game.advance`
        assert programs.raw_advance == advance, (
            "shared ExecutorPrograms was built for a different advance "
            "function — its compiled programs would silently simulate the "
            "wrong game"
        )
        self._advance = programs.advance
        self._state = jax.tree_util.tree_map(jnp.asarray, init_state)
        self._inputs_to_array = inputs_to_array
        self._with_checksums = with_checksums
        self._checksum = programs.checksum
        self._burst = programs.burst

    # ------------------------------------------------------------------

    @property
    def state(self) -> Any:
        """The live device state pytree."""
        return self._state

    def warmup(self, example_inputs: Any, burst_depths: Sequence[int] = ()) -> None:
        """Compile the executor's programs without mutating live state.  Call
        before entering a latency-sensitive loop: a first-use compile stall
        inside a live session stops the host's poll/ack pump long enough to
        trip peers' disconnect timers (spurious Disconnected + split-brain
        rollback) or overflow a spectator's 128-pending-input window
        (/root/reference/src/network/protocol.rs:441-445).

        ``burst_depths``: rollback depths to pre-compile the fused replay
        for — the scan specializes per depth, so pass the depths the session
        can emit: ``range(2, max_prediction + 2)``, because a full-window
        rollback of ``max_prediction`` resim pairs groups with the trailing
        live advance into one ``max_prediction + 1``-deep burst (depth 1 uses
        the single-advance path)."""
        outs = [self._advance(self._state, example_inputs)]
        if self._with_checksums:
            outs.append(self._checksum(self._state))
        for n in burst_depths:
            if n < 2:
                continue
            stacked = jax.tree_util.tree_map(
                lambda l: jnp.stack([jnp.asarray(l)] * n), example_inputs
            )
            outs.append(self._burst(self._state, stacked))
        jax.block_until_ready(outs)

    def run(self, requests: List[GgrsRequest]) -> None:
        """Execute a session's request list in order."""
        i = 0
        n = len(requests)
        while i < n:
            req = requests[i]
            if isinstance(req, SaveGameState):
                self._do_save(req)
                i += 1
            elif isinstance(req, LoadGameState):
                self._do_load(req)
                pairs, saves, i = self._collect_burst(requests, i + 1)
                self._run_pairs(pairs, saves)
            elif isinstance(req, AdvanceFrame):
                pairs, saves, i = self._collect_burst(requests, i)
                self._run_pairs(pairs, saves)
            else:  # pragma: no cover
                raise TypeError(f"unknown request {req!r}")

    @staticmethod
    def _collect_burst(
        requests: List[GgrsRequest], start: int
    ) -> Tuple[List[AdvanceFrame], List[Optional[SaveGameState]], int]:
        """Collect the (Advance, Save?)* run starting at ``start``."""
        j = start
        n = len(requests)
        pairs: List[AdvanceFrame] = []
        saves: List[Optional[SaveGameState]] = []
        while j < n and isinstance(requests[j], AdvanceFrame):
            pairs.append(requests[j])
            j += 1
            if j < n and isinstance(requests[j], SaveGameState):
                saves.append(requests[j])
                j += 1
            else:
                saves.append(None)
        return pairs, saves, j

    def _run_pairs(
        self,
        pairs: List[AdvanceFrame],
        saves: List[Optional[SaveGameState]],
    ) -> None:
        """Execute an (Advance, Save?)* run, fused when it's a real burst."""
        if not pairs:
            return
        if len(pairs) == 1:
            self._do_advance(pairs[0])
            if saves[0] is not None:
                self._do_save(saves[0])
            return
        self._do_burst(pairs, saves)

    # ------------------------------------------------------------------

    def _cell_checksum(self, state: Any) -> Optional[DeviceChecksum]:
        if not self._with_checksums:
            return None
        return DeviceChecksum(self._checksum(state))

    def _do_save(self, req: SaveGameState) -> None:
        req.cell.save(req.frame, self._state, self._cell_checksum(self._state))

    def _do_load(self, req: LoadGameState) -> None:
        data = req.cell.data()
        assert data is not None, f"loading frame {req.frame} from an empty cell"
        self._state = data

    def _do_advance(self, req: AdvanceFrame) -> None:
        self._state = self._advance(
            self._state, self._inputs_to_array(req.inputs)
        )

    def _do_burst(
        self,
        pairs: List[AdvanceFrame],
        saves: List[Optional[SaveGameState]],
    ) -> None:
        """(Advance, Save?)×N as one scan dispatch; save cells receive the
        per-step jit outputs directly (device handles, lazy checksums)."""
        stacked = _stack_pytrees(
            [self._inputs_to_array(p.inputs) for p in pairs]
        )
        final, steps, sums = self._burst(self._state, stacked)
        self._state = final
        for k, save in enumerate(saves):
            if save is None:
                continue
            cs = DeviceChecksum(sums[k]) if self._with_checksums else None
            save.cell.save(save.frame, steps[k], cs)

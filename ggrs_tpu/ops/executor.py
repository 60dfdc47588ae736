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

With a ``speculation`` strategy (``parallel.SpeculativeRollback``) attached,
the executor keeps K branch trajectories alive between ticks and lets a
rollback be fulfilled by *branch selection* instead of replay: matching,
selection, and the fallback replay are ONE fused ``lax.cond`` dispatch
(``SpeculativeRollback.fulfill``), so the host never reads whether it hit —
the TPU answer to the reference's rollback hot loop
(/root/reference/src/sessions/p2p_session.rs:658-714).  Misses cost one
replay inside that same dispatch — correctness never depends on a hit.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.types import (
    AdvanceFrame,
    GgrsRequest,
    InputStatus,
    LoadGameState,
    SaveGameState,
)
from ..parallel.spec_rollback import SpeculativeRollback, _stack_pytrees
from .checksum import DeviceChecksum, checksum_device

InputsToArray = Callable[[Sequence[Tuple[Any, InputStatus]]], Any]


class ExecutorPrograms:
    """The compiled device programs for one ``advance`` function — the jitted
    single advance, the fused rollback burst, and the checksum — shareable
    across every ``DeviceRequestExecutor`` driving the same game.

    jit caches hang off the wrapped callables, so N peers in one process (or
    the speculation-on/off variants of a benchmark) that each build their own
    executor would otherwise compile every program N times.  Build one of
    these and pass it to each executor's ``programs`` argument to compile
    once.
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
    ``speculation``    optional ``SpeculativeRollback``: K branch trajectories
                       that turn a rollback into a device-side select (see
                       module docstring).  The executor re-anchors the
                       branches at frame ``load+1`` after every rollback (the
                       next rollback's steady-state target) and extends them
                       by one hypothesized frame per executed advance.
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
        speculation: Optional[SpeculativeRollback] = None,
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
        self._spec = speculation
        self._spec_rollbacks = 0  # host-side: rollbacks seen while speculating
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
        if self._spec is not None:
            # the fused speculation programs (extend, advance+extend, and
            # per-depth fulfill/refill) compile lazily too — warm them all
            self._spec.warmup(
                self._state,
                example_inputs,
                range(1, self._spec.max_window + 1),
                self._with_checksums,
            )

    @property
    def spec_hits(self) -> int:
        """Rollbacks fulfilled by a branch hit.  Reads the device counter —
        call outside timed paths."""
        return 0 if self._spec is None else self._spec.hits

    @property
    def spec_misses(self) -> int:
        """Rollbacks that fell back to replay (including windows the host
        already knew were unanswerable)."""
        return self._spec_rollbacks - self.spec_hits

    def run(self, requests: List[GgrsRequest]) -> None:
        """Execute a session's request list in order."""
        i = 0
        n = len(requests)
        while i < n:
            req = requests[i]
            if isinstance(req, SaveGameState):
                self._do_save(req)
                if self._spec is not None and self._spec.root_frame is None:
                    self._spec.root(req.frame, self._state)
                i += 1
            elif isinstance(req, LoadGameState):
                pairs, saves, i = self._collect_burst(requests, i + 1)
                if self._spec is not None and pairs:
                    self._run_rollback_spec(req, pairs, saves)
                else:
                    if self._spec is not None:
                        # a rollback we can't resolve disproves the predicted
                        # inputs the branch prefixes were validated against
                        self._spec.invalidate()
                    self._do_load(req)
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
        arrays: Optional[List[Any]] = None,
    ) -> List[Tuple[int, SaveGameState, Any]]:
        """Execute an (Advance, Save?)* run, fused when it's a real burst.
        Returns the fulfilled saves as ``(pair_index, request, snapshot)``."""
        if not pairs:
            return []
        if len(pairs) == 1:
            self._do_advance(pairs[0], inputs=arrays[0] if arrays else None)
            if saves[0] is not None:
                self._do_save(saves[0])
                return [(0, saves[0], self._state)]
            return []
        return self._do_burst(pairs, saves, arrays=arrays)

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

    def _do_advance(self, req: AdvanceFrame, inputs: Any = None) -> None:
        if inputs is None:
            inputs = self._inputs_to_array(req.inputs)
        if self._spec is not None:
            # live advance + K branch extensions fused into one dispatch
            nxt = self._spec.advance_and_extend(self._state, inputs)
            if nxt is not None:
                self._state = nxt
                return
        self._state = self._advance(self._state, inputs)

    def _do_burst(
        self,
        pairs: List[AdvanceFrame],
        saves: List[Optional[SaveGameState]],
        arrays: Optional[List[Any]] = None,
    ) -> List[Tuple[int, SaveGameState, Any]]:
        """(Advance, Save?)×N as one scan dispatch; save cells receive the
        per-step jit outputs directly (device handles, lazy checksums).
        Returns the fulfilled saves as ``(pair_index, request, snapshot)`` so
        callers can re-anchor speculation without refetching."""
        if arrays is None:
            arrays = [self._inputs_to_array(p.inputs) for p in pairs]
        # host-side stack when the arrays are NumPy: the single H2D then
        # happens inside the fused call instead of as eager device ops
        stacked = _stack_pytrees(arrays)
        final, steps, sums = self._burst(self._state, stacked)
        self._state = final
        if self._spec is not None:
            # keep the one-extend-per-executed-advance invariant fulfill()
            # depends on (no-op while unrooted, e.g. on the rollback miss path)
            for arr in arrays:
                self._spec.extend(arr)
        fulfilled: List[Tuple[int, SaveGameState, Any]] = []
        for k, save in enumerate(saves):
            if save is None:
                continue
            cs = DeviceChecksum(sums[k]) if self._with_checksums else None
            save.cell.save(save.frame, steps[k], cs)
            fulfilled.append((k, save, steps[k]))
        return fulfilled

    # ------------------------------------------------------------------
    # speculative rollback fulfillment
    # ------------------------------------------------------------------

    def _run_rollback_spec(
        self,
        load: LoadGameState,
        pairs: List[AdvanceFrame],
        saves: List[Optional[SaveGameState]],
    ) -> None:
        """Fulfill ``Load + (Advance, Save?)*`` with one fused
        resolve-or-replay dispatch when the speculation window can answer it;
        otherwise fall back to load + fused replay.

        The burst's trailing advance carries the *live* (not resimulated)
        frame exactly when it has no trailing save — the session always saves
        the current frame before the live advance — so the resolve window is
        all advances except a saveless last one.  (When every advance has a
        save — e.g. sparse saving hit the threshold — treating them all as
        resim frames is equally correct: the fused program only selects
        branches whose inputs are bit-equal, so trajectory states equal
        replay states.)
        """
        g = load.frame
        m = len(pairs)
        n_resim = m if saves[-1] is not None else m - 1
        arrays = [self._inputs_to_array(p.inputs) for p in pairs]
        self._spec_rollbacks += 1

        if n_resim >= 1 and self._spec.window_valid(g, n_resim):
            # ONE dispatch for the whole rollback TICK: hypothesis match +
            # branch select (or the fallback replay — the host never reads
            # which), re-anchoring the branches at frame g+1, re-hypothesizing
            # the still-unconfirmed tail, and — when the burst has a trailing
            # saveless live advance — that advance plus its window extension.
            has_live = n_resim < m
            out = self._spec.fulfill_and_refill(
                g,
                arrays[:n_resim],
                load.cell.data(),
                self._with_checksums,
                live_inputs=arrays[-1] if has_live else None,
            )
            steps, sums = out[0], out[1]
            for j in range(n_resim):
                if saves[j] is not None:
                    cs = (
                        DeviceChecksum(sums[j])
                        if self._with_checksums
                        else None
                    )
                    saves[j].cell.save(saves[j].frame, steps[j], cs)
            self._state = out[2] if has_live else steps[n_resim - 1]
        else:
            # window can't answer this rollback (host-known): the rollback
            # disproved the predicted inputs the prefixes were validated
            # against — invalidate, replay, and re-anchor at the first saved
            # frame of the burst.
            self._spec.invalidate()
            self._do_load(load)
            fulfilled = self._run_pairs(pairs, saves, arrays=arrays)
            if fulfilled:
                j0, save0, snap0 = fulfilled[0]
                self._spec.root(save0.frame, snap0)
                for arr in arrays[j0 + 1 :]:
                    self._spec.extend(arr)

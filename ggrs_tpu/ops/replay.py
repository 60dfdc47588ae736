"""The fused rollback replay: load → (advance, save)^d → advance, as one XLA
program.

This is the TPU-native form of the reference's hot loop — the request list a
SyncTest/P2P session emits per tick (Load, then ``check_distance`` resimulated
Save/Advance pairs, then the live Save/Advance;
/root/reference/src/sessions/sync_test_session.rs:85-150 and
/root/reference/src/sessions/p2p_session.rs:658-714).  The reference executes
those 2d+2 requests one by one through user callbacks; here a whole *tick* is
one jitted function and ``run_*`` scans hundreds of ticks per dispatch, so
state and inputs stay in HBM and only scalar desync counters ever reach the
host.

Determinism checking is also device-side: a first-seen checksum history ring is
compared against every resimulated frame's digest, reproducing the SyncTest
contract (first-seen vs. later resimulations,
/root/reference/src/sessions/sync_test_session.rs:173-190) without a per-frame
device→host sync.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp

from .checksum import CHECKSUM_LANES, checksum_device
from .ring import DeviceStateRing

_I32_MAX = jnp.iinfo(jnp.int32).max

AdvanceFn = Callable[[Any, Any], Any]  # (state_pytree, inputs_for_frame) -> state
ChecksumFn = Callable[[Any], jax.Array]  # state_pytree -> (4,) uint32


@dataclass(frozen=True)
class ReplayPrograms:
    """Compiled tick programs over a fixed (advance, ring, check_distance).

    ``carry`` layout (a plain pytree, lives on device between calls):
      ring       — DeviceStateRing buffers (states / checksums / frames)
      inputs     — input ring, same slotting as the state ring
      hist       — (R, 4) u32 first-seen checksum per frame slot
      live       — the current (unsaved) game state
      frame      — i32 scalar, the session's current frame (bookkeeping only)
      mismatches — i32 count of resimulated frames whose digest diverged
      first_bad  — i32 earliest mismatched frame (INT32_MAX if none)

    The tick programs take the starting frame as a SEPARATE scalar argument
    (``run_steady(carry, inputs, start_frame)``) rather than reading
    ``carry["frame"]``: when sessions are batched with ``vmap`` the carry is
    per-session, and a per-session traced frame would turn every ring
    save/load and history update into a batched scatter/gather over the whole
    ``[B, R, ...]`` buffer — measured ~30× slower on the 256-session ChipVM
    bench.  Sessions tick in lockstep, so the slot index is a function of the
    host-known tick count; passing it unbatched (``in_axes=None`` under vmap)
    keeps every ring op a shared-index slice update.  ``carry["frame"]`` is
    still maintained (one vector add per call) for inspection and tests.
    """

    ring: DeviceStateRing
    check_distance: int
    run_warmup: Callable[[Any, Any], Any]
    run_steady: Callable[[Any, Any], Any]
    init_carry: Callable[[Any, Any], Any]
    # un-jitted pure forms of run_warmup/run_steady, for composition with
    # vmap / shard_map (session batching) before the final jit
    scan_warmup: Callable[[Any, Any], Any] = None
    scan_steady: Callable[[Any, Any], Any] = None

    @property
    def warmup_ticks(self) -> int:
        """Ticks before rollback starts: frames 0..d inclusive (the reference
        only rolls back once current_frame > check_distance)."""
        return self.check_distance + 1

    def split_at_warmup(self, ticks_run: int, n: int) -> int:
        """How many of the next ``n`` ticks must go through the warmup program
        given ``ticks_run`` ticks already executed."""
        return min(max(0, self.warmup_ticks - ticks_run), n)


def _store_input(ring: DeviceStateRing, inputs: Any, frame: jax.Array, inp: Any) -> Any:
    i = ring.slot(frame)
    return jax.tree_util.tree_map(
        lambda buf, leaf: jax.lax.dynamic_update_index_in_dim(
            buf, jnp.asarray(leaf, buf.dtype), i, axis=0
        ),
        inputs,
        inp,
    )


def build_scrub_program(
    advance: AdvanceFn,
    donate: bool = True,
    unroll: int = 4,
):
    """Compile the confirmed-only playback program: advance N frames in ONE
    fused dispatch — the fast-forward mode of journal replay
    (``sessions.replay.ReplaySession``).

    Replaying a journal never rolls back (every input is confirmed, like a
    spectator's stream), so the 2d+2 request pattern the rollback programs
    above fuse collapses to a bare advance scan: no ring, no checksum
    history, no resimulation.  The returned callable is
    ``scrub(state, stacked_inputs) -> state`` where ``stacked_inputs``
    stacks the window's per-frame inputs on the leading axis; state and
    inputs stay in HBM for the whole window, exactly like ``run_steady``.
    """
    def scrub(state: Any, stacked_inputs: Any) -> Any:
        def body(st: Any, inp: Any) -> Tuple[Any, None]:
            return advance(st, inp), None

        out, _ = jax.lax.scan(body, state, stacked_inputs, unroll=unroll)
        return out

    return jax.jit(scrub, donate_argnums=(0,) if donate else ())


def build_replay_programs(
    advance: AdvanceFn,
    ring_length: int,
    check_distance: int,
    checksum: ChecksumFn = checksum_device,
    donate: bool = True,
    unroll_resim: bool = False,
    unroll_ticks: int = 4,
) -> ReplayPrograms:
    """Compile the warmup/steady tick programs.

    ``advance`` must be a pure JAX function ``(state, inputs) -> state`` with
    static shapes — the user-supplied simulation, the analog of fulfilling an
    AdvanceFrame request (/root/reference/src/lib.rs:183-189).
    ``ring_length`` must exceed ``check_distance`` so the rollback target is
    still in the ring, mirroring ``max_prediction + 1`` cells in the reference.
    ``donate``: donate the carry buffers to each dispatch (in-place HBM
    update).  On by default on every backend, so the CPU tests run the
    program the chip runs; callers that re-wrap ``scan_*`` in their own jit
    (``BatchedSessions``) or hand the same carry to two programs pass False.
    ``unroll_resim``/``unroll_ticks``: loop unrolling for the inner (resim)
    and outer (tick) scans.  Defaults were retuned in round 4 under
    completion-fenced timing: the ROLLED inner resim loop measures ~1.3x
    faster than fully unrolled on the flagship config (the earlier
    unroll-everything choice was tuned against enqueue-rate fiction —
    smaller programs schedule better here), while moderate tick unroll (4)
    stays best.  See docs/DESIGN.md §11.
    """
    assert check_distance >= 1, "device replay needs check_distance >= 1"
    assert ring_length > check_distance, "ring must cover the rollback window"
    ring = DeviceStateRing(ring_length)
    d = check_distance

    def warmup_tick(carry: Any, inp: Any, frame: jax.Array) -> Any:
        # [Save, Advance] — the pre-rollback request pattern
        cs = checksum(carry["live"])
        new_ring = ring.save(carry["ring"], frame, carry["live"], cs)
        hist = jax.lax.dynamic_update_index_in_dim(
            carry["hist"], cs, ring.slot(frame), axis=0
        )
        inputs = _store_input(ring, carry["inputs"], frame, inp)
        live = advance(carry["live"], inp)
        # first-seen digest for frame+1 comes from this live advance; later
        # resimulations of that frame are compared against it (this makes
        # every resim frame checkable — stronger than the reference, which
        # never digests the live advance and so cannot compare the newest
        # window frame)
        hist = jax.lax.dynamic_update_index_in_dim(
            hist, checksum(live), ring.slot(frame + 1), axis=0
        )
        return {
            **carry,
            "ring": new_ring,
            "inputs": inputs,
            "hist": hist,
            "live": live,
        }

    def steady_tick(carry: Any, inp: Any, frame: jax.Array) -> Any:
        # [Load, (Save, Advance)×d resim, Save, Advance] — 2d+2 requests fused
        inputs = _store_input(ring, carry["inputs"], frame, inp)

        loaded = ring.load(carry["ring"], frame - d)

        # pre-gather the window's d inputs in ONE (traced-index) gather per
        # leaf instead of a dynamic gather per resim step inside the scan —
        # every op removed from the scan body is d ops off the tick's
        # critical path
        window_frames = frame - d + jnp.arange(d, dtype=jnp.int32)
        window_slots = ring.slot(window_frames)
        window_inputs = jax.tree_util.tree_map(
            lambda buf: buf[window_slots], inputs
        )

        def resim_step(st: Any, inp_j: Any) -> Tuple[Any, Tuple[Any, jax.Array]]:
            st = advance(st, inp_j)
            cs = checksum(st)
            return st, (st, cs)

        # the scan emits the resim trajectory as stacked ys; the ring is
        # updated ONCE per tick below (one scatter per buffer) instead of
        # once per step — five dynamic-updates per resim step were ~35% of
        # the flagship's step time (round-5 floor probe)
        st, (resim_states, resim_cs) = jax.lax.scan(
            resim_step,
            loaded,
            window_inputs,
            unroll=d if unroll_resim else 1,
        )
        saved_frames = frame - d + 1 + jnp.arange(d, dtype=jnp.int32)
        new_ring = ring.save_many(
            carry["ring"], saved_frames, resim_states, resim_cs
        )
        # resim_cs[j] digests frame F-d+1+j.  Every entry has a first-seen
        # digest in the history (frame F's was recorded by the previous
        # tick's live advance), so the whole window is compared — including
        # at check_distance=1, where the reference's scheme has nothing to
        # compare against.
        resim_frames = saved_frames
        # one vectorized gather over the window's history slots (the vmapped
        # per-frame dynamic_index form cost one gather per resim frame)
        seen = carry["hist"][ring.slot(resim_frames)]
        bad = jnp.any(resim_cs != seen, axis=1)
        mismatches = carry["mismatches"] + jnp.sum(bad, dtype=jnp.int32)
        first_bad = jnp.minimum(
            carry["first_bad"],
            jnp.min(jnp.where(bad, resim_frames, _I32_MAX)),
        )
        live = advance(st, inp)  # st is the resimulated state at F
        hist = jax.lax.dynamic_update_index_in_dim(
            carry["hist"], checksum(live), ring.slot(frame + 1), axis=0
        )
        return {
            "ring": new_ring,
            "inputs": inputs,
            "hist": hist,
            "live": live,
            "mismatches": mismatches,
            "first_bad": first_bad,
        }

    def _scan_ticks(
        tick: Callable, carry: Any, tick_inputs: Any, start_frame: Any = None
    ) -> Any:
        """Run ``tick`` over the leading axis of ``tick_inputs``.  The frame
        for each tick is ``start_frame + i`` — a scalar sequence passed as
        scan xs, NOT read from the (possibly vmapped) carry, so ring slots
        stay shared-index slice ops under session batching (see class doc).
        ``start_frame`` defaults to the carry's own frame counter."""
        n = jax.tree_util.tree_leaves(tick_inputs)[0].shape[0]
        if start_frame is None:
            start_frame = carry["frame"]
        start_frame = jnp.asarray(start_frame, jnp.int32)
        frames = start_frame + jnp.arange(n, dtype=jnp.int32)
        frame_counter = carry["frame"]
        carry = {k: v for k, v in carry.items() if k != "frame"}

        def body(c: Any, xs: Any) -> Tuple[Any, None]:
            inp, f = xs
            return tick(c, inp, f), None

        out, _ = jax.lax.scan(
            body, carry, (tick_inputs, frames), unroll=unroll_ticks
        )
        out["frame"] = frame_counter + n
        return out

    donate_argnums = (0,) if donate else ()
    scan_warmup = partial(_scan_ticks, warmup_tick)
    scan_steady = partial(_scan_ticks, steady_tick)
    run_warmup = jax.jit(scan_warmup, donate_argnums=donate_argnums)
    run_steady = jax.jit(scan_steady, donate_argnums=donate_argnums)

    def init_carry(init_state: Any, input_template: Any) -> Any:
        """Device carry for a session starting at frame 0 with ``init_state``.
        ``input_template`` is one frame's worth of inputs (e.g. a (P,) array)
        used to shape the input ring."""
        inputs = jax.tree_util.tree_map(
            lambda leaf: jnp.zeros(
                (ring_length,) + jnp.asarray(leaf).shape, jnp.asarray(leaf).dtype
            ),
            input_template,
        )
        return {
            "ring": ring.init(init_state),
            "inputs": inputs,
            "hist": jnp.zeros((ring_length, CHECKSUM_LANES), jnp.uint32),
            # copy, never alias: on TPU the carry is DONATED every dispatch,
            # and jnp.asarray would alias a caller's jax Arrays — their
            # init_state buffers would be invalidated by the first tick
            # (surfaces as INVALID_ARGUMENT at the next use)
            "live": jax.tree_util.tree_map(
                lambda l: jnp.array(l, copy=True), init_state
            ),
            "frame": jnp.int32(0),
            "mismatches": jnp.int32(0),
            "first_bad": jnp.int32(_I32_MAX),
        }

    return ReplayPrograms(
        ring=ring,
        check_distance=d,
        run_warmup=run_warmup,
        run_steady=run_steady,
        init_carry=init_carry,
        scan_warmup=scan_warmup,
        scan_steady=scan_steady,
    )

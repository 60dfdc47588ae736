"""Floor probe: what does a BARE lax.scan(advance) cost?

Measures, on the same chip with the same completion fence as bench.py:
  1. bare      — jit(lax.scan(advance)) alone: no ring, no digest, no history
  2. +digest   — bare plus the 4-lane checksum per step
  3. +ring     — bare plus digest plus the state-ring save per step
  4. flagship  — the full steady replay program (DeviceSyncTestSession path)

All variants run the same number of advance() steps per dispatch and the
same number of dispatches, so the per-step deltas attribute the flagship's
overhead.  If (1) is already below the 100k resim-frames/sec north star,
the serial scan step IS the floor and the target re-scopes to the batch
axis with this as evidence; if (1) clears 100k, the extras are the gap and
must be shaved.
"""

from __future__ import annotations

import sys
import time

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np

from bench import REPEATS
from ggrs_tpu.utils.device import place_compile_cache, require_chip
from ggrs_tpu.games import BoxGame
from ggrs_tpu.ops.checksum import checksum_device, CHECKSUM_LANES
from ggrs_tpu.ops.ring import DeviceStateRing
from ggrs_tpu.sessions import DeviceSyncTestSession

D = 8                    # flagship check distance
TICKS_PER_DISPATCH = 1024
DISPATCHES = 8
PLAYERS = 2

# flagship steady tick = d resim advances + 1 live advance; count d "resim
# frames" per tick.  Bare variants run the same TOTAL advance steps per
# dispatch as the flagship's (d+1)*ticks, credited at the same d-per-tick
# rate, so per-step work is identical and only the extras differ.
STEPS_PER_DISPATCH = (D + 1) * TICKS_PER_DISPATCH


def main() -> None:
    require_chip()  # a floor measured on the CPU backend is not the floor
    place_compile_cache()
    game = BoxGame(PLAYERS)
    init = game.init_state()
    rng = np.random.default_rng(7)

    def staged_inputs(n):
        return jnp.asarray(rng.integers(0, 16, size=(n, PLAYERS), dtype=np.uint8))

    # ---- variant builders: (state-carry, inputs) -> state-carry -------------
    def bare_body(st, inp):
        return game.advance(st, inp), None

    def digest_body(carry, inp):
        st, acc = carry
        st = game.advance(st, inp)
        return (st, acc ^ checksum_device(st)), None

    ring = DeviceStateRing(D + 2)

    def ring_body(carry, xs):
        st, rbufs = carry
        inp, f = xs
        st = game.advance(st, inp)
        cs = checksum_device(st)
        rbufs = ring.save(rbufs, f, st, cs)
        return (st, rbufs), None

    bare = jax.jit(lambda st, inps: jax.lax.scan(bare_body, st, inps)[0])
    digest = jax.jit(
        lambda c, inps: jax.lax.scan(digest_body, c, inps)[0]
    )
    ringp = jax.jit(lambda c, xs: jax.lax.scan(ring_body, c, xs)[0])

    frames = jnp.arange(STEPS_PER_DISPATCH, dtype=jnp.int32)
    inps = staged_inputs(STEPS_PER_DISPATCH)

    st0 = jax.tree_util.tree_map(jnp.asarray, init)
    acc0 = jnp.zeros((CHECKSUM_LANES,), jnp.uint32)
    rbufs0 = ring.init(init)

    # flagship program via the session, exactly as bench.py drives it
    sess = DeviceSyncTestSession(
        game.advance, init, jnp.zeros((PLAYERS,), jnp.uint8),
        check_distance=D, max_prediction=D,
    )
    tick_inps = staged_inputs(TICKS_PER_DISPATCH)

    # ---- warm up (compile) every variant ----------------------------------
    jax.block_until_ready(bare(st0, inps))
    jax.block_until_ready(digest((st0, acc0), inps))
    jax.block_until_ready(ringp((st0, rbufs0), (inps, frames)))
    sess.run_ticks(tick_inps, check=False)
    sess.run_ticks(tick_inps, check=False)
    sess.block_until_ready()

    def timed(fn) -> float:
        best = float("inf")
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            out = None
            for _ in range(DISPATCHES):
                out = fn()
            jax.block_until_ready(out)
            best = min(best, time.perf_counter() - t0)
        return best

    results = {}
    t = timed(lambda: bare(st0, inps))
    results["bare"] = t
    t = timed(lambda: digest((st0, acc0), inps))
    results["digest"] = t
    t = timed(lambda: ringp((st0, rbufs0), (inps, frames)))
    results["ring"] = t

    def flagship_pass():
        sess.run_ticks(tick_inps, check=False)
        return None

    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(DISPATCHES):
            flagship_pass()
        sess.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    results["flagship"] = best

    total_steps = DISPATCHES * STEPS_PER_DISPATCH
    resim_credit = DISPATCHES * TICKS_PER_DISPATCH * D  # what bench.py counts
    print(f"backend={jax.default_backend()} device={jax.devices()[0].device_kind}")
    for name, dt in results.items():
        steps_ps = total_steps / dt
        resim_ps = resim_credit / dt
        us = dt / total_steps * 1e6
        print(
            f"{name:10s} {dt*1e3:9.1f} ms  {us:7.3f} us/advance-step  "
            f"{steps_ps:10.0f} steps/s  -> {resim_ps:10.0f} resim-credit f/s"
        )
    print(
        "verdict: bare scan resim-credit "
        f"{resim_credit / results['bare']:.0f} f/s vs 100k north star"
    )
    sess.verify()
    print("desync gate green")


if __name__ == "__main__":
    main()

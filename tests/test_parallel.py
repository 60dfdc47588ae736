"""Batched sessions (shard_map).

Runs on the virtual 8-device CPU mesh set up in conftest.py."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ggrs_tpu.games import BoxGame
from ggrs_tpu.parallel import (
    BatchedSessions,
    make_mesh,
    make_mesh2d,
)


def _random_inputs(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 16, size=shape).astype(np.uint8)


class TestBatchedSessions:
    def test_virtual_mesh_has_8_devices(self):
        assert len(jax.devices()) == 8

    def test_batched_matches_single_session(self):
        game = BoxGame(2)
        mesh = make_mesh(8)
        B, n = 16, 30
        batch = BatchedSessions(
            game.advance,
            game.init_state(),
            jnp.zeros((2,), jnp.uint8),
            batch_size=B,
            mesh=mesh,
            check_distance=2,
        )
        inputs = _random_inputs((B, n, 2), seed=11)
        stats = batch.run_ticks(inputs)
        assert stats["mismatches"] == 0
        assert batch.current_frame == n

        # session 5 must equal an independent forward NumPy simulation
        live = batch.live_states()
        s_np = game.init_state_np()
        for i in range(n):
            s_np = game.advance_np(s_np, inputs[5, i])
        for k in ("pos", "vel", "rot"):
            np.testing.assert_array_equal(np.asarray(live[k])[5], s_np[k])

    def test_uneven_batch_rejected(self):
        game = BoxGame(2)
        with pytest.raises(AssertionError):
            BatchedSessions(
                game.advance,
                game.init_state(),
                jnp.zeros((2,), jnp.uint8),
                batch_size=9,
                mesh=make_mesh(8),
            )

    def test_2d_host_mesh_matches_1d_mesh_bitwise(self):
        """The multi-host shape: a (2 hosts × 4 chips) mesh must produce
        bit-identical states and the same global stats as the flat 8-chip
        mesh — moving to multi-host is a mesh swap, not a program change."""
        game = BoxGame(2)
        B, n = 16, 24
        inputs = _random_inputs((B, n, 2), seed=23)
        results = []
        for mesh in (make_mesh(8), make_mesh2d(2, 4)):
            batch = BatchedSessions(
                game.advance,
                game.init_state(),
                jnp.zeros((2,), jnp.uint8),
                batch_size=B,
                mesh=mesh,
                check_distance=2,
            )
            stats = batch.run_ticks(inputs)
            assert stats["mismatches"] == 0
            results.append(batch.live_states())
        flat, two_d = results
        for k in ("pos", "vel", "rot"):
            np.testing.assert_array_equal(
                np.asarray(flat[k]), np.asarray(two_d[k]), err_msg=k
            )

    def test_distributed_mesh_single_process_degenerate_form(self):
        """make_distributed_mesh on one process: a (1, n_devices) mesh
        running the identical program — the virtual-mesh gate for the
        multi-host launch recipe (its two-host form differs only in
        jax.distributed initialization, documented in its docstring)."""
        from ggrs_tpu.parallel import make_distributed_mesh

        mesh = make_distributed_mesh()
        assert mesh.devices.shape == (1, len(jax.devices()))
        assert mesh.axis_names == ("hosts", "sessions")

        game = BoxGame(2)
        B, n = 16, 12
        inputs = _random_inputs((B, n, 2), seed=5)
        results = []
        for m in (make_mesh(8), mesh):
            batch = BatchedSessions(
                game.advance, game.init_state(), jnp.zeros((2,), jnp.uint8),
                batch_size=B, mesh=m, check_distance=2,
            )
            stats = batch.run_ticks(inputs)
            assert stats["mismatches"] == 0
            results.append(batch.live_states())
        for k in ("pos", "vel", "rot"):
            np.testing.assert_array_equal(
                np.asarray(results[0][k]), np.asarray(results[1][k])
            )

    def test_2d_mesh_detects_corruption_across_hosts(self):
        """The psum/pmin health reduction must cross BOTH mesh axes: corrupt
        a session owned by the second host row and read the global stats."""
        game = BoxGame(2)
        B = 16
        batch = BatchedSessions(
            game.advance,
            game.init_state(),
            jnp.zeros((2,), jnp.uint8),
            batch_size=B,
            mesh=make_mesh2d(2, 4),
            check_distance=2,
        )
        batch.run_ticks(_random_inputs((B, 10, 2), seed=3))
        ring_len = batch._programs.ring.length
        slot = 8 % ring_len
        states = batch._carry["ring"]["states"]
        # session 12 lives in the second host row (sessions are host-major)
        states["pos"] = states["pos"].at[12, slot, 0, 0].add(1)
        stats = batch.run_ticks(_random_inputs((B, 5, 2), seed=4))
        assert stats["mismatches"] >= 1
        assert stats["first_bad"] == 9

    def test_corruption_in_one_session_detected_globally(self):
        game = BoxGame(2)
        B = 8
        batch = BatchedSessions(
            game.advance,
            game.init_state(),
            jnp.zeros((2,), jnp.uint8),
            batch_size=B,
            mesh=make_mesh(8),
            check_distance=2,
        )
        batch.run_ticks(_random_inputs((B, 10, 2), seed=1))
        # corrupt session 3's saved frame-8 slot (loaded by the next tick)
        ring_len = batch._programs.ring.length
        slot = 8 % ring_len
        states = batch._carry["ring"]["states"]
        states["pos"] = states["pos"].at[3, slot, 0, 0].add(1)
        stats = batch.run_ticks(_random_inputs((B, 5, 2), seed=2))
        assert stats["mismatches"] >= 1
        assert stats["first_bad"] == 9


class TestPooledTickOverTheMesh:
    def test_shards_with_different_deepest_plans_equal_one_device(self):
        """``BatchedRequestExecutor``'s burst loop runs as many steps as the
        deepest plan asks, and under ``shard_map`` that is each shard's own
        maximum (no collective): shards whose loops differ in length, and of
        which one alone takes the post-load save, give the bytes the
        single-device program gives."""
        from ggrs_tpu.parallel import BatchedRequestExecutor

        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device virtual mesh")
        game = BoxGame(2)
        B, R, D = 8, 10, 9  # one session a shard

        def pool(mesh):
            ex = BatchedRequestExecutor(
                game.advance, game.init_state(),
                lambda pairs: np.asarray([p[0] for p in pairs], np.uint8),
                batch_size=B, ring_length=R, max_burst=D, mesh=mesh,
            )
            ex.warmup(np.zeros((2,), np.uint8))
            return ex

        single, sharded = pool(None), pool(make_mesh(8))
        descs = []
        for f in range(D):  # quiet ticks: frames 0..8 saved in every ring
            desc = single._blank_desc()
            desc["pre_save"][:] = True
            desc["pre_frame"][:] = f
            desc["n_adv"][:] = 1
            desc["inputs"][:] = _random_inputs(desc["inputs"].shape, seed=f)
            descs.append(desc)
        # frame 9: shard b's one session rolls back depth[b] - 1 frames
        # (0: idle; 1: a quiet tick), so the shards' trip counts differ
        desc = single._blank_desc()
        desc["inputs"][:] = _random_inputs(desc["inputs"].shape, seed=D)
        for b, depth in enumerate([0, 1, 2, 3, 5, 9, 1, 2]):
            desc["n_adv"][b] = depth
            if depth == 1:
                desc["pre_save"][b] = True
                desc["pre_frame"][b] = D
            elif depth > 1:
                desc["do_load"][b] = True
                desc["load_frame"][b] = D - (depth - 1)
                desc["save_mask"][b, : depth - 1] = True
                desc["save_frame"][b, : depth - 1] = (
                    D - (depth - 1) + 1 + np.arange(depth - 1)
                )
        # and one shard alone saves the state it loaded (sparse saving):
        # the conditional around that write is each shard's own too
        desc["postload_save"][3] = True
        desc["postload_frame"][3] = desc["load_frame"][3]
        descs.append(desc)
        for desc in descs:
            for ex in (single, sharded):
                # a copy each: the CPU backend may alias a host buffer
                ex._carry = ex._tick(
                    ex._carry, jax.tree_util.tree_map(np.copy, desc)
                )
        want, got = jax.device_get((single._carry, sharded._carry))
        for w, g in zip(jax.tree_util.tree_leaves(want),
                        jax.tree_util.tree_leaves(got)):
            np.testing.assert_array_equal(g, w)
        # the rollbacks wrote: the deepest shard's resimulation saved frames
        # 2..9, the last of them into the slot no quiet tick had filled
        assert sorted(got["ring"]["frames"][5].tolist()) == list(range(10))

"""Session parallelism: many independent sessions batched across a device mesh.

The reference runs exactly one session per process; scaling to hundreds of
matches means hundreds of processes.  On TPU the same hundreds of sessions are
one program: session state gets a leading batch axis (``vmap``), the batch is
sharded across chips over a 1-D ``Mesh`` with ``shard_map`` so each chip owns
``B / n_devices`` sessions, and the only cross-chip traffic is the scalar
health reduction (``psum`` over ICI — desync and frame counters), exactly the
collective-over-ICI design SURVEY §2's backend note calls for.  This is
BASELINE config 5's shape (256 concurrent SyncTest sessions on v5e-8).

Works identically on a virtual ``--xla_force_host_platform_device_count=N``
CPU mesh, which is how tests and the driver's multi-chip dry-run exercise it.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.replay import ReplayPrograms, build_replay_programs

SESSION_AXIS = "sessions"


def make_mesh(n_devices: Optional[int] = None, axis: str = SESSION_AXIS) -> Mesh:
    """1-D mesh over the first ``n_devices`` (default: all) devices."""
    devs = jax.devices()
    if n_devices is not None:
        assert n_devices <= len(devs), (
            f"asked for {n_devices} devices, have {len(devs)}"
        )
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


HOST_AXIS = "hosts"


def make_mesh2d(
    n_hosts: int,
    chips_per_host: int,
    axes: Tuple[str, str] = (HOST_AXIS, SESSION_AXIS),
) -> Mesh:
    """2-D ``(hosts, chips)`` mesh — the multi-host shape.

    On a real multi-host job (``jax.distributed``), ``jax.devices()`` spans
    every host and the natural factorization puts the slow interconnect (DCN)
    on the outer axis and ICI on the inner one, so XLA routes the per-host
    partial reductions over ICI and only the scalar host-level combine over
    DCN — the hierarchy SURVEY §2's backend note calls for.  ``BatchedSessions``
    accepts either mesh rank and shards its session axis over ALL mesh axes,
    so moving from one host to N is a mesh swap, not a program change.  Tests
    exercise the same program on a virtual ``(2, 4)`` CPU mesh.
    """
    devs = jax.devices()
    need = n_hosts * chips_per_host
    assert need <= len(devs), f"asked for {need} devices, have {len(devs)}"
    grid = np.asarray(devs[:need]).reshape(n_hosts, chips_per_host)
    return Mesh(grid, axes)


def make_distributed_mesh(
    axes: Tuple[str, str] = (HOST_AXIS, SESSION_AXIS),
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> Mesh:
    """The multi-host ``(hosts, chips)`` mesh for a real ``jax.distributed``
    job — ``make_mesh2d``'s launchable form.

    Call once per host process.  If the process is not yet part of a
    distributed job and a coordinator is known (arguments or the standard
    ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``
    environment), ``jax.distributed.initialize`` is called first; after
    that ``jax.devices()`` spans every host and the mesh is built host-major
    (outer axis = DCN between hosts, inner = ICI within a host), so
    ``BatchedSessions``' health ``psum`` reduces over ICI first and crosses
    DCN only for the per-host scalar combine.

    Two-host launch recipe (same binary on both, e.g. examples or a
    hosting server)::

        # host 0 (also the coordinator)
        JAX_COORDINATOR_ADDRESS=host0:8476 JAX_NUM_PROCESSES=2 \\
            JAX_PROCESS_ID=0 python my_server.py
        # host 1
        JAX_COORDINATOR_ADDRESS=host0:8476 JAX_NUM_PROCESSES=2 \\
            JAX_PROCESS_ID=1 python my_server.py

    where ``my_server.py`` does ``mesh = make_distributed_mesh()`` and
    passes it to ``BatchedSessions(..., mesh=mesh)`` — no other program
    change versus single-host.  On a single process (including the virtual
    CPU mesh) this degenerates to a ``(1, n_devices)`` mesh running the
    identical program, which is how tests and the driver's multi-chip
    dry-run keep it validated without multi-host hardware.
    """
    import os

    # jax.distributed.initialize must run before ANY jax call that could
    # initialize the XLA backend (even jax.process_count() does) — so decide
    # from args/env alone, touching no jax state first.  If the caller
    # already ran jax.distributed.initialize themselves, they must NOT also
    # provide coordinator args here (a second initialize raises).
    addr = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc = num_processes or int(
        os.environ.get("JAX_NUM_PROCESSES", "0") or 0
    )
    if addr and nproc > 1:
        pid = (
            process_id
            if process_id is not None
            else os.environ.get("JAX_PROCESS_ID")
        )
        if pid is None:
            raise ValueError(
                "make_distributed_mesh: a coordinator and num_processes are "
                "set but no process id — pass process_id= or export "
                "JAX_PROCESS_ID (defaulting to 0 would register every host "
                "as process 0 and deadlock the coordinator barrier)"
            )
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=nproc,
            process_id=int(pid),
        )

    devs = jax.devices()  # global list: spans every host once initialized
    n_hosts = jax.process_count()
    per_host = len(devs) // n_hosts
    if per_host * n_hosts != len(devs):
        raise RuntimeError(
            f"make_distributed_mesh: {len(devs)} devices do not divide over "
            f"{n_hosts} hosts"
        )
    grid = np.empty((n_hosts, per_host), dtype=object)
    fill = [0] * n_hosts
    for d in devs:
        p = d.process_index
        grid[p, fill[p]] = d
        fill[p] += 1
    if fill != [per_host] * n_hosts:
        raise RuntimeError(
            f"make_distributed_mesh: devices are not evenly attached per "
            f"host: {fill}"
        )
    return Mesh(grid, axes)


class BatchedSessions:
    """B independent device-synctest sessions as one sharded program.

    All sessions share the same (advance, check_distance) program but have
    independent states, inputs, and desync counters.  ``run_ticks`` dispatches
    one program for the whole batch; mismatch totals come back via an on-mesh
    ``psum`` so the host reads two scalars per call, regardless of B.
    """

    def __init__(
        self,
        advance: Callable[[Any, Any], Any],
        init_state: Any,
        input_template: Any,
        batch_size: int,
        mesh: Optional[Mesh] = None,
        check_distance: int = 2,
        max_prediction: int = 8,
    ) -> None:
        self.mesh = mesh if mesh is not None else make_mesh()
        n_dev = self.mesh.devices.size
        assert batch_size % n_dev == 0, (
            f"batch_size {batch_size} must divide evenly over {n_dev} devices"
        )
        self.batch_size = batch_size
        ring_length = max(max_prediction, check_distance) + 1
        self._programs: ReplayPrograms = build_replay_programs(
            advance, ring_length, check_distance, donate=False
        )
        self.check_distance = check_distance
        self._ticks_run = 0
        self._last_stats: Optional[Dict[str, Any]] = None

        # shard the leading (session) axis over EVERY mesh axis: on a 1-D
        # mesh that's plain chip-sharding; on a 2-D (hosts, chips) mesh the
        # batch splits host-major so reductions combine over ICI first, DCN
        # last (see make_mesh2d)
        axis_names = tuple(self.mesh.axis_names)
        spec_b = P(axis_names)
        sharding = NamedSharding(self.mesh, spec_b)
        self._sharding = sharding  # kept for checkpoint restore

        # one carry per session, stacked on a leading B axis and sharded
        carry0 = self._programs.init_carry(init_state, input_template)
        batched = jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(
                leaf[None, ...], (batch_size,) + leaf.shape
            ),
            carry0,
        )
        self._carry = jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, sharding), batched
        )

        def _sharded(
            scan_fn, carry: Any, inputs: Any, start_frame: Any
        ) -> Tuple[Any, Dict[str, Any]]:
            def local(carry_l: Any, inputs_l: Any):
                # start_frame enters as an UNBATCHED scalar closure: ring
                # slots stay shared-index slice ops instead of per-session
                # scatters (see ReplayPrograms doc — ~30× on this bench)
                out = jax.vmap(lambda c, i: scan_fn(c, i, start_frame))(
                    carry_l, inputs_l
                )
                stats = {
                    "mismatches": jax.lax.psum(
                        jnp.sum(out["mismatches"]), axis_names
                    ),
                    "first_bad": jax.lax.pmin(
                        jnp.min(out["first_bad"]), axis_names
                    ),
                }
                return out, stats

            return shard_map(
                local,
                mesh=self.mesh,
                in_specs=(spec_b, spec_b),
                out_specs=(spec_b, P()),
                check_vma=False,
            )(carry, inputs)

        self._run_warmup = jax.jit(partial(_sharded, self._programs.scan_warmup))
        self._run_steady = jax.jit(partial(_sharded, self._programs.scan_steady))

    # ------------------------------------------------------------------

    @property
    def current_frame(self) -> int:
        return self._ticks_run

    def run_ticks(self, inputs: Any, check: bool = True) -> Optional[Dict[str, int]]:
        """Advance all sessions ``n`` frames.  ``inputs`` leading axes are
        ``(B, n, ...per-frame...)``.  Returns global stats from the on-mesh
        reduction: total mismatches and earliest bad frame across all
        sessions.

        ``check=False`` defers the stats fetch: the call stays fully async
        (no device→host read, which would wait for the queued work) and
        returns None; read the accumulated result later with ``verify()``."""
        inputs = jax.tree_util.tree_map(jnp.asarray, inputs)
        leaf0 = jax.tree_util.tree_leaves(inputs)[0]
        assert leaf0.shape[0] == self.batch_size
        n = leaf0.shape[1]
        if n == 0:
            return {"mismatches": 0, "first_bad": np.iinfo(np.int32).max} if check else None
        n_warm = self._programs.split_at_warmup(self._ticks_run, n)
        stats = None
        if n_warm:
            head = jax.tree_util.tree_map(lambda a: a[:, :n_warm], inputs)
            self._carry, stats = self._run_warmup(
                self._carry, head, np.int32(self._ticks_run)
            )
        if n > n_warm:
            tail = jax.tree_util.tree_map(lambda a: a[:, n_warm:], inputs)
            self._carry, stats = self._run_steady(
                self._carry, tail, np.int32(self._ticks_run + n_warm)
            )
        self._ticks_run += n
        self._last_stats = stats  # device scalars; fetched on demand
        if not check:
            return None
        return self.verify()

    def verify(self) -> Dict[str, int]:
        """Fetch the deferred global stats (one transfer for both scalars)."""
        if self._last_stats is None:
            return {"mismatches": 0, "first_bad": np.iinfo(np.int32).max}
        mismatches, first_bad = jax.device_get(
            (self._last_stats["mismatches"], self._last_stats["first_bad"])
        )
        return {"mismatches": int(mismatches), "first_bad": int(first_bad)}

    def live_states(self) -> Any:
        """All B live states, gathered to host (leading axis B)."""
        return jax.device_get(self._carry["live"])

    # ------------------------------------------------------------------
    # durable checkpoints (beyond the reference — SURVEY §5 checkpoint note):
    # the whole batch's sharded carry gathers to host and resumes bit-exactly
    # on any mesh of the same total device count divisor (batch_size checks)
    # ------------------------------------------------------------------

    def save_checkpoint(self, path: str) -> None:
        """Write every session's carry + the tick counter to ``path``."""
        from ..utils.checkpoint import save_pytree

        save_pytree(
            path,
            self._carry,
            {
                "ticks_run": self._ticks_run,
                "check_distance": self.check_distance,
                "batch_size": self.batch_size,
            },
        )

    def load_checkpoint(self, path: str) -> None:
        """Restore a checkpoint written by ``save_checkpoint`` into this
        batch (same game, batch_size, and check_distance; the mesh may
        differ — leaves are re-placed under this batch's sharding)."""
        from ..core.errors import InvalidRequest
        from ..utils.checkpoint import load_pytree

        carry, meta = load_pytree(path, self._carry)
        if meta["check_distance"] != self.check_distance:
            raise InvalidRequest(
                f"checkpoint was taken at check_distance="
                f"{meta['check_distance']}, batch uses {self.check_distance}"
            )
        if meta["batch_size"] != self.batch_size:
            raise InvalidRequest(
                f"checkpoint holds {meta['batch_size']} sessions, batch was "
                f"built for {self.batch_size}"
            )
        # device_put straight from the host arrays: shards across the mesh in
        # one step (jnp.asarray first would commit each leaf to one device
        # and then reshard device-to-device — wasted copies on restore)
        self._carry = jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, self._sharding), carry
        )
        self._ticks_run = int(meta["ticks_run"])
        self._last_stats = None

    def block_until_ready(self) -> None:
        jax.block_until_ready(self._carry)

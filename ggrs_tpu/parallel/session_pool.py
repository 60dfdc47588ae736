"""Massed P2P hosting: fulfill MANY live sessions' request lists in ONE
device dispatch per tick.

The reference binds one rollback session to one process — a server hosting
hundreds of matches runs hundreds of processes, each paying its own
per-request state churn (/root/reference/src/sessions/p2p_session.rs:254-265).
``ops.DeviceRequestExecutor`` already moves a single session's save/load/
advance onto HBM, but a pool of N executors still costs N device dispatches
per tick — and for games this small the dispatch overhead, not the game,
is the bill.  This module batches the *fulfillment*: B independent host
sessions (P2P, SyncTest, Spectator — anything that emits the reference's
request grammar) hand their per-tick request lists to one
``BatchedRequestExecutor``,
which compiles a single uniform tick program over ``[B, ...]`` state and
dispatches it once for the whole pool.

Uniformity is the TPU trade: every session's tick is normalized to the same
fixed-shape descriptor —

    [pre-save*] [load [post-load-save]*] (advance, save?) * <= max_burst

— padded with masked no-ops, so heterogeneous ticks (one session rolling
back 8 frames, another advancing once, a third skipping on prediction
threshold) are ONE program with per-session predication, not B programs.
The burst loop runs as many steps as the deepest plan of the batch asks
(read on the device from the ``n_adv`` column), not ``max_burst``: a tick
in which no session rolls back runs one step, and a shallower session
idles through the steps of the deepest by its mask.
Grammar parity: the same ``Save | Load (Adv Save?)* | Adv`` request shapes
``ops.DeviceRequestExecutor`` executes (/root/reference/src/lib.rs:170-195).

Saved states live in per-session device rings ``[B, R, ...]`` tagged with
frame numbers and (optionally) 4-lane digests; ``GameStateCell``s are
fulfilled with lazy slot references and lazy checksums, so desync detection
and user ``cell.load()`` work unchanged while the live path never WAITS for
a device→host read: a pool that detects desyncs inside the host bank has
its digests fetched by one batched read a tick that wants any, enqueued
behind the dispatch and handed over when it has landed
(``_exchange_digests``; docs/DESIGN.md §4).  The carry is donated to every tick; a large ring leaf
is made, passed and returned in the layout the tick program computes in
(``ring_leaf_layout`` below, chosen per leaf from its shape where
``tick_program`` builds the carry), so no whole-ring transposition stands
at the program's entry or exit, and a save writes such a leaf in place: the
one slot a session saves, not a select over all of them, and the kernel that
writes it takes its digest from the block it holds (``ops/ring.py``
``write_slot``; docs/DESIGN.md §3 "Per-session slots").
"""

from __future__ import annotations

import contextlib
import functools
import math
from collections import deque
from collections.abc import Mapping
from typing import (
    Any,
    Callable,
    Dict,
    List,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from ..core.types import (
    AdvanceFrame,
    Frame,
    GgrsRequest,
    LoadGameState,
    NULL_FRAME,
    SaveGameState,
)
from ..obs.registry import default_registry
from ..obs.trace import default_tracer
from ..ops.checksum import CHECKSUM_LANES, checksum_device, checksum_to_u128

# obs (DESIGN.md §12): device-dispatch accounting for the pooled executor —
# process-wide counters, observational only
_OBS_DISPATCHES = default_registry().counter(
    "ggrs_executor_dispatches_total",
    "pooled tick programs dispatched to the device",
)
_OBS_EMPTY_TICKS = default_registry().counter(
    "ggrs_executor_empty_ticks_total",
    "run() calls where every session's request list was empty (no dispatch)",
)
_OBS_ROLLBACK_LOADS = default_registry().counter(
    "ggrs_executor_rollback_loads_total",
    "sessions that carried a LoadGameState (rollback) into a pooled tick",
)
_OBS_STATE_BYTES = default_registry().gauge(
    "ggrs_executor_state_bytes",
    "bytes of one session's state in the newest pooled executor",
)
_OBS_RING_RESIDENT_BYTES = default_registry().gauge(
    "ggrs_executor_ring_resident_bytes",
    "bytes the newest pooled executor's carry holds on its fullest device: "
    "ring slots, live states, kept digests and frames",
)
_OBS_RING_RELAID_BYTES = default_registry().gauge(
    "ggrs_executor_ring_relaid_bytes",
    "bytes, on its fullest device, of the ring leaves the newest pooled "
    "executor holds row-major between ticks instead of in the device's "
    "default layout (0: the rule left every leaf alone)",
)
_OBS_RING_INPLACE_BYTES = default_registry().gauge(
    "ggrs_executor_ring_inplace_bytes",
    "bytes, on its fullest device, of the ring leaves whose saves the newest "
    "pooled executor writes in place, one slot a session, instead of by a "
    "select over every slot (0: none is)",
)
_OBS_DIGEST_AT_WRITE_BYTES = default_registry().gauge(
    "ggrs_executor_digest_at_write_bytes",
    "bytes, on its fullest device, of one batch's state leaves whose digest "
    "the newest pooled executor takes in the kernel that writes their ring "
    "slot, not in a pass of its own over every session's state (0: none, or "
    "no digests are kept)",
)
_OBS_MESH_DEVICES = default_registry().gauge(
    "ggrs_executor_mesh_devices",
    "devices the newest pooled executor's session axis is sharded over "
    "(1: no mesh)",
)
_OBS_DIGESTS_MISSED = default_registry().counter(
    "ggrs_executor_checksum_frames_missed_total",
    "saved frames whose digest the host bank wanted for a ChecksumReport "
    "after their ring slot had been written again (never reported)",
)
_OBS_BURST_DEPTH = default_registry().histogram(
    "ggrs_executor_burst_depth_frames",
    "deepest per-session advance burst (replay depth) per dispatched tick",
    buckets=(1, 2, 4, 8, 16, 32),
)
_OBS_BACKEND_COMPILES = default_registry().counter(
    "ggrs_process_backend_compiles_total",
    "programs this process asked the backend compiler for (one fetched from "
    "the persistent cache counts too): a served pool compiles in warmup and "
    "never after",
)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _count_backend_compile(event: str, duration: float, **_: Any) -> None:
    """The program counts its own compiles (DESIGN.md §14): JAX reports each
    backend compile request on the thread that made it, so where the tracer
    records, the instant names the span and the tick the compile fell in."""
    if event != _BACKEND_COMPILE_EVENT:
        return
    _OBS_BACKEND_COMPILES.inc()
    tracer = default_tracer()
    if tracer.enabled:
        stack = tracer._thread_stack()
        parent, tick = stack[-1] if stack else (None, None)
        tracer.add_instant(
            "device.compile", secs=duration, parent=parent, tick=tick
        )


# once a process: the module is imported once, and the listener serves
# every executor and every other program the process compiles
jax.monitoring.register_event_duration_secs_listener(_count_backend_compile)


def _tree_where(pred: jax.Array, a: Any, b: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(pred, x, y), a, b
    )


# ----------------------------------------------------------------------
# the ring's layout at the program's boundary (docs/DESIGN.md §3)
# ----------------------------------------------------------------------

# A ring leaf smaller than this on a device keeps the device's default
# layout whatever its shape: both transposes of 32 MiB are under a quarter
# of a millisecond a tick (7 ms a gigabyte measured, PERF.md §6, PR 32), a
# sixtieth of the host tick that hides the program in every pool of that
# size, and the compiled texts the rule was read from are of leaves of
# hundreds of megabytes.
_RELAY_MIN_BYTES = 1 << 25
_LANES = 128  # a TPU tile's minor dimension
_SUBLANES = 8  # and the most its second-minor holds, of 4-byte elements


def ring_leaf_layout(
    shape: Sequence[int], itemsize: int
) -> Optional[Layout]:
    """The layout in which one device's share ``[B, R, ...]`` of a ring leaf
    is held between ticks: ``None`` for the device's default, or row-major
    over ``[B, R, ...]`` under the tile XLA:TPU itself computes in.

    The default layout of a TPU array puts the session axis minor-most
    when that saves padding; the tick program computes on a wide leaf
    row-major, and transposes the whole leaf at its entry and back at its
    exit unless the carry already arrives so.  Row-major pays where the
    minor-most dimension fills the 128 lanes (a 2-wide one would be padded
    sixty-four times over) and the leaf is large enough for the transposes
    to cost something.  Read off the shape alone: no option, no game's
    name."""
    if len(shape) < 3 or itemsize != 4:
        return None
    if shape[-1] < _LANES or math.prod(shape) * itemsize < _RELAY_MIN_BYTES:
        return None
    rows = 1
    while rows < min(shape[-2], _SUBLANES):
        rows *= 2
    return Layout(
        major_to_minor=tuple(range(len(shape))), tiling=((rows, _LANES),)
    )


# A program that RETURNS a re-laid leaf is compiled in the process that runs
# it, never loaded from JAX's persistent compilation cache: jaxlib 0.9.0
# labels every result of a deserialized executable with the device's default
# layout whatever layout the executable writes (the buffer is right, its
# label is not), and the next call's check of the argument against the
# ``Format`` it was compiled for then fails (PERF.md §6, PR 32;
# tests/test_ring_layout.py holds the jaxlib behaviour, so that an upgrade
# which cures it says so).  The price is the compile at every start, which
# is why the two programs concerned are compiled at a low effort: 2.6 s a
# start and a tick of 51.6 ms in particles-2p.wan-sat, where full effort
# reads 5.3 s and 50.6 ms and breaks the bound on setup_s (PERF.md §6).
_IN_PROCESS_COMPILER_OPTIONS = {"exec_time_optimization_effort": -1.0}


@contextlib.contextmanager
def _compiled_in_process(formats: Any):
    """What compiles inside bypasses the persistent compilation cache when
    ``formats`` re-lays any leaf; otherwise nothing changes."""
    if not jax.tree_util.tree_leaves(formats):
        yield
        return
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # the decision is taken once and kept
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def digest_fetch_program(
    batch_size: int, mesh: Optional["jax.sharding.Mesh"] = None
) -> Callable[[jax.Array, Any], jax.Array]:
    """The one batched read of saved frames' digests (docs/DESIGN.md §4):
    ``(ring checksums [B, R, 4], ring slots [B]) -> [B, 4]`` u32, session
    ``b``'s digest of the frame in its slot ``slots[b]``.  One fixed shape
    whatever the rows wanted, so one compile (in ``warmup``) and one
    transfer each way a tick that wants any digest; over a mesh every
    device reads its own sessions' rows, no collective."""

    def fetch(checksums: jax.Array, slots: jax.Array) -> jax.Array:
        with jax.named_scope("digest.fetch"):
            return jax.vmap(
                lambda row, s: jax.lax.dynamic_index_in_dim(
                    row, s, 0, keepdims=False
                )
            )(checksums, slots)

    if mesh is None:
        return jax.jit(fetch)
    from jax import shard_map
    from jax.sharding import PartitionSpec

    spec_b = PartitionSpec(tuple(mesh.axis_names))
    return jax.jit(
        shard_map(
            fetch,
            mesh=mesh,
            in_specs=(spec_b, spec_b),
            out_specs=spec_b,
            check_vma=False,
        )
    )


class TickProgram(NamedTuple):
    """A pool's device program, from shapes alone (nothing is allocated
    until ``init`` is called, so it can be lowered for a chip that is only
    described)."""

    init: Callable[[Any], Dict[str, Any]]  # one session's state -> the carry
    tick: Callable[[Dict[str, Any], Dict[str, Any]], Dict[str, Any]]
    formats: Dict[str, Any]  # carry-shaped: a Format where re-laid, else None
    carry: Dict[str, Any]  # carry-shaped ShapeDtypeStructs, placed as held
    # shaped like the ring's states: whether a save writes the leaf in place
    in_place: Any


def tick_program(
    advance: Callable[[Any, Any], Any],
    state0: Any,
    batch_size: int,
    ring_length: int,
    with_checksums: bool = True,
    mesh: Optional["jax.sharding.Mesh"] = None,
    device: Optional["jax.Device"] = None,
) -> TickProgram:
    """Build ``BatchedRequestExecutor``'s carry initialiser and tick program.

    ``device`` is the one device of a pool without a mesh: the process's
    default when ``None``; a described, unattached one to read the compiled
    program without a chip (``scripts/profile_tick.py --dump-hlo``)."""
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from ..ops.ring import DeviceStateRing, writes_slot_in_place

    B = batch_size
    dring = DeviceStateRing(ring_length)
    zero_cs = jnp.zeros((CHECKSUM_LANES,), jnp.uint32)

    if mesh is not None:
        # the session axis over every mesh axis; the rule reads a shard
        spec_b = PartitionSpec(tuple(mesh.axis_names))
        sharding: Any = NamedSharding(mesh, spec_b)
        shards = mesh.devices.size
        platform = mesh.devices.flat[0].platform
    else:
        if device is None:
            (device,) = jnp.zeros(()).devices()
        sharding = SingleDeviceSharding(device)
        shards = 1
        platform = device.platform

    # A re-laid leaf of one state dimension, [B, R, N], is held with a unit
    # axis, [B, R, 1, N]: row-major as it is, the RING axis would be the
    # tile's second-minor (ten slots padded to sixteen rows, one slot one row
    # of a tile that holds seven others); with the axis a slot of a session
    # is a block of its own under (1, 128), which the in-place write needs.
    unit_axis = jax.tree_util.tree_map(
        lambda l: l.ndim == 1
        and ring_leaf_layout(
            (B // shards, ring_length, 1) + l.shape, l.dtype.itemsize
        )
        is not None,
        state0,
    )

    def fresh(state0: Any) -> Dict[str, Any]:
        def per_session(l):
            return jnp.broadcast_to(l[None, ...], (B,) + l.shape)

        ring = jax.tree_util.tree_map(per_session, dring.init(state0))
        ring["states"] = jax.tree_util.tree_map(
            lambda l, unit: l[:, :, None] if unit else l,
            ring["states"],
            unit_axis,
        )
        return {
            "live": jax.tree_util.tree_map(per_session, state0),
            # one DeviceStateRing (states / checksums / frames) per session,
            # stacked on a leading B axis; its frame tags back the host-side
            # accessors and the _parse-time ring-capacity guard
            "ring": ring,
        }

    shapes = jax.eval_shape(fresh, state0)

    def ring_format(l: jax.ShapeDtypeStruct) -> Optional[Format]:
        layout = ring_leaf_layout(
            (l.shape[0] // shards,) + l.shape[1:], l.dtype.itemsize
        )
        return None if layout is None else Format(layout, sharding)

    formats = {
        "live": jax.tree_util.tree_map(lambda l: None, shapes["live"]),
        "ring": jax.tree_util.tree_map(ring_format, shapes["ring"]),
    }
    held = jax.tree_util.tree_map(
        lambda f: sharding if f is None else f,
        formats,
        is_leaf=lambda f: f is None,
    )
    carry = jax.tree_util.tree_map(
        lambda l, f: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=f),
        shapes,
        held,
    )
    options = (
        _IN_PROCESS_COMPILER_OPTIONS
        if jax.tree_util.tree_leaves(formats)
        else None
    )
    init = jax.jit(fresh, out_shardings=held, compiler_options=options)

    # which ring leaves a save writes in place, touching the one slot it
    # saves: those held row-major between ticks (a slot of a session is then
    # one contiguous block), where the kernel takes the shape; every other
    # leaf, and every leaf of a pool the rule leaves alone, keeps the select
    # (docs/DESIGN.md §3 "Per-session slots").  Off the TPU the same kernel
    # runs under Pallas's interpreter, so that the program tier-1 runs on
    # the CPU is the program the chip runs.
    in_place = jax.tree_util.tree_map(
        lambda l, f: f is not None
        and writes_slot_in_place(l.shape, l.dtype.itemsize),
        shapes["ring"]["states"],
        formats["ring"]["states"],
    )
    interpret = platform != "tpu"
    # and the digest of a save follows the write: where a kernel writes any
    # leaf, the kernels take the lane sums of what they write and
    # save_where_batch adds the select's leaves' (the same digest, bit for
    # bit); where the select writes every leaf, checksum_device as ever
    digest_at_write = any(jax.tree_util.tree_leaves(in_place))

    def tick(carry: Dict[str, Any], desc: Descriptor) -> Dict[str, Any]:
        """One tick of every session: the per-session pieces (the load, the
        game's step) under ``vmap``, the ring write and the digest of what
        it saves at batch level (``save_where_batch``).  Under ``shard_map``
        the batch is a shard's own: no collective."""
        live, ring = carry["live"], carry["ring"]
        # the one buffer of the shard's rows, unpacked into its ten fields
        desc = desc.unpack()
        sessions = desc["n_adv"].shape[0]
        # what the whole batch asks:
        n_steps = jnp.max(desc["n_adv"])  # its deepest plan
        # whether any session saves after its load
        any_postload = jnp.any(desc["postload_save"])

        # the scopes name the program's parts in a device profile
        # (metadata only: the lowered operations are the same)
        def write(ring, frame, st, pred):
            if not with_checksums:
                cs = jnp.broadcast_to(zero_cs, (sessions,) + zero_cs.shape)
            elif digest_at_write:
                # taken by the kernels that write, from the block they hold,
                # for the sessions that save
                cs = None
            else:
                with jax.named_scope("digest"):
                    cs = jax.vmap(checksum_device)(st)
            return dring.save_where_batch(
                ring, frame, st, cs, pred, in_place, interpret
            )

        per_session_where = jax.vmap(_tree_where)

        with jax.named_scope("ring.pre_save"):
            ring = write(ring, desc["pre_frame"], live, desc["pre_save"])
        with jax.named_scope("ring.load"):
            loaded = jax.vmap(dring.load)(ring, desc["load_frame"])
            st = per_session_where(
                desc["do_load"],
                # (a slot held with a unit axis, as the state has it)
                jax.tree_util.tree_map(
                    lambda got, l: got.reshape(l.shape), loaded, live
                ),
                live,
            )
        # sparse saving can save the just-loaded state before any advance
        # (reference: p2p_session.rs:666-672 — the min_confirmed save);
        # a batch in which no session does skips the write and its
        # digest: the ring passes through the conditional uncopied
        # (PERF.md section 5, PR 30)
        with jax.named_scope("ring.save"):
            ring = jax.lax.cond(
                any_postload,
                lambda ring: write(
                    ring, desc["postload_frame"], st, desc["postload_save"]
                ),
                lambda ring: ring,
                ring,
            )

        # the burst: as many trips as the deepest plan of the batch asks
        # (1 on a quiet tick, 2 with a one-frame rollback, max_burst at
        # most), not max_burst whatever the plans hold.  A step beyond
        # that is a no-op for every session (act false: the advance
        # discarded, the save's predicate false), so leaving it out
        # changes no byte.  The counter is the batch's, so the descriptor
        # columns are read at ONE index (a slice, not a gather); a
        # session whose plan is shallower idles through the rest by act.
        def step(j, carry):
            st, ring = carry
            inp, smask, sframe = (
                jax.lax.dynamic_index_in_dim(col, j, 1, keepdims=False)
                for col in (
                    desc["inputs"], desc["save_mask"], desc["save_frame"]
                )
            )
            act = j < desc["n_adv"]
            with jax.named_scope("advance"):
                st = per_session_where(act, jax.vmap(advance)(st, inp), st)
            with jax.named_scope("ring.save"):
                ring = write(ring, sframe, st, act & smask)
            return st, ring

        live, ring = jax.lax.fori_loop(
            jnp.int32(0), n_steps, step, (st, ring)
        )
        return {"live": live, "ring": ring}

    if mesh is not None:
        # sessions are independent: shard the B axis, no collectives
        from jax import shard_map

        tick = shard_map(
            tick,
            mesh=mesh,
            in_specs=(spec_b, spec_b),
            out_specs=spec_b,
            check_vma=False,
        )

    # the carry is donated on every backend (in-place ring update): the
    # program tier-1 runs on the CPU is the program the chip runs, so a
    # use of a donated buffer fails in a test, not as a deferred error
    # on the chip.  A re-laid ring leaf comes in and goes out in its
    # Format, so the donated buffer is the result's and no transpose
    # stands at either end; every other leaf is left to the default.
    return TickProgram(
        init,
        jax.jit(
            tick,
            donate_argnums=(0,),
            in_shardings=(formats, None),
            out_shardings=formats,
            compiler_options=options,
        ),
        formats,
        carry,
        in_place,
    )


class Descriptor(Mapping):
    """One tick's descriptor, the tick program's second argument: ONE host
    buffer ``packed``, ``u8[B, W]``, a row of W bytes a session, and its ten
    fields as NumPy views into it under their names (``desc["n_adv"]``,
    ``desc["inputs"]``), so a fill writes the buffer the call sends.

    A pytree of one leaf: ``row``, the row's NumPy structured dtype, is
    static, so a dispatch sends one buffer (over a mesh, one block of rows
    a device) and the program gets the ten fields back from ``unpack``."""

    __slots__ = ("packed", "row", "_fields")

    def __init__(self, packed: Any, row: np.dtype) -> None:
        self.packed = packed
        self.row = row
        self._fields: Optional[Dict[str, np.ndarray]] = None

    def __getitem__(self, name: str) -> np.ndarray:
        if self._fields is None:
            records = self.packed.view(self.row)[:, 0]  # [B, 1] -> [B]
            self._fields = {n: records[n] for n in self.row.names}
        return self._fields[name]

    def __iter__(self):
        return iter(self.row.names)

    def __len__(self) -> int:
        return len(self.row.names)

    def unpack(self) -> Dict[str, jax.Array]:
        """The device side: the ten fields, in the shapes and dtypes the
        views have, each a static slice of the rows (bitcast where wider
        than a byte, ``!= 0`` for a mask)."""
        packed = self.packed
        b = packed.shape[0]
        fields = {}
        for name in self.row.names:
            field, offset = self.row.fields[name][:2]
            base = field.base
            raw = packed[:, offset : offset + field.itemsize]
            if base == np.bool_:
                got = raw != 0
            else:
                got = jax.lax.bitcast_convert_type(
                    raw.reshape(b, -1, base.itemsize), base
                )
            fields[name] = got.reshape((b,) + field.shape)
        return fields


jax.tree_util.register_pytree_node(
    Descriptor,
    lambda d: ((d.packed,), d.row),
    lambda row, leaves: Descriptor(leaves[0], row),
)


@functools.lru_cache(maxsize=None)
def _desc_row(
    max_burst: int, input_shape: Tuple[int, ...], input_dtype: np.dtype
) -> np.dtype:
    """A session's descriptor row: the int32 fields first, then the inputs,
    then the masks, each at its natural alignment."""
    D, i32 = max_burst, np.int32
    return np.dtype(
        [
            ("pre_frame", i32),
            ("load_frame", i32),
            ("postload_frame", i32),
            ("n_adv", i32),
            ("save_frame", i32, (D,)),
            ("inputs", input_dtype, (D,) + input_shape),
            ("pre_save", np.bool_),
            ("do_load", np.bool_),
            ("postload_save", np.bool_),
            ("save_mask", np.bool_, (D,)),
        ],
        align=True,
    )


def blank_desc(
    batch_size: int, max_burst: int, input_shape: Tuple[int, ...], input_dtype
) -> Descriptor:
    """One tick's descriptor with every row idle: one ``np.zeros``."""
    row = _desc_row(max_burst, tuple(input_shape), np.dtype(input_dtype))
    return Descriptor(np.zeros((batch_size, row.itemsize), np.uint8), row)


class _BatchSlotRef:
    """What a fulfilled SaveGameState cell holds: a reference into the pool's
    device ring.  ``load()``/``data()`` on the cell returns this; materialize
    via the owning executor (a device gather + transfer — diagnostics only,
    the live path never calls it)."""

    __slots__ = ("owner", "index", "frame")

    def __init__(self, owner: "BatchedRequestExecutor", index: int, frame: Frame):
        self.owner = owner
        self.index = index
        self.frame = frame

    def materialize(self) -> Any:
        return self.owner.ring_state(self.index, self.frame)

    def __repr__(self) -> str:  # pragma: no cover
        return f"_BatchSlotRef(session={self.index}, frame={self.frame})"


class _LazyBatchChecksum:
    """Lazy u128 checksum handle backed by the pool's digest ring; satisfies
    ``GameStateCell.save``'s ``materialize()`` protocol so the desync
    exchange only pays a device read for frames it actually reports."""

    __slots__ = ("_owner", "_index", "_frame", "_value")

    def __init__(self, owner: "BatchedRequestExecutor", index: int, frame: Frame):
        self._owner = owner
        self._index = index
        self._frame = frame
        self._value: Optional[int] = None

    def materialize(self) -> int:
        if self._value is None:
            self._value = self._owner.ring_checksum(self._index, self._frame)
        return self._value


class BatchedRequestExecutor:
    """Fulfills B sessions' GgrsRequest lists with one dispatch per tick.

    ``advance``         pure JAX ``(state, inputs_array) -> state`` (unbatched;
                        the pool vmaps it).
    ``init_state``      one session's initial state pytree.
    ``inputs_to_array`` maps a request's ``[(input, status), ...]`` to the
                        array ``advance`` consumes — same contract as
                        ``ops.DeviceRequestExecutor``.
    ``batch_size``      B, the number of pooled sessions (index 0..B-1).
    ``ring_length``     saved-state slots per session; must exceed the
                        sessions' ``max_prediction`` (the reference keeps
                        ``max_prediction + 1`` cells, sync_layer.rs:144-166).
    ``max_burst``       most advances one tick can carry (rollback resims +
                        the live advance): ``max_prediction + 1`` for the
                        stock P2P session.
    ``mesh``            optional ``jax.sharding.Mesh``: shard the session
                        axis over every mesh axis (the device count must
                        divide ``batch_size``) so one pool spans chips — sessions
                        are independent, so the tick program needs no
                        collectives and scales linearly over ICI-attached
                        devices.  The descriptor, one host buffer a tick,
                        is split into a block of rows a device by the call.
    ``raw_inputs_to_array``  optional bulk twin of ``inputs_to_array`` for
                        the descriptor plane (DESIGN.md §21): called as
                        ``raw(blobs, statuses)`` with the ENCODED input
                        bytes ``[k, players, input_size]`` (u8) and status
                        codes ``[k, players]`` (u8) of k advances, it must
                        return the ``[k, ...]`` array ``advance`` consumes —
                        the vectorized equivalent of decoding each blob and
                        calling ``inputs_to_array`` per slot.  With it set,
                        a ``HostSessionPool`` RequestPlan's quiet slots are
                        consumed as flat NumPy columns: zero ``GgrsRequest``
                        objects, zero per-slot ``input_decode`` calls.
                        Without it, plans still work (per-slot
                        materialization — the reference semantics).
    """

    def __init__(
        self,
        advance: Callable[[Any, Any], Any],
        init_state: Any,
        inputs_to_array: Callable[[Sequence[Tuple[Any, Any]]], np.ndarray],
        batch_size: int,
        ring_length: int,
        max_burst: int,
        with_checksums: bool = True,
        mesh: Optional["jax.sharding.Mesh"] = None,
        raw_inputs_to_array: Optional[
            Callable[[np.ndarray, np.ndarray], np.ndarray]
        ] = None,
    ) -> None:
        assert batch_size >= 1 and ring_length >= 2 and max_burst >= 1
        self.batch_size = batch_size
        self.ring_length = ring_length
        self.max_burst = max_burst
        self._inputs_to_array = inputs_to_array
        self._raw_inputs = raw_inputs_to_array
        self._with_checksums = with_checksums
        # descriptor plane (§21): per-(session, ring slot) pooled
        # _BatchSlotRef/_LazyBatchChecksum pairs so the fast path's cell
        # fulfillment allocates nothing at steady state.  (Descriptor
        # buffers are deliberately NOT pooled — see _reset_desc.)
        self._ref_rings: List[Optional[List[Any]]] = [None] * batch_size
        self.mesh = mesh
        # devices the session axis is divided over, in contiguous blocks
        self._shards = mesh.devices.size if mesh is not None else 1
        assert batch_size % self._shards == 0, (
            f"batch_size {batch_size} must divide evenly over "
            f"{self._shards} mesh devices"
        )

        state0 = jax.tree_util.tree_map(jnp.asarray, init_state)
        program = tick_program(
            advance, state0, batch_size, ring_length, with_checksums, mesh
        )
        # made in the layout it is held in (one pass, no second ring); a
        # copy of init_state, never its buffers: the carry is donated
        self._formats = program.formats
        with _compiled_in_process(self._formats):
            self._carry: Dict[str, Any] = program.init(
                jax.tree_util.tree_map(np.asarray, state0)
            )
        self._tick = program.tick
        flat_formats = jax.tree_util.tree_leaves(
            self._formats, is_leaf=lambda f: f is None
        )
        relaid = [
            (leaf, f)
            for leaf, f in zip(
                jax.tree_util.tree_leaves(self._carry), flat_formats
            )
            if f is not None
        ]
        for leaf, f in relaid:
            if leaf.format.layout != f.layout:
                # a label that lies (see _compiled_in_process) would fail
                # the first tick less clearly, or not at all
                raise RuntimeError(
                    f"ring leaf {leaf.shape} was made in layout "
                    f"{leaf.format.layout}, not in {f.layout}"
                )
        # what the executor holds on the device, for a ledger line to tell
        # the ring's own bytes from the lowering's copies (DESIGN.md §14);
        # the session axis shards evenly, so a device's share is 1/size
        devices = self._shards
        _OBS_MESH_DEVICES.set(devices)
        _OBS_STATE_BYTES.set(
            sum(l.nbytes for l in jax.tree_util.tree_leaves(state0))
        )
        _OBS_RING_RESIDENT_BYTES.set(
            sum(l.nbytes for l in jax.tree_util.tree_leaves(self._carry))
            // devices
        )
        _OBS_RING_RELAID_BYTES.set(
            sum(leaf.nbytes for leaf, _ in relaid) // devices
        )

        def in_place_bytes(states: Any) -> int:
            return (
                sum(
                    leaf.nbytes
                    for leaf, direct in zip(
                        jax.tree_util.tree_leaves(states),
                        jax.tree_util.tree_leaves(program.in_place),
                    )
                    if direct
                )
                // devices
            )

        _OBS_RING_INPLACE_BYTES.set(in_place_bytes(self._carry["ring"]["states"]))
        _OBS_DIGEST_AT_WRITE_BYTES.set(
            in_place_bytes(self._carry["live"]) if with_checksums else 0
        )
        self._input_dtype: Optional[np.dtype] = None
        self._input_shape: Optional[Tuple[int, ...]] = None
        # tracing (DESIGN.md §14): device dispatch (fill + launch) and
        # fence spans on the process's default tracer, as the host pool's
        # are; assign another Tracer (or NULL_TRACER) to change that
        self.tracer = default_tracer()
        self._dispatched_tick = 0  # pool tick of the newest dispatch
        # set on a failed run(): once a tick aborts mid-parse, fulfilled
        # cells reference slots that were never written — every later use
        # must fail loudly instead of serving stale state
        self._invalid: Optional[str] = None
        # host shadow of the ring frame tags: loud failure at _parse time if
        # a session rolls back past ring_length (device aliasing is silent)
        self._host_frames = np.full(
            (batch_size, ring_length), -1, np.int64
        )

        # slot probe with TRACED indices: one compile covers every
        # (session, slot) the desync exchange ever reads.  Eager integer
        # indexing would bake the indices into the program and recompile per
        # distinct pair — measured ~1s of compile per exchange interval,
        # enough to trip real-clock disconnect timers mid-session.
        def _fetch(frames: jax.Array, checksums: jax.Array, b, s):
            row_f = jax.lax.dynamic_index_in_dim(frames, b, 0, keepdims=False)
            row_c = jax.lax.dynamic_index_in_dim(checksums, b, 0, keepdims=False)
            return (
                jax.lax.dynamic_index_in_dim(row_f, s, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(row_c, s, 0, keepdims=False),
            )

        self._fetch_slot = jax.jit(_fetch)
        # desync detection inside the host bank (DESIGN.md §4): the batched
        # digest read, and the reads in flight, oldest first: (pool, tick,
        # slots, frames, the [B, 4] result on its way to the host)
        self._fetch_digests = digest_fetch_program(batch_size, mesh)
        self._digest_fetches: deque = deque()
        # ticks from a digest being wanted to its landing on the host
        # (the newest, and the most); the bank sends it one crossing later
        self.checksum_lag_ticks = 0
        self.checksum_lag_ticks_max = 0

    # ------------------------------------------------------------------
    # request-list parsing (host, NumPy only — zero dispatches)
    # ------------------------------------------------------------------

    def _parse(
        self, index: int, requests: List[GgrsRequest], desc: Descriptor
    ) -> None:
        """Normalize one session's tick into the descriptor row ``index``,
        fulfilling its Save cells with lazy slot references.

        Fulfillment is eager (cell + ``_host_frames`` updated during parse)
        because the ring-capacity guard below must see this tick's pre-saves
        in DEVICE order — the tick program writes pre-saves before the load,
        so a pre-save that aliases the load's slot means the gather returns
        the pre-saved frame, and only the updated shadow catches that.  The
        flip side — a parse failure partway through ``run()`` leaves earlier
        sessions' cells pointing at slots the aborted dispatch never wrote —
        is handled by invalidating the whole pool (see ``run``)."""
        i = 0
        n = len(requests)
        b = index

        def fulfill_save(req: SaveGameState) -> None:
            self._host_frames[b, req.frame % self.ring_length] = req.frame
            req.cell.save(
                req.frame,
                _BatchSlotRef(self, b, req.frame),
                _LazyBatchChecksum(self, b, req.frame)
                if self._with_checksums
                else None,
            )

        # optional pre-save(s) of the live state (the frame-0 tick emits the
        # initial save AND the per-frame save, both of frame 0 — reference:
        # p2p_session.rs:307-310); all must label the same frame, since no
        # advance runs between them
        while i < n and isinstance(requests[i], SaveGameState):
            if desc["pre_save"][b] and desc["pre_frame"][b] != requests[i].frame:
                raise ValueError(
                    f"session {b}: consecutive pre-saves of different frames "
                    f"({desc['pre_frame'][b]} then {requests[i].frame})"
                )
            desc["pre_save"][b] = True
            desc["pre_frame"][b] = requests[i].frame
            fulfill_save(requests[i])
            i += 1

        if i < n and isinstance(requests[i], LoadGameState):
            req = requests[i]
            data = req.cell.data()
            # real exceptions, not asserts: these guards are the only thing
            # standing between an undersized ring and a silent desync, and
            # ``python -O`` strips asserts
            if not (
                isinstance(data, _BatchSlotRef)
                and data.owner is self
                and data.index == b
                and data.frame == req.frame
            ):
                raise ValueError(
                    f"session {b} loads frame {req.frame} from a cell this "
                    f"pool did not save ({data!r})"
                )
            # ring-capacity guard: the device gather cannot tell an aliased
            # slot from the right one, so check the host shadow of the frame
            # tags loudly here (a session whose max_prediction reaches
            # ring_length would otherwise silently load a NEWER frame)
            held = self._host_frames[b, req.frame % self.ring_length]
            if held != req.frame:
                raise RuntimeError(
                    f"session {b}: rollback to frame {req.frame} but its ring "
                    f"slot holds frame {held} — ring_length={self.ring_length} "
                    f"is too small for this session's prediction window"
                )
            desc["do_load"][b] = True
            desc["load_frame"][b] = req.frame
            i += 1
            # sparse saving: save of the just-loaded state before any advance
            while i < n and isinstance(requests[i], SaveGameState):
                if (
                    desc["postload_save"][b]
                    and desc["postload_frame"][b] != requests[i].frame
                ):
                    raise ValueError(
                        f"session {b}: consecutive post-load saves of "
                        f"different frames ({desc['postload_frame'][b]} then "
                        f"{requests[i].frame})"
                    )
                desc["postload_save"][b] = True
                desc["postload_frame"][b] = requests[i].frame
                fulfill_save(requests[i])
                i += 1

        j = 0
        while i < n and isinstance(requests[i], AdvanceFrame):
            if j >= self.max_burst:
                raise ValueError(
                    f"session {b}: tick carries more than max_burst="
                    f"{self.max_burst} advances"
                )
            # shapes were recorded by warmup(); _blank_desc asserts that
            desc["inputs"][b, j] = np.asarray(
                self._inputs_to_array(requests[i].inputs)
            )
            i += 1
            if i < n and isinstance(requests[i], SaveGameState):
                desc["save_mask"][b, j] = True
                desc["save_frame"][b, j] = requests[i].frame
                fulfill_save(requests[i])
                i += 1
            j += 1
        desc["n_adv"][b] = j
        if i != n:
            raise ValueError(
                f"session {b}: unsupported request shape at position {i}: "
                f"{requests[i]!r}"
            )

    def _blank_desc(self) -> Descriptor:
        assert self._input_shape is not None, (
            "call warmup(example_inputs) before the first run()"
        )
        return blank_desc(
            self.batch_size, self.max_burst,
            self._input_shape, self._input_dtype,
        )

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def warmup(self, example_inputs: Any) -> None:
        """Record the per-frame input array shape and compile the tick
        program before any live session exists (a compile stall inside a live
        loop trips real-clock disconnect timers — see ops executor warmup)."""
        arr = np.asarray(example_inputs)
        self._input_dtype = arr.dtype
        self._input_shape = arr.shape
        desc = self._blank_desc()
        with _compiled_in_process(self._formats):
            out = self._tick(self._carry, desc)
        jax.block_until_ready(out)
        # a no-op tick leaves the carry semantically unchanged; keep the
        # result, because the dispatch donated (invalidated) its input
        self._carry = out
        # the desync exchange's reads must be compiled up front too: the
        # slot probe (Python sessions, diagnostics) and the batched fetch
        # (sessions that detect inside the host bank)
        jax.block_until_ready(
            (
                self._fetch_slot(
                    self._carry["ring"]["frames"],
                    self._carry["ring"]["checksums"],
                    np.int32(0),
                    np.int32(0),
                ),
                self._fetch_digests(
                    self._carry["ring"]["checksums"],
                    np.zeros((self.batch_size,), np.int32),
                ),
            )
        )

    def _reset_desc(self) -> Descriptor:
        """A fresh descriptor buffer for one tick.  NOT reused in place:
        jax may alias a host numpy buffer zero-copy (CPU backend) and the
        dispatch is asynchronous, so mutating last tick's buffer while the
        program may still read it corrupts the dispatch silently."""
        return self._blank_desc()

    def _fulfill_fast(self, cells, b: int, frame: Frame) -> None:
        """Fulfill one save cell WITHOUT a SaveGameState object (the
        descriptor path): pooled per-(session, ring slot) refs refilled in
        place.  Refs are pooled per ring slot, not per session — an older
        cell in a different slot must keep seeing its own frozen frame."""
        ring = self._ref_rings[b]
        if ring is None:
            ring = self._ref_rings[b] = [
                (_BatchSlotRef(self, b, -1), _LazyBatchChecksum(self, b, -1))
                for _ in range(self.ring_length)
            ]
        slot = frame % self.ring_length
        ref, cs = ring[slot]
        ref.frame = frame
        if self._with_checksums:
            cs._frame = frame
            cs._value = None
        self._host_frames[b, slot] = frame
        cells.get_cell(frame).save(
            frame, ref, cs if self._with_checksums else None
        )

    def _check_load(self, cells, b: int, lf: int, n_adv: int) -> None:
        """The guards the request path applies through a LoadGameState
        cell, for a resim filled from its descriptor row: ownership and
        ring capacity; and the burst's depth."""
        data = cells.get_cell(lf).data()
        if not (
            isinstance(data, _BatchSlotRef)
            and data.owner is self
            and data.index == b
            and data.frame == lf
        ):
            raise ValueError(
                f"session {b} loads frame {lf} from a cell this pool did "
                f"not save ({data!r})"
            )
        held = self._host_frames[b, lf % self.ring_length]
        if held != lf:
            raise RuntimeError(
                f"session {b}: rollback to frame {lf} but its ring slot "
                f"holds frame {held} — ring_length={self.ring_length} is "
                f"too small for this session's prediction window"
            )
        if n_adv > self.max_burst:
            raise ValueError(
                f"session {b}: tick carries more than max_burst="
                f"{self.max_burst} advances"
            )

    def _fill_resim(self, plan, desc: Descriptor, b: int,
                    lf: int, n_adv: int, trailing: bool, adv_off: int,
                    adv_stride: int) -> None:
        """One rollback-resim slot straight from its descriptor row:
        load→advance^N with interleaved saves, inputs gathered from the
        tick output buffer — the ``load→advance^N→save`` program selection
        of DESIGN.md §21, no request objects."""
        cells = plan.saved_states(b)
        self._check_load(cells, b, lf, n_adv)
        desc["do_load"][b] = True
        desc["load_frame"][b] = lf
        desc["n_adv"][b] = n_adv
        players, isize = plan.players, plan.input_size
        span = players * (1 + isize)
        buf = plan.buffer
        raw = self._raw_inputs
        for j in range(n_adv):
            so = adv_off + j * adv_stride
            st = buf[so : so + players]
            blobs = buf[so + players : so + span].reshape(1, players, isize)
            desc["inputs"][b, j] = raw(blobs, st[None])[0]
            # every advance except (with a trailing live advance) the last
            # is followed by a save of the frame it produced: lf + 1 + j
            if (j < n_adv - 1) if trailing else True:
                f = lf + 1 + j
                desc["save_mask"][b, j] = True
                desc["save_frame"][b, j] = f
                self._fulfill_fast(cells, b, f)

    def _fill_sparse_resims(self, plan, desc: Descriptor) -> None:
        """The plan's sparse-saving resim rows straight from their
        descriptors: a load, ``n_adv`` advances, and at most one save, of
        ``save_frame``, which sits before advance ``k = save_frame - lf``:
        right after the load for k = 0 (the post-load save), else after
        advance k - 1.  Every row's advances are gathered from the tick
        output in one go: such rows are up to ``max_burst`` deep."""
        rows, steps, at = [], [], []
        for (b, lf, n_adv, _, adv_off, adv_stride,
             save_frame) in plan.sparse_resim_rows:
            cells = plan.saved_states(b)
            self._check_load(cells, b, lf, n_adv)
            desc["do_load"][b] = True
            desc["load_frame"][b] = lf
            desc["n_adv"][b] = n_adv
            k = save_frame - lf if save_frame != NULL_FRAME else -1
            for j in range(n_adv):
                rows.append(b)
                steps.append(j)
                # the save's op (9 bytes) shifts the advances after it
                at.append(adv_off + j * adv_stride + (9 if 0 < k <= j else 0))
            if k == 0:
                desc["postload_save"][b] = True
                desc["postload_frame"][b] = save_frame
            elif k > 0:
                desc["save_mask"][b, k - 1] = True
                desc["save_frame"][b, k - 1] = save_frame
            if k >= 0:
                self._fulfill_fast(cells, b, save_frame)
        statuses, blobs = plan.gather_at(np.asarray(at, np.int64))
        desc["inputs"][rows, steps] = self._raw_inputs(blobs, statuses)

    def _run_plan(self, plan) -> None:
        """The descriptor-plane run() (DESIGN.md §21): consume a
        ``HostSessionPool`` RequestPlan's flat columns directly — quiet
        slots fill the device descriptor vectorized, resim/save-only slots
        fill it per row, and only the plan's eager (slow/other) slots go
        through request materialization and the classic ``_parse``."""
        pool = plan.pool
        if plan.tick_no != pool._tick_no or plan is not pool._plan:
            # the same staleness contract the materialization surface
            # enforces: the columns view the pool's REUSED output buffer,
            # so consuming an old plan would dispatch garbage silently
            raise RuntimeError(
                "stale RequestPlan: request plans are only valid until "
                "the next advance_all"
            )
        rows = plan.quiet_rows
        adv_only = plan.adv_only_rows
        eager = list(plan.eager_rows)
        vector = self._raw_inputs is not None and plan.uniform
        any_work = bool(
            rows.size or adv_only.size or plan.resim_rows
            or plan.sparse_resim_rows or plan.save_only_rows
        ) or any(plan.lists[b] for b in eager)
        if not any_work:
            _OBS_EMPTY_TICKS.inc()
            return
        try:
            tracer = self.tracer
            with tracer.span("device.fill") as fill:
                # the fill's two halves, each a span (every session is of
                # one kind a tick and touches its own row only, so their
                # order is free): first the descriptor arrays,
                with tracer.span("device.descriptors"):
                    desc = self._reset_desc()
                    n_quiet = rows.size if vector else 0
                    if n_quiet:
                        frames = plan.quiet_frames
                        desc["pre_save"][rows] = True
                        desc["pre_frame"][rows] = frames
                        desc["n_adv"][rows] = 1
                        statuses, blobs = plan.gather_quiet()
                        desc["inputs"][rows, 0] = self._raw_inputs(
                            blobs, statuses
                        )
                    else:
                        # no bulk converter / non-uniform pool: quiet slots
                        # materialize like any other (reference semantics)
                        eager.extend(rows.tolist())
                    if vector and adv_only.size:
                        # a sparse session's quiet tick: advance, no save
                        desc["n_adv"][adv_only] = 1
                        statuses, blobs = plan.gather_at(plan.adv_only_at)
                        desc["inputs"][adv_only, 0] = self._raw_inputs(
                            blobs, statuses
                        )
                    else:
                        eager.extend(adv_only.tolist())
                    for (b, lf, n_adv, trailing, adv_off,
                         adv_stride) in plan.resim_rows:
                        if vector:
                            self._fill_resim(plan, desc, b, lf, n_adv,
                                             trailing, adv_off, adv_stride)
                        else:
                            eager.append(b)
                    if vector and plan.sparse_resim_rows:
                        self._fill_sparse_resims(plan, desc)
                    elif plan.sparse_resim_rows:
                        eager.extend(row[0] for row in plan.sparse_resim_rows)
                    for b in eager:
                        reqs = plan[b]
                        if reqs:
                            self._parse(b, reqs, desc)
                    # the ring writes and the rows, with the save-only rows
                    # the fulfill below adds (each its one pre-save)
                    saves, rows_carried = self._ring_writes(desc)
                    only = len(plan.save_only_rows)
                    writes = (saves + only, rows_carried + only)
                # then the one Python call a quiet or save-only row
                # (ROADMAP A1 (iii))
                with tracer.span(
                    "device.fulfill",
                    rows=n_quiet + len(plan.save_only_rows),
                ):
                    # _fulfill_fast writes the _host_frames shadow too —
                    # one writer for the ring tags
                    if n_quiet:
                        for b, f in zip(rows.tolist(), frames.tolist()):
                            self._fulfill_fast(plan.saved_states(b), b, f)
                    for b, f in plan.save_only_rows:
                        desc["pre_save"][b] = True
                        desc["pre_frame"][b] = f
                        self._fulfill_fast(plan.saved_states(b), b, f)
                self._count_dispatch(desc, fill, writes)
            self._launch(desc)
        except BaseException as e:  # incl. KeyboardInterrupt mid-fill
            self._invalid = f"{type(e).__name__}: {e}"
            raise

    @staticmethod
    def _ring_writes(desc: Descriptor) -> Tuple[int, int]:
        """``(saves, rows)``: the ring writes a descriptor carries
        (pre-save, post-load and per-step) and the sessions it carries."""
        pre = desc["pre_save"]
        saves = (np.count_nonzero(pre) + np.count_nonzero(desc["postload_save"])
                 + np.count_nonzero(desc["save_mask"]))
        rows = np.count_nonzero(pre | desc["do_load"]
                                | desc["n_adv"].astype(bool))
        return int(saves), int(rows)

    def _count_dispatch(self, desc: Descriptor, fill,
                        writes: Tuple[int, int]) -> None:
        """The filled descriptor's counts, on the registry and on the
        ``device.fill`` span that ends here; ``writes`` is its
        ``_ring_writes``."""
        loads = int(desc["do_load"].sum())
        max_burst = int(desc["n_adv"].max())
        _OBS_DISPATCHES.inc()
        _OBS_ROLLBACK_LOADS.inc(loads)
        _OBS_BURST_DEPTH.observe(max_burst)
        # max_burst is the tick program's trip count, burst_cap the most it
        # can be: their ratio says how much of the burst loop the traffic
        # left out (benchmark: burst_steps_share).  saves over rows: how
        # many of the sessions it carries write the ring, and how often
        # (benchmark: save_rows_share)
        saves, rows = writes
        fill.set(loads=loads, max_burst=max_burst, burst_cap=self.max_burst,
                 saves=saves, rows=rows)

    def _launch(self, desc: Descriptor) -> None:
        """The call of the tick program: transfer of the descriptor and
        enqueue; in a closed loop the runtime's back-pressure too.  The
        descriptor is one host buffer of all sessions' rows: over a mesh the
        call splits it on the session axis and sends every device its block,
        so one dispatch is one transfer a device (DESIGN.md §3; benchmark:
        launch_transfers_per_dispatch)."""
        shards = self._shards
        tracer = self.tracer
        args = dict(shards=shards, transfers=shards, dispatches=1)
        if tracer.enabled:
            # who paces, from inside: had the device finished the previous
            # dispatch (whose result the carry is) when the host came with
            # the next?  Asked of one small leaf, never waited for, and no
            # reference outlives the call: the carry is donated below
            args["device_ready"] = int(
                self._carry["ring"]["frames"].is_ready()
            )
        with tracer.span("device.launch", **args):
            self._carry = self._tick(self._carry, desc)

    def run(self, request_lists: Sequence[List[GgrsRequest]]) -> None:
        """Fulfill all B sessions' request lists — ONE device dispatch (zero
        if every list is empty).  ``request_lists[b]`` belongs to session
        ``b``; sessions with nothing to do this tick pass ``[]``.

        A ``HostSessionPool`` RequestPlan (the descriptor plane, §21) is
        consumed through its flat columns — no ``GgrsRequest`` objects are
        constructed for fast-path slots."""
        self._check_valid()
        if len(request_lists) != self.batch_size:
            raise ValueError(
                f"run() got {len(request_lists)} request lists for a pool of "
                f"{self.batch_size} sessions"
            )
        tick = getattr(request_lists, "tick_no", None)
        self._dispatched_tick = (
            tick if tick is not None else self._dispatched_tick + 1
        )
        with self.tracer.span("device.dispatch"):
            if getattr(request_lists, "quiet_rows", None) is not None:
                self._run_plan(request_lists)
            else:
                self._run_lists(request_lists)
        wanted = getattr(request_lists, "checksum_wanted", None)
        if wanted is not None or self._digest_fetches:
            self._exchange_digests(request_lists, wanted)

    def _exchange_digests(self, request_lists, wanted) -> None:
        """Desync detection inside the host bank (DESIGN.md §4): hand the
        pool the digests that have landed, and start the read of those the
        bank asked for this tick.  Never waits for the device: a read is
        enqueued behind the dispatch that precedes it (so it sees the ring
        as that dispatch leaves it, and the next dispatch's donation of the
        carry is ordered after it), its copy to the host starts at once,
        and it is handed over by the first later tick that finds it
        landed."""
        fetches = self._digest_fetches
        tick = self._dispatched_tick
        landed = 0
        for fetch in fetches:  # in the order asked: reports go out so
            if not fetch[4].is_ready():
                break
            landed += 1
        if wanted is None and not landed:
            return  # a read in flight, nothing to do about it this tick
        tracer = self.tracer
        with tracer.span("device.checksum_fetch") as span:
            rows = 0
            for _ in range(landed):
                pool, asked, slots, frames, out = fetches.popleft()
                with tracer.span("checksum.deliver", rows=len(slots)):
                    pool.deliver_checksums(
                        slots, frames, np.asarray(out)[slots]
                    )
                rows += len(slots)
                self.checksum_lag_ticks = tick - asked
                self.checksum_lag_ticks_max = max(
                    self.checksum_lag_ticks_max, tick - asked
                )
            if wanted is not None:
                slots, frames = wanted
                with tracer.span("checksum.ask", rows=len(slots)):
                    ring_slots = np.zeros((self.batch_size,), np.int32)
                    ring_slots[slots] = frames % self.ring_length
                    # a session that reports one frame a tick can fall
                    # behind its ring when a burst of confirmations makes
                    # several interval frames due at once (intervals under
                    # the window; the Python session fails its assert
                    # there): such a frame is never reported, the next are
                    kept = (
                        self._host_frames[slots, ring_slots[slots]] == frames
                    )
                    if not kept.all():
                        _OBS_DIGESTS_MISSED.inc(int((~kept).sum()))
                        slots, frames = slots[kept], frames[kept]
                    if len(slots):
                        out = self._fetch_digests(
                            self._carry["ring"]["checksums"], ring_slots
                        )
                        out.copy_to_host_async()
                        fetches.append(
                            (request_lists.pool, tick, slots, frames, out)
                        )
            span.set(
                wanted=0 if wanted is None else len(wanted[0]),
                landed=rows, lag_ticks=self.checksum_lag_ticks,
                in_flight=len(fetches),
            )

    def _run_lists(self, request_lists: Sequence[List[GgrsRequest]]) -> None:
        if all(not reqs for reqs in request_lists):
            _OBS_EMPTY_TICKS.inc()
            return
        # parse fulfills cells eagerly (the ring-capacity guard needs this
        # tick's pre-saves visible in device order — see _parse); if any
        # session's list fails to parse, or the dispatch itself fails,
        # earlier sessions already hold cells referencing slots this aborted
        # tick never wrote, so the pool is unusable: poison it loudly rather
        # than let a caller that caught the error keep running on stale loads
        try:
            with self.tracer.span("device.fill") as fill:
                desc = self._reset_desc()
                for b, reqs in enumerate(request_lists):
                    if reqs:
                        self._parse(b, reqs, desc)
                self._count_dispatch(desc, fill, self._ring_writes(desc))
            self._launch(desc)
        except BaseException as e:  # incl. KeyboardInterrupt mid-parse
            self._invalid = f"{type(e).__name__}: {e}"
            raise

    # ------------------------------------------------------------------
    # accessors (device reads — diagnostics / desync exchange, not hot path)
    # ------------------------------------------------------------------

    @property
    def live_states(self) -> Any:
        """The [B, ...] live state pytree (device handles; no transfer)."""
        self._check_valid()
        return self._carry["live"]

    def live_state(self, index: int) -> Any:
        """One session's live state, fetched to host."""
        self._check_valid()
        return jax.device_get(
            jax.tree_util.tree_map(lambda l: l[index], self._carry["live"])
        )

    def _check_valid(self) -> None:
        if self._invalid is not None:
            raise RuntimeError(
                f"pool was invalidated by an earlier failed tick "
                f"({self._invalid}); rebuild it — its rings and fulfilled "
                f"cells are out of sync"
            )

    def _slot_probe(self, index: int, frame: Frame):
        """(slot, held_frame, checksum_lanes) via the precompiled traced-index
        fetch — one program for every (session, slot), one transfer for both
        scalars."""
        self._check_valid()
        slot = frame % self.ring_length
        held, lanes = jax.device_get(
            self._fetch_slot(
                self._carry["ring"]["frames"],
                self._carry["ring"]["checksums"],
                np.int32(index),
                np.int32(slot),
            )
        )
        if int(held) != frame:
            raise RuntimeError(
                f"session {index}: ring slot {slot} holds frame {int(held)}, "
                f"wanted {frame} (rolled past ring_length={self.ring_length}?)"
            )
        return slot, lanes

    def ring_state(self, index: int, frame: Frame) -> Any:
        """A saved state, fetched to host (validates the slot still holds
        ``frame``).  Diagnostics path — eager slicing is fine here."""
        slot, _ = self._slot_probe(index, frame)
        return jax.device_get(
            jax.tree_util.tree_map(
                # (a slot held with a unit axis, as the state has it)
                lambda buf, l: buf[index, slot].reshape(l.shape[1:]),
                self._carry["ring"]["states"],
                self._carry["live"],
            )
        )

    def ring_checksum(self, index: int, frame: Frame) -> int:
        """A saved frame's u128 checksum (validates the slot)."""
        assert self._with_checksums, "pool was built with with_checksums=False"
        _, lanes = self._slot_probe(index, frame)
        return checksum_to_u128(lanes)

    def ring_frames(self) -> np.ndarray:
        """``[B, ring_length]``: the frame each ring slot holds, as the
        device tags it (negative: never written), fetched to host — the
        tags ``ring_state`` and ``ring_checksum`` validate a slot against."""
        self._check_valid()
        return np.asarray(jax.device_get(self._carry["ring"]["frames"]))

    def block_until_ready(self) -> None:
        with self.tracer.root_span("device.fence", tick=self._dispatched_tick):
            jax.block_until_ready(self._carry)


class HostedPool:
    """The full massed-hosting tick, both halves pooled: a
    ``host_bank.HostSessionPool`` steps all B sessions' protocol + sync
    mechanism in ONE ctypes crossing, and a ``BatchedRequestExecutor``
    fulfills the B request lists in ONE device dispatch — two crossings of
    any boundary per pool tick, total, regardless of B.

    ``host_pool`` must hold the same sessions, in the same order, as the
    executor's batch indices.  When the native bank does not engage the
    host half runs per-session Python sessions (identical request lists,
    per-session cost): ``host.native_active`` / ``host.native_reason`` say
    which tier is serving and why — assert them, a measurement must not
    infer the tier from timing.
    """

    def __init__(self, host_pool, executor: BatchedRequestExecutor) -> None:
        if len(host_pool) != executor.batch_size:
            raise ValueError(
                f"host pool has {len(host_pool)} sessions but the executor "
                f"was built for batch_size={executor.batch_size}"
            )
        self.host = host_pool
        self.executor = executor

    def tick(self, local_inputs: Sequence[Tuple[int, int, Any]]) -> None:
        """One pool tick: stage ``(session_index, handle, value)`` local
        inputs (ONE batched native call on the descriptor plane, §21),
        advance every session, fulfill every request list."""
        host = self.host
        # the root span of the served tick (DESIGN.md §14): the host pool's
        # and the executor's spans nest in it when they share its tracer,
        # which the process's default tracer makes so
        with host.tracer.root_span("hosted.tick", tick=host._tick_no + 1):
            stage = getattr(host, "stage_inputs", None)
            if stage is not None:
                stage(local_inputs)
            else:
                add = host.add_local_input
                for index, handle, value in local_inputs:
                    add(index, handle, value)
            self.executor.run(host.advance_all())

    def block_until_ready(self) -> None:
        self.executor.block_until_ready()

"""Speculative rollback: skip the replay entirely when a branch guessed right.

The reference rolls back and resimulates every time a prediction was wrong
(/root/reference/src/sessions/p2p_session.rs:658-714) — and its single
repeat-last predictor is wrong whenever a remote player changes input.  On
TPU we can afford K predictions at once: this module keeps K branch
trajectories *incrementally extended each tick* under K different
remote-input hypotheses, so when confirmed inputs arrive and a rollback is
requested, a matching branch turns the whole load→(advance, save)^N replay
into a device-side select.  Misses fall back to the replay — correctness
never depends on a hit.

Zero device→host reads on the live path.  The round-1 design read the
hit/miss flag back to the host per rollback; a D2H read makes the host
wait for everything the device has queued (a pipeline stall on any
transport), so the redesign moves the decision on-device:

- branch states, trajectories, hypothesized inputs, and prefix-validity masks
  live in fixed-shape ``[W, K, ...]`` device ring buffers;
- ``extend`` is ONE fused dispatch (vmap advance + hypothesis match + buffer
  writes);
- ``fulfill`` is ONE fused dispatch per rollback: hypothesis matching, branch
  selection, and the fallback replay scan are a single ``lax.cond`` program,
  so the host never learns (or needs to learn) whether it hit — it always
  receives the correct per-step trajectory as device handles;
- ``refill`` re-anchors and re-extends the window after a rollback as one
  fused scan;
- hit counters accumulate on device and are only fetched when the
  ``spec_hits`` property is read (diagnostics, after timing).

``branch_inputs(k, frame, local_inputs)`` builds hypothesis k's full input
array for ``frame`` on the host; return **NumPy** arrays to keep hypothesis
construction off the dispatch path (JAX arrays are accepted but each costs an
eager device op).  ``DeviceRequestExecutor`` drives this through its
``speculation`` constructor argument — see ``ops.executor`` and
``tests/test_spec_integration.py``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

AdvanceFn = Callable[[Any, Any], Any]
# branch_inputs(branch_k, frame, tick_inputs_array) -> full inputs array for
# branch k at ``frame`` (local players' real inputs merged with hypothesis
# k's remote inputs; the session's own prediction arrives as ``tick_inputs``
# so the identity function is the "trust the predictor" branch)
BranchInputsFn = Callable[[int, int, Any], Any]


def _stack_pytrees(trees: Sequence[Any]) -> Any:
    """Stack pytrees on a new leading axis (branch or time, per the caller),
    staying on the host when every leaf is NumPy — the single H2D transfer
    then happens inside the consuming jit instead of as eager device ops."""

    def stack(*leaves: Any) -> Any:
        if all(isinstance(l, np.ndarray) for l in leaves):
            return np.stack(leaves)
        return jnp.stack([jnp.asarray(l) for l in leaves])

    return jax.tree_util.tree_map(stack, *trees)


def _swap01(tree: Any) -> Any:
    """Swap the two leading axes of every leaf, host-side when NumPy."""
    return jax.tree_util.tree_map(
        lambda l: np.swapaxes(l, 0, 1)
        if isinstance(l, np.ndarray)
        else jnp.swapaxes(jnp.asarray(l), 0, 1),
        tree,
    )


class SpeculativeRollback:
    """K incrementally-extended branch trajectories rooted at a saved frame.

    Usage per tick:
      - ``root(frame, state)`` whenever the rollback anchor moves (a Save of
        the confirmed frame);
      - ``extend(local_inputs)`` once per advanced frame: every branch steps
        under its own hypothesis (ONE fused dispatch for all K);
      - on rollback to ``frame``: if ``window_valid(frame, n)``, call
        ``fulfill`` (one fused resolve-or-replay dispatch) then ``refill`` to
        re-anchor; otherwise ``invalidate`` and replay normally.
    """

    def __init__(
        self,
        advance: AdvanceFn,
        num_branches: int,
        branch_inputs: BranchInputsFn,
        max_window: int = 16,
        branch_inputs_all: Optional[Callable[[int, Any], Any]] = None,
    ) -> None:
        assert num_branches >= 1
        self.K = num_branches
        self.max_window = max_window
        self._advance = advance
        self._branch_inputs = branch_inputs
        # optional vectorized hypothesis builder: one call producing the whole
        # [K, ...] stack for a frame instead of K per-branch calls — hypothesis
        # construction runs on the host every extend, so for large K the
        # per-branch Python loop becomes the tick's overhead
        self._branch_inputs_all = branch_inputs_all

        self._root_frame: Optional[int] = None
        self._count = 0  # host-tracked window length (never read from device)
        self._states: Any = None  # [K, ...] current branch states
        self._traj_buf: Any = None  # [W, K, ...] post-advance states
        self._inp_buf: Any = None  # [W, K, ...] hypothesized inputs
        self._prefix_buf: Optional[jax.Array] = None  # [W, K] cumulative ok
        self._hit_count = jnp.zeros((), jnp.uint32)

        self._root_fn = jax.jit(self._root_impl)
        # donate the [W, K, ...] ring buffers so the per-tick slot write
        # updates HBM in place instead of copying the whole window (same
        # treatment as ops.replay's carry, on every backend) — warmup()
        # must hand scratch buffers to these programs, never the live ones
        # it restores afterwards
        self._extend_fn = jax.jit(
            self._extend_impl, donate_argnums=(1, 2, 3)
        )

        def _adv_ext(live_state, live_inputs, *extend_args):
            return (
                advance(live_state, live_inputs),
                *self._extend_impl(*extend_args),
            )

        self._adv_ext_fn = jax.jit(
            _adv_ext, donate_argnums=(3, 4, 5)
        )
        self._fulfill_cache: Dict[Tuple[int, bool], Any] = {}
        self._fulfill_refill_cache: Dict[Tuple[int, bool], Any] = {}
        self._refill_cache: Dict[int, Any] = {}
        self._resolve_cache: Dict[int, Any] = {}

    # ------------------------------------------------------------------
    # fused programs
    # ------------------------------------------------------------------

    def _match(self, hyp: Any, target: Any) -> jax.Array:
        """[K] mask: which branches' hypothesis pytree equals ``target``."""

        def leaf_eq(h: jax.Array, c: Any) -> jax.Array:
            c = jnp.asarray(c)
            return jnp.all((h == c[None, ...]).reshape(self.K, -1), axis=1)

        eqs = jax.tree_util.tree_map(leaf_eq, hyp, target)
        return jax.tree_util.tree_reduce(
            jnp.logical_and, eqs, jnp.ones((self.K,), bool)
        )

    def _root_impl(self, state: Any) -> Any:
        return jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(
                jnp.asarray(leaf)[None, ...],
                (self.K,) + jnp.shape(jnp.asarray(leaf)),
            ),
            state,
        )

    def _extend_impl(
        self,
        states: Any,
        traj_buf: Any,
        inp_buf: Any,
        prefix_buf: jax.Array,
        t: jax.Array,
        inputs_k: Any,
        local_inputs: Any,
    ) -> Tuple[Any, Any, Any, jax.Array]:
        new_states = jax.vmap(self._advance)(states, inputs_k)
        step_ok = self._match(inputs_k, local_inputs)
        prev = jnp.where(
            t > 0, prefix_buf[jnp.maximum(t - 1, 0)], jnp.ones((self.K,), bool)
        )
        write = lambda buf, val: jax.tree_util.tree_map(
            lambda b, v: b.at[t].set(v), buf, val
        )
        return (
            new_states,
            write(traj_buf, new_states),
            write(inp_buf, inputs_k),
            prefix_buf.at[t].set(prev & step_ok),
        )

    def _resolve_window(
        self,
        traj_buf: Any,
        inp_buf: Any,
        prefix_buf: jax.Array,
        offset: jax.Array,
        load_state: Any,
        confirmed: Any,  # [n, ...] stacked
        n: int,
        with_checksums: bool,
    ):
        """Traced core shared by every fulfill program: hypothesis matching,
        branch selection, and the fallback replay as one ``lax.cond``.
        Returns ``(steps, sums, hit)`` — the n per-step post-advance states,
        their digests (or None), and the device hit flag."""
        from ..ops.checksum import checksum_device

        sl = lambda buf: jax.tree_util.tree_map(
            lambda b: jax.lax.dynamic_slice_in_dim(b, offset, n, axis=0),
            buf,
        )
        win_inp, win_traj = sl(inp_buf), sl(traj_buf)
        match = jnp.where(
            offset > 0,
            prefix_buf[jnp.maximum(offset - 1, 0)],
            jnp.ones((self.K,), bool),
        )
        frame_at = lambda tree, t: jax.tree_util.tree_map(
            lambda l: l[t], tree
        )
        for t in range(n):
            match = match & self._match(
                frame_at(win_inp, t), frame_at(confirmed, t)
            )
        hit = jnp.any(match)
        idx = jnp.argmax(match)

        def take_branch(_):
            return jax.tree_util.tree_map(
                lambda l: jax.lax.dynamic_index_in_dim(
                    l, idx, axis=1, keepdims=False
                ),
                win_traj,
            )

        def replay(_):
            def body(st: Any, inp: Any):
                nxt = self._advance(st, inp)
                return nxt, nxt

            _, ys = jax.lax.scan(body, load_state, confirmed)
            return ys

        out = jax.lax.cond(hit, take_branch, replay, None)
        steps = [frame_at(out, t) for t in range(n)]
        sums = [checksum_device(s) for s in steps] if with_checksums else None
        return steps, sums, hit

    def _extend_scan(self, states0: Any, hyps: Any, session_inputs: Any):
        """Traced core shared by refill programs: extend K branches over the
        [m, K, ...] hypotheses, matching each step against the session's own
        [m, ...] inputs.  Returns (states, traj, prefixes)."""

        def body(carry, xs):
            st, prefix = carry
            hyp_k, sess = xs
            nxt = jax.vmap(self._advance)(st, hyp_k)
            prefix = prefix & self._match(hyp_k, sess)
            return (nxt, prefix), (nxt, prefix)

        (states, _), (traj, prefixes) = jax.lax.scan(
            body,
            (states0, jnp.ones((self.K,), bool)),
            (hyps, session_inputs),
        )
        return states, traj, prefixes

    def _build_fulfill(self, n: int, with_checksums: bool):
        def fulfill(
            traj_buf: Any,
            inp_buf: Any,
            prefix_buf: jax.Array,
            offset: jax.Array,
            load_state: Any,
            confirmed: Any,  # [n, ...] stacked
            hit_count: jax.Array,
        ):
            steps, sums, hit = self._resolve_window(
                traj_buf, inp_buf, prefix_buf, offset, load_state,
                confirmed, n, with_checksums,
            )
            return steps, sums, hit_count + hit.astype(jnp.uint32)

        return jax.jit(fulfill)

    def _build_fulfill_refill(
        self, n: int, with_checksums: bool, with_live: bool = False
    ):
        """fulfill + re-anchor + re-extend as ONE program: the rollback's
        resolve-or-replay, rooting the branches at the window's first frame,
        and re-hypothesizing the confirmed tail — so a speculative rollback
        costs exactly one dispatch, the same as the plain fused replay.

        ``with_live`` additionally fuses the tick's trailing *live* advance
        (the saveless AdvanceFrame that follows every rollback burst) and the
        matching one-frame window extension into the same program: the whole
        rollback tick then costs ONE dispatch, exactly like the plain path's
        single load+replay+advance burst."""
        m = n - 1
        m_ext = m + (1 if with_live else 0)
        def fused(
            traj_buf: Any,
            inp_buf: Any,
            prefix_buf: jax.Array,
            offset: jax.Array,
            load_state: Any,
            confirmed: Any,  # [n, ...] stacked
            hyps: Any,  # [m_ext, K, ...] stacked (None when m_ext=0)
            hit_count: jax.Array,
            live_inputs: Any = None,  # only when with_live
        ):
            steps, sums, hit = self._resolve_window(
                traj_buf, inp_buf, prefix_buf, offset, load_state,
                confirmed, n, with_checksums,
            )
            # re-anchor at steps[0] and extend the confirmed tail (plus, when
            # fused, the live frame hypothesized against the live inputs)
            states = self._root_impl(steps[0])
            if m_ext:
                tail = jax.tree_util.tree_map(lambda l: l[1:], confirmed)
                if with_live:
                    tail = jax.tree_util.tree_map(
                        lambda c, lv: jnp.concatenate(
                            [c, jnp.asarray(lv)[None]], axis=0
                        ),
                        tail,
                        live_inputs,
                    )
                states, traj, prefixes = self._extend_scan(states, hyps, tail)
                put = lambda buf, val: jax.tree_util.tree_map(
                    lambda b, v: jax.lax.dynamic_update_slice_in_dim(
                        b, v, 0, axis=0
                    ),
                    buf,
                    val,
                )
                traj_buf = put(traj_buf, traj)
                inp_buf = put(inp_buf, hyps)
                prefix_buf = jax.lax.dynamic_update_slice_in_dim(
                    prefix_buf, prefixes, 0, axis=0
                )
            live = (
                self._advance(steps[-1], live_inputs) if with_live else None
            )
            return (
                steps,
                sums,
                hit_count + hit.astype(jnp.uint32),
                states,
                traj_buf,
                inp_buf,
                prefix_buf,
                live,
            )

        return jax.jit(fused, donate_argnums=(0, 1, 2))

    def _build_refill(self, m: int):
        def refill(root_state: Any, hyps: Any, session_inputs: Any):
            """Re-anchor at ``root_state`` and extend ``m`` steps under
            ``hyps`` ([m, K, ...]), matching against ``session_inputs``
            ([m, ...]); returns (states, traj [m,K,...], prefix [m,K])."""
            return self._extend_scan(self._root_impl(root_state), hyps, session_inputs)

        return jax.jit(refill)

    # ------------------------------------------------------------------
    # buffers
    # ------------------------------------------------------------------

    def _ensure_buffers(self, inputs_k: Any) -> None:
        if self._traj_buf is not None:
            return
        W = self.max_window
        alloc = lambda tree: jax.tree_util.tree_map(
            lambda l: jnp.zeros((W,) + jnp.shape(l), jnp.asarray(l).dtype),
            tree,
        )
        self._traj_buf = alloc(self._states)
        self._inp_buf = alloc(
            jax.tree_util.tree_map(jnp.asarray, inputs_k)
        )
        self._prefix_buf = jnp.zeros((W, self.K), bool)

    def _hypotheses(self, frame: int, local_inputs: Any) -> Any:
        if self._branch_inputs_all is not None:
            return self._branch_inputs_all(frame, local_inputs)
        per_branch = [
            self._branch_inputs(k, frame, local_inputs) for k in range(self.K)
        ]
        return _stack_pytrees(per_branch)

    def _window_hypotheses(self, frame: int, inputs_seq: Sequence[Any]) -> Any:
        """Hypotheses for a whole window as ``[m, K, ...]``: branch k's
        inputs for frames ``frame + t`` built from ``inputs_seq[t]``.  Shared
        by ``refill`` and ``fulfill_and_refill`` — their windows must stay
        frame-offset-identical for the fused program's promise
        ("equals refill(frame + 1, steps[0], confirmed[1:])") to hold."""
        if self._branch_inputs_all is not None:
            return _stack_pytrees(
                [
                    self._branch_inputs_all(frame + t, inputs_seq[t])
                    for t in range(len(inputs_seq))
                ]
            )
        hyps = _stack_pytrees(
            [
                _stack_pytrees(
                    [
                        self._branch_inputs(k, frame + t, inputs_seq[t])
                        for t in range(len(inputs_seq))
                    ]
                )
                for k in range(self.K)
            ]
        )
        # built as [K, m, ...]; scan wants [m, K, ...]
        return _swap01(hyps)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    @property
    def window(self) -> int:
        return self._count

    @property
    def root_frame(self) -> Optional[int]:
        return self._root_frame

    @property
    def hits(self) -> int:
        """Fetches the device hit counter — call only outside timed paths."""
        return int(jax.device_get(self._hit_count))

    def invalidate(self) -> None:
        """Drop the anchor and the whole window.  Callers MUST invalidate on
        any rollback that is not fulfilled by ``fulfill`` + ``refill``: such a
        rollback disproves the predicted inputs the prefix masks were
        validated against, so the window is unsound from then on.  ``extend``
        no-ops and ``window_valid`` is false until the next ``root``."""
        self._root_frame = None
        self._states = None
        self._count = 0

    def root(self, frame: int, state: Any) -> None:
        """Re-anchor all branches at ``state`` (the save of ``frame``)."""
        self._root_frame = frame
        self._states = self._root_fn(state)
        self._count = 0

    def extend(self, local_inputs: Any) -> None:
        """Advance every branch one frame under its hypothesis — one fused
        dispatch.  The frame being hypothesized is ``root_frame + window``
        (extensions are sequential from the anchor)."""
        if self._root_frame is None or self._count >= self.max_window:
            return
        inputs_k = self._hypotheses(self._root_frame + self._count, local_inputs)
        self._ensure_buffers(inputs_k)
        (
            self._states,
            self._traj_buf,
            self._inp_buf,
            self._prefix_buf,
        ) = self._extend_fn(
            self._states,
            self._traj_buf,
            self._inp_buf,
            self._prefix_buf,
            np.int32(self._count),
            inputs_k,
            local_inputs,
        )
        self._count += 1

    def advance_and_extend(self, state: Any, inputs: Any) -> Optional[Any]:
        """Advance the live ``state`` AND extend all K branches in ONE fused
        dispatch — speculation's steady-state tick costs the same dispatch
        count as running without it.  Returns the new live state, or None
        when the window cannot extend (unrooted / full): the caller must then
        advance the live state itself (``extend`` would no-op identically)."""
        if self._root_frame is None or self._count >= self.max_window:
            return None
        inputs_k = self._hypotheses(self._root_frame + self._count, inputs)
        self._ensure_buffers(inputs_k)
        (
            new_state,
            self._states,
            self._traj_buf,
            self._inp_buf,
            self._prefix_buf,
        ) = self._adv_ext_fn(
            state,
            inputs,
            self._states,
            self._traj_buf,
            self._inp_buf,
            self._prefix_buf,
            np.int32(self._count),
            inputs_k,
            inputs,
        )
        self._count += 1
        return new_state

    def window_valid(self, frame: int, n: int) -> bool:
        """Host-side check (no device read): can a rollback to ``frame``
        covering ``n`` resimulated frames be answered from this window?"""
        if self._root_frame is None or n < 1:
            return False
        offset = frame - self._root_frame
        return 0 <= offset and offset + n <= self._count

    def fulfill(
        self,
        frame: int,
        confirmed: Sequence[Any],
        load_state: Any,
        with_checksums: bool,
    ) -> Tuple[List[Any], Optional[List[Any]]]:
        """Resolve-or-replay as ONE dispatch: returns the ``n`` per-step
        post-advance states for the rollback window (device handles) and,
        when requested, their device checksum lanes.  The states come from the
        matching branch when one hypothesized exactly these inputs, else from
        the fallback replay of ``load_state`` — the host never reads which.

        Requires ``window_valid(frame, len(confirmed))``.  ``frame`` may lie
        past the root: rollback targets are the first mispredicted frame, so
        every frame between root and target was predicted correctly — a
        branch is valid iff its hypotheses equalled the session's own inputs
        over that prefix (the ``_prefix_buf`` masks) and the confirmed inputs
        from the target on."""
        n = len(confirmed)
        assert self.window_valid(frame, n)
        key = (n, with_checksums)
        fn = self._fulfill_cache.get(key)
        if fn is None:
            fn = self._fulfill_cache[key] = self._build_fulfill(
                n, with_checksums
            )
        steps, sums, self._hit_count = fn(
            self._traj_buf,
            self._inp_buf,
            self._prefix_buf,
            np.int32(frame - self._root_frame),
            load_state,
            _stack_pytrees(confirmed),
            self._hit_count,
        )
        return steps, sums

    def fulfill_and_refill(
        self,
        frame: int,
        confirmed: Sequence[Any],
        load_state: Any,
        with_checksums: bool,
        live_inputs: Any = None,
    ) -> Union[
        Tuple[List[Any], Optional[List[Any]]],
        Tuple[List[Any], Optional[List[Any]], Any],
    ]:
        """``fulfill`` plus the post-rollback re-anchor/re-extend in ONE
        dispatch: resolve-or-replay the window, root the branches at
        ``frame + 1`` (the next rollback's steady-state target), and
        re-hypothesize the still-unconfirmed tail.  Same return value as
        ``fulfill``; the window afterwards equals ``refill(frame + 1,
        steps[0], confirmed[1:])``.

        With ``live_inputs``, the tick's trailing live advance rides the same
        dispatch: the return gains a third element — the live state
        ``advance(steps[-1], live_inputs)`` — and the window also extends one
        hypothesized frame for the live frame (``frame + n``), exactly as a
        subsequent ``advance_and_extend`` would have."""
        n = len(confirmed)
        assert self.window_valid(frame, n)
        m = n - 1
        with_live = live_inputs is not None
        tail = list(confirmed[1:])
        if with_live:
            tail.append(live_inputs)
        hyps = self._window_hypotheses(frame + 1, tail) if tail else None
        key = (n, with_checksums, with_live)
        fn = self._fulfill_refill_cache.get(key)
        if fn is None:
            fn = self._fulfill_refill_cache[key] = self._build_fulfill_refill(
                n, with_checksums, with_live
            )
        args = [
            self._traj_buf,
            self._inp_buf,
            self._prefix_buf,
            np.int32(frame - self._root_frame),
            load_state,
            _stack_pytrees(confirmed),
            hyps,
            self._hit_count,
        ]
        if with_live:
            args.append(live_inputs)
        (
            steps,
            sums,
            self._hit_count,
            self._states,
            self._traj_buf,
            self._inp_buf,
            self._prefix_buf,
            live,
        ) = fn(*args)
        self._root_frame = frame + 1
        self._count = m + (1 if with_live else 0)
        if with_live:
            return steps, sums, live
        return steps, sums

    def refill(self, frame: int, state: Any, local_inputs: Sequence[Any]) -> None:
        """Re-anchor at ``(frame, state)`` and re-extend the still-unconfirmed
        tail (``local_inputs``, one per frame from ``frame`` on) as one fused
        dispatch — the post-rollback replacement for root + N×extend."""
        m = min(len(local_inputs), self.max_window)
        local_inputs = list(local_inputs)[:m]
        self._root_frame = frame
        if m == 0:
            self._states = self._root_fn(state)
            self._count = 0
            return
        hyps = self._window_hypotheses(frame, local_inputs)
        sess = _stack_pytrees(local_inputs)
        fn = self._refill_cache.get(m)
        if fn is None:
            fn = self._refill_cache[m] = self._build_refill(m)
        self._states, traj, prefixes = fn(state, hyps, sess)
        if self._traj_buf is None:
            # allocate from the first hypothesis row; states are already [K,..]
            self._ensure_buffers(
                jax.tree_util.tree_map(lambda l: l[0], hyps)
            )
        put = lambda buf, val: jax.tree_util.tree_map(
            lambda b, v: jax.lax.dynamic_update_slice_in_dim(b, v, 0, axis=0),
            buf,
            val,
        )
        self._traj_buf = put(self._traj_buf, traj)
        self._inp_buf = put(self._inp_buf, hyps)
        self._prefix_buf = jax.lax.dynamic_update_slice_in_dim(
            self._prefix_buf, prefixes, 0, axis=0
        )
        self._count = m

    def warmup(
        self,
        state: Any,
        example_inputs: Any,
        depths: Sequence[int],
        with_checksums: bool,
    ) -> None:
        """Pre-compile every program a live session can dispatch — the fused
        extend, advance+extend, and per-depth fulfill/refill — so no jit
        compile ever stalls the poll/ack pump mid-session.  Runs on scratch
        data; all window state (including the device hit counter) is restored
        afterwards."""
        saved = (
            self._root_frame,
            self._count,
            self._states,
            self._traj_buf,
            self._inp_buf,
            self._prefix_buf,
            self._hit_count,
        )
        try:
            # fresh scratch buffers: the fused programs donate their ring
            # buffers on TPU, so the saved live buffers must never be
            # handed to them here (they would be invalidated)
            self._traj_buf = None
            self._inp_buf = None
            self._prefix_buf = None
            self.root(0, state)
            self.advance_and_extend(state, example_inputs)
            for n in sorted(set(depths)):
                if not 1 <= n <= self.max_window:
                    continue
                for live in (None, example_inputs):
                    self.root(0, state)
                    for _ in range(n):
                        self.extend(example_inputs)
                    self.fulfill_and_refill(
                        0, [example_inputs] * n, state, with_checksums,
                        live_inputs=live,
                    )
            jax.block_until_ready(self._states)
        finally:
            (
                self._root_frame,
                self._count,
                self._states,
                self._traj_buf,
                self._inp_buf,
                self._prefix_buf,
                self._hit_count,
            ) = saved

    # ------------------------------------------------------------------
    # diagnostic / test API (reads device→host; not for the live path)
    # ------------------------------------------------------------------

    def resolve(
        self, frame: int, confirmed: Sequence[Any]
    ) -> Optional[List[Any]]:
        """Match hypotheses against the ``confirmed`` input arrays for the
        frames from ``frame`` on; returns the matched branch's per-step states
        or None.  Reads the hit flag back to the host — use ``fulfill`` on
        live paths."""
        n = len(confirmed)
        if not self.window_valid(frame, n):
            return None
        fn = self._resolve_cache.get(n)
        if fn is None:

            def resolve_n(
                traj_buf, inp_buf, prefix_buf, offset, confirmed_stacked
            ):
                sl = lambda buf: jax.tree_util.tree_map(
                    lambda b: jax.lax.dynamic_slice_in_dim(
                        b, offset, n, axis=0
                    ),
                    buf,
                )
                win_inp, win_traj = sl(inp_buf), sl(traj_buf)
                match = jnp.where(
                    offset > 0,
                    prefix_buf[jnp.maximum(offset - 1, 0)],
                    jnp.ones((self.K,), bool),
                )
                for t in range(n):
                    match = match & self._match(
                        jax.tree_util.tree_map(lambda l: l[t], win_inp),
                        jax.tree_util.tree_map(lambda l: l[t], confirmed_stacked),
                    )
                hit = jnp.any(match)
                idx = jnp.argmax(match)
                traj = jax.tree_util.tree_map(
                    lambda l: jax.lax.dynamic_index_in_dim(
                        l, idx, axis=1, keepdims=False
                    ),
                    win_traj,
                )
                return hit, traj

            fn = self._resolve_cache[n] = jax.jit(resolve_n)
        hit, traj = fn(
            self._traj_buf,
            self._inp_buf,
            self._prefix_buf,
            np.int32(frame - self._root_frame),
            _stack_pytrees(confirmed),
        )
        if not bool(jax.device_get(hit)):
            return None
        return [
            jax.tree_util.tree_map(lambda l, _t=t: l[_t], traj)
            for t in range(n)
        ]

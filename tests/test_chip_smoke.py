"""Pins for the chip path's guard rails (ISSUE 21), on the CPU.

``chip_smoke.py`` itself proves the system on the chip; what tier-1 can pin
is that nothing lets a run LOOK fine without one: the default invocation
refuses the CPU, the rehearsal walks every leg but cannot print the passing
verdict, an unknown device is an error, the compile cache goes where it was
placed from outside (and otherwise to one fixed path), and the native
library is trusted by the content it was built from, its build errors kept.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from ggrs_tpu.net import _native
from ggrs_tpu.utils import device

REPO = Path(__file__).resolve().parents[1]


def _smoke(*args: str, tmp_path: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    # placed from outside: the run must not write a cache into the checkout
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600,
    )


class TestChipSmoke:
    def test_default_invocation_refuses_the_cpu(self, tmp_path):
        r = _smoke(tmp_path=tmp_path)
        assert r.returncode != 0
        assert r.stdout == "", "no chip, no result"
        last = r.stderr.strip().splitlines()[-1]
        assert "no TPU" in last and "cpu" in last, last

    def test_rehearsal_runs_every_leg_but_cannot_pass(self, tmp_path):
        r = _smoke("--rehearse-cpu", tmp_path=tmp_path)
        assert r.returncode not in (0, 1), (r.returncode, r.stderr[-2000:])
        lines = r.stdout.strip().splitlines()
        # the last line is the verdict, with exactly the contract's keys
        verdict = json.loads(lines[-1])
        assert list(verdict) == ["ok", "device"] and verdict["ok"] is False
        assert list(verdict["device"]) == ["platform", "kind", "count"]
        assert verdict["device"]["platform"] == "cpu"
        assert isinstance(verdict["device"]["kind"], str)
        assert type(verdict["device"]["count"]) is int
        summary = json.loads(lines[-2].partition("summary: ")[2])
        assert summary["ok"] is False and summary["rehearsal"] is True
        assert summary["device"] == verdict["device"]
        assert summary["claim"] is None
        assert list(summary)[-1] == "claim"
        assert [name for name in summary["legs"]] == [
            "pool", "fence", "synctest", "games", "pallas", "udp"
        ]
        assert all(l["verdict"] == "pass" for l in summary["legs"].values())
        assert summary["legs"]["pallas"]["interpreted"] is True
        pool = summary["legs"]["pool"]
        assert pool["crossings"] == pool["plan_ticks"] == pool["ticks"]
        assert pool["rollback_loads"] > 0 and pool["compiles_in_ticks"] == 0
        # desync detection on, inside the bank: at least one report
        # compared a session, none differing
        assert pool["native"] == "native bank engaged"
        assert pool["checksum_reports_compared"] >= pool["sessions"]
        assert (pool["checksum_reports_sent"]
                >= pool["checksum_reports_compared"])
        assert pool["desyncs"] == 0
        # every line a rehearsal prints says what it is
        assert all(
            l.startswith("REHEARSAL") for l in lines[:-1]
        ), [l[:40] for l in lines[:-1]]
        assert '"ok": true' not in r.stdout

    def test_failed_leg_ends_on_a_false_verdict_and_is_not_survived(
            self, tmp_path):
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [sys.executable, "-c",
             "import chip_smoke as c\n"
             "def boom(*a): c.check(False, 'games: made to fail')\n"
             "c.leg_games = boom\n"
             "raise SystemExit(c.main(['--rehearse-cpu', '--legs', 'games']))"],
            cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600,
        )
        assert r.returncode == 1, (r.returncode, r.stderr[-2000:])
        assert "SmokeFailure: games: made to fail" in r.stderr
        verdict = json.loads(r.stdout.strip().splitlines()[-1])
        assert list(verdict) == ["ok", "device"] and verdict["ok"] is False


class TestDeviceModule:
    def test_require_chip_rejects_the_cpu(self):
        with pytest.raises(device.NoChipError, match="no TPU"):
            device.require_chip()

    def test_unknown_device_kind_is_an_error(self, monkeypatch):
        monkeypatch.setattr(
            device, "device_record",
            lambda: {"platform": "tpu", "kind": "TPU v9 imaginary", "count": 1},
        )
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(device.NoChipError, match="TPU v9 imaginary"):
            device.require_chip()
        with pytest.raises(device.NoChipError):
            device.device_peaks("cpu")
        assert device.device_peaks("TPU v5 lite")["hbm_gbs"] == 819.0

    def test_chip_count_the_machine_lacks_is_refused(self, monkeypatch):
        monkeypatch.setattr(
            device, "device_record",
            lambda: {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        )
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert device.require_chip(1)["count"] == 1
        with pytest.raises(device.NoChipError, match="4 chips"):
            device.require_chip(4)

    @pytest.fixture
    def cache_config(self):
        keys = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs")
        before = {k: getattr(jax.config, k) for k in keys}
        yield
        for k, v in before.items():
            jax.config.update(k, v)

    def test_cache_dir_set_outside_is_left_alone(self, monkeypatch,
                                                 cache_config):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        before = jax.config.jax_compilation_cache_dir
        assert device.place_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before

    def test_cache_defaults_to_the_fixed_checkout_path(self, monkeypatch,
                                                       cache_config):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        fixed = str(REPO / ".jax_cache")
        assert device.place_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        # sub-second programs are cached, deliberately
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_cpu_backend_gets_no_cache_unless_placed(self, monkeypatch,
                                                     cache_config):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        assert device.place_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before


needs_gxx = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no g++ toolchain"
)


@needs_gxx
class TestNativeBuildProvenance:
    @pytest.fixture
    def native_copy(self, tmp_path, monkeypatch):
        """A private copy of native/ the loader is pointed at."""
        src = tmp_path / "native"
        shutil.copytree(REPO / "native", src)
        monkeypatch.setattr(_native, "_native_dir", lambda: src)
        return src

    def test_broken_source_surfaces_the_compilers_stderr(self, tmp_path,
                                                         native_copy):
        (native_copy / "codec.cpp").write_text(
            (native_copy / "codec.cpp").read_text()
            + "\nthis_is_not_cxx deliberately broken;\n"
        )
        lib = tmp_path / "_broken.so"
        with pytest.raises(_native.NativeBuildError) as exc:
            _native.ensure_built(lib)
        assert "this_is_not_cxx" in str(exc.value)
        assert "error" in str(exc.value)
        assert not lib.exists()
        assert not list(tmp_path.glob("_broken.so.build.*"))

    def test_library_with_another_digest_is_rebuilt_not_trusted(
            self, tmp_path, native_copy):
        lib = tmp_path / "_copy.so"
        # something that rode in with a copy of the tree: right name, fresh
        # mtime, wrong content
        lib.write_bytes(b"\x7fELF not the library these sources build")
        assert not _native._is_current(lib)
        assert _native.ensure_built(lib) == lib
        assert _native._is_current(lib)
        built = lib.read_bytes()
        assert _native.ensure_built(lib) == lib
        assert lib.read_bytes() == built, "a current library was rebuilt"
        # touching the CONTENT of a header (not its mtime) makes it stale
        header = native_copy / "wire_common.h"
        os.utime(header, None)
        assert _native._is_current(lib)
        header.write_text(header.read_text() + "\n// edited\n")
        assert not _native._is_current(lib)
        _native.ensure_built(lib)
        assert _native._is_current(lib) and lib.read_bytes() != built

    def test_pool_says_why_it_is_or_is_not_native(self, monkeypatch):
        import random

        from ggrs_tpu.core import Local, Remote
        from ggrs_tpu.games import boxgame_config
        from ggrs_tpu.net import InMemoryNetwork
        from ggrs_tpu.parallel import HostSessionPool
        from ggrs_tpu.sessions import SessionBuilder

        def pool():
            net = InMemoryNetwork()
            p = HostSessionPool()
            for me in (0, 1):
                p.add_session(
                    SessionBuilder(boxgame_config())
                    .with_clock(lambda: 0)
                    .with_rng(random.Random(me))
                    .add_player(Local(), me)
                    .add_player(Remote(f"peer{1 - me}"), 1 - me),
                    net.socket(f"peer{me}"),
                )
            return p

        p = pool()
        assert p.native_active and p.native_reason == "native bank engaged"
        monkeypatch.setenv("GGRS_TPU_NO_NATIVE", "1")
        p = pool()
        assert not p.native_active
        assert "GGRS_TPU_NO_NATIVE" in p.native_reason


class TestOneProgramOnBothBackends:
    """Donation is unconditional: the CPU suite runs the donating programs
    the chip runs, so holding on to a donated buffer fails here."""

    def test_pool_tick_and_replay_donate_their_carry_on_the_cpu(self):
        import numpy as np

        import jax.numpy as jnp

        from ggrs_tpu.core.types import AdvanceFrame, InputStatus
        from ggrs_tpu.games import BoxGame
        from ggrs_tpu.ops import build_replay_programs
        from ggrs_tpu.parallel import BatchedRequestExecutor

        game = BoxGame(2)
        ex = BatchedRequestExecutor(
            game.advance, game.init_state(),
            lambda pairs: np.asarray([p[0] for p in pairs], np.uint8),
            batch_size=2, ring_length=10, max_burst=9,
        )
        ex.warmup(np.zeros((2,), np.uint8))
        before = jax.tree_util.tree_leaves(ex._carry)
        ex.run([
            [AdvanceFrame(inputs=[(np.uint8(1), InputStatus.CONFIRMED)] * 2)],
            [],
        ])
        assert all(leaf.is_deleted() for leaf in before)
        assert ex.live_state(0)["pos"].shape == (2, 2)  # the new carry lives

        progs = build_replay_programs(game.advance, 9, 8)
        carry = progs.init_carry(game.init_state(), jnp.zeros((2,), jnp.uint8))
        leaves = jax.tree_util.tree_leaves(carry)
        progs.run_warmup(
            carry, jnp.zeros((progs.warmup_ticks, 2), jnp.uint8)
        )
        assert all(leaf.is_deleted() for leaf in leaves)
        with pytest.raises(RuntimeError, match="deleted"):
            np.asarray(leaves[0])

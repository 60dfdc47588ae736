"""Which device a result came from, and where compiled programs are kept.

Everything that measures or proves something about the chip goes through
this module, so that no entry point can look fine while the chip sits idle:

* ``require_chip()`` fails unless JAX's default backend is a TPU whose
  ``device_kind`` is in ``DEVICE_PEAKS``.  An unknown device is an error,
  not a default (`/opt/skills/guides/on-chip-measurement` §3).
* ``device_record()`` is the ``platform`` / ``device_kind`` / device-count
  triple every emitted result carries, on any backend.
* ``place_compile_cache()`` puts JAX's persistent compilation cache where
  the deployer said (``JAX_COMPILATION_CACHE_DIR``) or, failing that, at one
  fixed in-checkout path — the path is part of the cache key, so a
  directory that moves never hits.

Importing this module initialises no JAX backend; calling its functions
does.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional, Union

import jax

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.  One
# row per device somebody here has run on and could check the numbers for.
DEVICE_PEAKS: Dict[str, Dict[str, Union[float, str]]] = {
    "TPU v5 lite": {
        "bf16_tflops": 197.0,
        "hbm_gbs": 819.0,
        "hbm_gib": 16.0,
        "source": 'Google Cloud documentation, "TPU v5e" system architecture',
    },
}

# The fixed in-checkout cache path (git-ignored); see place_compile_cache.
REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


class NoChipError(RuntimeError):
    """The process has no usable accelerator (or one nobody has peaks for)."""


def device_record() -> Dict[str, Union[str, int]]:
    """``{"platform", "kind", "count"}`` as JAX reports the default backend.
    Initialises the backend."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def device_peaks(kind: str) -> Dict[str, Union[float, str]]:
    """The published peaks of ``kind``; unknown kinds raise."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise NoChipError(
            f"device_kind {kind!r} is not in ggrs_tpu.utils.device."
            f"DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)}); add its published "
            f"peaks with their source before measuring on it"
        ) from None


def require_chip(chips: int = 1) -> Dict[str, Union[str, int]]:
    """The device record, or ``NoChipError`` unless the default backend is a
    TPU of a known kind with at least ``chips`` devices.  There is no CPU
    fallback: a measurement path that finds no chip fails."""
    rec = device_record()
    if jax.default_backend() != "tpu":
        raise NoChipError(
            f"no TPU: jax.default_backend()={jax.default_backend()!r}, "
            f"devices()[0]={rec['platform']}/{rec['kind']} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    device_peaks(str(rec["kind"]))
    if int(rec["count"]) < chips:
        raise NoChipError(
            f"asked for {chips} chips, this machine has {rec['count']}"
        )
    return rec


def place_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory (None: no persistent cache).  Call once, before
    the first compile, from every entry point that compiles
    (``chip_smoke.py``, ``benchmark/run.py``, the examples); tests do not
    call it.  Initialises the backend.

    If ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and no
    directory is set in code.  Otherwise the cache goes to the fixed
    ``<repo>/.jax_cache`` — never a temp name, pid or time — unless the
    backend is the CPU: XLA:CPU logs two multi-KB "machine feature" error
    lines per entry it loads (they filled the stderr pipes of
    tests/test_examples.py and stalled it 3x), and nobody deploys that
    backend, so only a deployer's explicit directory turns it on there.

    Thresholds, decided deliberately: JAX by default persists only programs
    that took >= 1 s to compile.  This repo's entry points compile dozens
    of sub-second programs (``_fetch_slot``, each game's ``advance``, the
    executor's per-depth bursts) whose sum is what a cold start pays — and
    a live session's disconnect timers run through it — so everything is
    cached (minimum compile time 0; entry size left to JAX's filesystem
    override).  An explicit ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``
    in the environment still wins.
    """
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def cache_entry_count(cache_dir: Optional[str]) -> int:
    """Files under the cache directory (0 when there is none yet)."""
    if cache_dir is None:
        return 0
    return sum(len(files) for _, _, files in os.walk(cache_dir))

"""ctypes loader for the native input codec (native/codec.cpp).

The codec is the per-packet hot path on the UDP side, the one place hand
written C++ is warranted (SURVEY §2 native note).  This module compiles the
shared library on first use (g++, no pybind11 needed), caches it next to the
package, and exposes ``encode``/``decode`` with the exact signatures of
``ggrs_tpu.net.compression`` — the pure-Python implementations remain the
fallback whenever a toolchain is unavailable.

A library on disk is trusted only if it carries the digest of the CONTENT
of today's ``native/*.cpp``/``*.h`` and compiler flags (``ensure_built``);
anything else — an older build, one copied in from another checkout — is
rebuilt, never loaded.  When the build or the load fails, ``load_error()``
says why, compiler output included.

Set GGRS_TPU_NO_NATIVE=1 to force the Python codec.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import List, Optional, Sequence

from .wire import WireError

_LIB_NAME = "_ggrs_codec.so"
# GGRS_NATIVE_SANITIZE (scripts/build_sanitized.sh) loads/builds a separate
# sanitizer-instrumented library so the parity and fault fuzzes can run
# under sanitizers without touching the production .so:
#   "1" / "address" -> ASan+UBSan (_ggrs_codec_san.so)
#   "thread"        -> TSan (_ggrs_codec_tsan.so), for the GIL-released
#                      native I/O threads (ggrs_bank_pump / NetBatch)
_SANITIZE = os.environ.get("GGRS_NATIVE_SANITIZE") or None
if _SANITIZE == "thread":
    _LIB_NAME = "_ggrs_codec_tsan.so"
elif _SANITIZE:
    _LIB_NAME = "_ggrs_codec_san.so"
# Resource caps for the fast path.  Real packets sit under the ~508-byte UDP
# budget with at most the 128-input pending window; anything bigger (but
# still legal for the Python codec, whose hard cap is 1<<22 bytes) falls back
# to the Python implementation rather than holding megabytes of scratch.
_DECODE_CAP_BYTES = 1 << 20
_DECODE_CAP_INPUTS = 4096
# error codes that mean "packet exceeded the fast path's resources", not
# "packet is malformed" — mirror codec.cpp's kErrBufferTooSmall / TooMany
_RESOURCE_ERRORS = (-11, -12)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
# why the library is not loaded (build or dlopen failure, compiler output
# included); None while unattempted or loaded.  Latched: one attempt per
# process.
_load_error: Optional[str] = None
_decode_out = None
_decode_sizes = None

_ERROR_NAMES = {
    -1: "truncated data",
    -2: "uvarint too long",
    -3: "decoded data exceeds maximum size",
    -4: "literal run exceeds remaining data",
    -5: "invalid size-mode byte",
    -6: "input size is negative or too large",
    -7: "decoded byte count does not match expected sizes",
    -8: "reference must be non-empty to decode inputs of unknown size",
    -9: "encoded bytes not a multiple of the reference size",
    -10: "trailing bytes after message",
    -11: "output buffer too small",
    -12: "too many inputs",
}


# must mirror struct GgrsMsg in native/codec.cpp field-for-field (ctypes
# reproduces the C compiler's alignment/padding for same-ordered fields)
_MAX_PLAYERS_ON_WIRE = 64


class _GgrsMsg(ctypes.Structure):
    _fields_ = [
        ("magic", ctypes.c_uint16),
        ("tag", ctypes.c_uint8),
        ("disconnect_requested", ctypes.c_uint8),
        ("start_frame", ctypes.c_int64),
        ("ack_frame", ctypes.c_int64),
        ("frame", ctypes.c_int64),
        ("frame_advantage", ctypes.c_int16),
        ("ping", ctypes.c_uint64),
        ("pong", ctypes.c_uint64),
        ("checksum_lo", ctypes.c_uint64),
        ("checksum_hi", ctypes.c_uint64),
        ("random_nonce", ctypes.c_uint64),
        ("n_status", ctypes.c_int32),
        ("payload_off", ctypes.c_uint64),
        ("payload_len", ctypes.c_uint64),
        ("status_disconnected", ctypes.c_uint8 * _MAX_PLAYERS_ON_WIRE),
        ("status_last_frame", ctypes.c_int64 * _MAX_PLAYERS_ON_WIRE),
    ]


# message-framing error codes (mirror codec.cpp's msg section); kMsgFallback
# means "legal for Python's unbounded ints but not for the fast path" —
# callers retry with the Python decoder
_MSG_FALLBACK = -100
_MSG_ERROR_NAMES = {
    -1: "truncated data",
    -2: "uvarint too long",
    -20: "invalid bool byte",
    -21: "unknown message tag",
    -22: "too many connect statuses",
    -23: "trailing bytes after message",
}


def _native_dir() -> Path:
    return Path(__file__).resolve().parents[2] / "native"


def _sources() -> List[Path]:
    return [
        _native_dir() / "codec.cpp",
        _native_dir() / "endpoint.cpp",
        _native_dir() / "sync_core.cpp",
        _native_dir() / "session_bank.cpp",
        _native_dir() / "net_batch.cpp",
    ]


def _lib_path() -> Path:
    return Path(__file__).resolve().parent / _LIB_NAME


def _compile_flags() -> List[str]:
    if _SANITIZE == "thread":
        flags = ["-O1", "-g", "-fsanitize=thread"]
    elif _SANITIZE:
        flags = ["-O1", "-g", "-fsanitize=address,undefined",
                 "-fno-sanitize-recover=all"]
    else:
        flags = ["-O2"]
    return flags + ["-shared", "-fPIC", "-std=c++17"]


# codec.cpp compiles this prefix + the -DGGRS_BUILD_DIGEST value into the
# library (ggrs_build_digest); _is_current looks for it in the file's bytes
_DIGEST_MARKER = b"ggrs-build-digest:"


def _build_digest() -> str:
    """sha256 over the compiler flags and the name + content of every
    native source and header: what the library on disk must have been
    built from to be loaded."""
    h = hashlib.sha256()
    h.update("\0".join(_compile_flags()).encode())
    files = sorted(
        list(_native_dir().glob("*.cpp")) + list(_native_dir().glob("*.h"))
    )
    for p in files:
        h.update(b"\0" + p.name.encode() + b"\0")
        h.update(p.read_bytes())
    return h.hexdigest()


def _is_current(lib_path: Path) -> bool:
    """Does ``lib_path`` carry today's build digest?  Decided from the
    file's bytes, before any dlopen: staleness by mtime cannot survive a
    copy of the tree, and a stale library must not be mapped at all (glibc
    hands back the already-mapped object for the same path afterwards)."""
    try:
        blob = lib_path.read_bytes()
    except OSError:
        return False
    return _DIGEST_MARKER + _build_digest().encode() in blob


class NativeBuildError(RuntimeError):
    """The native library could not be compiled; carries g++'s stderr."""


def _build(lib_path: Path) -> None:
    """Compile the native library to ``lib_path``; ``NativeBuildError``
    (with the compiler's stderr) on failure.

    g++ writes to a pid-unique temp beside the target and the result is
    moved in atomically: the module-level ``_lock`` is per-process, so two
    concurrently-starting processes would otherwise race compiler output
    into the same file and one would dlopen a torn .so.
    """
    srcs = _sources()
    missing = [s.name for s in srcs if not s.exists()]
    if missing:
        raise NativeBuildError(f"native sources missing: {missing}")
    # Sweep temps orphaned by hard-killed builds (different pid → never
    # reused).  Age-gated to the 120 s build timeout: a fresh temp from a
    # CONCURRENTLY-building process must survive — unlinking it mid-write
    # would cost that process its native fast paths for its whole lifetime.
    cutoff = time.time() - 120
    for stale in lib_path.parent.glob(f"{lib_path.name}.build.*"):
        if stale.name == f"{lib_path.name}.build.{os.getpid()}":
            continue
        try:
            if stale.stat().st_mtime < cutoff:
                stale.unlink(missing_ok=True)
        except OSError:
            pass  # raced with the owning process: leave it alone
    tmp = lib_path.with_name(f"{lib_path.name}.build.{os.getpid()}")
    cmd = [
        "g++",
        *_compile_flags(),
        f'-DGGRS_BUILD_DIGEST="{_build_digest()}"',
        "-o",
        str(tmp),
    ] + [str(s) for s in srcs]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, timeout=120
        )
        tmp.replace(lib_path)
    except subprocess.CalledProcessError as e:
        tmp.unlink(missing_ok=True)
        stderr = e.stderr.decode(errors="replace").strip()
        raise NativeBuildError(
            f"g++ exited {e.returncode}:\n{stderr[-4000:]}"
        ) from None
    except (subprocess.SubprocessError, OSError) as e:
        tmp.unlink(missing_ok=True)
        raise NativeBuildError(f"{type(e).__name__}: {e}") from None


def ensure_built(lib_path: Optional[Path] = None) -> Path:
    """The path of a native library built from today's sources and flags,
    compiling it first unless the one on disk already carries their digest.
    ``NativeBuildError`` on failure.  Does not dlopen — also the build
    entry point of scripts/build_sanitized.sh."""
    lib_path = _lib_path() if lib_path is None else lib_path
    if not _is_current(lib_path):
        _build(lib_path)
    return lib_path


def load_error() -> Optional[str]:
    """Why the native library is not in use after a failed attempt (the
    compiler's stderr, a dlopen error), else None.  ``GGRS_TPU_NO_NATIVE``
    is reported by the callers that honour it, not here."""
    return _load_error


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_error
    if _lib is not None:
        return _lib
    if _load_error is not None or os.environ.get("GGRS_TPU_NO_NATIVE"):
        return None
    with _lock:
        if _lib is not None or _load_error is not None:
            return _lib
        try:
            lib = ctypes.CDLL(str(ensure_built()))
        except (NativeBuildError, OSError) as e:
            _load_error = f"{type(e).__name__}: {e}"
            return None

        lib.ggrs_codec_encode_bound.restype = ctypes.c_size_t
        lib.ggrs_codec_encode_bound.argtypes = [ctypes.c_size_t, ctypes.c_size_t]
        lib.ggrs_codec_encode.restype = ctypes.c_int
        lib.ggrs_codec_encode.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.ggrs_codec_decode.restype = ctypes.c_int
        lib.ggrs_codec_decode.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.ggrs_msg_decode.restype = ctypes.c_int
        lib.ggrs_msg_decode.argtypes = [
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(_GgrsMsg),
        ]
        lib.ggrs_msg_encode.restype = ctypes.c_int
        lib.ggrs_msg_encode.argtypes = [
            ctypes.POINTER(_GgrsMsg),
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        # ---- endpoint datapath (native/endpoint.cpp) ----
        lib.ggrs_ep_new.restype = ctypes.c_void_p
        lib.ggrs_ep_new.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_int64,
        ]
        lib.ggrs_ep_free.restype = None
        lib.ggrs_ep_free.argtypes = [ctypes.c_void_p]
        lib.ggrs_ep_pending_len.restype = ctypes.c_int64
        lib.ggrs_ep_pending_len.argtypes = [ctypes.c_void_p]
        lib.ggrs_ep_last_recv_frame.restype = ctypes.c_int64
        lib.ggrs_ep_last_recv_frame.argtypes = [ctypes.c_void_p]
        lib.ggrs_ep_ack.restype = None
        lib.ggrs_ep_ack.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.ggrs_ep_push.restype = ctypes.c_int64
        lib.ggrs_ep_push.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_size_t,
        ]
        lib.ggrs_ep_emit_input.restype = ctypes.c_int
        lib.ggrs_ep_emit_input.argtypes = [
            ctypes.c_void_p, ctypes.c_uint16,
            ctypes.c_char_p, ctypes.c_char_p,  # disc bytes, LE-packed frames
            ctypes.c_int32, ctypes.c_uint8,
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.ggrs_ep_on_input.restype = ctypes.c_int
        lib.ggrs_ep_on_input.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.ggrs_ep_commit.restype = None
        lib.ggrs_ep_commit.argtypes = [ctypes.c_void_p]
        lib.ggrs_ep_handle_input_datagram.restype = ctypes.c_int
        lib.ggrs_ep_handle_input_datagram.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_uint16), ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int64),
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t), ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.ggrs_ep_fetch_base.restype = ctypes.c_int
        lib.ggrs_ep_fetch_base.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_char_p, ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.ggrs_ep_store_one.restype = None
        lib.ggrs_ep_store_one.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_size_t,
        ]
        if hasattr(lib, "ggrs_ep_seed_send"):
            # eviction-adoption seam; absent on a prebuilt older .so (such a
            # library also lacks ggrs_bank_harvest, so bank_lib() keeps the
            # pool on the Python fallback)
            lib.ggrs_ep_seed_send.restype = None
            lib.ggrs_ep_seed_send.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_char_p, ctypes.c_size_t,
            ]
        if hasattr(lib, "ggrs_ep_rewind_send"):
            # fleet failover seam (send-window rewind on regressive acks);
            # absent on a prebuilt older .so — PeerProtocol then skips the
            # rewind and the match degrades exactly as before the seam
            lib.ggrs_ep_rewind_send.restype = None
            lib.ggrs_ep_rewind_send.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_char_p, ctypes.c_size_t,
            ]
        if hasattr(lib, "ggrs_ep_stats"):
            # observability counters (obs stat harvest); absent on a
            # prebuilt pre-obs .so — readers degrade to zeros
            lib.ggrs_ep_stats.restype = None
            lib.ggrs_ep_stats.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.ggrs_ep_last_acked_frame.restype = ctypes.c_int64
            lib.ggrs_ep_last_acked_frame.argtypes = [ctypes.c_void_p]
        if hasattr(lib, "ggrs_sync_new"):
            lib.ggrs_sync_new.restype = ctypes.c_void_p
            lib.ggrs_sync_new.argtypes = [ctypes.c_int, ctypes.c_int]
            lib.ggrs_sync_free.restype = None
            lib.ggrs_sync_free.argtypes = [ctypes.c_void_p]
            lib.ggrs_sync_set_frame_delay.restype = None
            lib.ggrs_sync_set_frame_delay.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
            ]
            lib.ggrs_sync_reset_prediction.restype = None
            lib.ggrs_sync_reset_prediction.argtypes = [ctypes.c_void_p]
            lib.ggrs_sync_add_input.restype = ctypes.c_int64
            lib.ggrs_sync_add_input.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int64, ctypes.c_char_p,
            ]
            lib.ggrs_sync_synchronized_inputs.restype = ctypes.c_int
            lib.ggrs_sync_synchronized_inputs.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_int32),
            ]
            lib.ggrs_sync_confirmed_inputs.restype = ctypes.c_int
            lib.ggrs_sync_confirmed_inputs.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
                ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ]
            lib.ggrs_sync_set_last_confirmed.restype = ctypes.c_int
            lib.ggrs_sync_set_last_confirmed.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
            ]
            lib.ggrs_sync_last_confirmed.restype = ctypes.c_int64
            lib.ggrs_sync_last_confirmed.argtypes = [ctypes.c_void_p]
            lib.ggrs_sync_check_consistency.restype = ctypes.c_int64
            lib.ggrs_sync_check_consistency.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
            ]
            lib.ggrs_sync_first_incorrect.restype = ctypes.c_int64
            lib.ggrs_sync_first_incorrect.argtypes = [
                ctypes.c_void_p, ctypes.c_int,
            ]
            lib.ggrs_sync_last_added.restype = ctypes.c_int64
            lib.ggrs_sync_last_added.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.ggrs_sync_confirmed_input.restype = ctypes.c_int
            lib.ggrs_sync_confirmed_input.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int64, ctypes.c_char_p,
            ]
            if hasattr(lib, "ggrs_sync_seed"):
                lib.ggrs_sync_seed.restype = ctypes.c_int
                lib.ggrs_sync_seed.argtypes = [
                    ctypes.c_void_p, ctypes.c_int, ctypes.c_int64,
                    ctypes.c_int32, ctypes.c_char_p,
                ]
                lib.ggrs_sync_tail_frame.restype = ctypes.c_int64
                lib.ggrs_sync_tail_frame.argtypes = [
                    ctypes.c_void_p, ctypes.c_int,
                ]
        # ---- session bank (native/session_bank.cpp) ----
        if hasattr(lib, "ggrs_bank_new"):
            lib.ggrs_bank_new.restype = ctypes.c_void_p
            lib.ggrs_bank_new.argtypes = []
            lib.ggrs_bank_free.restype = None
            lib.ggrs_bank_free.argtypes = [ctypes.c_void_p]
            lib.ggrs_bank_add_session.restype = ctypes.c_int64
            lib.ggrs_bank_add_session.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_int64, ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int, ctypes.c_int,
            ]
            lib.ggrs_bank_add_endpoint.restype = ctypes.c_int64
            lib.ggrs_bank_add_endpoint.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint16,
                ctypes.POINTER(ctypes.c_int32), ctypes.c_int, ctypes.c_int64,
            ]
            lib.ggrs_bank_tick.restype = ctypes.c_int
            lib.ggrs_bank_tick.argtypes = [
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_char_p, ctypes.c_size_t,
                ctypes.c_char_p, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_size_t),
            ]
            lib.ggrs_bank_fetch_out.restype = ctypes.c_int
            lib.ggrs_bank_fetch_out.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_size_t),
            ]
            lib.ggrs_bank_session_count.restype = ctypes.c_int64
            lib.ggrs_bank_session_count.argtypes = [ctypes.c_void_p]
            if hasattr(lib, "ggrs_bank_harvest"):
                lib.ggrs_bank_harvest.restype = ctypes.c_int
                lib.ggrs_bank_harvest.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_char_p, ctypes.c_size_t,
                    ctypes.POINTER(ctypes.c_size_t),
                ]
            if hasattr(lib, "ggrs_bank_stats"):
                # one-crossing stat harvest (obs); absent on a prebuilt
                # pre-obs .so — HostSessionPool.scrape degrades gracefully
                lib.ggrs_bank_stats.restype = ctypes.c_int
                lib.ggrs_bank_stats.argtypes = [
                    ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
                    ctypes.POINTER(ctypes.c_size_t),
                ]
            if hasattr(lib, "ggrs_bank_attach_spectator"):
                # broadcast subsystem (spectator fan-out + journal tap);
                # absent on a prebuilt pre-broadcast .so — the pool then
                # treats every hub as absent (spectator matches fall back
                # to per-session Python relaying) and parses the
                # pre-broadcast tick output layout
                lib.ggrs_bank_attach_spectator.restype = ctypes.c_int64
                lib.ggrs_bank_attach_spectator.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint16,
                    ctypes.c_int64,
                ]
                lib.ggrs_bank_detach_spectator.restype = ctypes.c_int
                lib.ggrs_bank_detach_spectator.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ]
                lib.ggrs_bank_set_confirmed_stream.restype = ctypes.c_int
                lib.ggrs_bank_set_confirmed_stream.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
                ]
            # desync detection inside the crossing (DESIGN.md §4): the
            # interval a session reports its saved frames' digests at
            lib.ggrs_bank_set_desync_detection.restype = ctypes.c_int
            lib.ggrs_bank_set_desync_detection.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ]
            # GGRS's sparse saving inside the crossing (DESIGN.md §4)
            lib.ggrs_bank_set_sparse_saving.restype = ctypes.c_int
            lib.ggrs_bank_set_sparse_saving.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
            ]
            if hasattr(lib, "ggrs_bank_set_timing"):
                # in-crossing phase timers (tracing, DESIGN.md §14);
                # absent on a prebuilt pre-trace .so — the pool then runs
                # Python-side spans only, with no native timing tail
                lib.ggrs_bank_set_timing.restype = ctypes.c_int
                lib.ggrs_bank_set_timing.argtypes = [
                    ctypes.c_void_p, ctypes.c_int,
                ]
            if hasattr(lib, "ggrs_bank_hdr_stride"):
                # packed per-tick output header (DESIGN.md §19); absent on
                # a prebuilt pre-header .so — pools then parse the legacy
                # body-only tick output and skip the vectorized fast path
                lib.ggrs_bank_hdr_stride.restype = ctypes.c_int
                lib.ggrs_bank_hdr_stride.argtypes = []
            if hasattr(lib, "ggrs_bank_req_stride"):
                # descriptor plane (DESIGN.md §21): batched input staging,
                # the per-slot request descriptor table, and the harvest
                # staged tail; absent on a prebuilt pre-descriptor .so —
                # pools then keep the legacy parse and per-call staging
                lib.ggrs_bank_req_stride.restype = ctypes.c_int
                lib.ggrs_bank_req_stride.argtypes = []
                lib.ggrs_bank_stage_stride.restype = ctypes.c_int
                lib.ggrs_bank_stage_stride.argtypes = []
                lib.ggrs_bank_stage_inputs.restype = ctypes.c_int64
                lib.ggrs_bank_stage_inputs.argtypes = [
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_char_p, ctypes.c_size_t,
                ]
            if hasattr(lib, "ggrs_bank_wire_stride"):
                # the tick's outbound table (BANK_WIRE_FIELDS)
                lib.ggrs_bank_wire_stride.restype = ctypes.c_int
                lib.ggrs_bank_wire_stride.argtypes = []
            if hasattr(lib, "ggrs_net_send_table"):
                # one-shot batched outbound over arbitrary fds (§21);
                # shares the non-Linux stub policy of the NetBatch surface
                lib.ggrs_net_send_table.restype = ctypes.c_int
                lib.ggrs_net_send_table.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_char_p, ctypes.c_size_t,
                    ctypes.POINTER(ctypes.c_uint64),
                    ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
                ]
            if hasattr(lib, "ggrs_net_recv_table"):
                # datapath gen 2 (§23): one-crossing inbound drain over
                # arbitrary fds + dispatch demux + GSO fan-out; absent on
                # a prebuilt gen-1 .so — pools keep the per-slot
                # receive_all_datagrams reference drain
                lib.ggrs_net_recv_table.restype = ctypes.c_int
                lib.ggrs_net_recv_table.argtypes = [
                    ctypes.c_void_p, ctypes.c_int,
                    ctypes.c_void_p, ctypes.c_int,
                    ctypes.c_void_p, ctypes.c_int,
                    ctypes.c_void_p, ctypes.c_int64,
                    ctypes.POINTER(ctypes.c_uint64),
                    ctypes.POINTER(ctypes.c_int32), ctypes.c_int,
                    ctypes.POINTER(ctypes.c_int32),
                ]
                lib.ggrs_net_gso_supported.restype = ctypes.c_int
                lib.ggrs_net_gso_supported.argtypes = []
                lib.ggrs_net_set_gso.restype = None
                lib.ggrs_net_set_gso.argtypes = [ctypes.c_int]
                if hasattr(lib, "ggrs_net_gro_supported"):
                    # GRO inbound (§23d); absent on a pre-GRO .so — the
                    # recv table then never splits and pools leave the
                    # sockets' GRO posture off
                    lib.ggrs_net_gro_supported.restype = ctypes.c_int
                    lib.ggrs_net_gro_supported.argtypes = []
                    lib.ggrs_net_set_gro.restype = None
                    lib.ggrs_net_set_gro.argtypes = [ctypes.c_int]
                lib.ggrs_net_inject_table_errno.restype = None
                lib.ggrs_net_inject_table_errno.argtypes = [
                    ctypes.c_int, ctypes.c_int64, ctypes.c_int,
                ]
                for _probe in (
                    "ggrs_net_recv_stride", "ggrs_net_route_stride",
                    "ggrs_net_fd_stride", "ggrs_net_send_stats_len",
                    "ggrs_net_recv_stats_len",
                ):
                    getattr(lib, _probe).restype = ctypes.c_int
                    getattr(lib, _probe).argtypes = []
            if hasattr(lib, "ggrs_bank_pump"):
                # kernel-batched socket datapath (net_batch.cpp + the
                # bank's pump entry, DESIGN.md §15); absent on a prebuilt
                # pre-io .so — pools keep the Python shuttle, and the
                # stats layout then carries no per-slot io tail
                lib.ggrs_bank_pump.restype = ctypes.c_int
                lib.ggrs_bank_pump.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64,
                    ctypes.c_char_p, ctypes.c_size_t,
                    ctypes.c_char_p, ctypes.c_size_t,
                    ctypes.POINTER(ctypes.c_size_t),
                ]
                lib.ggrs_bank_attach_socket.restype = ctypes.c_int
                lib.ggrs_bank_attach_socket.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
                ]
                lib.ggrs_bank_detach_socket.restype = ctypes.c_int
                lib.ggrs_bank_detach_socket.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64,
                ]
                lib.ggrs_bank_map_addr.restype = ctypes.c_int
                lib.ggrs_bank_map_addr.argtypes = [
                    ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
                    ctypes.c_int64, ctypes.c_uint32, ctypes.c_uint16,
                ]
                lib.ggrs_net_supported.restype = ctypes.c_int
                lib.ggrs_net_supported.argtypes = []
                lib.ggrs_net_attach.restype = ctypes.c_void_p
                lib.ggrs_net_attach.argtypes = [ctypes.c_int, ctypes.c_int]
                lib.ggrs_net_free.restype = None
                lib.ggrs_net_free.argtypes = [ctypes.c_void_p]
                lib.ggrs_net_recv_all.restype = ctypes.c_int
                lib.ggrs_net_recv_all.argtypes = [ctypes.c_void_p]
                lib.ggrs_net_stage.restype = ctypes.c_int
                lib.ggrs_net_stage.argtypes = [
                    ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint16,
                    ctypes.c_char_p, ctypes.c_size_t,
                ]
                lib.ggrs_net_flush.restype = ctypes.c_int
                lib.ggrs_net_flush.argtypes = [ctypes.c_void_p]
                lib.ggrs_net_staged_len.restype = ctypes.c_int64
                lib.ggrs_net_staged_len.argtypes = [ctypes.c_void_p]
                lib.ggrs_net_stats.restype = None
                lib.ggrs_net_stats.argtypes = [
                    ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
                ]
                lib.ggrs_net_set_capture.restype = None
                lib.ggrs_net_set_capture.argtypes = [
                    ctypes.c_void_p, ctypes.c_int,
                ]
                lib.ggrs_net_drain_capture.restype = ctypes.c_int
                lib.ggrs_net_drain_capture.argtypes = [
                    ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
                    ctypes.POINTER(ctypes.c_size_t),
                ]
                lib.ggrs_net_inject_send_errno.restype = None
                lib.ggrs_net_inject_send_errno.argtypes = [
                    ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
                ]
        _lib = lib
        return _lib


# endpoint-datapath return codes (mirror native/endpoint.cpp)
EP_DROP = -30
EP_FALLBACK = -31
EP_BAD_PENDING_HEAD = -32
EP_ERR_BUFFER_TOO_SMALL = -11
EP_ERR_TOO_MANY_INPUTS = -12  # kErrTooManyInputs: > _MAX_PLAYERS_ON_WIRE

# sync-core return codes (mirror native/sync_core.cpp SyncRc)
SYNC_OK = 0
SYNC_ERR_PREDICTION_PENDING = -40
SYNC_ERR_BEFORE_TAIL = -41
SYNC_ERR_NO_CONFIRMED = -42
SYNC_ERR_NON_SEQUENTIAL = -43
SYNC_ERR_CONFIRM_PAST_INCORRECT = -44
SYNC_ERR_BAD_ARGS = -45
SYNC_ERR_QUEUE_FULL = -46  # kSyncErrQueueFull: 128-slot ring exhausted

# session-bank return codes (mirror native/session_bank.cpp; the buffer
# code is wire_common.h's kErrBufferTooSmall, shared with the codec)
BANK_ERR_BUFFER_TOO_SMALL = -11
BANK_OK = 0
BANK_ERR_CMD = -60
BANK_ERR_LANDED_SPLIT = -70
BANK_ERR_SYNC = -71
BANK_ERR_SYNC_INPUTS = -72
BANK_ERR_CONFIRM = -73
BANK_ERR_NO_PLAYERS = -74
BANK_ERR_SEQUENCE = -75
BANK_ERR_INJECTED = -76  # chaos-harness simulated slot fault (ctrl op 2)
BANK_ERR_SPEC_STREAM = -77  # confirmed-input fan-out / journal tap failed
BANK_ERR_IO = -78  # batched socket I/O failed fatally (per-slot fault)

# net_batch.cpp return codes
NET_OK = 0
NET_ERR_UNSUPPORTED = -80
NET_ERR_FATAL = -81
NET_ERR_BAD_ARGS = -82

# NetBatch counter order (ggrs_net_stats; also the per-slot io tail of
# ggrs_bank_stats).  After the six scalars come two 8-bucket batch-size
# histograms (recv then send) with upper bounds IO_BATCH_BUCKETS + inf.
IO_STAT_FIELDS = (
    "recv_calls", "recv_datagrams", "send_calls", "send_datagrams",
    "send_errors", "oversized",
)
IO_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64)
IO_STAT_WORDS = len(IO_STAT_FIELDS) + 2 * (len(IO_BATCH_BUCKETS) + 1)  # 22

# endpoint-core observability counter order (ggrs_ep_stats out7; also the
# per-endpoint tail of each ggrs_bank_stats record)
EP_STAT_FIELDS = (
    "emits", "emit_bytes", "acks", "datagrams", "new_frames", "drops",
    "fallbacks",
)

# per-session command-stream flag byte (session_bank.cpp kFlag*): bit 0 =
# local inputs present (advance runs), bit 1 = skip (slot quarantined or
# evicted, no further fields follow for this session), bit 2 = staged
# (inputs were staged natively via ggrs_bank_stage_inputs — no inline
# input bytes follow the flag byte)
CMD_FLAG_INPUTS = 1
CMD_FLAG_SKIP = 2
CMD_FLAG_STAGED = 4

# ---- descriptor plane (session_bank.cpp §21 structs) --------------------
# Batched input staging record (ggrs_bank_stage_inputs): one fixed-stride
# descriptor per staged input, jumping into a shared payload blob — the
# PR 10 packed-header/jump-table idiom applied to the INBOUND direction.
# `frame` is reserved (must be NULL_FRAME today: "this tick"); `len` is
# the variable-size seam and must equal the slot's input_size for now.
BANK_STAGE_FIELDS = (
    ("slot", "<u4"), ("handle", "<i4"), ("frame", "<i8"),
    ("off", "<u4"), ("len", "<u4"),
)  # itemsize 24 == ggrs_bank_stage_stride()
BANK_STAGE_STRIDE = 24

# Per-slot request descriptor record (the SECOND fixed-stride table of
# every tick output, after the header table): the tick's request program
# as flat data — pattern, advance count/offsets, and the save/load frame —
# so the pool's decode and BatchedRequestExecutor's device dispatch read
# NumPy columns instead of parsing op bytes per slot.
BANK_REQ_FIELDS = (
    ("pattern", "<u1"), ("rflags", "<u1"), ("n_adv", "<u2"),
    ("adv_off", "<u4"), ("adv_stride", "<u4"), ("ops_end", "<u4"),
    ("frame", "<i8"),
)  # itemsize 24 == ggrs_bank_req_stride()
BANK_REQ_STRIDE = 24
REQ_OTHER = 0       # unclassified shape: use the generic op decoder
REQ_QUIET = 1       # ops are exactly [save frame, advance]
REQ_RESIM = 2       # [load frame, adv, (save, adv)*, save] (+ trailing adv)
REQ_SAVE_ONLY = 3   # [save frame] — the prediction-limit tick
REQ_EMPTY = 4       # no ops (skip / faulted records)
REQ_ADV_ONLY = 5    # [advance] — a sparse session's quiet tick
REQ_SPARSE_RESIM = 6  # [load frame, advance^n] with at most one save, of
                      # frame + the advances before it; its frame is the
                      # HEADER's save_frame (NULL_FRAME: none)
REQ_FLAG_TRAILING_ADV = 1  # the tick's last op was an advance ("advanced")
REQ_FLAG_FORCED = 2  # sparse saving's check_last_saved_state resimulated

# The tick's outbound table (session_bank.cpp kWireStride): after the body
# records and the wanted tail, u32 n_rows then one row per datagram of the
# records' two outbound sections, in record order — the slot, endpoint and
# section, the datagram's offset (absolute in the tick output) and length,
# and its zlib.crc32, so the pool sends and taps fast slots' datagrams
# from columns instead of walking their sections.
BANK_WIRE_FIELDS = (
    ("slot", "<u4"), ("ep", "<u2"), ("section", "<u1"), ("off", "<u4"),
    ("len", "<u4"), ("crc", "<u4"),
)  # itemsize 19 == ggrs_bank_wire_stride()
BANK_WIRE_STRIDE = 19

# Batched outbound send record (net_batch.cpp ggrs_net_send_table): per
# datagram fd + wire address + a jump into the shared payload (usually the
# tick output buffer itself).  Records for one fd must form one contiguous
# run.  ``flags`` bit 0 (NET_SEND_FLAG_DISPATCH) marks a record on a
# SHARED dispatch fd: a fatal errno there faults only that record's slot,
# co-tenant records keep flushing (gen 2, §23).
NET_SEND_FIELDS = (
    ("fd", "<i4"), ("ip", "<u4"), ("port", "<u2"), ("flags", "<u2"),
    ("off", "<u4"), ("len", "<u4"),
)  # itemsize 20 == net_batch.cpp kSendStride
NET_SEND_STRIDE = 20
NET_SEND_FLAG_DISPATCH = 1  # net_batch.cpp kSendFlagDispatch

# ggrs_net_send_table stats words (net_batch.cpp kSendTableStats):
# {sent, transient_errors, oversized, gso_sends, gso_segments}
NET_SEND_STATS = 5

# ---- datapath gen 2 (net_batch.cpp §23 tables) --------------------------
# One-crossing inbound drain (ggrs_net_recv_table).  The fd table names
# every socket to drain (slot == -1 marks a shared dispatch fd demuxed by
# source address); the route table maps (ip, port) -> slot and must be
# sorted ascending by (ip << 16) | port; the record table describes each
# received datagram as a jump into the shared slab, in per-fd arrival
# order — exactly what the per-slot receive_all_datagrams reference sees.
NET_FD_FIELDS = (
    ("fd", "<i4"), ("slot", "<i4"),
)  # itemsize 8 == net_batch.cpp kFdStride
NET_FD_STRIDE = 8
NET_ROUTE_FIELDS = (
    ("ip", "<u4"), ("port", "<u2"), ("pad", "<u2"), ("slot", "<i4"),
)  # itemsize 12 == net_batch.cpp kRouteStride
NET_ROUTE_STRIDE = 12
NET_RECV_FIELDS = (
    ("slot", "<i4"), ("fd_idx", "<i4"), ("ip", "<u4"), ("port", "<u2"),
    ("seg", "<u2"), ("off", "<u4"), ("len", "<u4"),
)  # itemsize 24 == net_batch.cpp kRecvStride; ``seg`` is the segment
# index when a GRO-coalesced train was split back into wire datagrams
# (0 for ordinary datagrams — pre-GRO .so files always write 0 here)
NET_RECV_STRIDE = 24

# ggrs_net_recv_table stats words (net_batch.cpp kRecvTableStats):
# {recv_calls, datagrams, unroutable, backpressure_stops} + the 8-bucket
# batch-size histogram (bounds IO_BATCH_BUCKETS + inf) occupying words
# [4..11], then the GRO tail APPENDED at [12..13] (gro_datagrams,
# gro_segments) so existing indices never move.  ``datagrams`` counts
# post-split wire datagrams, so it matches the GRO-off count exactly.
NET_RECV_TABLE_STAT_FIELDS = (
    "recv_calls", "datagrams", "unroutable", "backpressure_stops",
    "gro_datagrams", "gro_segments",
)
NET_RECV_TABLE_STATS = 14

# packed per-tick output header (session_bank.cpp kHdr*; DESIGN.md §19):
# one BANK_HDR_DTYPE-shaped record per session leads the tick output when
# the library exports ggrs_bank_hdr_stride.  The pool classifies all B
# slots from this table (NumPy over the output buffer); slots with no
# events/spectator/consensus/dirty activity take the fast path — ops
# decoded through pooled request objects, the events/mirror/spectator
# sections JUMPED via rec_len.  The QUIET bit and save_frame field label
# the canonical [save, advance] tick shape (classification metadata); on a
# REQ_SPARSE_RESIM row save_frame is the frame of its one save, which the
# descriptor plane reads to place that save.
BANK_HDR_LIVE = 1        # stepped this tick and err == 0
BANK_HDR_QUIET = 2       # ops are exactly [save, advance]
BANK_HDR_EVENTS = 4      # protocol events present
BANK_HDR_SPEC = 8        # spectator endpoints / streams / events present
BANK_HDR_CONSENSUS = 16  # disconnect consensus pending
BANK_HDR_DIRTY = 32      # a status mirror changed this tick
BANK_HDR_OUT = 64        # outbound datagram sections non-empty
BANK_HDR_SKIP = 128      # status-only record (slot was skipped)
BANK_HDR_CONF = 256      # journal-tap confirmed records present
BANK_HDR_FIELDS = (
    ("flags", "<u4"), ("rec_len", "<u4"), ("err", "<i4"), ("fa", "<i4"),
    ("landed", "<i8"), ("current", "<i8"), ("confirmed", "<i8"),
    ("save_frame", "<i8"),
)  # itemsize 48 == ggrs_bank_hdr_stride()

# in-crossing phase order (session_bank.cpp BankPhase; the timing tails on
# the tick and stats outputs carry one u64 of nanoseconds per entry, in
# this order, with the count byte last)
BANK_PHASES = (
    "inbound", "timers", "commit", "rollback", "outbound", "fanout",
    "emit", "other", "staging", "checksum",
)
# "checksum" (desync detection: reports out, wanted rows, compares) is an
# in-crossing phase like the first seven, appended so no older index moves.
# "staging" is special: it accumulates OUTSIDE the tick window (the
# ggrs_bank_stage_inputs crossings since the last tick) and rides the next
# tick's tail — it is never part of the in-crossing sum that "other"
# closes, and the tracer emits it as a sibling span of the crossing, not a
# child.

BANK_ERR_NAMES = {
    BANK_ERR_CMD: "malformed command stream",
    BANK_ERR_LANDED_SPLIT: "local inputs landed on different frames",
    BANK_ERR_SYNC: "sync-core operation failed",
    BANK_ERR_SYNC_INPUTS: "synchronized-input assembly failed",
    BANK_ERR_CONFIRM: "confirmed-frame watermark invariant broken",
    BANK_ERR_NO_PLAYERS: "every player disconnected",
    BANK_ERR_SEQUENCE: "remote input frame out of sequence",
    BANK_ERR_INJECTED: "injected fault (chaos harness)",
    BANK_ERR_SPEC_STREAM: "confirmed-input fan-out failed",
    BANK_ERR_IO: "batched socket I/O failed fatally",
}


def net_lib() -> Optional[ctypes.CDLL]:
    """The loaded library when the kernel-batched socket datapath is
    usable: net_batch.cpp built with the bank's pump entry AND
    recvmmsg/sendmmsg supported on this platform (``ggrs_net_supported``
    is 0 on non-Linux stub builds).  ``GGRS_TPU_NO_NATIVE_IO=1`` forces
    None — pools then keep the per-datagram Python shuttle, the
    documented fallback (DESIGN.md §15)."""
    lib = bank_lib()
    if (
        lib is None
        or os.environ.get("GGRS_TPU_NO_NATIVE_IO")
        or not hasattr(lib, "ggrs_bank_pump")
        or not lib.ggrs_net_supported()
    ):
        return None
    return lib


def broadcast_lib() -> Optional[ctypes.CDLL]:
    """The loaded library when it carries the broadcast entry points
    (spectator fan-out + journal tap), or None.  A prebuilt pre-broadcast
    library keeps the bank fast path but routes spectator matches to the
    per-session Python relay."""
    lib = bank_lib()
    if lib is None or not hasattr(lib, "ggrs_bank_attach_spectator"):
        return None
    return lib


def sync_lib() -> Optional[ctypes.CDLL]:
    """The loaded library for the native sync core, or None (use the Python
    input queues).  Same load/fallback policy as the other fast paths."""
    lib = _load()
    if lib is None or not hasattr(lib, "ggrs_sync_new"):
        return None
    return lib


def available() -> bool:
    return _load() is not None


def bank_lib() -> Optional[ctypes.CDLL]:
    """The loaded library for the native session bank, or None (drive the
    per-session Python sessions).  Same load/fallback policy as the other
    fast paths; a prebuilt pre-bank library keeps its older fast paths.
    ``ggrs_bank_harvest`` is required alongside ``ggrs_bank_new``: the
    supervision layer's eviction path needs it (and the seed symbols built
    with it), so a pre-supervision prebuilt library must route pools to the
    Python fallback rather than run a bank whose faults could never
    evict."""
    lib = _load()
    if (
        lib is None
        or not hasattr(lib, "ggrs_bank_new")
        or not hasattr(lib, "ggrs_bank_harvest")
    ):
        return None
    return lib


def endpoint_lib() -> Optional[ctypes.CDLL]:
    """The loaded library for NativeEndpointCore, or None (use the Python
    core).  Same load/fallback policy as the codec fast path, plus the
    endpoint symbols must actually be present (a prebuilt pre-endpoint
    library keeps its codec fast path but not this one)."""
    lib = _load()
    if lib is None or not hasattr(lib, "ggrs_ep_new"):
        return None
    return lib


def encode(reference: bytes, inputs: Sequence[bytes]) -> Optional[bytes]:
    """Native encode; returns None if the library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    blob = b"".join(inputs)
    n = len(inputs)
    lens = (ctypes.c_size_t * max(n, 1))(*[len(i) for i in inputs])
    cap = lib.ggrs_codec_encode_bound(len(blob), n)
    out = ctypes.create_string_buffer(cap)
    out_len = ctypes.c_size_t(0)
    rc = lib.ggrs_codec_encode(
        reference,
        len(reference),
        blob,
        lens,
        n,
        out,
        cap,
        ctypes.byref(out_len),
    )
    if rc != 0:  # pragma: no cover - encode can only fail on a bad bound
        return None  # fall back to the Python encoder rather than fail
    return ctypes.string_at(out, out_len.value)  # .raw would copy all of cap


_msg_scratch = _GgrsMsg()
_msg_out_cap = 1 << 16
_msg_out: Optional[ctypes.Array] = None
_M = None  # lazily-bound ggrs_tpu.net.messages module (avoids import cycle
#            at module load AND the per-call `from . import` lookup cost)


def _messages():
    global _M
    if _M is None:
        from . import messages

        _M = messages
    return _M

_TAG_INPUT = 0
_TAG_INPUT_ACK = 1
_TAG_QUALITY_REPORT = 2
_TAG_QUALITY_REPLY = 3
_TAG_CHECKSUM_REPORT = 4
_TAG_KEEP_ALIVE = 5
_TAG_SYNC_REQUEST = 6
_TAG_SYNC_REPLY = 7


def msg_decode(data: bytes):
    """Native Message decode; returns the built ``messages.Message``, or
    ``None`` when the library is unavailable / the packet needs the Python
    decoder (varints beyond u64).  Raises ``wire.WireError`` on malformed
    data, like the Python decoder."""
    lib = _load()
    if lib is None:
        return None
    M = _messages()

    with _lock:  # the scratch struct is reused; protocol use is 1-thread
        m = _msg_scratch
        rc = lib.ggrs_msg_decode(data, len(data), ctypes.byref(m))
        if rc == _MSG_FALLBACK:
            return None
        if rc != 0:
            raise WireError(_MSG_ERROR_NAMES.get(rc, f"native error {rc}"))
        tag = m.tag
        if tag == _TAG_INPUT:
            n = m.n_status
            # bulk-slice the ctypes arrays (one C call each) and construct
            # positionally — this wrapper runs for every received input
            # packet, so per-element ctypes indexing and kwargs cost real time
            CS = M.ConnectionStatus
            disc = m.status_disconnected[:n]
            frames = m.status_last_frame[:n]
            off = m.payload_off
            body = M.InputMessage(
                [CS(bool(disc[i]), frames[i]) for i in range(n)],
                bool(m.disconnect_requested),
                m.start_frame,
                m.ack_frame,
                data[off : off + m.payload_len],
            )
        elif tag == _TAG_INPUT_ACK:
            body = M.InputAck(m.ack_frame)
        elif tag == _TAG_QUALITY_REPORT:
            body = M.QualityReport(m.frame_advantage, m.ping)
        elif tag == _TAG_QUALITY_REPLY:
            body = M.QualityReply(m.pong)
        elif tag == _TAG_CHECKSUM_REPORT:
            body = M.ChecksumReport(
                m.checksum_lo | (m.checksum_hi << 64), m.frame
            )
        elif tag == _TAG_KEEP_ALIVE:
            body = M.KeepAlive()
        elif tag == _TAG_SYNC_REQUEST:
            body = M.SyncRequest(m.random_nonce)
        else:  # _TAG_SYNC_REPLY (unknown tags already errored in C++)
            body = M.SyncReply(m.random_nonce)
        return M.Message(m.magic, body)


def msg_encode(msg) -> Optional[bytes]:
    """Native Message encode; returns the wire bytes or ``None`` when the
    library is unavailable or a field exceeds the fast path's 64-bit range
    (caller falls back to the Python encoder)."""
    lib = _load()
    if lib is None:
        return None
    M = _messages()

    global _msg_out
    b = msg.body

    # EXPLICIT range checks — ctypes structure-field assignment silently
    # truncates out-of-range ints (no OverflowError), which would put bytes
    # on the wire that differ from the Python encoder.  Any out-of-range
    # field returns None so the Python path keeps its exact semantics
    # (unbounded zigzag for huge frames, struct.error for i16 overflow,
    # ValueError for negative nonces).
    def i64_ok(v) -> bool:
        return isinstance(v, int) and -(1 << 63) <= v < (1 << 63)

    def i16_ok(v) -> bool:
        return isinstance(v, int) and -(1 << 15) <= v < (1 << 15)

    def u64_ok(v) -> bool:
        return isinstance(v, int) and 0 <= v < (1 << 64)

    with _lock:
        m = _msg_scratch
        payload = b""
        try:
            m.magic = msg.magic & 0xFFFF
            if isinstance(b, M.InputMessage):
                statuses = b.peer_connect_status
                if len(statuses) > _MAX_PLAYERS_ON_WIRE:
                    return None  # python encoder handles (and the wire rejects)
                if not (i64_ok(b.start_frame) and i64_ok(b.ack_frame)):
                    return None
                if not all(i64_ok(cs.last_frame) for cs in statuses):
                    return None
                m.tag = _TAG_INPUT
                m.n_status = len(statuses)
                for i, cs in enumerate(statuses):
                    m.status_disconnected[i] = 1 if cs.disconnected else 0
                    m.status_last_frame[i] = cs.last_frame
                m.disconnect_requested = 1 if b.disconnect_requested else 0
                m.start_frame = b.start_frame
                m.ack_frame = b.ack_frame
                # normalize: the c_char_p argument below rejects bytearray/
                # memoryview with a ctypes.ArgumentError the Python encoder
                # would have accepted.  Go through memoryview rather than
                # bytes() so an int payload (bytes(5) == five NULs!) falls
                # through to the Python encoder's loud TypeError instead of
                # fabricating zero inputs on the wire.
                payload = (
                    b.bytes
                    if isinstance(b.bytes, bytes)
                    else bytes(memoryview(b.bytes))
                )
            elif isinstance(b, M.InputAck):
                if not i64_ok(b.ack_frame):
                    return None
                m.tag = _TAG_INPUT_ACK
                m.ack_frame = b.ack_frame
            elif isinstance(b, M.QualityReport):
                if not i16_ok(b.frame_advantage):
                    return None  # python raises struct.error, as before
                m.tag = _TAG_QUALITY_REPORT
                m.frame_advantage = b.frame_advantage
                m.ping = b.ping & 0xFFFFFFFFFFFFFFFF
            elif isinstance(b, M.QualityReply):
                m.tag = _TAG_QUALITY_REPLY
                m.pong = b.pong & 0xFFFFFFFFFFFFFFFF
            elif isinstance(b, M.ChecksumReport):
                if not i64_ok(b.frame):
                    return None
                m.tag = _TAG_CHECKSUM_REPORT
                m.frame = b.frame
                m.checksum_lo = b.checksum & 0xFFFFFFFFFFFFFFFF
                m.checksum_hi = (b.checksum >> 64) & 0xFFFFFFFFFFFFFFFF
            elif isinstance(b, M.KeepAlive):
                m.tag = _TAG_KEEP_ALIVE
            elif isinstance(b, M.SyncRequest):
                if not u64_ok(b.random):
                    return None  # python raises ValueError on negatives
                m.tag = _TAG_SYNC_REQUEST
                m.random_nonce = b.random
            elif isinstance(b, M.SyncReply):
                if not u64_ok(b.random):
                    return None
                m.tag = _TAG_SYNC_REPLY
                m.random_nonce = b.random
            else:
                return None  # unknown body: let the Python encoder raise
        except (OverflowError, TypeError):
            # belt-and-braces for non-int field types the checks above missed
            return None
        if _msg_out is None or len(payload) + 1024 > len(_msg_out):
            _msg_out = ctypes.create_string_buffer(
                max(_msg_out_cap, len(payload) + 1024)
            )
        out_len = ctypes.c_size_t(0)
        rc = lib.ggrs_msg_encode(
            ctypes.byref(m), payload, len(payload),
            _msg_out, len(_msg_out), ctypes.byref(out_len),
        )
        if rc != 0:
            return None  # python path as the universal fallback
        return ctypes.string_at(_msg_out, out_len.value)


def decode(reference: bytes, data: bytes) -> Optional[List[bytes]]:
    """Native decode; returns None when unavailable OR when the packet
    exceeds the fast path's resource caps (caller falls back to Python).
    Raises ``CodecError`` (the same type the Python codec raises) on
    malformed data."""
    lib = _load()
    if lib is None:
        return None
    from .compression import CodecError

    global _decode_out, _decode_sizes
    with _lock:  # buffers are reused across calls; protocol use is 1-thread
        if _decode_out is None:
            _decode_out = ctypes.create_string_buffer(_DECODE_CAP_BYTES)
            _decode_sizes = (ctypes.c_size_t * _DECODE_CAP_INPUTS)()
        out, out_sizes = _decode_out, _decode_sizes
        out_count = ctypes.c_size_t(0)
        rc = lib.ggrs_codec_decode(
            reference,
            len(reference),
            data,
            len(data),
            out,
            _DECODE_CAP_BYTES,
            out_sizes,
            _DECODE_CAP_INPUTS,
            ctypes.byref(out_count),
        )
        if rc in _RESOURCE_ERRORS:
            return None  # legal-but-huge packet: Python path handles it
        if rc != 0:
            raise CodecError(_ERROR_NAMES.get(rc, f"native error {rc}"))
        # copy only the decoded bytes out of the scratch buffer — .raw would
        # materialize the whole 1MB cap on every access (measured ~100us per
        # packet; string_at of the used prefix is ~2us)
        sizes = out_sizes[: out_count.value]
        blob = ctypes.string_at(out, sum(sizes))
        result: List[bytes] = []
        pos = 0
        for size in sizes:
            result.append(blob[pos : pos + size])
            pos += size
        return result

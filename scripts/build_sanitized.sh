#!/usr/bin/env bash
# Static analysis + sanitized native legs — the correctness gate for the
# crossing (DESIGN.md §20 for the static plane, §9/§15 for the dynamic).
#
# 1. ggrs-verify: the static-analysis plane (cross-language layout
#    checker, determinism lint vs its committed baseline, ownership
#    lint, tree hygiene).  Runs first and cheapest; layout drift or a
#    new determinism violation fails the build before anything compiles.
# 2. ASan+UBSan leg: builds _ggrs_codec_san.so
#    (-fsanitize=address,undefined -fno-sanitize-recover=all) and runs
#    the bank parity/fault fuzzes under it, so any native heap/UB bug
#    aborts the run loudly instead of corrupting the bank.  ASan must be
#    loaded before Python, hence the LD_PRELOAD.
# 3. TSan leg: builds _ggrs_codec_tsan.so (-fsanitize=thread) and runs
#    the tests that drive the GIL-released native I/O threads
#    (ggrs_bank_pump's recvmmsg/sendmmsg ring, the out-of-process
#    runner's serving loop).  Only the native library is instrumented,
#    so reports are races in OUR code, not CPython noise.
#
# Usage: scripts/build_sanitized.sh [extra pytest args]
#   GGRS_SKIP_VERIFY=1  skip the static gate (sanitizers only)
#   GGRS_SKIP_MODEL=1   skip the model-exploration leg (static only)
#   GGRS_SKIP_TSAN=1    skip the TSan leg (ASan only)
set -euo pipefail
cd "$(dirname "$0")/.."

echo "=== ggrs-verify (static analysis plane) ==="
if [ -z "${GGRS_SKIP_VERIFY:-}" ]; then
    JAX_PLATFORMS=cpu python scripts/ggrs_verify.py
else
    echo "skipped (GGRS_SKIP_VERIFY)"
fi

# Model-exploration leg (DESIGN.md §22): breadth-first exploration of
# the §9/§16/§17 protocol machines.  HEAD models must be
# invariant-clean; the known-broken fixtures (pre-PR-11 checkpoint
# ordering, barrier-less journal, threshold-1 rebase, premature
# failover) must keep their pinned shortest counterexamples.  The whole
# catalog runs in well under the 60s wall budget — ggrs_verify prints
# the states/elapsed budget line for the record.
echo "=== ggrs-model (protocol model exploration) ==="
if [ -z "${GGRS_SKIP_MODEL:-}" ] && [ -z "${GGRS_SKIP_VERIFY:-}" ]; then
    JAX_PLATFORMS=cpu timeout -k 10 60 \
        python scripts/ggrs_verify.py --model --no-runtime
else
    echo "skipped (GGRS_SKIP_MODEL / GGRS_SKIP_VERIFY)"
fi

if ! command -v g++ >/dev/null; then
    echo "skip: no g++ toolchain" >&2
    exit 0
fi
asan_rt="$(g++ -print-file-name=libasan.so)"
if [ ! -e "$asan_rt" ]; then
    echo "skip: g++ has no libasan runtime" >&2
    exit 0
fi

# Built by the loader's own ensure_built (same flags, plus the source digest
# it checks before trusting a library), WITHOUT the sanitizer runtime
# preloaded into the compiler.
echo "=== ASan+UBSan leg: building _ggrs_codec_san.so ==="
GGRS_NATIVE_SANITIZE=1 JAX_PLATFORMS=cpu python -c \
    "from ggrs_tpu.net._native import ensure_built; print(ensure_built())"

# detect_leaks=0: CPython itself "leaks" interned objects at exit, which is
# noise here — the target is heap corruption / UB in the native cores while
# the parity fuzz and the chaos tests drive them.
#
# The -k filter keeps the sanitized leg on the HOST-only tests: the
# batched-executor integration tests JIT through XLA, whose own compiler
# trips ASan's interceptors (an upstream finding, not ours) and aborts the
# run before the bank code under test even executes; the fused-scrub
# replay test JITs too.  The slow soak is excluded by default; pass
# "-m" "slow" to run it sanitized too.
# tests/test_fleet_proc.py is included: its shard-runner children
# inherit LD_PRELOAD/GGRS_NATIVE_SANITIZE, so the out-of-process serving
# loop exercises the SANITIZED native bank in the subprocess too.
LD_PRELOAD="$asan_rt" \
ASAN_OPTIONS="detect_leaks=0:abort_on_error=1" \
GGRS_NATIVE_SANITIZE=1 \
JAX_PLATFORMS=cpu \
python -m pytest tests/test_session_bank.py tests/test_policy_plane.py \
    tests/test_descriptor_plane.py \
    tests/test_bank_faults.py \
    tests/test_obs.py tests/test_broadcast.py tests/test_replay_journal.py \
    tests/test_trace.py tests/test_desync_detection.py \
    tests/test_native_io.py tests/test_socket_datapath.py \
    tests/test_net_gen2.py tests/test_decode_parallel.py \
    tests/test_fleet.py tests/test_fleet_rpc.py tests/test_fleet_proc.py \
    tests/test_fleet_link.py tests/test_fleet_obs.py \
    tests/test_ingress.py tests/test_placement.py \
    tests/test_input_plane.py \
    tests/test_timeline_slo.py \
    -q -p no:cacheprovider -m "not slow" \
    -k "not batched_executor and not size_mismatch and not fused_scrub and not scrub_matches and not device_state_bit_identical and not reaches_the_device and not plane_on_off and not plane_parity and not b64_plane and not jax_advance" "$@"

if [ -n "${GGRS_SKIP_TSAN:-}" ]; then
    echo "TSan leg skipped (GGRS_SKIP_TSAN)"
    exit 0
fi
tsan_rt="$(g++ -print-file-name=libtsan.so)"
if [ ! -e "$tsan_rt" ]; then
    echo "skip: g++ has no libtsan runtime" >&2
    exit 0
fi

echo "=== TSan leg: building _ggrs_codec_tsan.so ==="
GGRS_NATIVE_SANITIZE=thread JAX_PLATFORMS=cpu python -c \
    "from ggrs_tpu.net._native import ensure_built; print(ensure_built())"

# The TSan leg targets the concurrency surface: the kernel-batched
# socket datapath (GIL released around recvmmsg/sendmmsg), the
# thread-ownership guard, and the subprocess shard runner (children
# inherit the preload and GGRS_NATIVE_SANITIZE=thread, so the runner's
# serving loop drives the TSan bank too).  halt_on_error aborts the
# run on the first race; second_deadlock_stack improves lock reports.
# GGRS_TPU_DECODE_BACKEND=thread forces the §24 decode plane onto real
# worker threads here, so its fan-out/merge runs under TSan even on
# builds where the runtime default would resolve serial.
LD_PRELOAD="$tsan_rt" \
TSAN_OPTIONS="halt_on_error=1:second_deadlock_stack=1" \
GGRS_NATIVE_SANITIZE=thread \
GGRS_TPU_DECODE_BACKEND=thread \
JAX_PLATFORMS=cpu \
python -m pytest tests/test_native_io.py tests/test_socket_datapath.py \
    tests/test_net_gen2.py tests/test_decode_parallel.py \
    tests/test_thread_ownership.py tests/test_fleet_proc.py \
    tests/test_fleet_link.py tests/test_descriptor_plane.py \
    tests/test_ingress.py tests/test_placement.py \
    tests/test_input_plane.py \
    tests/test_timeline_slo.py \
    -q -p no:cacheprovider -m "not slow" \
    -k "not batched_executor and not size_mismatch and not device_state_bit_identical and not reaches_the_device and not plane_on_off and not plane_parity and not b64_plane and not jax_advance" "$@"

echo "sanitized legs green (ASan+UBSan, TSan) + ggrs-verify"

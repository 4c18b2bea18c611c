"""Sanitizer CI for the native core (SURVEY.md §5: the reference ships no
TSAN/ASAN CI; the rebuild adds it — round-2 verdict #7: ~2,900 LoC of
hand-rolled threaded C++ was guarded only by Python-level tests).

Strategy: build the core with -fsanitize={thread|address,undefined}
(``make tsan`` / ``make asan``), point workers at the instrumented .so via
``HVDTPU_NATIVE_LIB``, LD_PRELOAD the sanitizer runtime (the python host
binary is uninstrumented), and drive the full process-mode op menu
(``proc_worker.py``: queue, controller negotiation, fusion, TCP ring data
plane, join) across 2 real ranks. Any report fails the run: TSan/ASan exit
66 on findings, and UBSan "runtime error" lines are scanned explicitly.
"""

import os
import subprocess

import pytest

from conftest import assert_all_ok, launch_world, wait_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "horovod_tpu", "native")
WORKER = os.path.join(REPO, "tests", "data", "proc_worker.py")


def _gcc_file(name: str) -> str:
    out = subprocess.run(["g++", f"-print-file-name={name}"],
                         capture_output=True, text=True)
    path = out.stdout.strip()
    return path if os.path.isabs(path) else ""


def _build(target: str) -> str:
    lib = os.path.join(NATIVE, f"build-{target}", "libhvdtpu_core.so")
    r = subprocess.run(["make", "-C", NATIVE, target], capture_output=True,
                       text=True)
    if r.returncode != 0 or not os.path.exists(lib):
        pytest.skip(f"sanitizer build '{target}' unavailable: "
                    f"{r.stderr[-300:]}")
    return lib


def _scan(results, *markers):
    assert_all_ok(results)
    for rank, (_rc, _out, err) in enumerate(results):
        for line in err.splitlines():
            if any(m in line for m in markers):
                raise AssertionError(f"rank {rank} sanitizer report: {line}")


@pytest.mark.slow  # ~40 s: sanitizer rebuild + 2-rank world; tier-1 keeps the tsan unit-test + pipelined smokes
def test_tsan_process_mode():
    rt = _gcc_file("libtsan.so")
    if not rt:
        pytest.skip("libtsan.so not found")
    lib = _build("tsan")
    results = launch_world(2, WORKER, extra_env={
        "HVDTPU_NATIVE_LIB": lib,
        "LD_PRELOAD": rt,
        # exitcode=66 turns any data-race report into a worker failure.
        "TSAN_OPTIONS": "exitcode=66 report_thread_leaks=0",
        # TCP lanes: cross-PROCESS shm gives TSan nothing (it cannot see the
        # peer's accesses to the shared rings) while the ring spin-waits
        # burn CPU that two TSan'd python workers on a small host need —
        # the rings' real TSan coverage is `make check-tsan`'s in-process
        # worlds, where both sides are instrumented.
        "HVDTPU_SHM": "0",
    }, timeout=240)
    _scan(results, "ThreadSanitizer")


def test_tsan_native_unit_tests():
    """TSan-instrumented native unit tests: the pipelined data plane
    (SendRecvSegmented sender/receiver/reducer handoff, every allreduce
    algorithm across threaded in-process worlds) with no Python host in the
    way — seconds even on tiny machines (ISSUE 1 satellite). Since ISSUE 2
    this binary also covers the shm transport (ring wraparound, futex
    doorbell wakeup, abort-path shm_unlink cleanup) and the hierarchical
    allreduce worlds — the rings are MAP_SHARED atomics, so TSan checks the
    exact cross-process protocol. Since ISSUE 3 it also runs the compressed
    allreduce worlds (fp16/int8/int4 x ring/recursive-doubling x TCP/shm
    lanes + compressed-leader hierarchical) and the wire quantizer's
    round-trip/EF kernels."""
    r = subprocess.run(["make", "-C", NATIVE, "check-tsan"],
                       capture_output=True, text=True, timeout=150)
    assert r.returncode == 0, f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    assert "ALL OK" in r.stdout
    for line in (r.stdout + r.stderr).splitlines():
        assert "ThreadSanitizer" not in line, line


@pytest.mark.slow  # ~100 s: full ASan+UBSan unit-test binary; tier-1 keeps the tsan unit-test + pipelined smokes
def test_asan_ubsan_native_unit_tests():
    """ASan+UBSan build of the same native unit-test binary (ISSUE 2
    satellite): the shm rings' mmap'ed cursor arithmetic and the segment
    teardown paths are where an off-by-one corrupts silently; any report
    exits 66 via the Makefile's ASAN_OPTIONS."""
    r = subprocess.run(["make", "-C", NATIVE, "check-asan"],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    assert "ALL OK" in r.stdout
    for line in (r.stdout + r.stderr).splitlines():
        assert "AddressSanitizer" not in line and "runtime error" not in line, \
            line


def test_tsan_pipelined_allreduce():
    """End-to-end pipelined allreduce under TSan through the full core
    (event-driven background loop, controller negotiation, segmented ring
    with many handoffs per chunk at a 32 KB segment size) — driven by the
    benchmark's raw-ctypes worker, which needs no JAX import: the full
    Python stack under TSan exceeds any reasonable timeout on small hosts
    (ISSUE 1 satellite)."""
    import socket
    import sys
    rt = _gcc_file("libtsan.so")
    if not rt:
        pytest.skip("libtsan.so not found")
    lib = _build("tsan")
    bench = os.path.join(REPO, "scripts", "bench_native_allreduce.py")
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []
    for r in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, bench, "--worker", "--rank", str(r),
             "--world", "2", "--port", str(port), "--algo", "auto",
             "--sizes", "4096,4194304", "--lib", lib,
             "--segment", "32768", "--crossover", "-1"],
            env={**os.environ, "LD_PRELOAD": rt,
                 "TSAN_OPTIONS": "exitcode=66 report_thread_leaks=0"},
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results = wait_world(procs)
    for rank, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {rank} rc={rc}:\n{err[-2000:]}\n{out[-500:]}"
        for line in err.splitlines():
            assert "ThreadSanitizer" not in line, \
                f"rank {rank} sanitizer report: {line}"
    # Rank 0 emitted one verified result row per size (the worker checks
    # reduction values itself and exits nonzero on mismatch).
    assert results[0][1].count('"bytes"') == 2, results[0][1]


@pytest.mark.slow  # ~45 s: standalone UBSan unit-test binary; tier-1 keeps the tsan unit-test + pipelined smokes
def test_ubsan_native_unit_tests():
    """Standalone UBSan build of the native unit-test binary (ISSUE 5
    satellite): -fsanitize=undefined alone with -fno-sanitize-recover=all,
    so pure-UB findings (misaligned loads, signed overflow in the quantizer
    math, bad enum casts from wire bytes) abort instead of riding along
    under ASan's error path where an address report can mask them."""
    r = subprocess.run(["make", "-C", NATIVE, "check-ubsan"],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"{r.stdout[-2000:]}\n{r.stderr[-2000:]}"
    assert "ALL OK" in r.stdout
    for line in (r.stdout + r.stderr).splitlines():
        assert "runtime error" not in line, line


@pytest.mark.slow  # ~55 s: UBSan rebuild + 2-rank world; tier-1 keeps the tsan unit-test + pipelined smokes
def test_ubsan_process_mode():
    """The full process-mode op menu against the UBSan-only .so. libubsan
    is preloaded for the uninstrumented python host; any runtime-error
    report fails the run via halt_on_error (the build is
    -fno-sanitize-recover=all, so recovery is impossible anyway)."""
    rt = _gcc_file("libubsan.so")
    stdcxx = _gcc_file("libstdc++.so")
    if not rt or not stdcxx:
        pytest.skip("libubsan.so/libstdc++.so not found")
    lib = _build("ubsan")
    results = launch_world(2, WORKER, extra_env={
        "HVDTPU_NATIVE_LIB": lib,
        "LD_PRELOAD": f"{rt} {stdcxx}",
        "UBSAN_OPTIONS": "print_stacktrace=1,halt_on_error=1",
    }, timeout=240)
    _scan(results, "runtime error")


@pytest.mark.slow  # ~175 s: ASan rebuild + 2-rank world; tier-1 keeps the tsan unit-test + pipelined smokes
def test_asan_ubsan_process_mode():
    rt = _gcc_file("libasan.so")
    stdcxx = _gcc_file("libstdc++.so")
    if not rt or not stdcxx:
        pytest.skip("libasan.so/libstdc++.so not found")
    lib = _build("asan")
    results = launch_world(2, WORKER, extra_env={
        "HVDTPU_NATIVE_LIB": lib,
        # libstdc++ preloaded too: ASan's __cxa_throw interceptor cannot
        # bind when the (python) host loads libstdc++ lazily.
        "LD_PRELOAD": f"{rt} {stdcxx}",
        # detect_leaks=0: the python host leaks by design; we care about
        # memory errors in the core, which still abort with exitcode 66.
        "ASAN_OPTIONS": "detect_leaks=0,abort_on_error=0,exitcode=66",
    }, timeout=240)
    _scan(results, "AddressSanitizer", "runtime error")

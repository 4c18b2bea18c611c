"""Native-core feature tests: response cache, Bayesian autotune, runtime
timeline control.

Reference analogs: response_cache.{h,cc} steady-state behavior,
parameter_manager.{h,cc} autotuning, horovod_start_timeline/stop_timeline
(operations.cc:735-790). Strategy per SURVEY.md §4: real multi-process over
localhost TCP.
"""

import os

import pytest

from conftest import assert_all_ok, launch_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")


@pytest.mark.parametrize("capacity", ["1024", "3", "0"])
def test_response_cache(capacity):
    """Steady-state repeat collectives stay correct with the cache at default
    capacity, at a tiny capacity (forcing evictions and the NEED_FULL repair
    round trip), and disabled."""
    results = launch_world(2, os.path.join(DATA, "cache_worker.py"),
                           extra_env={"HVDTPU_CACHE_CAPACITY": capacity})
    assert_all_ok(results)


def test_response_cache_world_4():
    results = launch_world(4, os.path.join(DATA, "cache_worker.py"))
    assert_all_ok(results)


def test_response_cache_counters_steady_state():
    """The cache-effectiveness counters (docs/metrics.md): a repeating
    tensor set at default capacity negotiates each name in full exactly
    once, then every later announcement is a bare-name hit — the worker
    asserts hits ~ steps x names with misses an order of magnitude
    smaller on every rank."""
    results = launch_world(2, os.path.join(DATA, "cache_worker.py"),
                           extra_env={"TEST_ASSERT_CACHE_COUNTERS": "1"})
    assert_all_ok(results)


def test_autotune(tmp_path):
    """The parameter manager explores (params move off defaults), logs scored
    samples, and collectives stay correct throughout."""
    log = tmp_path / "autotune.csv"
    results = launch_world(
        2, os.path.join(DATA, "autotune_worker.py"),
        extra_env={
            "HVDTPU_AUTOTUNE": "1",
            "HVDTPU_AUTOTUNE_LOG": str(log),
            # Small budgets so tuning concludes within the test run.
            "HVDTPU_AUTOTUNE_WARMUP_SAMPLES": "1",
            "HVDTPU_AUTOTUNE_STEPS_PER_SAMPLE": "4",
            "HVDTPU_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "6",
        })
    assert_all_ok(results)


def test_stall_shutdown():
    """A rank that never announces must abort the job after
    HVDTPU_STALL_SHUTDOWN_TIME_SECONDS, not hang (reference:
    StallInspector::ShutdownIfStalled)."""
    results = launch_world(
        2, os.path.join(DATA, "stall_worker.py"),
        extra_env={
            "HVDTPU_STALL_CHECK_TIME_SECONDS": "1",
            "HVDTPU_STALL_SHUTDOWN_TIME_SECONDS": "3",
        }, timeout=60)
    assert_all_ok(results)


def test_runtime_timeline(tmp_path):
    """start_timeline/stop_timeline bracket exactly the traced phase."""
    results = launch_world(
        2, os.path.join(DATA, "timeline_worker.py"),
        extra_env={"TEST_TIMELINE_PATH": str(tmp_path / "tl")})
    assert_all_ok(results)


def test_native_unit_tests():
    """Build and run the C++ unit-test binary (SURVEY.md §4: the reference
    tests its native core only through Python; the rebuild adds direct
    native-layer tests — wire roundtrips, truncation safety, half floats,
    reduction ops, GP/Bayesian-optimizer math)."""
    import subprocess
    native = os.path.abspath(os.path.join(DATA, "..", "..", "horovod_tpu",
                                          "native"))
    r = subprocess.run(["make", "-C", native, "check"], capture_output=True,
                       text=True, timeout=150)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr}"
    assert "ALL OK" in r.stdout

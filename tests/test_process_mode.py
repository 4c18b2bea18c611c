"""Process-mode (native core) integration tests: real multi-process over
localhost TCP.

Mirrors the reference's strategy for testing multi-node behavior as
multi-process on one machine (SURVEY.md §4; ``test/integration/test_static_run.py``)
— here the data plane is the native TCP ring instead of Gloo.
"""

import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "data", "proc_worker.py")


from conftest import subprocess_env as _subprocess_env  # noqa: E402
from conftest import launch_world as _launch_world  # noqa: E402


@pytest.mark.parametrize("n", [2, 4])
def test_full_collective_menu(n):
    """The whole eager op menu: allreduce variants, broadcast, allgatherv,
    alltoall, min/max, bfloat16, fusion, object collectives, shape/dtype
    error agreement, Adasum, join."""
    results = _launch_world(n, WORKER)
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


@pytest.mark.parametrize("n", [2, 3])
def test_true_async_collectives(n):
    """N async allreduces are all in flight on the native core before the
    first synchronize (round-1 verdict #2: backward/comm overlap)."""
    worker = os.path.join(os.path.dirname(WORKER), "async_worker.py")
    results = _launch_world(n, worker)
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


@pytest.mark.parametrize("algo", ["ring", "recursive_doubling", "tree",
                                  "scatter_allgather", "parameter_server"])
def test_allreduce_algorithms(algo):
    """Every native allreduce algorithm produces exact results end to end
    (HVDTPU_ALLREDUCE_ALGO -> basics.py -> hvdtpu_set_allreduce_tuning).
    The tiny segment size forces the ring's segmented pipeline even at
    test-sized tensors."""
    results = _launch_world(2, os.path.join(REPO, "tests", "data",
                                            "algo_worker.py"),
                            extra_env={
                                "HVDTPU_ALLREDUCE_ALGO": algo,
                                "HVDTPU_ALLREDUCE_SEGMENT_BYTES": "8192",
                                "TEST_ALGO_ITERS": "2",
                            })
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


# Non-power-of-two worlds: every algorithm must handle remainder ranks —
# the ring's uneven chunking, recursive doubling's non-participant fold,
# the tree's odd fan-in, scatter-allgather's uneven ownership rotation and
# the parameter server's (world-1)-worker star. Cross-rank bitwise
# equality is asserted through the divergence-probe fingerprints
# (HVDTPU_GRADCHECK_SAMPLE=1: the worker CRCs every collective output and
# rank 0 convicts any rank whose fingerprint differs). Tier-1 runs w3 for
# every algorithm x transport; w5/w6 ride the slow marker.
_NPO2_ALGOS = ["ring", "recursive_doubling", "tree", "scatter_allgather",
               "parameter_server"]


def _npo2_world(n, algo, shm):
    results = _launch_world(
        n, os.path.join(REPO, "tests", "data", "grad_worker.py"),
        extra_env={
            "TEST_GRAD_ITERS": "2",
            "HVDTPU_ALLREDUCE_ALGO": algo,
            "HVDTPU_GRADCHECK_SAMPLE": "1",
            "HVDTPU_SHM": shm,
        },
        timeout=150)
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


@pytest.mark.parametrize("shm", ["0", "1"])
@pytest.mark.parametrize("algo", _NPO2_ALGOS)
def test_npo2_world_bitwise(algo, shm):
    """w3: the smallest world where every algorithm hits its remainder
    path, over both TCP and shared-memory lanes."""
    _npo2_world(3, algo, shm)


@pytest.mark.slow
@pytest.mark.parametrize("n", [5, 6])
@pytest.mark.parametrize("algo", _NPO2_ALGOS)
def test_npo2_world_bitwise_large(algo, n):
    """w5 (prime) and w6 (even, non-power) over TCP: deeper remainder
    coverage for the recursive-doubling fold and SA ownership rotation."""
    _npo2_world(n, algo, "0")


# First-class reduce-scatter & allgather (docs/collectives.md): every
# transport x wire-compression cell, with w3 covering the non-power-of-two
# chunking (ragged RS chunks, uneven AG blocks). The divergence probe
# (HVDTPU_GRADCHECK_SAMPLE=1) asserts the bitwise cross-rank invariant on
# the gathered outputs — under compression that is the quantize-once
# owner-code guarantee, the op-level claim this PR ships.
def _rsag_world(n, shm, comp, timeout=150):
    results = _launch_world(
        n, os.path.join(REPO, "tests", "data", "rsag_worker.py"),
        extra_env={
            "TEST_RSAG_ITERS": "2",
            "HVDTPU_SHM": shm,
            "HVDTPU_COMPRESSION": comp,
            "HVDTPU_COMPRESSION_MIN_BYTES": "0",
            "HVDTPU_COMPRESSION_SKIP_REGEX": "",
            "HVDTPU_GRADCHECK_SAMPLE": "1",
        },
        timeout=timeout)
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


@pytest.mark.parametrize("comp", ["none", "fp16", "int8", "int4"])
@pytest.mark.parametrize("shm", ["0", "1"])
def test_reducescatter_allgather_matrix(shm, comp):
    """w2: the full {tcp,shm} x {none,fp16,int8,int4} cell matrix."""
    _rsag_world(2, shm, comp)


@pytest.mark.parametrize("comp", ["none", "int4"])
def test_reducescatter_allgather_npo2(comp):
    """w3 (non-power-of-two): ragged chunk starts on the RS rotation and
    uneven negotiated blocks on the AG, dense and heaviest-quantized."""
    _rsag_world(3, "0", comp)


@pytest.mark.slow
@pytest.mark.parametrize("comp", ["none", "fp16", "int8", "int4"])
def test_reducescatter_allgather_npo2_large(comp):
    """w5 over TCP: prime-world chunking across every wire mode."""
    _rsag_world(5, "0", comp, timeout=360)


# First-class broadcast & alltoall(v) (docs/collectives.md "Broadcast &
# alltoall", PR 19): every transport x wire-compression cell, with the npo2
# worlds (w3/w5) covering the binomial tree's non-power-of-two vrank
# rotation and uneven pairwise splits. The worker asserts dense exactness,
# compressed tolerance, world-bitwise outputs over a lossless CRC channel
# AND via the divergence probe (broadcast outputs are fingerprinted), the
# grouped-enqueue ctrl-frame reduction, and raw/wire timeline args.
def _ba_world(n, shm, comp, timeout=150, tmp_path=None):
    extra = {
        "TEST_BA_ITERS": "2",
        "HVDTPU_SHM": shm,
        "HVDTPU_COMPRESSION": comp,
        "HVDTPU_COMPRESSION_MIN_BYTES": "0",
        "HVDTPU_COMPRESSION_SKIP_REGEX": "",
        "HVDTPU_GRADCHECK_SAMPLE": "1",
    }
    if tmp_path is not None:
        extra["TEST_TIMELINE_PATH"] = str(tmp_path / "ba_tl")
    results = _launch_world(
        n, os.path.join(REPO, "tests", "data", "bcast_a2a_worker.py"),
        extra_env=extra, timeout=timeout)
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


@pytest.mark.parametrize("comp", ["none", "fp16", "int8", "int4"])
@pytest.mark.parametrize("shm", ["0", "1"])
def test_broadcast_alltoall_matrix(shm, comp, tmp_path):
    """w2: the full {tcp,shm} x {none,fp16,int8,int4} cell matrix, with
    timeline op-done byte args asserted."""
    _ba_world(2, shm, comp, tmp_path=tmp_path)


@pytest.mark.parametrize("comp", ["none", "int4"])
@pytest.mark.parametrize("shm", ["0", "1"])
def test_broadcast_alltoall_npo2(shm, comp):
    """w3 (non-power-of-two): binomial tree with a remainder subtree and
    uneven pairwise rotation, dense and heaviest-quantized, both lanes."""
    _ba_world(3, shm, comp)


@pytest.mark.slow
@pytest.mark.parametrize("comp", ["none", "fp16", "int8", "int4"])
def test_broadcast_alltoall_npo2_large(comp):
    """w5 (prime) over TCP: deeper tree + 4-peer pairwise schedule across
    every wire mode."""
    _ba_world(5, "0", comp, timeout=360)


@pytest.mark.parametrize("shm", ["1", "0"])
def test_shm_transport_toggle(shm):
    """The whole collective menu stays correct over the shared-memory lanes
    (HVDTPU_SHM default) AND with them disabled (TCP everywhere) — both
    sides of every same-host pair must agree on the lane, so the toggle
    exercises the socket handshake's negative path too."""
    results = _launch_world(2, WORKER, extra_env={"HVDTPU_SHM": shm})
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


def test_hierarchical_allreduce_two_hosts():
    """Hierarchical two-level allreduce across a synthetic two-host world:
    ranks 0-1 advertise 127.0.0.1, ranks 2-3 advertise localhost (both
    resolve locally, so the leader TCP hop is real while the native layer
    sees two hosts). Every rank must produce the exact flat result."""
    import subprocess

    from conftest import free_port, subprocess_env, wait_world

    worker = os.path.join(REPO, "tests", "data", "algo_worker.py")
    port = free_port()
    hosts = ["127.0.0.1", "127.0.0.1", "localhost", "localhost"]
    procs = []
    for r in range(4):
        env = subprocess_env()
        env.update({
            "HVDTPU_RANK": str(r), "HVDTPU_SIZE": "4",
            "HVDTPU_LOCAL_RANK": str(r % 2), "HVDTPU_LOCAL_SIZE": "2",
            "HVDTPU_CROSS_RANK": str(r // 2), "HVDTPU_CROSS_SIZE": "2",
            "HVDTPU_HOSTNAME": hosts[r],
            "HVDTPU_CONTROLLER_PORT": str(port),
            "HVDTPU_ALLREDUCE_HIER": "1",
            "HVDTPU_ALLREDUCE_SEGMENT_BYTES": "8192",
            "TEST_ALGO_ITERS": "2",
        })
        procs.append(subprocess.Popen([sys.executable, worker], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE, text=True))
    for r, (rc, out, err) in enumerate(wait_world(procs)):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


def test_invalid_allreduce_hier_rejected():
    """A bad HVDTPU_ALLREDUCE_HIER fails fast at init with the valid menu in
    the message (same contract as HVDTPU_ALLREDUCE_ALGO)."""
    results = _launch_world(2, os.path.join(REPO, "tests", "data",
                                            "algo_worker.py"),
                            extra_env={"HVDTPU_ALLREDUCE_HIER": "sideways"},
                            timeout=60)
    for _rc, _out, err in results:
        assert _rc != 0
        assert "HVDTPU_ALLREDUCE_HIER" in err and "sideways" in err


def test_invalid_allreduce_algo_rejected():
    """A bad HVDTPU_ALLREDUCE_ALGO fails fast at init with the valid menu in
    the message, instead of silently falling back."""
    results = _launch_world(2, os.path.join(REPO, "tests", "data",
                                            "algo_worker.py"),
                            extra_env={"HVDTPU_ALLREDUCE_ALGO": "warp"},
                            timeout=60)
    for _rc, _out, err in results:
        assert _rc != 0
        assert "HVDTPU_ALLREDUCE_ALGO" in err and "warp" in err


@pytest.mark.slow
def test_large_allreduce_socket_buffer_regression():
    """4-process, 64 MB fp32 allreduce: every ring chunk dwarfs the kernel
    socket buffers, so any send that loses its concurrent receive (or an
    out-of-order pipeline segment) deadlocks right here (ISSUE 1 satellite;
    marked slow to stay out of the tier-1 budget)."""
    results = _launch_world(4, os.path.join(REPO, "tests", "data",
                                            "big_allreduce_worker.py"),
                            timeout=600)
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


def test_hvdrun_cli(tmp_path):
    """hvdrun end-to-end (reference: test_static_run.py)."""
    timeline = tmp_path / "tl"
    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--timeline", str(timeline), sys.executable, WORKER],
        env=_subprocess_env(), capture_output=True, text=True, timeout=150)
    assert rc.returncode == 0, rc.stderr
    import json
    events = json.load(open(f"{timeline}.0.json"))
    names = {e["name"] for e in events}
    assert "ALLREDUCE" in names and "NEGOTIATE" in names
    # Transport tag per op (ISSUE 2): every data-plane op records its lane
    # mix in the trace args — localhost world => shm (or tcp if the shm
    # setup fell back; never absent).
    lanes = {e.get("args", {}).get("transport")
             for e in events if e["name"] == "ALLREDUCE"}
    assert lanes & {"shm", "tcp", "tcp-zc", "shm+tcp", "shm+tcp-zc"}, lanes


def test_programmatic_run():
    """horovod_tpu.runner.run(fn, np=2) returns per-rank results
    (reference: horovod.run, horovod/runner/__init__.py:99). The fn is a
    closure so cloudpickle ships it by value (test modules are not importable
    in workers)."""
    import horovod_tpu.runner as runner

    factor = 2

    def rank_times(factor=factor):
        import horovod_tpu as hvd
        return hvd.rank() * factor

    results = runner.run(rank_times, np=2)
    assert results == [0, 2]


def test_worker_failure_terminates_job(tmp_path):
    """A crashing worker must take the job down, not hang it
    (reference: safe_shell_exec process-group kill)."""
    script = tmp_path / "crasher.py"
    script.write_text(
        "import os, sys\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "if hvd.rank() == 1: sys.exit(3)\n"
        "import numpy as np\n"
        "try:\n"
        "    hvd.allreduce(np.ones(4, np.float32), name='x')\n"
        "except Exception:\n"
        "    pass\n"  # peer death surfaces as an error or shutdown
        "hvd.shutdown()\n")
    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         sys.executable, str(script)],
        env=_subprocess_env(), capture_output=True, text=True, timeout=120)
    assert rc.returncode != 0


def test_peer_death_between_steps_fails_over(tmp_path):
    """A worker that dies with NO ops in flight must still break the next
    collective on the survivors instead of hanging (regression: the
    coordinator only set world_broken_ when tables were non-empty)."""
    script = tmp_path / "quitter.py"
    script.write_text(
        "import os, sys, time\n"
        "import numpy as np\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "import horovod_tpu as hvd\n"
        "from horovod_tpu.exceptions import HvdTpuInternalError\n"
        "hvd.init()\n"
        "hvd.allreduce(np.ones(4, np.float32), name='warm')\n"
        "if hvd.rank() == 1:\n"
        "    os._exit(0)\n"  # vanish between steps, no join, no shutdown
        "time.sleep(1.0)\n"  # let the coordinator observe the EOF
        "try:\n"
        "    hvd.allreduce(np.ones(4, np.float32), name='after')\n"
        "except HvdTpuInternalError:\n"
        "    print('FAILED OVER')\n"
        "    sys.exit(0)\n"
        "print('HUNG OR SUCCEEDED', file=sys.stderr)\n"
        "sys.exit(9)\n")
    results = _launch_world(2, str(script), timeout=60)
    rc0, out0, err0 = results[0]
    assert rc0 == 0, f"rank 0: rc={rc0}\n{err0}\n{out0}"
    assert "FAILED OVER" in out0


def test_join_after_peer_death_fails_over(tmp_path):
    """hvd.join() by survivors after a non-joined peer died must error, not
    hang (JOIN announcements bypass the ready-request dead-peer guard)."""
    script = tmp_path / "join_quitter.py"
    script.write_text(
        "import os, sys, time\n"
        "import numpy as np\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "import horovod_tpu as hvd\n"
        "from horovod_tpu.exceptions import HvdTpuInternalError\n"
        "hvd.init()\n"
        "hvd.allreduce(np.ones(4, np.float32), name='warm')\n"
        "if hvd.rank() == 1:\n"
        "    os._exit(0)\n"
        "time.sleep(1.0)\n"
        "try:\n"
        "    hvd.join()\n"
        "except HvdTpuInternalError:\n"
        "    print('JOIN FAILED OVER')\n"
        "    sys.exit(0)\n"
        "sys.exit(9)\n")
    results = _launch_world(3, str(script), timeout=60)
    for r in (0, 2):
        rc, out, err = results[r]
        assert rc == 0, f"rank {r}: rc={rc}\n{err}\n{out}"
        assert "JOIN FAILED OVER" in out


def test_single_rank_without_native_core(monkeypatch):
    """Source-only installs (no compiled .so) keep working at size 1:
    init falls back to a pure-Python local core (ADVICE r1 low)."""
    import horovod_tpu as hvd
    from horovod_tpu import basics, runtime

    def boom(*a, **k):
        raise OSError("simulated missing libhvdtpu_core.so")

    monkeypatch.setattr(basics, "NativeCore", boom)
    monkeypatch.setenv("HVDTPU_RANK", "0")
    monkeypatch.setenv("HVDTPU_SIZE", "1")
    hvd.shutdown()
    try:
        hvd.init()
        assert hvd.size() == 1 and hvd.rank() == 0
        assert isinstance(runtime.core(), runtime._SingleRankCore)
        out = hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum)
        np.testing.assert_allclose(np.asarray(out), np.ones(4))
        gathered = hvd.allgather(np.arange(3.0, dtype=np.float32))
        np.testing.assert_allclose(np.asarray(gathered),
                                   np.arange(3.0, dtype=np.float32))
        hvd.join()
    finally:
        hvd.shutdown()


def test_spmd_multihost_bootstrap():
    """REAL multi-host SPMD: two processes bootstrap via jax.distributed
    (HVDTPU_COORDINATOR_ADDR), build ONE global mesh, and run cross-host
    in-step collectives (the compiled-path control plane; SURVEY §2.7 —
    the role MPI_Init/gloo rendezvous plays in the reference)."""
    import subprocess
    import sys

    from conftest import free_port, subprocess_env, wait_world

    port = free_port()
    worker = os.path.join(REPO, "tests", "data", "spmd_multihost_worker.py")
    procs = []
    for pid in range(2):
        env = subprocess_env()
        env.pop("XLA_FLAGS", None)  # the worker sets its own device count
        env.update({
            "HVDTPU_COORDINATOR_ADDR": f"127.0.0.1:{port}",
            "HVDTPU_NUM_PROCESSES": "2",
            "HVDTPU_PROCESS_ID": str(pid),
        })
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    for i, (rc, out, err) in enumerate(wait_world(procs)):
        assert rc == 0, f"process {i}:\n{err}\n{out}"
        assert "ALL OK" in out

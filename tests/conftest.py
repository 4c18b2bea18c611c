"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's strategy of testing multi-node behavior as multi-process
on one machine (SURVEY.md §4): here, multi-chip behavior is tested as a virtual
8-device CPU mesh (`--xla_force_host_platform_device_count=8`), exactly how the
driver dry-runs the multi-chip path. Process-mode (eager controller) tests spawn
real subprocesses over localhost TCP instead.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# hvd.init() places the persistent compilation cache inside the checkout
# (runtime._place_compilation_cache); the tests and the workers they spawn
# neither read nor fill it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

# Tests run on the virtual CPU mesh even where an accelerator is present.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def subprocess_env() -> dict:
    """Env for worker subprocesses: repo importable from anywhere (workers run
    as ``python <script>``, so sys.path[0] is the script dir, not the repo).

    JAX_PLATFORMS=cpu keeps workers on the CPU platform: process mode runs
    one worker per rank, and on a host with accelerators every worker would
    otherwise try to open every chip."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    prev = env.get("PYTHONPATH")
    env["PYTHONPATH"] = REPO_ROOT + (os.pathsep + prev if prev else "")
    return env


def free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_world(procs, timeout=150, grace=30):
    """Waits for every process of a world against one deadline and returns
    ``[(returncode, stdout, stderr)]`` in the processes' order, every pipe
    read as it fills. Once a process has failed the others have ``grace``
    seconds left: a world that lost a rank fails over within seconds or
    waits for it for ever. Past the deadline, or when the test is
    interrupted (``_limit`` below), whatever still runs is killed, so no
    worker outlives its test; a killed process reads ``-9`` with its stderr
    headed ``[killed after timeout]``."""
    import threading
    import time
    outputs = [None] * len(procs)

    def read(i, p):
        outputs[i] = p.communicate()

    readers = [threading.Thread(target=read, args=(i, p), daemon=True)
               for i, p in enumerate(procs)]
    for reader in readers:
        reader.start()
    deadline = time.monotonic() + timeout
    try:
        while any(p.poll() is None for p in procs):
            if any(p.poll() for p in procs):
                deadline = min(deadline, time.monotonic() + grace)
            if time.monotonic() >= deadline:
                break
            time.sleep(0.05)
    finally:
        stalled = [p for p in procs if p.poll() is None]
        for p in stalled:
            p.kill()
        for reader in readers:
            reader.join()
    return [(-9, out, f"[killed after timeout]\n{err}") if p in stalled
            else (p.returncode, out, err)
            for p, (out, err) in zip(procs, outputs)]


def launch_world(n: int, script: str, extra_env=None, timeout=150):
    """Spawn an n-rank process-mode world running ``script``; returns
    [(returncode, stdout, stderr)] per rank (SURVEY.md §4: multi-node tested
    as multi-process on localhost)."""
    import subprocess
    import sys
    port = free_port()
    procs = []
    for r in range(n):
        env = subprocess_env()
        env.update({
            "HVDTPU_RANK": str(r), "HVDTPU_SIZE": str(n),
            "HVDTPU_LOCAL_RANK": str(r), "HVDTPU_LOCAL_SIZE": str(n),
            "HVDTPU_CONTROLLER_PORT": str(port),
        })
        env.update(extra_env or {})
        procs.append(subprocess.Popen([sys.executable, script], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE, text=True))
    return wait_world(procs, timeout)


def assert_all_ok(results):
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


# Every test's limit, in seconds: what rightly takes longer is the ``slow``
# tier's. Every ``timeout=`` of a tier-1 test stays under it, so that a
# stalled world is killed and reported by ``wait_world`` with its ranks'
# stderr, and only a wait nothing else bounds ends here.
TEST_LIMIT_S = 180


def _kill_descendants(pid):
    """SIGKILL to every process below ``pid`` (Linux's ``/proc``)."""
    import signal
    try:
        for task in os.listdir(f"/proc/{pid}/task"):
            with open(f"/proc/{pid}/task/{task}/children") as f:
                for child in map(int, f.read().split()):
                    _kill_descendants(child)
                    os.kill(child, signal.SIGKILL)
    except OSError:     # gone meanwhile
        pass


@pytest.fixture(autouse=True)
def _limit(request):
    """A test that runs past ``TEST_LIMIT_S`` fails alone: every thread's
    stack goes to stderr, the processes the test left running are killed
    (a wait inside the library, ``run_elastic``'s for one, stops no worker
    of its own when it is interrupted) and the test fails by name of the
    limit, where a hang used to cost the whole run its clock. An xdist
    worker runs its tests on the main thread, which is where Python delivers
    a signal. The ``slow`` tier, which no timed run includes, is left to its
    own waits."""
    import faulthandler
    import signal
    import sys
    if request.node.get_closest_marker("slow"):
        yield
        return
    limit = TEST_LIMIT_S

    def expired(signum, frame):
        try:
            faulthandler.dump_traceback(file=sys.stderr)
        except (AttributeError, OSError, ValueError):   # captured, no fd
            faulthandler.dump_traceback(file=sys.__stderr__)
        _kill_descendants(os.getpid())
        pytest.fail(f"the test ran past its limit of {limit} s "
                    "(tests/conftest.py::TEST_LIMIT_S)")

    before = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, before)


@pytest.fixture
def spmd8():
    """Initialized SPMD runtime over the 8-device CPU mesh."""
    hvd.shutdown()
    hvd.init()
    assert hvd.size() == 8
    yield hvd
    hvd.shutdown()


@pytest.fixture
def make_runtime():
    """Factory for runtimes over custom device subsets / mesh shapes."""
    def _make(**kwargs):
        hvd.shutdown()
        hvd.init(**kwargs)
        return hvd
    yield _make
    hvd.shutdown()


@pytest.fixture
def moe_row_tile(monkeypatch):
    """Sets the expert layer's tile of rows (``parallel/moe.py::ROW_TILE``,
    512) for a test, so that a share's window of rows (``moe.share_rows``)
    is fewer than all of them at a test's few tokens. The layer reads it
    while JAX traces; JAX keeps what it traced of a checkpointed block by the
    block's function and shapes, and the tile is neither, so a test that
    traces a function of the library's own under ``jax.checkpoint``
    (``models/gpt.py``'s block) asks for ``fresh``: JAX's caches emptied at
    the change and after the test."""
    import jax
    from horovod_tpu.parallel import moe
    emptied = []

    def _set(tile, fresh=False):
        monkeypatch.setattr(moe, "ROW_TILE", tile)
        if fresh:
            jax.clear_caches()
            emptied.append(True)
    yield _set
    if emptied:
        jax.clear_caches()


@pytest.fixture
def equations_of():
    """``equations_of(jaxpr)`` yields ``(equation, under a checkpoint's
    equation?)`` for every equation of ``jaxpr`` and of the jaxprs inside
    it: what a backward pass makes again is what lies under
    ``jax.checkpoint``'s primitive (printed as ``checkpoint``, named
    ``remat2``)."""
    import jax

    def walk(jaxpr, inside=False):
        for eqn in jaxpr.eqns:
            yield eqn, inside
            within = inside or eqn.primitive.name in ("remat2", "checkpoint")
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, within)

    return walk


@pytest.fixture
def products_like(equations_of):
    """``products_like(jaxpr, lhs, rhs)``: how many ``dot_general``
    equations on operands of the shapes ``lhs`` and ``rhs`` a jaxpr holds,
    ``(outside its checkpoint equations, under them)``: of a differentiated
    step, the forward pass's and those its backward pass makes again (the
    backward pass's own products have other operands, where no two extents
    are alike)."""
    def count(jaxpr, lhs, rhs):
        found = [inside for eqn, inside in equations_of(jaxpr)
                 if eqn.primitive.name == "dot_general"
                 and [v.aval.shape for v in eqn.invars] == [lhs, rhs]]
        return len(found) - sum(found), sum(found)

    return count

"""Runner unit tests (no processes spawned).

Reference: ``test/test_run.py`` (944 LoC, 44 tests) — arg parsing, host
parsing, ``get_host_assignments``.
"""

import pytest

from horovod_tpu.runner import hosts
from horovod_tpu.runner.launch import parse_args


class TestHostParsing:
    def test_parse_hosts(self):
        assert hosts.parse_hosts("a:2,b:4") == [("a", 2), ("b", 4)]
        assert hosts.parse_hosts("a") == [("a", 1)]
        assert hosts.parse_hosts("a:1, b:2 ,") == [("a", 1), ("b", 2)]

    def test_parse_hostfile(self, tmp_path):
        f = tmp_path / "hostfile"
        f.write_text("h1 slots=4\n# comment\nh2 slots=2\nh3\n")
        assert hosts.parse_hostfile(str(f)) == [("h1", 4), ("h2", 2),
                                                ("h3", 1)]


class TestAssignments:
    def test_single_host(self):
        slots = hosts.get_host_assignments([("localhost", 4)], 4)
        assert [s.rank for s in slots] == [0, 1, 2, 3]
        assert [s.local_rank for s in slots] == [0, 1, 2, 3]
        assert all(s.local_size == 4 and s.cross_size == 1 and
                   s.cross_rank == 0 for s in slots)

    def test_two_hosts(self):
        """Reference: hosts.py:100 — rank-major across hosts in order."""
        slots = hosts.get_host_assignments([("a", 2), ("b", 2)], 4)
        assert [(s.hostname, s.rank, s.local_rank) for s in slots] == [
            ("a", 0, 0), ("a", 1, 1), ("b", 2, 0), ("b", 3, 1)]
        assert all(s.cross_size == 2 for s in slots)
        assert [s.cross_rank for s in slots] == [0, 0, 1, 1]

    def test_partial_use(self):
        slots = hosts.get_host_assignments([("a", 4), ("b", 4)], 5)
        assert [s.hostname for s in slots] == ["a"] * 4 + ["b"]
        assert slots[4].local_size == 1

    def test_uneven_cross_ranks(self):
        slots = hosts.get_host_assignments([("a", 2), ("b", 1)], 3)
        # local_rank 0 exists on both hosts; local_rank 1 only on a.
        by = {(s.hostname, s.local_rank): s for s in slots}
        assert by[("a", 0)].cross_size == 2
        assert by[("b", 0)].cross_rank == 1
        assert by[("a", 1)].cross_size == 1

    def test_insufficient_slots(self):
        with pytest.raises(ValueError):
            hosts.get_host_assignments([("a", 2)], 4)


class TestArgParsing:
    def test_basic(self):
        args = parse_args(["-np", "4", "python", "train.py", "--lr", "0.1"])
        assert args.num_proc == 4
        assert args.command == ["python", "train.py", "--lr", "0.1"]

    def test_flags(self):
        args = parse_args(["-np", "2", "-H", "h1:2", "--cycle-time-ms", "5",
                           "--fusion-threshold-mb", "16", "--timeline", "/t",
                           "python", "x.py"])
        assert args.hosts == "h1:2"
        assert args.cycle_time_ms == 5.0
        assert args.fusion_threshold_mb == 16.0

    def test_missing_command(self):
        with pytest.raises(SystemExit):
            parse_args(["-np", "2"])

    def test_allreduce_algo_flag(self):
        """--allreduce-algo validates against the native menu and lands in
        the workers' env as HVDTPU_ALLREDUCE_ALGO (ISSUE 1 satellite)."""
        from horovod_tpu.runner.launch import _apply_tuning_env
        from horovod_tpu.utils import envvars as ev

        args = parse_args(["-np", "2", "--allreduce-algo",
                           "recursive_doubling", "python", "x.py"])
        assert args.allreduce_algo == "recursive_doubling"
        env = _apply_tuning_env({}, args)
        assert env[ev.HVDTPU_ALLREDUCE_ALGO] == "recursive_doubling"
        # Default is auto (size-adaptive).
        args = parse_args(["-np", "2", "python", "x.py"])
        assert _apply_tuning_env({}, args)[ev.HVDTPU_ALLREDUCE_ALGO] == "auto"

    def test_allreduce_algo_flag_rejects_unknown(self):
        with pytest.raises(SystemExit):
            parse_args(["-np", "2", "--allreduce-algo", "hypercube",
                        "python", "x.py"])

    def test_compression_flags(self):
        """--compression/--compression-min-bytes validate against the wire
        menu and land in the workers' env (ISSUE 3 satellite)."""
        from horovod_tpu.runner.launch import _apply_tuning_env
        from horovod_tpu.utils import envvars as ev

        args = parse_args(["-np", "2", "--compression", "int8",
                           "--compression-min-bytes", "4096",
                           "python", "x.py"])
        assert args.compression == "int8"
        env = _apply_tuning_env({}, args)
        assert env[ev.HVDTPU_COMPRESSION] == "int8"
        assert env[ev.HVDTPU_COMPRESSION_MIN_BYTES] == "4096"
        # No flag: the knobs stay out of the env (a user-exported
        # HVDTPU_COMPRESSION wins; the native default is none/1024).
        args = parse_args(["-np", "2", "python", "x.py"])
        env = _apply_tuning_env({}, args)
        assert ev.HVDTPU_COMPRESSION not in env
        assert ev.HVDTPU_COMPRESSION_MIN_BYTES not in env

    def test_metrics_port_flags(self):
        """--metrics-port/--metrics-interval land in the workers' env as
        HVDTPU_METRICS_PORT/_INTERVAL (ISSUE 4 satellite); no flag keeps
        the knobs out (a user-exported env var wins; native default off)."""
        from horovod_tpu.runner.launch import _apply_tuning_env
        from horovod_tpu.utils import envvars as ev

        args = parse_args(["-np", "2", "--metrics-port", "9100",
                           "--metrics-interval", "2.5", "python", "x.py"])
        assert args.metrics_port == 9100
        env = _apply_tuning_env({}, args)
        assert env[ev.HVDTPU_METRICS_PORT] == "9100"
        assert env[ev.HVDTPU_METRICS_INTERVAL] == "2.5"
        args = parse_args(["-np", "2", "python", "x.py"])
        env = _apply_tuning_env({}, args)
        assert ev.HVDTPU_METRICS_PORT not in env
        assert ev.HVDTPU_METRICS_INTERVAL not in env

    def test_metrics_port_rejects_negative(self):
        from horovod_tpu.runner.launch import _apply_tuning_env
        with pytest.raises(SystemExit):
            args = parse_args(["-np", "2", "--metrics-port", "-1",
                               "python", "x.py"])
            _apply_tuning_env({}, args)

    def test_compression_flag_rejects_unknown(self):
        with pytest.raises(SystemExit):
            parse_args(["-np", "2", "--compression", "int2",
                        "python", "x.py"])
        with pytest.raises(SystemExit):
            from horovod_tpu.runner.launch import _apply_tuning_env
            args = parse_args(["-np", "2", "--compression-min-bytes", "-5",
                               "python", "x.py"])
            _apply_tuning_env({}, args)

    def test_zerocopy_lane_flags(self):
        """--tcp-zerocopy/--shm-numa/--doorbell-batch land in the workers'
        env as HVDTPU_TCP_ZEROCOPY/_SHM_NUMA/_DOORBELL_BATCH (ISSUE 9); no
        flag keeps the knobs out (user-exported env wins; native defaults
        auto/auto/256 KiB)."""
        from horovod_tpu.runner.launch import _apply_tuning_env
        from horovod_tpu.utils import envvars as ev

        args = parse_args(["-np", "2", "--tcp-zerocopy", "uring",
                           "--shm-numa", "on", "--doorbell-batch", "65536",
                           "python", "x.py"])
        assert args.tcp_zerocopy == "uring"
        env = _apply_tuning_env({}, args)
        assert env[ev.HVDTPU_TCP_ZEROCOPY] == "uring"
        assert env[ev.HVDTPU_SHM_NUMA] == "on"
        assert env[ev.HVDTPU_DOORBELL_BATCH] == "65536"
        args = parse_args(["-np", "2", "python", "x.py"])
        env = _apply_tuning_env({}, args)
        assert ev.HVDTPU_TCP_ZEROCOPY not in env
        assert ev.HVDTPU_SHM_NUMA not in env
        assert ev.HVDTPU_DOORBELL_BATCH not in env

    def test_zerocopy_lane_flags_reject_bad_values(self):
        from horovod_tpu.runner.launch import _apply_tuning_env
        with pytest.raises(SystemExit):
            parse_args(["-np", "2", "--tcp-zerocopy", "always",
                        "python", "x.py"])
        with pytest.raises(SystemExit):
            parse_args(["-np", "2", "--shm-numa", "2", "python", "x.py"])
        with pytest.raises(SystemExit):
            args = parse_args(["-np", "2", "--doorbell-batch", "-1",
                               "python", "x.py"])
            _apply_tuning_env({}, args)


class TestPythonPlaceholder:
    """Per-slot interpreter substitution (a mixed local+remote job cannot
    use one literal: the launcher's venv python is absent on remote hosts)."""

    def test_local_resolves_to_launcher_interpreter(self):
        import sys
        from horovod_tpu.runner.safe_exec import (PYTHON_PLACEHOLDER,
                                                  resolve_python)
        cmd = resolve_python([PYTHON_PLACEHOLDER, "-m", "mod"], local=True)
        assert cmd == [sys.executable, "-m", "mod"]

    def test_remote_resolves_to_remote_python(self):
        from horovod_tpu.runner.safe_exec import (PYTHON_PLACEHOLDER,
                                                  resolve_python)
        cmd = resolve_python([PYTHON_PLACEHOLDER, "x.py"], local=False,
                             remote_python="/opt/py/bin/python3")
        assert cmd == ["/opt/py/bin/python3", "x.py"]

    def test_plain_commands_pass_through(self):
        from horovod_tpu.runner.safe_exec import resolve_python
        assert resolve_python(["python", "t.py"], local=False) == \
            ["python", "t.py"]

    def test_elastic_settings_carry_remote_python(self):
        """--remote-python must reach the elastic driver's spawn path too
        (round-3 advisor, low: the elastic {python} placeholder always
        resolved to the default python3 on remote hosts)."""
        from horovod_tpu.runner.elastic import ElasticSettings
        args = parse_args(["-np", "2", "--min-np", "1",
                           "--host-discovery-script", "./d.sh",
                           "--remote-python", "/opt/py/bin/python3",
                           "python", "train.py"])
        settings = ElasticSettings(
            min_np=args.min_np or args.num_proc,
            max_np=args.max_np or args.num_proc,
            remote_python=args.remote_python)
        assert settings.remote_python == "/opt/py/bin/python3"


class TestDuplicateHosts:
    def test_repeated_hostname_merged(self):
        slots = hosts.get_host_assignments([("h", 1), ("h", 1)], 2)
        assert [(s.rank, s.local_rank) for s in slots] == [(0, 0), (1, 1)]
        assert all(s.cross_size == 1 and s.cross_rank == 0 for s in slots)


class TestPreflight:
    """Connectivity preflight (reference: driver_service.py:193 NIC probing;
    round-2 verdict #6: wrong-NIC process-mode launches were silent hangs)."""

    @staticmethod
    def _local_spawn(extra_env=None):
        import subprocess
        import sys
        from conftest import subprocess_env
        from horovod_tpu.runner import safe_exec

        def spawn(host, env):
            full = subprocess_env()
            full.update(env)
            full.update(extra_env or {})
            return safe_exec.WorkerProcess(
                [sys.executable, "-m", "horovod_tpu.runner.preflight"],
                full, f"preflight@{host}")
        return spawn

    def test_all_reachable(self):
        from conftest import free_port
        from horovod_tpu.runner.preflight import check_connectivity
        port = free_port()
        # hostA is the controller host (listen role); hostB connects. Both
        # probes actually run on localhost, exercising the real protocol.
        check_connectivity(["127.0.0.1", "localhost"], "127.0.0.1", port,
                           self._local_spawn(), timeout=30.0)

    def test_advertise_address_separates_listen_and_dial(self):
        """--controller-advertise-address: the listener binds on the rank-0
        SLOT host while connectors dial the advertised ADDRESS (regression:
        the listen role was keyed on the dial address, so no probe ever
        bound the port and healthy clusters failed preflight)."""
        from conftest import free_port
        from horovod_tpu.runner.preflight import check_connectivity
        port = free_port()
        check_connectivity(["hostA", "hostB"], "127.0.0.1", port,
                           self._local_spawn(), timeout=30.0,
                           listen_host="hostA")

    def test_unreachable_controller_named(self):
        import pytest
        from conftest import free_port
        from horovod_tpu.runner.preflight import check_connectivity
        port = free_port()
        # The "controller host" probe never runs (not in the host list), so
        # connectors time out waiting for the listener — the failure must
        # name the host and suggest the advertise-address knob.
        with pytest.raises(RuntimeError) as ei:
            check_connectivity(["localhost"], "203.0.113.1", port,
                               self._local_spawn(), timeout=8.0)
        msg = str(ei.value)
        assert "localhost" in msg
        assert "advertise-address" in msg

    def test_kv_unreachable_named(self):
        import pytest
        from conftest import free_port
        from horovod_tpu.runner.preflight import check_connectivity
        port = free_port()
        # Probe pointed at a KV address it cannot reach: "no response" path.
        with pytest.raises(RuntimeError, match="no response"):
            check_connectivity(
                ["localhost"], "localhost", port,
                self._local_spawn({"HVDTPU_PREFLIGHT_KV_ADDR":
                                   "203.0.113.1"}),
                timeout=8.0)

    def test_advertise_addr_env_override(self, monkeypatch):
        from horovod_tpu.runner.preflight import local_addr
        monkeypatch.setenv("HVDTPU_ADVERTISE_ADDR", "10.1.2.3")
        assert local_addr() == "10.1.2.3"

    def test_launch_flags_parse(self):
        from horovod_tpu.runner.launch import parse_args
        args = parse_args(["-np", "2", "--controller-advertise-address",
                           "10.0.0.5", "--no-preflight", "python", "t.py"])
        assert args.controller_advertise_address == "10.0.0.5"
        assert args.no_preflight

    def test_metrics_port_preflight_busy_port(self):
        """hvdrun probes every local worker's metrics port (base+rank)
        before spawning; a busy port fails fast naming rank and port
        (ISSUE 4 satellite)."""
        import socket

        import pytest
        from horovod_tpu.runner.preflight import check_metrics_ports
        from test_metrics import _free_port_block

        base = _free_port_block(3)
        blocker = socket.socket()
        blocker.bind(("", base))  # rank 0's endpoint
        try:
            with pytest.raises(RuntimeError) as e:
                check_metrics_ports(["localhost", "127.0.0.1"], base,
                                    aggregator_port=base + 2)
            assert f"port {base}" in str(e.value)
            assert "rank 0" in str(e.value)
        finally:
            blocker.close()
        # All free: passes silently.
        check_metrics_ports(["localhost", "127.0.0.1"], base,
                            aggregator_port=base + 2)


def test_a_test_past_its_limit_fails_alone(tmp_path):
    """``conftest._limit``: a test that never ends fails by the limit's name
    with every thread's stack on stderr, the process it left is killed, and
    the file's next test runs."""
    import os
    import subprocess
    import sys

    import conftest

    case = tmp_path / "test_sleeper.py"
    case.write_text(
        "import subprocess, sys, time\n"
        "import conftest\n"
        "conftest.TEST_LIMIT_S = 1\n"
        "def test_sleeps():\n"
        "    global child\n"
        "    child = subprocess.Popen([sys.executable, '-c',\n"
        "                              'import time; time.sleep(60)'])\n"
        "    time.sleep(60)  # the sleeping line\n"
        "def test_after():\n"
        "    assert child.wait(timeout=30) == -9\n")
    env = conftest.subprocess_env()
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(conftest.__file__), env["PYTHONPATH"]])
    run = subprocess.run(
        [sys.executable, "-m", "pytest", str(case), "-q", "-p", "conftest",
         "-p", "no:cacheprovider", "-p", "no:randomly", "--rootdir",
         str(tmp_path)],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=120)
    text = run.stdout + run.stderr
    assert run.returncode == 1, text
    assert "1 failed, 1 passed" in text, text
    assert "ran past its limit of 1 s" in text
    # pytest's own traceback and faulthandler's dump both name the line.
    assert "time.sleep(60)  # the sleeping line" in text
    assert 'test_sleeper.py", line 8 in test_sleeps' in text


def test_a_world_that_lost_a_rank_is_not_waited_on_to_the_deadline():
    """``conftest.wait_world``: results in the ranks' order, pipes read past
    their buffers, a rank's failure leaves the others ``grace`` seconds, and
    what is killed says so."""
    import subprocess
    import sys
    import time

    import conftest

    def world(*bodies):
        return [subprocess.Popen([sys.executable, "-c", body],
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE, text=True)
                for body in bodies]

    (rc0, out0, _), (rc1, _, err1) = conftest.wait_world(world(
        "print('a' * 200000)",
        "import sys; sys.stderr.write('b' * 300000)"), timeout=60)
    assert (rc0, len(out0), rc1, len(err1)) == (0, 200001, 0, 300000)
    began = time.monotonic()
    failed, stalled = conftest.wait_world(world(
        "import sys; sys.exit(3)", "import time; time.sleep(120)"),
        timeout=60, grace=1)
    assert time.monotonic() - began < 30
    assert failed[0] == 3 and stalled[0] == -9
    assert stalled[2].startswith("[killed after timeout]")

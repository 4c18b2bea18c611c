"""Always-on flight recorder + post-mortem forensics tests (ISSUE 12).

The native core keeps an unsampled in-memory ring of compact binary phase
records (``native/flightrec.{h,cpp}``), dumped to ``flightrec.<rank>.bin``
on the abort cascade / stall escalation / fatal signals and served live on
``/debugz``. ``horovod_tpu/flightrec.py`` decodes dumps;
``horovod_tpu/postmortem.py`` + ``scripts/postmortem.py`` merge surviving
ranks' dumps (PR-8 clock alignment) and produce the verdict.

Tier-1 acceptance (ISSUE 12): a ``HVDTPU_CHAOS`` rank-kill job yields a
merged post-mortem report that names the dead rank and its last in-flight
op.
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import pytest

from conftest import subprocess_env as _subprocess_env
from conftest import wait_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _single_rank_core(extra_env=None):
    """A started size-1 NativeCore (collectives run locally, the recorder
    still records op/fusion events)."""
    for key, val in (extra_env or {}).items():
        os.environ[key] = val
    from horovod_tpu.basics import NativeCore
    core = NativeCore(0, 1, coord_port=_free_port())
    core.start()
    return core


class TestSnapshotDecode:
    def test_inprocess_roundtrip(self, monkeypatch):
        """Ops recorded on a live core decode back with names, types and
        the header's identity/clock fields."""
        import numpy as np

        from horovod_tpu.flightrec import parse_dump
        core = _single_rank_core()
        try:
            for i in range(3):
                core.collective("allreduce", f"t{i}",
                                np.ones(256, np.float32))
            snap = core.flightrec_snapshot()
        finally:
            core.shutdown()
        assert snap[:8] == b"HVDFREC1"
        dump = parse_dump(snap)
        assert dump.rank == 0 and dump.world_size == 1
        assert dump.reason == "on_demand"
        assert dump.write_count == len(dump.events) > 0
        kinds = [ev.type for ev in dump.events]
        assert "op_begin" in kinds and "op_end" in kinds
        begun = [ev for ev in dump.events if ev.type == "op_begin"]
        assert [ev.name for ev in begun] == ["t0", "t1", "t2"]
        assert all(ev.bytes == 1024 for ev in begun)
        # All ops completed cleanly: nothing in flight, nothing failed.
        assert dump.last_inflight_op() is None
        assert dump.last_failed_op() is None

    def test_disabled_recorder_snapshots_empty(self, monkeypatch):
        monkeypatch.setenv("HVDTPU_FLIGHTREC", "0")
        core = _single_rank_core()
        try:
            assert core.flightrec_snapshot() == b""
            assert core.flightrec_dump() is False
        finally:
            core.shutdown()

    def test_parse_rejects_garbage(self):
        from horovod_tpu.flightrec import parse_dump
        with pytest.raises(ValueError, match="magic"):
            parse_dump(b"NOTADUMP" + b"\x00" * 100)
        with pytest.raises(ValueError, match="magic"):
            parse_dump(b"")

    def test_ondemand_dump_to_explicit_path(self, tmp_path):
        import numpy as np

        from horovod_tpu.flightrec import parse_dump
        core = _single_rank_core()
        try:
            core.collective("allreduce", "x", np.ones(8, np.float32))
            target = str(tmp_path / "manual.bin")
            assert core.flightrec_dump(target) is True
            dump = parse_dump(open(target, "rb").read())
            assert dump.reason == "on_demand"
            assert any(ev.name == "x" for ev in dump.events)
        finally:
            core.shutdown()

    def test_event_enum_mirrors_are_dense(self):
        """The decoder's mirrors cover exactly the native value ranges
        (the linter pins values; this pins the reverse maps)."""
        from horovod_tpu.flightrec import (DUMP_REASONS, EVENT_NAMES,
                                           FLIGHT_EVENTS, REASON_NAMES)
        assert sorted(FLIGHT_EVENTS.values()) == list(range(17))
        assert sorted(DUMP_REASONS.values()) == list(range(5))
        assert EVENT_NAMES[FLIGHT_EVENTS["sendrecv"]] == "sendrecv"
        assert REASON_NAMES[DUMP_REASONS["abort"]] == "abort"


class TestDebugz:
    def test_debugz_dict_shapes(self):
        from horovod_tpu.flightrec import debugz_dict
        assert debugz_dict(b"") == {"flightrec": "disabled"}

    def test_hvd_debugz_inprocess(self, monkeypatch):
        import numpy as np
        core = _single_rank_core()
        try:
            core.collective("allreduce", "dz", np.ones(64, np.float32))
            from horovod_tpu.flightrec import debugz_dict
            dz = debugz_dict(core.flightrec_snapshot())
            assert dz["flightrec"] == "on"
            assert dz["rank"] == 0 and dz["records_written"] > 0
            assert dz["inflight_op"] is None  # op completed
            assert any(ev["name"] == "dz" for ev in dz["last_events"])
        finally:
            core.shutdown()

    def test_debugz_endpoint(self):
        """/debugz rides the metrics server next to /metrics, secret-gated
        the same way; servers without a debugz source 404."""
        import urllib.error

        from horovod_tpu.observability import MetricsServer, scrape
        server = MetricsServer(dump_fn=lambda: "", port=0,
                               debugz_fn=lambda: json.dumps(
                                   {"flightrec": "on", "rank": 7}))
        server.start()
        try:
            body = json.loads(scrape("127.0.0.1", server.port, "/debugz"))
            assert body == {"flightrec": "on", "rank": 7}
        finally:
            server.stop()
        bare = MetricsServer(dump_fn=lambda: "", port=0)
        bare.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                scrape("127.0.0.1", bare.port, "/debugz")
            assert e.value.code == 404
        finally:
            bare.stop()

    def test_debugz_endpoint_requires_secret(self):
        import urllib.error

        from horovod_tpu.observability import MetricsServer, scrape
        server = MetricsServer(dump_fn=lambda: "", port=0, secret="s3cret",
                               debugz_fn=lambda: "{}")
        server.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                scrape("127.0.0.1", server.port, "/debugz")
            assert e.value.code == 403
            assert json.loads(scrape("127.0.0.1", server.port, "/debugz",
                                     secret="s3cret")) == {}
        finally:
            server.stop()


def _make_dump(rank, world, reason="abort", detail=-1, events=(),
               clock=(0, 10)):
    from horovod_tpu.flightrec import FlightDump
    return FlightDump(rank=rank, world_size=world,
                      clock_offset_us=clock[0], clock_err_us=clock[1],
                      steady_now_us=1_000_000, wall_now_us=2_000_000,
                      write_count=len(events), capacity=4096,
                      reason=reason, detail=detail, names=[],
                      events=list(events))


def _ev(type_, t, name="", name_id=-1, arg=0, send=-1, recv=-1, dur=0,
        bytes_=0, lane="tcp"):
    from horovod_tpu.flightrec import FlightEventRecord
    return FlightEventRecord(t_end_us=t, dur_us=dur, type_=type_,
                             lane=lane, bytes_=bytes_, name_id=name_id,
                             arg=arg, send_peer=send, recv_peer=recv,
                             name=name)


class TestVerdictUnits:
    def test_sigkilled_rank_convicted_by_absence_and_votes(self):
        from horovod_tpu.postmortem import build_verdict, format_verdict
        survivors = {}
        for r in (0, 2, 3):
            survivors[r] = _make_dump(r, 4, reason="abort", detail=1, events=[
                _ev("op_begin", 100, name="grad/3", name_id=1, arg=0,
                    bytes_=4096),
                _ev("sendrecv", 200, send=1, recv=1, dur=50, bytes_=2048),
                _ev("fail_detect", 300, send=1),
                _ev("abort", 301, send=1),
                _ev("op_end", 310, name="grad/3", name_id=1, arg=1),
            ])
        v = build_verdict(survivors)
        assert [d["rank"] for d in v["dead"]] == [1]
        assert v["suspect"] == 1
        assert v["fatal_op"]["name"] == "grad/3"
        assert v["fatal_op"]["kind"] == "ALLREDUCE"
        assert v["fatal_op"]["rank"] == 1
        text = format_verdict(v)
        assert "DEAD rank 1" in text
        assert "grad/3" in text

    def test_signal_dump_convicts_itself_but_sigterm_does_not(self):
        from horovod_tpu.postmortem import build_verdict
        v = build_verdict({
            0: _make_dump(0, 2, reason="signal", detail=11, events=[
                _ev("op_begin", 10, name="w", name_id=1)]),
            1: _make_dump(1, 2, reason="signal", detail=15, events=[]),
        })
        assert [d["rank"] for d in v["dead"]] == [0]
        assert "SIGSEGV" in v["dead"][0]["how"]
        assert v["terminated"] == [1]
        # The segfaulting rank's own dump names its in-flight op.
        assert v["fatal_op"]["name"] == "w"
        assert v["fatal_op"]["source"] == "the dead rank's own dump"

    def test_stall_dump_convicts_the_silent_rank(self):
        """A stall escalation freezes the coordinator's ring with the
        tensor AND the first rank that never announced it; the verdict
        names that rank as hung even though no lane ever failed."""
        from horovod_tpu.postmortem import build_verdict, format_verdict
        v = build_verdict({
            0: _make_dump(0, 2, reason="stall", events=[
                _ev("stall", 100, name="slow/t", name_id=1, arg=1,
                    send=1)]),
            # The wedged rank was later SIGTERMed by the watchdog: its dump
            # marks it terminated, not the cause.
            1: _make_dump(1, 2, reason="signal", detail=15, events=[]),
        })
        assert v["stalled_coordinator"] == [0]
        assert [d["rank"] for d in v["dead"]] == [1]
        assert "never announced" in v["dead"][0]["how"]
        assert "slow/t" in v["dead"][0]["how"]
        assert v["terminated"] == [1]
        text = format_verdict(v)
        assert "stall escalation" in text and "DEAD rank 1" in text

    def test_remote_ranks_uncollected_not_convicted(self):
        """Multi-host: a rank whose dump lives on a remote host is
        'uncollected', never convicted as dead by absence — only ranks the
        launcher expected to dump LOCALLY convict that way."""
        from horovod_tpu.postmortem import build_verdict, format_verdict
        survivor = _make_dump(0, 4, reason="abort", detail=2, events=[
            _ev("op_begin", 100, name="t", name_id=1, bytes_=64),
            _ev("fail_detect", 200, send=2),
            _ev("op_end", 210, name="t", name_id=1, arg=1)])
        # Ranks 0 and 2 ran locally; 1 and 3 on another host.
        v = build_verdict({0: survivor}, local_ranks={0, 2})
        assert [d["rank"] for d in v["dead"]] == [2]
        assert v["uncollected"] == [1, 3]
        text = format_verdict(v)
        assert "uncollected rank(s) [1, 3]" in text
        # Topology unknown: absence still convicts, with the caveat.
        v2 = build_verdict({0: survivor})
        assert [d["rank"] for d in v2["dead"]] == [1, 2, 3]
        assert "caveat: host topology unknown" in format_verdict(v2)

    def test_merge_window_keeps_only_recent_events(self):
        from horovod_tpu.postmortem import merge_to_chrome
        old = _ev("op_begin", 1_000, name="old", name_id=1)
        old_end = _ev("op_end", 2_000, name="old", name_id=1, dur=1000)
        new = _ev("op_begin", 10_000_000, name="new", name_id=2)
        new_end = _ev("op_end", 10_000_500, name="new", name_id=2, dur=500)
        dump = _make_dump(0, 1, events=[old, old_end, new, new_end])
        merged = merge_to_chrome({0: dump}, window_ms=500)
        names = [e["name"] for e in merged if e.get("pid") == "rank 0" and
                 e.get("tid") == "ops"]
        assert "new" in names and "old" not in names
        # window 0 = keep everything.
        all_names = [e["name"] for e in
                     merge_to_chrome({0: dump}, window_ms=0)
                     if e.get("tid") == "ops"]
        assert "old" in all_names

    def test_clock_offsets_align_merge(self):
        """Rank 1's clock runs 1 s ahead; after alignment its op lands at
        the same merged timestamp as rank 0's (PR-8 machinery reused)."""
        from horovod_tpu.postmortem import merge_to_chrome
        d0 = _make_dump(0, 2, clock=(0, 0), events=[
            _ev("op_begin", 5_000_000, name="t", name_id=1),
            _ev("op_end", 5_000_100, name="t", name_id=1, dur=100)])
        d1 = _make_dump(1, 2, clock=(-1_000_000, 5), events=[
            _ev("op_begin", 6_000_000, name="t", name_id=1),
            _ev("op_end", 6_000_100, name="t", name_id=1, dur=100)])
        merged = merge_to_chrome({0: d0, 1: d1}, window_ms=0)
        ts = {e["pid"]: e["ts"] for e in merged
              if e.get("tid") == "ops" and e["name"] == "t"}
        assert ts["rank 0"] == ts["rank 1"]


class TestPostmortemKill:
    """Tier-1 acceptance: a HVDTPU_CHAOS rank-kill job yields a merged
    post-mortem report naming the dead rank and its last in-flight op."""

    def _run_kill_world(self, tmp_path, extra_env=None):
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""\
            import os, sys
            os.environ.setdefault('JAX_PLATFORMS', 'cpu')
            import numpy as np
            from horovod_tpu.basics import NativeCore
            from horovod_tpu.exceptions import HvdTpuInternalError
            rank = int(os.environ['HVDTPU_RANK'])
            core = NativeCore(rank, int(os.environ['HVDTPU_SIZE']))
            core.start()
            try:
                for i in range(6):
                    core.collective('allreduce', f'grad/{i}',
                                    np.ones(4096, np.float32))
            except HvdTpuInternalError:
                print('SURVIVOR FAILED OVER')
            core.shutdown()
        """))
        port = _free_port()
        procs = []
        for r in range(2):
            env = _subprocess_env()
            env.update({
                "HVDTPU_RANK": str(r), "HVDTPU_SIZE": "2",
                "HVDTPU_LOCAL_RANK": str(r), "HVDTPU_LOCAL_SIZE": "2",
                "HVDTPU_CONTROLLER_PORT": str(port),
                "HVDTPU_FLIGHTREC_DIR": str(tmp_path),
                "HVDTPU_FAILURE_DETECT_MS": "200",
            })
            if r == 1:
                env["HVDTPU_CHAOS"] = "rank1:kill@op=3"
            env.update(extra_env or {})
            procs.append(subprocess.Popen(
                [sys.executable, str(script)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        return wait_world(procs, timeout=120)

    def test_kill_yields_postmortem_verdict(self, tmp_path):
        results = self._run_kill_world(tmp_path)
        assert results[1][0] == -9, results[1]           # chaos SIGKILL
        assert "SURVIVOR FAILED OVER" in results[0][1], results[0]
        # The survivor's abort cascade froze its ring to disk.
        assert (tmp_path / "flightrec.0.bin").exists()
        assert not (tmp_path / "flightrec.1.bin").exists()

        from horovod_tpu.postmortem import (build_verdict, format_verdict,
                                            run_postmortem)
        verdict, merged_path = run_postmortem(str(tmp_path))
        # The verdict names the dead rank...
        assert [d["rank"] for d in verdict["dead"]] == [1]
        # ...and the last in-flight op (kill@op=3 = the 3rd allreduce).
        assert verdict["fatal_op"]["name"] == "grad/2"
        assert verdict["fatal_op"]["kind"] == "ALLREDUCE"
        # The survivor's own state: blocked inside the same op, last hop
        # against the dead peer, failure pinned on it.
        r0 = verdict["per_rank"][0]
        assert r0["inflight_op"] == "grad/2"
        assert 1 in r0["suspects"]
        hop_peer = (r0["last_hop"]["recv_peer"]
                    if r0["last_hop"]["recv_peer"] >= 0
                    else r0["last_hop"]["send_peer"])
        assert hop_peer == 1
        # Human-readable verdict names rank + op.
        text = format_verdict(verdict)
        assert "DEAD rank 1" in text and "grad/2" in text
        # The merged last-500ms Perfetto view exists and is non-empty.
        merged = json.load(open(merged_path))
        assert isinstance(merged, list) and merged
        assert any(e.get("tid") == "hops" for e in merged)

    def test_postmortem_cli_exit0_nonempty(self, tmp_path):
        self._run_kill_world(tmp_path)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "postmortem.py"),
             str(tmp_path)],
            env=_subprocess_env(), capture_output=True, text=True,
            timeout=60)
        assert r.returncode == 0, r.stderr
        assert "DEAD rank 1" in r.stdout
        assert "fatal op" in r.stdout
        assert (tmp_path / "merged_postmortem.json").exists()

    def test_postmortem_cli_no_dumps(self, tmp_path):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "postmortem.py"),
             str(tmp_path)],
            env=_subprocess_env(), capture_output=True, text=True,
            timeout=60)
        assert r.returncode == 1
        assert "no flightrec" in r.stderr


class TestHvdrunFlags:
    def test_postmortem_flag_runs_verdict_on_failure(self, tmp_path):
        """hvdrun --postmortem: the driver collects the surviving ranks'
        dumps and prints the verdict when the job fails (ISSUE 12)."""
        script = tmp_path / "worker.py"
        script.write_text(
            "import os, sys\n"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
            "import numpy as np\n"
            "import horovod_tpu as hvd\n"
            "from horovod_tpu.exceptions import HvdTpuInternalError\n"
            "hvd.init()\n"
            "try:\n"
            "    for i in range(6):\n"
            "        hvd.allreduce(np.ones(4096, np.float32), name=f't{i}')\n"
            "except HvdTpuInternalError:\n"
            "    sys.exit(0)\n"
            "hvd.shutdown()\n")
        pm_dir = tmp_path / "pm"
        rc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
             "--chaos", "rank1:kill@op=2", "--postmortem", str(pm_dir),
             sys.executable, str(script)],
            env=_subprocess_env(), capture_output=True, text=True,
            timeout=150)
        assert rc.returncode != 0          # a rank was SIGKILLed
        assert "post-mortem verdict" in rc.stderr
        assert "DEAD rank 1" in rc.stderr
        assert (pm_dir / "merged_postmortem.json").exists()

    def test_debugz_requires_metrics_port(self, tmp_path):
        from horovod_tpu.runner import launch as launch_mod
        args = launch_mod.parse_args(
            ["-np", "2", "--debugz", "python", "x.py"])
        with pytest.raises(SystemExit, match="metrics-port"):
            launch_mod.run_launcher(args)

    def test_flightrec_env_validation(self, monkeypatch):
        monkeypatch.setenv("HVDTPU_FLIGHTREC_EVENTS", "-5")
        from horovod_tpu.basics import NativeCore
        with pytest.raises(ValueError, match="HVDTPU_FLIGHTREC_EVENTS"):
            NativeCore(0, 1, coord_port=_free_port())

"""Always-on perf attribution tests (ISSUE 13; docs/observability.md).

Covers the snapshot decoder + report helpers, the /perfz endpoint, the
hvdtop frame renderer, the perf_diff cross-run sentry, the in-process
single-rank baseline stream, and the tier-1 acceptance run: a 4-rank
world with a chaos-delayed rank must produce (1) an ANOMALY
flight-recorder event, (2) a live /perfz scrape naming the delayed rank
the straggler mid-job, and (3) a perf_diff non-zero exit against the
clean profile.
"""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

from conftest import (assert_all_ok, free_port, launch_world, subprocess_env,
                      wait_world)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")


def _free_port_block(n: int) -> int:
    for _ in range(50):
        s = socket.socket()
        s.bind(("", 0))
        base = s.getsockname()[1]
        s.close()
        if base + n >= 65535:
            continue
        ok = True
        for off in range(n + 1):
            probe = socket.socket()
            try:
                probe.bind(("", base + off))
            except OSError:
                ok = False
                break
            finally:
                probe.close()
        if ok:
            return base
    raise RuntimeError("no free port block found")


def _snap(keys):
    return {"version": 1, "enabled": True, "slowdown_pct": 50.0,
            "min_samples": 20, "anomalies_total": 0, "keys": keys}


def _key(key, count, wall, wait=0.0, wire=0.0, reduce=0.0, codec=0.0,
         anomalies=0):
    phases = {"wall": wall, "wait": wait, "wire": wire, "reduce": reduce,
              "codec": codec}
    return {"key": key, "count": count, "ewma_us": phases,
            "p50_us": phases, "p99_us": phases, "anomalies": anomalies,
            "last_wall_us": wall, "samples_us": [wall] * min(count, 8)}


class TestSnapshotDecode:
    def test_parse_validates_shape(self):
        from horovod_tpu.perfstats import parse_snapshot
        snap = parse_snapshot(json.dumps(_snap([_key("a|ring|shm|0|none",
                                                     5, 100.0)])))
        assert snap["keys"][0]["count"] == 5
        with pytest.raises(ValueError):
            parse_snapshot("not json {")
        with pytest.raises(ValueError):
            parse_snapshot(json.dumps({"version": 2, "keys": []}))
        with pytest.raises(ValueError):
            parse_snapshot(json.dumps(
                {"version": 1, "keys": [{"key": "x"}]}))

    def test_phase_mirror_is_dense(self):
        # The dict must mirror hvdtpu::PerfPhase densely from 0 (the
        # linter pins the values; this pins the shape assumptions the
        # decoder makes).
        from horovod_tpu.perfstats import ATTRIBUTION, PERF_PHASES
        assert sorted(PERF_PHASES.values()) == list(range(len(PERF_PHASES)))
        assert set(ATTRIBUTION) == set(PERF_PHASES)

    def test_rank_summary_weights_by_count(self):
        from horovod_tpu.perfstats import rank_summary
        snap = _snap([
            _key("a|ring|shm|0|none", 90, wall=100.0, wire=80.0),
            _key("b|ring|shm|0|none", 10, wall=1000.0, reduce=900.0),
        ])
        s = rank_summary(snap)
        assert s["ops"] == 100
        assert abs(s["phase_us"]["wall"] - 190.0) < 1e-6
        assert s["busy_us"] == pytest.approx(190.0)
        # wire 72 vs reduce 90: reduce dominates.
        assert s["dominant"] == "reduce"
        assert "reduce-bound" in s["attribution"]

    def test_rank_summary_empty(self):
        from horovod_tpu.perfstats import rank_summary
        s = rank_summary(_snap([]))
        assert s["ops"] == 0 and s["busy_us"] == 0.0

    def test_find_straggler_picks_max_busy_not_max_wall(self):
        from horovod_tpu.perfstats import find_straggler
        # Rank 0 waits (victim: wall high, busy low); rank 2 burns its own
        # time in the wire phase.
        per_rank = {
            0: _snap([_key("a", 50, wall=1000.0, wait=900.0)]),
            1: _snap([_key("a", 50, wall=300.0, wire=100.0)]),
            2: _snap([_key("a", 50, wall=950.0, wait=50.0, wire=800.0)]),
        }
        s = find_straggler(per_rank)
        assert s["rank"] == 2
        assert s["attribution"] == "wire-slow"

    def test_find_straggler_never_blames_waiting(self):
        from horovod_tpu.perfstats import find_straggler
        # Every rank mostly waits (idle world): the pick must not carry a
        # "waiting on peers" attribution — busy time is what's compared.
        per_rank = {0: _snap([_key("a", 5, wall=100.0, wait=90.0)]),
                    1: _snap([_key("a", 5, wall=90.0, wait=85.0)])}
        s = find_straggler(per_rank)
        assert "peer-wait" not in s["attribution"]

    def test_format_report_renders_top_keys(self):
        from horovod_tpu.perfstats import format_report
        text = format_report(_snap(
            [_key(f"k{i}|ring|shm|0|none", 10, 100.0 * (i + 1))
             for i in range(12)]), top=3)
        assert "k11|ring|shm|0|none" in text  # highest count*wall first
        assert "9 more key(s)" in text
        assert "dominant=" in text


class TestInProcess:
    def test_single_rank_baselines_and_snapshot(self):
        import numpy as np

        from horovod_tpu.perfstats import parse_snapshot
        from tests.test_flightrec import _single_rank_core
        core = _single_rank_core()
        try:
            for _ in range(8):
                core.collective("allreduce", "pf", np.ones(64, np.float32))
            snap = parse_snapshot(core.perfstats_snapshot())
            entry = [e for e in snap["keys"]
                     if e["key"].startswith("pf|")]
            assert entry and entry[0]["count"] == 8
            assert entry[0]["ewma_us"]["wall"] >= 0
            assert len(entry[0]["samples_us"]) == 8
        finally:
            core.shutdown()

    def test_perfstats_disabled_by_env(self, monkeypatch):
        import numpy as np

        monkeypatch.setenv("HVDTPU_PERFSTATS", "0")
        from tests.test_flightrec import _single_rank_core
        core = _single_rank_core()
        try:
            core.collective("allreduce", "off", np.ones(8, np.float32))
            snap = json.loads(core.perfstats_snapshot())
            assert snap["enabled"] is False and snap["keys"] == []
        finally:
            core.shutdown()

    def test_bad_knobs_fail_loudly(self, monkeypatch):
        from horovod_tpu.basics import NativeCore
        monkeypatch.setenv("HVDTPU_PERF_SLOWDOWN_PCT", "-5")
        with pytest.raises(ValueError, match="HVDTPU_PERF_SLOWDOWN_PCT"):
            NativeCore(0, 1, coord_port=free_port())
        monkeypatch.delenv("HVDTPU_PERF_SLOWDOWN_PCT")
        monkeypatch.setenv("HVDTPU_PERF_MIN_SAMPLES", "0")
        with pytest.raises(ValueError, match="HVDTPU_PERF_MIN_SAMPLES"):
            NativeCore(0, 1, coord_port=free_port())

    def test_perfz_endpoint(self):
        from horovod_tpu.observability import MetricsServer, scrape
        payload = json.dumps(_snap([]))
        server = MetricsServer(dump_fn=lambda: "", port=0,
                               perfz_fn=lambda: payload)
        server.start()
        try:
            body = json.loads(scrape("127.0.0.1", server.port, "/perfz"))
            assert body["version"] == 1
        finally:
            server.stop()
        # No source -> 404, like /debugz.
        import urllib.error
        server = MetricsServer(dump_fn=lambda: "", port=0)
        server.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                scrape("127.0.0.1", server.port, "/perfz")
            assert e.value.code == 404
        finally:
            server.stop()

    def test_perfz_endpoint_requires_secret(self):
        import urllib.error

        from horovod_tpu.observability import MetricsServer, scrape
        server = MetricsServer(dump_fn=lambda: "", port=0, secret="s3cret",
                               perfz_fn=lambda: "{}")
        server.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                scrape("127.0.0.1", server.port, "/perfz")
            assert e.value.code == 403
            assert scrape("127.0.0.1", server.port, "/perfz",
                          secret="s3cret") == "{}"
        finally:
            server.stop()


class TestHvdtopFrame:
    def _metrics(self, ops=100, anomalies=0, clock_err=50, stalled=0):
        from horovod_tpu.observability import parse_prometheus_text
        return parse_prometheus_text(
            "# TYPE hvdtpu_ops_total counter\n"
            f'hvdtpu_ops_total{{op="ALLREDUCE"}} {ops}\n'
            "# TYPE hvdtpu_perf_anomalies_total counter\n"
            f'hvdtpu_perf_anomalies_total{{phase="wire"}} {anomalies}\n'
            "# TYPE hvdtpu_clock_err_us gauge\n"
            f"hvdtpu_clock_err_us {clock_err}\n"
            "# TYPE hvdtpu_stalled gauge\n"
            f"hvdtpu_stalled {stalled}\n")

    def test_render_frame_names_every_rank(self):
        from horovod_tpu.runner.hvdtop import render_frame
        endpoints = {0: ("hostA", 9090), 1: ("hostB", 9091)}
        metrics = {0: self._metrics(), 1: self._metrics(anomalies=3)}
        perf = {0: _snap([_key("a", 10, 100.0, wire=60.0)]),
                1: _snap([_key("a", 10, 400.0, wire=350.0)])}
        text, prev = render_frame(endpoints, metrics, perf, None, 10.0)
        assert "2/2 ranks up" in text
        assert "hostA" in text and "hostB" in text
        assert "straggler: rank 1" in text and "wire-slow" in text
        assert "ANOM" in text  # rank 1's anomaly flag
        # Second frame: interval ops/s appears.
        metrics2 = {0: self._metrics(ops=150), 1: self._metrics(ops=150)}
        text2, _ = render_frame(endpoints, metrics2, perf, prev, 20.0)
        assert "5.0" in text2  # (150-100)/10s

    def test_render_frame_flags_unreachable_and_clock_drift(self):
        from horovod_tpu.runner.hvdtop import render_frame
        endpoints = {0: ("h", 1), 1: ("h", 2), 2: ("h", 3)}
        metrics = {0: self._metrics(),
                   2: self._metrics(clock_err=50000)}
        text, _ = render_frame(endpoints, metrics, {}, None, 0.0)
        assert "1/3" not in text  # 2 of 3 up
        assert "2/3 ranks up" in text
        assert "UNREACHABLE" in text
        assert "CLKDRIFT" in text
        assert "straggler: n/a" in text

    def test_top_once_prints_best_frame_on_stop(self):
        import io

        from horovod_tpu.runner.hvdtop import TopConsole
        # Nothing listens on these ports: every scrape fails. Stopping a
        # --top-once console must still print the (all-UNREACHABLE) frame
        # rather than nothing.
        out = io.StringIO()
        console = TopConsole({0: ("127.0.0.1", free_port())}, once=True,
                             once_timeout=30.0, interval_s=0.1, out=out)
        console.start()
        time.sleep(0.5)
        console.stop()
        assert "hvdtop — " in out.getvalue()
        assert "UNREACHABLE" in out.getvalue()


class TestPerfDiff:
    def _profile(self, tmp_path, name, scale=1.0, ranks=(0, 1)):
        doc = {"version": 1, "ranks": {}}
        for r in ranks:
            keys = [{"key": "grad/0|ring|shm|0|none", "count": 40,
                     "ewma_us": {"wall": 500.0 * scale},
                     "p50_us": {"wall": 500.0 * scale},
                     "p99_us": {"wall": 800.0 * scale},
                     "anomalies": 0, "last_wall_us": 500 * scale,
                     "samples_us": [int((480 + 7 * i) * scale)
                                    for i in range(32)]}]
            doc["ranks"][str(r)] = {
                "version": 1, "rank": r, "size": len(ranks),
                "perfstats": _snap(keys), "anomalies": []}
            doc["ranks"][str(r)]["perfstats"]["keys"] = keys
        path = tmp_path / name
        path.write_text(json.dumps(doc))
        return str(path)

    def test_self_diff_is_clean(self, tmp_path):
        from scripts.perf_diff import main
        p = self._profile(tmp_path, "a.json")
        assert main([p, p]) == 0

    def test_confirmed_regression_exits_nonzero(self, tmp_path, capsys):
        from scripts.perf_diff import main
        old = self._profile(tmp_path, "old.json")
        new = self._profile(tmp_path, "new.json", scale=3.0)
        assert main([old, new, "--json", str(tmp_path / "r.json")]) == 1
        report = json.loads((tmp_path / "r.json").read_text())
        assert report["confirmed"]
        assert any(row["verdict"] == "REGRESSION"
                   for row in report["keys"])
        assert "CONFIRMED" in capsys.readouterr().out

    def test_speedup_is_not_a_regression(self, tmp_path):
        from scripts.perf_diff import main
        old = self._profile(tmp_path, "old.json")
        new = self._profile(tmp_path, "new.json", scale=0.5)
        assert main([old, new]) == 0

    def test_short_profiles_skip_cleanly(self, tmp_path):
        from scripts.perf_diff import main
        old = self._profile(tmp_path, "old.json")
        new = self._profile(tmp_path, "new.json", scale=3.0)
        # A sample floor above what the profiles hold: nothing comparable,
        # no false verdict either way.
        assert main([old, new, "--min-samples", "64"]) == 0

    def test_unreadable_profile_is_usage_error(self, tmp_path):
        from scripts.perf_diff import main
        bad = tmp_path / "bad.json"
        bad.write_text("{}")
        good = self._profile(tmp_path, "good.json")
        assert main([str(bad), good]) == 2

    def test_merge_profile_dir(self, tmp_path):
        from horovod_tpu.perfstats import merge_profile_dir
        for r in (0, 1):
            (tmp_path / f"perf_profile.{r}.json").write_text(json.dumps(
                {"version": 1, "rank": r, "size": 2,
                 "perfstats": _snap([]), "anomalies": []}))
        (tmp_path / "perf_profile.bad.json").write_text("nope")
        merged, found = merge_profile_dir(str(tmp_path))
        assert found == [0, 1]
        assert sorted(merged["ranks"]) == ["0", "1"]


def test_perf_4rank_chaos_delay_acceptance(tmp_path):
    """ISSUE 13 tier-1 acceptance: a 4-rank world with
    ``HVDTPU_CHAOS rank2:delay=...`` must produce (1) an ANOMALY
    flight-recorder event + non-zero anomaly counters on the delayed rank
    (the worker asserts both), (2) a live mid-job /perfz scrape naming
    rank 2 the straggler, and (3) a perf_diff CONFIRMED regression vs a
    clean profile of the same workload."""
    from horovod_tpu.perfstats import find_straggler, parse_snapshot
    from horovod_tpu.observability import scrape

    clean_dir = tmp_path / "clean"
    slow_dir = tmp_path / "slow"
    report_path = tmp_path / "report"

    # Clean baseline run (shorter: only its profile matters).
    results = launch_world(
        4, os.path.join(DATA, "perf_worker.py"),
        extra_env={"TEST_PERF_ITERS": "60",
                   "HVDTPU_PERF_MIN_SAMPLES": "5",
                   "HVDTPU_PERF_PROFILE_DIR": str(clean_dir)},
        timeout=150)
    assert_all_ok(results)

    # Delayed run: rank 2 sleeps 1.5 s inside an allreduce mid-run. The
    # delay must NOT trip failure detection (docs/fault-tolerance.md) but
    # MUST trip the perf sentry. Scrape /perfz live from the driver side
    # while the job runs.
    base = _free_port_block(4)
    secret = "perf-acceptance-secret"
    env = subprocess_env()
    env.update({
        # ~25 ms/iter pacing: the job runs ~10 s, so the driver-side poll
        # below reliably lands inside the post-delay window where the P²
        # p99 still carries the spike (~100 ops).
        "TEST_PERF_ITERS": "400",
        "TEST_PERF_ITER_SLEEP_MS": "25",
        "TEST_PERF_ASSERT_ANOMALY_RANK": "2",
        "TEST_PERF_REPORT_JSON": str(report_path),
        "HVDTPU_PERF_MIN_SAMPLES": "5",
        "HVDTPU_PERF_PROFILE_DIR": str(slow_dir),
        "HVDTPU_CHAOS": "rank2:delay=1500@op=120",
        "HVDTPU_METRICS_PORT": str(base),
        "HVDTPU_SECRET": secret,
    })
    procs = []
    coord = free_port()
    for r in range(4):
        worker_env = dict(env)
        worker_env.update({
            "HVDTPU_RANK": str(r), "HVDTPU_SIZE": "4",
            "HVDTPU_LOCAL_RANK": str(r), "HVDTPU_LOCAL_SIZE": "4",
            "HVDTPU_CONTROLLER_ADDR": "127.0.0.1",
            "HVDTPU_CONTROLLER_PORT": str(coord),
        })
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(DATA, "perf_worker.py")],
            env=worker_env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))

    straggler_seen = None
    deadline = time.monotonic() + 150
    try:
        while time.monotonic() < deadline:
            if all(p.poll() is not None for p in procs):
                break
            per_rank = {}
            for r in range(4):
                try:
                    per_rank[r] = parse_snapshot(scrape(
                        "127.0.0.1", base + r, "/perfz", secret=secret,
                        timeout=2.0))
                except Exception:
                    pass
            if len(per_rank) == 4:
                s = find_straggler(per_rank)
                if s is not None and s["rank"] == 2 and \
                        s["busy_us"] > 10_000:
                    straggler_seen = s
                    break
            time.sleep(0.25)
    except BaseException:
        wait_world(procs, timeout=0)    # kills what runs
        raise
    outs = wait_world(procs, timeout=deadline - time.monotonic())
    for rc, out, err in outs:
        assert rc == 0, f"worker failed: {err[-2000:]}"
        assert "ALL OK" in out
    # (2) the live scrape named the delayed rank the straggler mid-job.
    assert straggler_seen is not None, \
        "never saw rank 2 as live straggler via /perfz"
    # (1) the delayed rank's own report carries anomalies + the ANOMALY
    # flight event (asserted in-worker); cross-check the report file.
    with open(f"{report_path}.2") as f:
        r2 = json.load(f)
    assert r2["anomalies"] >= 1
    # (3) cross-run sentry: the delayed profile vs the clean one must be a
    # confirmed regression for rank 2's keys.
    from scripts.perf_diff import main as perf_diff_main
    assert (clean_dir / "perf_profile.0.json").exists()
    assert (slow_dir / "perf_profile.2.json").exists()
    rc = perf_diff_main([str(clean_dir), str(slow_dir)])
    assert rc == 1, "perf_diff must confirm the chaos-delay regression"


def test_hvdrun_top_flags():
    """Flag validation: --top needs --metrics-port, --top-once needs
    --top."""
    from horovod_tpu.runner.launch import parse_args

    args = parse_args(["-np", "2", "--metrics-port", "9090", "--top",
                       "--top-once", "python", "x.py"])
    assert args.top and args.top_once
    from horovod_tpu.runner.launch import run_launcher
    with pytest.raises(SystemExit, match="--top requires --metrics-port"):
        run_launcher(parse_args(["-np", "2", "--top", "python", "x.py"]))
    with pytest.raises(SystemExit, match="--top-once"):
        run_launcher(parse_args(["-np", "2", "--metrics-port", "9090",
                                 "--top-once", "python", "x.py"]))

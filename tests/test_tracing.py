"""Cross-rank distributed tracing (docs/tracing.md): merged clock-aligned
traces, the critical-path/straggler analyzer, hvdrun flags, and the
zero-copy transport tag in trace output.

The 4-rank acceptance case reuses the chaos harness's delay action: a rank
deliberately delayed mid-run must come out top of the straggler ranking
with compute-late attribution, and the delayed op's critical-path row must
name it as the gating rank.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")

from conftest import (free_port, launch_world, subprocess_env,  # noqa: E402
                      wait_world)

from horovod_tpu.trace_analysis import (build_report, diff_reports,  # noqa: E402
                                        format_report, load_trace_dir,
                                        merge_events)


# ---------------------------------------------------------------------------
# Synthetic-trace unit tests (no world, fast)
# ---------------------------------------------------------------------------

def _meta_event(rank, offset_us, err_us, steady_init_us):
    return {"name": "trace_meta", "ph": "i", "ts": 0,
            "pid": "__hvdtpu_trace_meta", "tid": rank,
            "args": {"rank": rank, "clock_offset_us": offset_us,
                     "clock_err_us": err_us,
                     "steady_init_us": steady_init_us}}


def _op_events(tensor, start, end, hops):
    """B/E activity pair + hop X spans (ts relative to the rank's file)."""
    events = [{"name": "ALLREDUCE", "ph": "B", "ts": start, "pid": tensor,
               "tid": 0, "args": {"transport": "tcp", "compression": "none"}},
              {"name": tensor, "ph": "E", "ts": end, "pid": tensor,
               "tid": 0}]
    for name, ts, dur, args in hops:
        events.append({"name": name, "ph": "X", "ts": ts, "dur": dur,
                       "pid": "hops", "tid": 0, "args": args})
    return events


def _write_trace(dirpath, rank, events):
    with open(os.path.join(dirpath, f"trace.{rank}.json"), "w") as f:
        json.dump(events, f)


def _synthetic_dir(tmp_path, name="tr"):
    """Two-rank synthetic run: rank 1 arrives 900us late at the wire
    (straggler, compute-late); rank 0 spends the op waiting on it."""
    d = tmp_path / name
    d.mkdir()
    r0 = [_meta_event(0, 0, 0, 1_000_000)]
    r0 += _op_events("grad/a", 100, 1100, [
        ("SENDRECV", 110, 980,
         {"send_peer": 1, "recv_peer": 1, "bytes": 4096, "lane": "tcp",
          "algo": "ring", "hier": 0, "compression": "none", "seg": 0,
          "wait_us": 900})])
    # Rank 1's clock runs 500us behind rank 0 and its file origin differs:
    # ts 0 in this file == steady 2_000_000 locally == 1_999_500 + 500 on
    # rank 0's axis after the offset shifts it.
    r1 = [_meta_event(1, 500, 3, 2_000_000 - 1_000_500)]
    r1 += _op_events("grad/a", 100, 1100, [
        ("SENDRECV", 1000, 90,
         {"send_peer": 0, "recv_peer": 0, "bytes": 4096, "lane": "tcp",
          "algo": "ring", "hier": 0, "compression": "none", "seg": 0,
          "wait_us": 0})])
    _write_trace(str(d), 0, r0)
    _write_trace(str(d), 1, r1)
    return str(d)


def test_merge_applies_clock_shift(tmp_path):
    d = _synthetic_dir(tmp_path)
    merged, metas = merge_events(load_trace_dir(d))
    assert metas[1]["clock_offset_us"] == 500
    by_pid = {}
    for e in merged:
        if e.get("ph") == "B":
            by_pid[e["pid"]] = e["ts"]
    # Both ranks' ops started at local ts 100; their global starts differ
    # by exactly the steady-origin difference + offset encoded above.
    assert by_pid["rank 0"] == 100  # rank 0 defines the origin here
    assert by_pid["rank 1"] == 100  # aligned: same global instant
    # Rank identity lands on the pid (process) axis, tracks become tids.
    tids = {e.get("tid") for e in merged if e["pid"] == "rank 1"}
    assert "hops" in tids and "grad/a" in tids


def test_straggler_and_critical_path(tmp_path):
    report = build_report(_synthetic_dir(tmp_path))
    assert report["ops_sampled"] == 1
    row = report["critical_path"][0]
    assert row["gating_rank"] == 1
    assert row["gating_phase"] == "compute-late"
    assert row["phases"]["startup_us"] == 900
    top = report["stragglers"][0]
    assert top["rank"] == 1 and top["attribution"] == "compute-late"
    # The victim shows up waiting, not active.
    victim = [s for s in report["stragglers"] if s["rank"] == 0][0]
    assert victim["mean_wait_us"] == 900
    text = format_report(report)
    assert "rank 1" in text and "compute-late" in text


def test_diff_reports(tmp_path):
    a = build_report(_synthetic_dir(tmp_path, "a"))
    b = build_report(_synthetic_dir(tmp_path, "b"))
    text = diff_reports(a, b)
    assert "1.00x" in text and "straggler: rank 1 -> rank 1" in text


def test_analyze_cli_and_merged_trace(tmp_path):
    d = _synthetic_dir(tmp_path)
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_analyze.py"),
         d, "--require-critical-path", "--json", str(tmp_path / "rep.json")],
        capture_output=True, text=True, timeout=60)
    assert rc.returncode == 0, rc.stderr + rc.stdout
    assert "critical path" in rc.stdout
    merged = json.load(open(os.path.join(d, "merged_trace.json")))
    assert isinstance(merged, list) and merged
    rep = json.load(open(tmp_path / "rep.json"))
    assert rep["stragglers"][0]["rank"] == 1
    # Empty table (no hop spans) must fail the smoke gate with exit 2.
    empty = tmp_path / "empty"
    empty.mkdir()
    _write_trace(str(empty), 0, [_meta_event(0, 0, 0, 0)] +
                 _op_events("t", 0, 10, []))
    rc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_analyze.py"),
         str(empty), "--require-critical-path", "--no-merged"],
        capture_output=True, text=True, timeout=60)
    assert rc.returncode == 2, (rc.returncode, rc.stderr)


# ---------------------------------------------------------------------------
# Process-mode worlds
# ---------------------------------------------------------------------------

def test_four_rank_trace_identifies_delayed_straggler(tmp_path):
    """Acceptance: a 4-rank traced job with rank 2 deliberately delayed
    (HVDTPU_CHAOS delay) produces one merged clock-aligned trace and a
    critical-path report naming rank 2 as the straggler.

    One retry (the test_chaos pattern): on a loaded 4-ranks-per-core CI
    box a scheduler stall on another rank can out-straggle the injected
    300 ms delay. Crashes and malformed traces never retry — only the
    straggler-ranking assertions, which depend on wall-clock contention.
    """
    for attempt in range(2):
        trace_dir = tmp_path / f"trace{attempt}"
        results = launch_world(
            4, os.path.join(DATA, "trace_worker.py"),
            extra_env={
                "HVDTPU_TRACE": str(trace_dir),
                "HVDTPU_TRACE_SAMPLE": "1",
                "HVDTPU_CHAOS": "rank2:delay=300@op=2",
            })
        for r, (rc, out, err) in enumerate(results):
            assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
            assert "ALL OK" in out

        report = build_report(str(trace_dir))
        assert report["ranks"] == [0, 1, 2, 3]
        # Every rank clock-synced at form-up; localhost bounds are tiny.
        for r in range(4):
            assert report["clock"][r]["err_us"] >= 0, report["clock"]
            assert report["clock"][r]["err_us"] < 100_000, report["clock"]
        assert report["critical_path"], "no sampled ops in the trace"
        top = report["stragglers"][0]
        slow = max(report["critical_path"],
                   key=lambda r_: r_["duration_us"])
        load_flaked = not (top["rank"] == 2 and
                           top["attribution"] == "compute-late" and
                           slow["duration_us"] > 250_000 and
                           slow["gating_rank"] == 2)
        if load_flaked and attempt == 0:
            continue
        # The delayed rank tops the straggler ranking as compute-late (the
        # sleep lands between the op starting and its first hop).
        assert top["rank"] == 2, report["stragglers"]
        assert top["attribution"] == "compute-late", top
        # The delayed op's own row names rank 2 as the gating leg.
        assert slow["duration_us"] > 250_000, slow
        assert slow["gating_rank"] == 2, slow

        # The merged trace is one valid JSON event list spanning all ranks.
        merged, _ = merge_events(load_trace_dir(str(trace_dir)))
        pids = {e["pid"] for e in merged}
        assert {"rank 0", "rank 1", "rank 2", "rank 3"} <= pids
        assert all(e["ts"] >= 0 for e in merged if "ts" in e)
        return


def test_hvdrun_trace_end_to_end(tmp_path):
    """hvdrun --trace DIR: per-rank traces, auto-merged trace, and the
    report on stderr at job end."""
    trace_dir = tmp_path / "tr"
    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--trace", str(trace_dir), "--trace-sample", "1",
         sys.executable, os.path.join(DATA, "trace_worker.py")],
        env=dict(subprocess_env(), TEST_TRACE_ITERS="2"),
        capture_output=True, text=True, timeout=150)
    assert rc.returncode == 0, rc.stderr
    assert (trace_dir / "trace.0.json").exists()
    assert (trace_dir / "trace.1.json").exists()
    merged = json.load(open(trace_dir / "merged_trace.json"))
    assert isinstance(merged, list) and merged
    assert "critical path" in rc.stderr
    assert "straggler ranking" in rc.stderr


def test_hvdrun_trace_flags():
    from horovod_tpu.runner.launch import _apply_tuning_env, parse_args
    from horovod_tpu.utils import envvars as ev

    args = parse_args(["-np", "2", "--trace", "/tmp/_hvd_tr",
                       "--trace-sample", "5", "python", "x.py"])
    assert args.trace == "/tmp/_hvd_tr" and args.trace_sample == 5
    env = _apply_tuning_env({}, args)
    assert env[ev.HVDTPU_TRACE] == "/tmp/_hvd_tr"
    assert env[ev.HVDTPU_TRACE_SAMPLE] == "5"

    bad = parse_args(["-np", "2", "--trace-sample", "-1", "python", "x.py"])
    with pytest.raises(SystemExit):
        _apply_tuning_env({}, bad)


def test_runtime_start_trace_samples_by_default(tmp_path):
    """hvd.start_trace(path) on a job launched WITHOUT --trace must still
    emit hop spans (the documented default-10 sampling falls back when no
    rate was configured at init — code-review regression)."""
    script = tmp_path / "rt_trace.py"
    script.write_text(
        "import os, sys, json, time\n"
        "import numpy as np\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "r = hvd.rank()\n"
        f"path = {str(tmp_path)!r} + f'/rt.{{r}}.json'\n"
        "hvd.start_trace(path)\n"  # sample=None, nothing configured
        "for i in range(3):\n"
        "    hvd.allreduce(np.ones(64, np.float32), name=f't{i}')\n"
        "hvd.stop_trace()\n"
        "deadline = time.time() + 30\n"
        "while True:\n"
        "    try:\n"
        "        events = json.load(open(path)); break\n"
        "    except Exception:\n"
        "        assert time.time() < deadline; time.sleep(0.05)\n"
        "assert any(e.get('pid') == 'hops' for e in events), 'no hop spans'\n"
        "hvd.shutdown()\n"
        "print('ALL OK')\n")
    results = launch_world(2, str(script))
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


def test_bad_trace_sample_fails_init_loudly():
    results = launch_world(2, os.path.join(DATA, "trace_worker.py"),
                           extra_env={"HVDTPU_TRACE_SAMPLE": "-3"},
                           timeout=60)
    for rc, _out, err in results:
        assert rc != 0
        assert "HVDTPU_TRACE_SAMPLE" in err


# ---------------------------------------------------------------------------
# Zero-copy transport tag in trace output (PR-7 satellite)
# ---------------------------------------------------------------------------

def test_timeline_pins_tcp_zc_tag(tmp_path):
    """2 ranks, shm off, zero-copy forced on: when the engine reports
    zero-copy sends, the per-op transport tag must read tcp-zc."""
    results = launch_world(
        2, os.path.join(DATA, "trace_tag_worker.py"),
        extra_env={
            "HVDTPU_SHM": "0",
            "HVDTPU_TCP_ZEROCOPY": "on",
            "TEST_TIMELINE_PATH": str(tmp_path / "tl"),
            "TEST_EXPECT_LANE": "tcp-zc",
        })
    for r, (rc, out, err) in enumerate(results):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out


def test_timeline_pins_shm_tcp_zc_tag(tmp_path):
    """4 ranks on two synthetic hosts (shm intra-host + zero-copy TCP
    cross-host): the lane-mix tag must read shm+tcp-zc."""
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
            "HVDTPU_TCP_ZEROCOPY": "on",
            "HVDTPU_ALLREDUCE_HIER": "0",
            "TEST_TIMELINE_PATH": str(tmp_path / "tl"),
            "TEST_EXPECT_LANE": "shm+tcp-zc",
        })
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(DATA, "trace_tag_worker.py")],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    for r, (rc, out, err) in enumerate(wait_world(procs)):
        assert rc == 0, f"rank {r} failed:\n{err}\n{out}"
        assert "ALL OK" in out

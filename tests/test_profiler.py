"""In-process sampling profiler tests (ISSUE 14; docs/profiling.md).

Covers the decode/merge layer (:mod:`horovod_tpu.profiler`), the native
window through the ctypes surface, the ``scripts/prof_report.py`` CLI, and
the two acceptance scenarios: a 4-rank world with a chaos-delayed rank whose
merged per-phase table attributes the delay to the expected phases, and a
profiler running straight through a chaos SIGKILL world (survivor profiles
intact, post-mortem verdict unchanged).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import (assert_all_ok, free_port, launch_world, subprocess_env,
                      wait_world)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Decode / merge layer (pure Python, synthetic data)
# ---------------------------------------------------------------------------

SYNTH_R0 = """\
wall;grad/0;main;Core::Loop;Execute 10
wire;grad/0;main;Core::Loop;Execute;Exchange;send 4
reduce;grad/0;main;Core::Loop;Execute;ReduceBuffer 6
idle;-;main;Core::Loop;poll 2
"""
SYNTH_R1 = """\
wait;grad/0;main;Core::Loop;Execute;Exchange;poll 30
wall;grad/0;main;Core::Loop;Execute 1
"""


class TestProfilerModule:
    def _per_rank(self):
        from horovod_tpu.profiler import parse_folded
        return {0: parse_folded(SYNTH_R0), 1: parse_folded(SYNTH_R1)}

    def test_parse_folded_shapes_and_counts(self):
        from horovod_tpu.profiler import parse_folded
        stacks = parse_folded(SYNTH_R0)
        assert len(stacks) == 4
        frames, count = stacks[0]
        assert frames[0] == "wall" and frames[1] == "grad/0"
        assert frames[-1] == "Execute" and count == 10

    @pytest.mark.parametrize("bad", [
        "wall;grad/0;main",            # no count
        "wall;grad/0;main notanumber",  # non-integer count
        "wall;grad/0;main 0",           # non-positive count
    ])
    def test_parse_folded_rejects_malformed(self, bad):
        from horovod_tpu.profiler import parse_folded
        with pytest.raises(ValueError):
            parse_folded(bad)

    def test_phase_table_and_merge(self):
        from horovod_tpu.profiler import merge_ranks, phase_table
        per_rank = self._per_rank()
        table = phase_table(per_rank)
        assert table[0] == {"wall": 10, "wire": 4, "reduce": 6, "idle": 2}
        assert table[1] == {"wait": 30, "wall": 1}
        merged = merge_ranks(per_rank)
        assert all(line.startswith(("rank0;", "rank1;")) for line in merged)
        assert "rank1;wait;grad/0;main;Core::Loop;Execute;Exchange;poll 30" \
            in merged

    def test_format_report_names_dominant_phase(self):
        from horovod_tpu.profiler import format_report
        text = format_report(self._per_rank())
        assert "rank" in text and "wait" in text
        # rank 1's dominant phase is wait; the star marks it.
        row1 = next(line for line in text.splitlines()
                    if line.strip().startswith("1 "))
        assert "30*" in row1
        assert "hot frames" in text

    def test_format_report_empty_inputs(self):
        from horovod_tpu.profiler import format_report
        assert "no profiles" in format_report({})

    def test_speedscope_document(self):
        from horovod_tpu.profiler import to_speedscope
        doc = to_speedscope(self._per_rank())
        assert doc["$schema"].endswith("file-format-schema.json")
        assert [p["name"] for p in doc["profiles"]] == ["rank 0", "rank 1"]
        frames = doc["shared"]["frames"]
        for prof in doc["profiles"]:
            assert len(prof["samples"]) == len(prof["weights"])
            assert prof["endValue"] == sum(prof["weights"])
            for sample in prof["samples"]:
                assert all(0 <= i < len(frames) for i in sample)

    def test_snapshot_to_folded_text_roundtrip(self):
        from horovod_tpu.profiler import parse_folded, to_folded_text
        doc = {"stacks": [
            {"phase": "reduce", "op": "grad/0", "count": 3,
             "frames": ["ReduceBuffer", "Exchange", "Loop"]},  # leaf first
            {"phase": "idle", "op": "", "count": 1,
             "frames": ["poll; with spaces"]},
        ]}
        text = to_folded_text(doc)
        stacks = parse_folded(text)
        # Root-first in folded form, sanitized frame names.
        assert stacks[0][0] == ["reduce", "grad/0", "Loop", "Exchange",
                                "ReduceBuffer"]
        assert stacks[0][1] == 3
        assert stacks[1][0] == ["idle", "-", "poll__with_spaces"]


# ---------------------------------------------------------------------------
# Native window through the ctypes surface (single rank, in-process)
# ---------------------------------------------------------------------------

class TestNativeWindow:
    def _core(self, monkeypatch, **env):
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        from horovod_tpu.basics import NativeCore
        core = NativeCore(rank=0, size=1)
        core.start()
        return core

    def test_window_samples_and_snapshot(self, monkeypatch):
        from horovod_tpu.profiler import parse_snapshot, to_folded_text
        core = self._core(monkeypatch, HVDTPU_PROF_CLOCK="wall",
                          HVDTPU_PROF_HZ="401")
        try:
            assert not core.profiler_running()
            core.profiler_start()
            assert core.profiler_running()
            for i in range(5):
                core.collective("allreduce", f"grad/{i}",
                                np.ones(4096, np.float32))
            # Wall clock: the background loop accrues samples while idle
            # too, so a short sleep guarantees a non-empty window.
            deadline = time.monotonic() + 5.0
            doc = {}
            while time.monotonic() < deadline:
                doc = parse_snapshot(core.profiler_snapshot())
                if doc.get("samples", 0) >= 3:
                    break
                time.sleep(0.05)
            core.profiler_stop()
            assert not core.profiler_running()
            assert doc["enabled"] and doc["clock"] == "wall"
            assert doc["samples"] >= 3, doc
            assert doc["stacks"], doc
            assert to_folded_text(doc).strip()
            # A fresh window clears the ring.
            core.profiler_start()
            core.profiler_stop()
            doc2 = parse_snapshot(core.profiler_snapshot())
            assert doc2["samples"] <= doc["samples"]
        finally:
            core.shutdown()

    def test_disabled_by_env(self, monkeypatch):
        from horovod_tpu.profiler import parse_snapshot
        core = self._core(monkeypatch, HVDTPU_PROF="0")
        try:
            core.profiler_start()
            assert not core.profiler_running()
            doc = parse_snapshot(core.profiler_snapshot())
            assert doc["enabled"] is False and doc["stacks"] == []
        finally:
            core.shutdown()

    def test_bad_knobs_fail_loudly(self, monkeypatch):
        monkeypatch.setenv("HVDTPU_PROF_HZ", "0")
        from horovod_tpu.basics import NativeCore
        with pytest.raises(ValueError, match="HVDTPU_PROF_HZ"):
            NativeCore(rank=0, size=1)
        monkeypatch.setenv("HVDTPU_PROF_HZ", "97")
        monkeypatch.setenv("HVDTPU_PROF_CLOCK", "sundial")
        with pytest.raises(ValueError, match="HVDTPU_PROF_CLOCK"):
            NativeCore(rank=0, size=1)


# ---------------------------------------------------------------------------
# Acceptance: 4-rank chaos-delayed world -> per-phase attribution
# ---------------------------------------------------------------------------

class TestProfileAcceptance:
    def test_chaos_delay_attributed_to_expected_phase(self, tmp_path):
        """Tier-1 acceptance (ISSUE 14): a 4-rank world where rank 2 is
        chaos-delayed 1.5 s mid-run, profiled wall-clock for the whole job.
        The merged per-phase table must attribute the delayed rank's
        samples to the op's execution (wall — the delay fires at op entry,
        inside the op scope but outside any hop) and the BLOCKED peers'
        samples to wait."""
        results = launch_world(
            4, os.path.join(REPO, "tests", "data", "perf_worker.py"),
            extra_env={
                "HVDTPU_PROF_DIR": str(tmp_path),
                "HVDTPU_PROF_CLOCK": "wall",
                "TEST_PERF_ITERS": "60",
                "HVDTPU_CHAOS": "rank2:delay=1500@op=40",
            })
        assert_all_ok(results)

        from horovod_tpu.profiler import (format_report, load_folded_dir,
                                          phase_table)
        per_rank = load_folded_dir(str(tmp_path))
        assert sorted(per_rank) == [0, 1, 2, 3]
        table = phase_table(per_rank)
        # The delayed rank slept ~1.5 s inside the op scope: at 97 Hz
        # that is ~145 wall samples — demand a robust fraction and wall
        # as its dominant phase.
        r2 = table[2]
        assert r2.get("wall", 0) >= 40, table
        assert max(r2, key=r2.get) == "wall", table
        # Every OTHER rank spent the delay blocked on rank 2: wait must
        # dominate their non-idle samples.
        for peer in (0, 1, 3):
            row = table[peer]
            busy = {p: c for p, c in row.items() if p != "idle"}
            assert busy.get("wait", 0) >= 40, (peer, table)
            assert max(busy, key=busy.get) == "wait", (peer, table)
        # The human table renders all four ranks.
        text = format_report(per_rank)
        for rank in range(4):
            assert any(line.strip().startswith(f"{rank} ")
                       for line in text.splitlines()), text

    def test_prof_report_cli_merges_and_gates(self, tmp_path):
        """scripts/prof_report.py over a real 2-rank --profile run: exit 0
        with --require-samples, a non-empty per-phase table, and both
        merged artifacts written."""
        results = launch_world(
            2, os.path.join(REPO, "tests", "data", "perf_worker.py"),
            extra_env={
                "HVDTPU_PROF_DIR": str(tmp_path),
                "HVDTPU_PROF_CLOCK": "wall",
                "TEST_PERF_ITERS": "40",
            })
        assert_all_ok(results)
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "prof_report.py"),
             str(tmp_path), "--require-samples", "--json",
             str(tmp_path / "table.json")],
            env=subprocess_env(), capture_output=True, text=True, timeout=60)
        assert r.returncode == 0, r.stderr + r.stdout
        assert "Per-phase sample attribution" in r.stdout
        assert (tmp_path / "profile_merged.folded").exists()
        assert (tmp_path / "profile.speedscope.json").exists()
        table = json.loads((tmp_path / "table.json").read_text())
        assert set(table["ranks"]) == {"0", "1"}
        assert all(sum(row.values()) > 0 for row in table["ranks"].values())
        # The speedscope doc loads and covers both ranks.
        doc = json.loads((tmp_path / "profile.speedscope.json").read_text())
        assert len(doc["profiles"]) == 2

    def test_prof_report_cli_requires_samples(self, tmp_path):
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "prof_report.py"),
             str(tmp_path), "--require-samples"],
            env=subprocess_env(), capture_output=True, text=True, timeout=60)
        assert r.returncode == 2, (r.returncode, r.stdout, r.stderr)


# ---------------------------------------------------------------------------
# Signal coexistence: profiler through a chaos SIGKILL world
# ---------------------------------------------------------------------------

class TestProfilerChaosKill:
    def test_survivor_profile_intact_and_verdict_unchanged(self, tmp_path):
        """ISSUE 14 satellite: the profiler sampling through a rank's
        SIGKILL must not corrupt either side of the forensics — the
        survivor's folded profile parses and holds samples, and the
        post-mortem verdict still names the dead rank."""
        import textwrap
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""\
            import os
            import numpy as np
            os.environ.setdefault('JAX_PLATFORMS', 'cpu')
            from horovod_tpu.basics import NativeCore
            from horovod_tpu.exceptions import HvdTpuInternalError
            rank = int(os.environ['HVDTPU_RANK'])
            core = NativeCore(rank, int(os.environ['HVDTPU_SIZE']))
            core.start()
            try:
                for i in range(8):
                    core.collective('allreduce', f'grad/{i}',
                                    np.ones(65536, np.float32))
            except HvdTpuInternalError:
                print('SURVIVOR FAILED OVER')
            core.shutdown()
        """))
        port = free_port()
        procs = []
        for r in range(2):
            env = subprocess_env()
            env.update({
                "HVDTPU_RANK": str(r), "HVDTPU_SIZE": "2",
                "HVDTPU_LOCAL_RANK": str(r), "HVDTPU_LOCAL_SIZE": "2",
                "HVDTPU_CONTROLLER_PORT": str(port),
                "HVDTPU_FLIGHTREC_DIR": str(tmp_path),
                "HVDTPU_PROF_DIR": str(tmp_path),
                "HVDTPU_PROF_CLOCK": "wall",
                "HVDTPU_PROF_HZ": "401",
                "HVDTPU_FAILURE_DETECT_MS": "200",
            })
            if r == 1:
                env["HVDTPU_CHAOS"] = "rank1:kill@op=4"
            procs.append(subprocess.Popen(
                [sys.executable, str(script)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
        results = wait_world(procs, timeout=120)
        assert results[1][0] == -9, results[1]  # chaos SIGKILL landed
        assert "SURVIVOR FAILED OVER" in results[0][1], results

        # Survivor's whole-job profile intact (SIGPROF fired through the
        # abort cascade and the flight dump); the dead rank never reached
        # shutdown, so only rank 0's folded file exists.
        from horovod_tpu.profiler import load_folded_dir
        per_rank = load_folded_dir(str(tmp_path))
        assert sorted(per_rank) == [0]
        assert sum(c for _f, c in per_rank[0]) > 0

        # Post-mortem verdict unchanged by the SIGPROF storm.
        from horovod_tpu.postmortem import format_verdict, run_postmortem
        verdict, _merged = run_postmortem(str(tmp_path))
        assert [d["rank"] for d in verdict["dead"]] == [1]
        assert "DEAD rank 1" in format_verdict(verdict)

"""Numerical-health observability (ISSUE 15; docs/numerics.md).

Covers the gradstats subsystem end to end: decoder/mirror units, the
chaos ``corrupt`` grammar, the ``hvdrun --top`` NAN/DIV/worst-SNR
surfaces, in-process single-rank telemetry (moments, NaN policies,
residual resets, the 1 Hz ``hvdtpu_residual_store_bytes`` staleness
window), the compressed-wire bitwise cross-rank invariant asserted
through the fingerprint machinery across {ring, RD, tree} x {int8, int4,
fp16} worlds, and the tier-1 acceptance scenarios: a chaos-corrupted
rank convicted by a DIVERGENCE event, a NaN gradient aborting the job
with the tensor named in the post-mortem verdict, and per-layer /gradz
SNR with the skip-regex layers absent.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from conftest import assert_all_ok, launch_world, subprocess_env

DATA = os.path.join(os.path.dirname(__file__), "data")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _single_rank_core(extra_env=None):
    for key, val in (extra_env or {}).items():
        os.environ[key] = val
    from horovod_tpu.basics import NativeCore
    core = NativeCore(0, 1, coord_port=_free_port())
    core.start()
    return core


class TestMirrorsAndDecoder:
    def test_enum_mirrors_are_dense(self):
        from horovod_tpu.gradstats import (GRAD_EVENT_NAMES, GRAD_EVENTS,
                                           NAN_POLICIES, NAN_POLICY_NAMES)
        assert sorted(GRAD_EVENTS.values()) == list(range(3))
        assert sorted(NAN_POLICIES.values()) == list(range(3))
        assert GRAD_EVENT_NAMES[GRAD_EVENTS["divergence"]] == "divergence"
        assert NAN_POLICY_NAMES[NAN_POLICIES["abort"]] == "abort"

    def test_parse_snapshot_validates_shape(self):
        from horovod_tpu.gradstats import parse_snapshot
        with pytest.raises(ValueError):
            parse_snapshot(b"not json {")
        with pytest.raises(ValueError):
            parse_snapshot('{"version": 2, "keys": []}')
        with pytest.raises(ValueError):
            parse_snapshot('{"version": 1, "keys": [{"key": "x"}]}')
        # A quantized key MUST carry its SNR fields.
        entry = {"key": "w", "count": 1, "norm": 1.0, "ewma_norm": 1.0,
                 "absmax": 1.0, "nonfinite": 0, "quant_count": 3}
        with pytest.raises(ValueError):
            parse_snapshot(json.dumps({"version": 1, "keys": [entry]}))
        entry.update(snr_db=30.0, ewma_snr_db=30.0, mse=0.1,
                     residual_norm=0.5, compression="int8")
        snap = parse_snapshot(json.dumps({"version": 1, "keys": [entry]}))
        assert snap["keys"][0]["snr_db"] == 30.0

    def _snap(self):
        return {
            "version": 1, "enabled": True, "nancheck": "warn",
            "gradcheck_sample": 64, "nonfinite_total": 0,
            "probes_total": 4, "divergence_total": 0,
            "residual_resets_total": 1,
            "keys": [
                {"key": "layer0/w", "count": 10, "norm": 5.0,
                 "ewma_norm": 5.1, "absmax": 0.4, "nonfinite": 0,
                 "quant_count": 10, "compression": "int4", "mse": 0.01,
                 "snr_db": 22.0, "ewma_snr_db": 21.5,
                 "residual_norm": 0.9},
                {"key": "layer0/bias", "count": 10, "norm": 0.5,
                 "ewma_norm": 0.5, "absmax": 0.1, "nonfinite": 0,
                 "quant_count": 0},
                {"key": "emb/w", "count": 10, "norm": 50.0,
                 "ewma_norm": 49.0, "absmax": 9.0, "nonfinite": 2,
                 "quant_count": 10, "compression": "int4", "mse": 4.0,
                 "snr_db": 9.0, "ewma_snr_db": 9.5,
                 "residual_norm": 20.0},
            ]}

    def test_worst_snr_picks_lowest_and_skips_dense(self):
        from horovod_tpu.gradstats import worst_snr
        worst = worst_snr(self._snap())
        assert worst["key"] == "emb/w" and worst["snr_db"] == 9.5
        assert worst["compression"] == "int4"
        assert worst_snr({"version": 1, "keys": []}) is None

    def test_format_report_renders_fields(self):
        from horovod_tpu.gradstats import format_report
        text = format_report(self._snap())
        assert "emb/w" in text and "layer0/bias" in text
        assert "worst SNR: emb/w" in text
        assert "residual_resets=1" in text

    def test_merge_profile_dir(self, tmp_path):
        from horovod_tpu.gradstats import merge_profile_dir, profile_ranks
        for r in (0, 1):
            (tmp_path / f"grad_profile.{r}.json").write_text(json.dumps(
                {"version": 1, "rank": r, "size": 2,
                 "gradstats": {"version": 1, "keys": []}}))
        (tmp_path / "grad_profile.1.json.tmp").write_text("junk")
        merged, found = merge_profile_dir(str(tmp_path))
        assert found == [0, 1]
        assert sorted(profile_ranks(merged)) == [0, 1]


class TestChaosCorruptSpec:
    def test_corrupt_grammar(self):
        from horovod_tpu.chaos import CHAOS_ACTIONS, parse_chaos
        spec = parse_chaos("rank2:corrupt@op=3", rank=2)
        assert spec.action == CHAOS_ACTIONS["corrupt"]
        assert spec.op_index == 3 and spec.hop_index == 0
        assert parse_chaos("rank2:corrupt@op=3", rank=1) is None

    def test_corrupt_rejects_hop_trigger_and_arg(self):
        from horovod_tpu.chaos import parse_chaos
        with pytest.raises(ValueError, match="op-gated"):
            parse_chaos("corrupt@hop=3", rank=0)
        with pytest.raises(ValueError, match="no '=<arg>'"):
            parse_chaos("corrupt=2@op=3", rank=0)


class TestConsoleFlags:
    def _metrics(self, nonfinite=0.0, div_suspects=()):
        parsed = {
            "hvdtpu_ops_total": {"type": "counter", "help": "",
                                 "samples": [("", {"op": "ALLREDUCE"},
                                              100.0)]},
            "hvdtpu_nonfinite_grads_total": {
                "type": "counter", "help": "",
                "samples": [("", {}, nonfinite)]},
        }
        if div_suspects:
            parsed["hvdtpu_divergence_total"] = {
                "type": "counter", "help": "",
                "samples": [("", {"suspect": str(r)}, 1.0)
                            for r in div_suspects]}
        return parsed

    def test_nan_flag_on_own_row(self):
        from horovod_tpu.runner.hvdtop import render_frame
        endpoints = {0: ("h", 1), 1: ("h", 2)}
        metrics = {0: self._metrics(), 1: self._metrics(nonfinite=3.0)}
        text, _ = render_frame(endpoints, metrics, {}, None, 0.0)
        rows = {ln.split()[0]: ln for ln in text.splitlines()
                if ln.strip() and ln.split()[0] in ("0", "1")}
        assert "NAN" not in rows["0"]
        assert "NAN" in rows["1"]

    def test_div_flag_lands_on_minority_rank(self):
        """The conviction lives on the COORDINATOR's scrape, but the flag
        must land on the minority rank's row — even when that rank's own
        endpoint is down."""
        from horovod_tpu.runner.hvdtop import render_frame
        endpoints = {0: ("h", 1), 1: ("h", 2), 2: ("h", 3)}
        metrics = {0: self._metrics(div_suspects=[2]),
                   1: self._metrics()}
        text, _ = render_frame(endpoints, metrics, {}, None, 0.0)
        rows = {ln.split()[0]: ln for ln in text.splitlines()
                if ln.strip() and ln.split()[0] in ("0", "1", "2")}
        assert "DIV" not in rows["0"]
        assert "DIV" not in rows["1"]
        assert "DIV" in rows["2"] and "UNREACHABLE" in rows["2"]

    def test_worst_snr_readout(self):
        from horovod_tpu.runner.hvdtop import render_frame
        endpoints = {0: ("h", 1)}
        grad = {0: {"version": 1, "keys": [
            {"key": "emb/w", "count": 5, "norm": 1, "ewma_norm": 1,
             "absmax": 1, "nonfinite": 0, "quant_count": 5,
             "compression": "int4", "mse": 1.0, "snr_db": 12.0,
             "ewma_snr_db": 12.5, "residual_norm": 1.0}]}}
        text, _ = render_frame(endpoints, {0: self._metrics()}, {}, None,
                               0.0, grad_by_rank=grad)
        assert "worst SNR: emb/w at 12.5 dB (int4, rank 0)" in text


class TestInProcess:
    def test_moments_and_gradz_fields(self):
        core = _single_rank_core({"HVDTPU_NANCHECK": "warn",
                                  "HVDTPU_GRADSTATS": "1"})
        try:
            from horovod_tpu.gradstats import parse_snapshot
            w = np.linspace(-2, 2, 4096).astype(np.float32)
            core.collective("allreduce", "m/w", w)
            snap = parse_snapshot(core.gradstats_snapshot())
            keys = {e["key"]: e for e in snap["keys"]}
            assert "m/w" in keys
            np.testing.assert_allclose(keys["m/w"]["norm"],
                                       np.linalg.norm(w), rtol=1e-5)
            np.testing.assert_allclose(keys["m/w"]["absmax"], 2.0,
                                       rtol=1e-6)
            assert keys["m/w"]["nonfinite"] == 0
            assert keys["m/w"]["quant_count"] == 0  # size-1: wire unused
        finally:
            core.shutdown()
            os.environ.pop("HVDTPU_NANCHECK", None)

    def test_nancheck_warn_counts_and_proceeds(self):
        core = _single_rank_core({"HVDTPU_NANCHECK": "warn"})
        try:
            from horovod_tpu.observability import sample_value
            x = np.ones(256, np.float32)
            x[7] = np.inf
            x[9] = np.nan
            out = core.collective("allreduce", "nf/t", x)
            assert np.isnan(out[9])  # op proceeded
            parsed = core.metrics()
            assert sample_value(parsed,
                                "hvdtpu_nonfinite_grads_total") == 2
        finally:
            core.shutdown()
            os.environ.pop("HVDTPU_NANCHECK", None)

    def test_nancheck_abort_raises_naming_tensor(self):
        from horovod_tpu.exceptions import HvdTpuInternalError
        core = _single_rank_core({"HVDTPU_NANCHECK": "abort"})
        try:
            x = np.ones(256, np.float32)
            x[0] = np.nan
            with pytest.raises(HvdTpuInternalError,
                               match="non-finite gradient in tensor "
                                     "'abort/t'"):
                core.collective("allreduce", "abort/t", x)
        finally:
            core.shutdown()
            os.environ.pop("HVDTPU_NANCHECK", None)

    def test_nancheck_off_is_silent(self):
        core = _single_rank_core({"HVDTPU_NANCHECK": "off"})
        try:
            from horovod_tpu.observability import sample_value
            x = np.ones(64, np.float32)
            x[0] = np.nan
            core.collective("allreduce", "off/t", x)
            assert sample_value(core.metrics(),
                                "hvdtpu_nonfinite_grads_total") == 0
        finally:
            core.shutdown()
            os.environ.pop("HVDTPU_NANCHECK", None)

    def test_gradstats_disabled_snapshot(self):
        core = _single_rank_core({"HVDTPU_GRADSTATS": "0"})
        try:
            core.collective("allreduce", "d/t", np.ones(64, np.float32))
            snap = json.loads(core.gradstats_snapshot())
            assert snap["enabled"] is False and snap["keys"] == []
        finally:
            core.shutdown()
            os.environ.pop("HVDTPU_GRADSTATS", None)

    def test_residual_reset_and_store_bytes_staleness(self):
        """ISSUE 15 satellites: a mid-run reshape bumps
        ``hvdtpu_residual_resets_total`` with a WARN, and the 1 Hz
        ``hvdtpu_residual_store_bytes`` gauge converges to
        ``ResidualStore::TotalBytes()`` (known here by construction)
        within one refresh window (docs/metrics.md)."""
        import time

        from horovod_tpu.observability import sample_value
        core = _single_rank_core({"HVDTPU_COMPRESSION": "int8",
                                  "HVDTPU_COMPRESSION_MIN_BYTES": "0"})
        try:
            core.collective("allreduce", "rs/w",
                            np.ones(8192, np.float32))
            # The gauge refreshes at most once per second: immediately
            # after the first compressed op it may still read 0 (the
            # documented staleness window) — poll past one window and it
            # MUST equal the store's true content: one 8192-float buffer.
            deadline = time.monotonic() + 5.0
            val = None
            while time.monotonic() < deadline:
                core.collective("allreduce", "tick",
                                np.ones(512, np.float32))
                val = sample_value(core.metrics(),
                                   "hvdtpu_residual_store_bytes")
                if val == (8192 + 512) * 4:
                    break
                time.sleep(0.25)
            assert val == (8192 + 512) * 4, val
            # Reshape: same name, new element count -> reset counted.
            core.collective("allreduce", "rs/w",
                            np.ones(4096, np.float32))
            resets = sample_value(core.metrics(),
                                  "hvdtpu_residual_resets_total")
            assert resets == 1, resets
        finally:
            core.shutdown()
            for key in ("HVDTPU_COMPRESSION",
                        "HVDTPU_COMPRESSION_MIN_BYTES"):
                os.environ.pop(key, None)


# The PR-3 invariant: every rank's collective output is bitwise identical,
# including the compressed paths (owner codes forwarded verbatim, both RD
# peers self-decode). Asserted through the new fingerprint machinery: the
# worker pins HVDTPU_GRADCHECK_SAMPLE=1 and rank 0 asserts zero divergence
# over every sampled op. Tier-1 runs the diagonal; the full 9-combo matrix
# rides the slow marker.
_BITWISE_DIAGONAL = [("ring", "int8"), ("recursive_doubling", "int4"),
                     ("tree", "fp16")]
_BITWISE_FULL = [(a, c)
                 for a in ("ring", "recursive_doubling", "tree")
                 for c in ("int8", "int4", "fp16")
                 if (a, c) not in _BITWISE_DIAGONAL]


def _bitwise_world(algo, comp, np_=2):
    results = launch_world(
        np_, os.path.join(DATA, "grad_worker.py"),
        extra_env={"TEST_GRAD_ITERS": "3",
                   "HVDTPU_ALLREDUCE_ALGO": algo,
                   "HVDTPU_COMPRESSION": comp,
                   "HVDTPU_COMPRESSION_MIN_BYTES": "1024",
                   "HVDTPU_GRADCHECK_SAMPLE": "1"},
        timeout=150)
    assert_all_ok(results)


@pytest.mark.parametrize("algo,comp", _BITWISE_DIAGONAL)
def test_bitwise_cross_rank_equality(algo, comp):
    _bitwise_world(algo, comp)


@pytest.mark.slow
@pytest.mark.parametrize("algo,comp", _BITWISE_FULL)
def test_bitwise_cross_rank_equality_full_matrix(algo, comp):
    _bitwise_world(algo, comp)


def test_corrupt_divergence_4rank_acceptance():
    """ISSUE 15 tier-1 acceptance: a chaos-corrupted rank is convicted by
    a DIVERGENCE flight event naming it, the coordinator's
    ``hvdtpu_divergence_total{suspect="2"}`` counter, and a DIV flag on
    its row in a live ``hvdrun --top`` frame — within one probe interval
    (sample=1). The worker asserts all three."""
    results = launch_world(
        4, os.path.join(DATA, "grad_worker.py"),
        extra_env={"TEST_GRAD_ITERS": "3",
                   "TEST_GRAD_EXPECT_DIVERGENCE": "2",
                   "HVDTPU_CHAOS": "rank2:corrupt@op=3",
                   "HVDTPU_GRADCHECK_SAMPLE": "1"},
        timeout=150)
    assert_all_ok(results)


def test_nancheck_abort_postmortem_acceptance(tmp_path):
    """ISSUE 15 tier-1 acceptance: an injected NaN gradient aborts the
    job under HVDTPU_NANCHECK=abort and the post-mortem verdict names the
    tensor."""
    pm = tmp_path / "pm"
    env = subprocess_env()
    env.update({"TEST_GRAD_ITERS": "3", "TEST_GRAD_NAN_RANK": "1",
                "TEST_GRAD_EXPECT_ABORT": "1", "HVDTPU_NANCHECK": "abort",
                "PYTHONPATH": REPO})
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np", "2",
         "--postmortem", str(pm), sys.executable,
         os.path.join(DATA, "grad_worker.py")],
        env=env, capture_output=True, text=True, timeout=150)
    out = proc.stdout + proc.stderr
    assert proc.returncode != 0, out  # the JOB must fail
    assert "saw the expected NaN abort" in out, out
    # The driver's verdict names the rank AND the tensor.
    assert "non-finite gradient" in out, out
    assert "layer1/w" in out, out
    # scripts/postmortem.py reproduces it from the dumps alone.
    rerun = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "postmortem.py"),
         str(pm)], env=env, capture_output=True, text=True, timeout=60)
    assert rerun.returncode == 0, rerun.stderr
    assert "non-finite gradient" in rerun.stdout, rerun.stdout
    assert "layer1/w" in rerun.stdout, rerun.stdout


def test_gradz_per_layer_snr_int4_acceptance(tmp_path):
    """ISSUE 15 tier-1 acceptance: an int4 run's /gradz reports per-layer
    SNR with the bias/norm-skipped layers absent (the worker asserts the
    snapshot shape), and the per-rank grad profiles survive the driver
    merge for scripts/grad_diff.py (self-diff exit 0)."""
    gp = tmp_path / "gp"
    results = launch_world(
        2, os.path.join(DATA, "grad_worker.py"),
        extra_env={"TEST_GRAD_ITERS": "3",
                   "HVDTPU_COMPRESSION": "int4",
                   "HVDTPU_COMPRESSION_MIN_BYTES": "1024",
                   "HVDTPU_GRAD_PROFILE_DIR": str(gp)},
        timeout=150)
    assert_all_ok(results)
    from horovod_tpu.gradstats import merge_profile_dir
    merged, found = merge_profile_dir(str(gp))
    assert found == [0, 1]
    merged_path = tmp_path / "grad_profile.json"
    merged_path.write_text(json.dumps(merged))
    diff = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "grad_diff.py"),
         str(merged_path), str(merged_path)],
        capture_output=True, text=True, timeout=60)
    assert diff.returncode == 0, diff.stdout + diff.stderr
    # And a doctored 10 dB drop is a confirmed quality regression.
    for prof in merged["ranks"].values():
        for e in prof["gradstats"]["keys"]:
            if e.get("quant_count", 0) > 0:
                e["ewma_snr_db"] -= 10.0
    bad_path = tmp_path / "doctored.json"
    bad_path.write_text(json.dumps(merged))
    diff = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "grad_diff.py"),
         str(merged_path), str(bad_path)],
        capture_output=True, text=True, timeout=60)
    assert diff.returncode == 1, diff.stdout + diff.stderr
    assert "REGRESSED" in diff.stderr, diff.stderr


def test_reshape_reset_visible_2rank():
    """ISSUE 15 satellite: a reshape mid-run is VISIBLE — the counter
    (worker-asserted) and a WARN naming the key."""
    results = launch_world(
        2, os.path.join(DATA, "grad_worker.py"),
        extra_env={"TEST_GRAD_ITERS": "2", "TEST_GRAD_RESHAPE": "1",
                   "HVDTPU_COMPRESSION": "int8",
                   "HVDTPU_COMPRESSION_MIN_BYTES": "1024"},
        timeout=150)
    assert_all_ok(results)
    assert any("error-feedback residual reset for 'reshape/w'" in err
               for _rc, _out, err in results), \
        [err for _rc, _out, err in results]


def test_runner_gradstats_flags():
    """hvdrun flag plumbing: --nancheck/--gradcheck-sample/--no-gradstats
    land in the worker env; bad values fail loudly."""
    from horovod_tpu.runner.launch import _apply_tuning_env, parse_args
    args = parse_args(["-np", "2", "--nancheck", "abort",
                       "--gradcheck-sample", "7", "--no-gradstats",
                       "python", "x.py"])
    env = _apply_tuning_env({}, args)
    assert env["HVDTPU_NANCHECK"] == "abort"
    assert env["HVDTPU_GRADCHECK_SAMPLE"] == "7"
    assert env["HVDTPU_GRADSTATS"] == "0"
    args = parse_args(["-np", "2", "--gradcheck-sample", "-1",
                       "python", "x.py"])
    with pytest.raises(SystemExit):
        _apply_tuning_env({}, args)


def test_bad_knobs_fail_loudly():
    os.environ["HVDTPU_NANCHECK"] = "explode"
    try:
        from horovod_tpu.basics import NativeCore
        with pytest.raises(ValueError, match="HVDTPU_NANCHECK"):
            NativeCore(0, 1, coord_port=_free_port())
    finally:
        os.environ.pop("HVDTPU_NANCHECK", None)
    os.environ["HVDTPU_GRADCHECK_SAMPLE"] = "-3"
    try:
        from horovod_tpu.basics import NativeCore
        with pytest.raises(ValueError, match="HVDTPU_GRADCHECK_SAMPLE"):
            NativeCore(0, 1, coord_port=_free_port())
    finally:
        os.environ.pop("HVDTPU_GRADCHECK_SAMPLE", None)

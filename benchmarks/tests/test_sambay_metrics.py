"""The decoder-hybrid-decoder cell's readers (``layer_metrics/s6_ms.py``,
``s6_scan_ms.py``, ``s6_scan_roofline_pct.py``, ``gmu_ms.py``,
``attn_cross_ms.py``, ``attn_diff_ms.py``) against
``data/sambay_trace.textproto``, whose operations, names and expected sums
are written out in the file; ``flops_s6`` against counts by hand; and the
``phi-4-mini-flash-reasoning_s16384`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks import flops, flops_s6, flops_window
from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "phi-4-mini-flash-reasoning_s16384"
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}
NEW = {"s6_ms": 8.5, "s6_scan_ms": 5.0, "gmu_ms": 1.0, "attn_cross_ms": 2.0,
       "attn_diff_ms": 2.5}


@pytest.fixture(scope="module")
def built(tmp_path_factory):
    """``built(name)``: ``data/<name>.textproto`` as an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    def build(name):
        with open(os.path.join(HERE, "data", name + ".textproto")) as f:
            space = ProfileData.text_proto_to_serialized_xspace(f.read())
        path = tmp_path_factory.mktemp(name) / (name + ".xplane.pb")
        path.write_bytes(space)
        return str(path)

    return build


def ctx_of(trace, **costs):
    costs = {"s6_scan": {"match": "^hvd_s6_", "ops": 1e9, "bytes": 1e6},
             "ssm_scan": {"match": "^hvd_ssd_", "ops": 1e9, "bytes": 1e6},
             **costs}
    return RunContext(
        job=types.SimpleNamespace(kernel_costs=costs), chips=1,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=2)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


@pytest.mark.parametrize("metric", sorted(NEW))
def test_scope_readers(metric, built, monkeypatch):
    path = built("sambay_trace")
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    ctx = ctx_of(tr.read_xplane(path, SPANS_NS))
    assert reader(metric)(ctx) == pytest.approx(NEW[metric])


def test_the_scans_roofline_and_the_other_scans_readers(built, monkeypatch):
    path = built("sambay_trace")
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    ctx = ctx_of(tr.read_xplane(path, SPANS_NS))
    # The least time 1 ms (1e9 operations at 1e12 a second; 1e6 bytes at 1e9)
    # over the scan's 5 ms.
    assert reader("s6_scan_roofline_pct")(ctx) == pytest.approx(20.0)
    assert ctx.kernel_roofline("s6_scan")[0] == pytest.approx(100 / 4.5)
    # Mamba-2's readers count its own kernel and none of the selective
    # scan's; the flash readers the cross layer's kernel as any other.
    assert reader("ssm_scan_ms")(ctx) == pytest.approx(1.0)
    assert reader("ssm_ms")(ctx) == pytest.approx(1.0)
    assert reader("flash_fwd_ms")(ctx) == pytest.approx(1.5)
    assert reader("flash_window_ms")(ctx) is None
    # A job that names no such cost has no roofline to read.
    bare = ctx_of(tr.read_xplane(path, SPANS_NS))
    del bare.job.kernel_costs["s6_scan"]
    assert reader("s6_scan_roofline_pct")(bare) is None


@pytest.mark.parametrize("name", ["ssm_trace", "window_trace", "moe_trace"])
def test_a_program_without_the_scopes_reads_nothing(built, monkeypatch, name):
    """The parent's programs, and a rehearsal's trace (no device plane):
    None, never an error."""
    path = built(name)
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    for metric in (*NEW, "s6_scan_roofline_pct"):
        if name == "window_trace" and metric == "attn_diff_ms":
            continue        # that trace's attention layers have no ``diff``
        assert reader(metric)(ctx_of(tr.read_xplane(path, SPANS_NS))) \
            is None, metric
        assert reader(metric)(ctx_of(tr.Trace({}, {}))) is None


def test_operation_counts_by_hand():
    # One Mamba-1 mixer, a token: in 2*8*(2*16), x_proj 2*16*(2+2*4), dt
    # 2*2*16, out 2*16*8.
    assert flops_s6.mamba_mixer_forward_flops(8, 16, 4, 2) \
        == 512 + 320 + 64 + 256
    assert flops_s6.gmu_forward_flops(8, 16) == 2 * 2 * 8 * 16
    # A differential layer, a token, 4:2 heads of 4 at 8 tokens: q and o
    # 2*8*16 each, k and v 2*8*8 each; 2 pairs, a pair two maps of a score
    # product over 4 and a value product over 8 (2*(8+16) = 48), over the
    # causal triangle's 36 pairs of 8 tokens: 36*2*48/8 = 432.
    shape = dict(seq_len=8, embed=8, heads=4, kv_heads=2, head_dim=4)
    assert flops_s6.diff_attention_forward_flops(**shape) == 768 + 432
    assert flops_s6.diff_attention_forward_flops(cross=True, **shape) \
        == 512 + 432
    # Under a window of 2: 1 + 2*7 = 15 pairs.
    assert flops_window.band_pairs(8, 2) == 15
    assert flops_s6.diff_attention_forward_flops(window=2, **shape) \
        == 768 + 15 * 2 * 48 // 8
    kinds = ("mamba", "window", "full", "gmu", "cross")
    fwd = (512 + 320 + 64 + 256) + (768 + 180) + (768 + 432) + 512 \
        + (512 + 432) + 5 * 6 * 8 * 32 + 2 * 8 * 100
    assert flops_s6.sambay_train_flops(
        8, kinds, 8, 4, 2, 4, window=2, mlp=32, vocab=100, inner=16, state=4,
        dt_rank=2) == 3 * fwd
    # The flash kernels' share of a differential layer: two calls, each 2
    # query and 1 key head: 36 pairs x 2 heads x 4 x (6 + 14) operations a
    # call; bytes a call, rows 8, itemsize 2, head_dim 4: forward (q, o of
    # twice the width: 3 widths a query head; k, v: 3 a key head) 8*2*4*(6+3)
    # + lse 8*2*4, backward (q, o 2, do 2, dq 1: 6 a query head; k, v 2, dk,
    # dv 2: 6 a key head) 8*2*4*(12+6) + 64.
    cost = flops_s6.diff_flash_cost(1, 8, 4, 2, 4)
    assert cost["ops"] == 2 * 36 * 2 * 4 * 20
    assert cost["bytes"] == 2 * ((576 + 64) + (1152 + 64))
    # A pass of the scan over 8 tokens of 16 channels and 4 states: 9
    # elementwise operations a channel and state and 2 a channel; u and y in
    # two bytes, dt in four, B and C in two.
    scan = flops_s6.scan_pass_cost(8, 16, 4)
    assert scan == {"ops": 8 * 16 * 38, "bytes": 8 * (16 * 8 + 16)}
    # At the cell's size the scan is bound by memory.
    cell = flops_s6.scan_pass_cost(16384, 5120, 16)
    assert flops.roofline_seconds(
        cell, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})[1] \
        == "memory"


def test_every_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in (*NEW, "s6_scan_roofline_pct"):
        assert entries[name]["workloads"] == [CELL], name
        assert entries[name]["moves"] == "tok_s_chip"
        assert entries[name]["source"] == "device_trace"
        assert callable(reader(name))
    # The cell reports the flash readers that find something in it and not
    # the one that has read nothing since PR 52, nor another scan's.
    for name, entry in entries.items():
        listed = CELL in entry.get("workloads", [])
        if name.startswith(("ssm_", "gdn_", "cca_", "mla_", "moe_",
                            "router_", "img_")) or name == "flash_dq_ms":
            assert not listed, name
        elif name.startswith(("tok_", "flash_")):
            assert listed, name


def test_the_cell_runs_in_rehearsal():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--rehearsal", "--trace", "1", "--seconds",
         "1", "--seed", "2147483999"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert len(line["compared"]) == 8
    read = next(l for l in done.stdout.splitlines() if "metrics read" in l)
    for metric in ("tok_mfu_pct", "flash_window_tiles_kept_pct",
                   "tok_kernels_unplaced"):
        assert metric in read

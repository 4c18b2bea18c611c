"""The latent expert layer's readers (``layer_metrics/moe_latent_ms.py``,
``moe_shared_ms.py``, ``moe_held_pairs_pct.py``) against
``data/latent_trace.textproto``, whose operations, names and expected sums
are written out in the file; ``flops_latent_moe`` against counts by hand;
and the ``nemotron-3-super-120b-a12b_s8192`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks import flops_latent_moe
from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "nemotron-3-super-120b-a12b_s8192"
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}


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


def ctx_of(trace, **job):
    costs = {"grouped_matmul": {"match": "^ragged-dot-", "ops": 1e9,
                                "bytes": 1e6},
             "ssm_scan": {"match": "^hvd_ssd_", "ops": 1e9, "bytes": 1e6}}
    return RunContext(
        job=types.SimpleNamespace(kernel_costs=costs, **job), chips=1,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=2)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


def test_the_projections_and_the_shared_expert_are_read_in_all_three_passes(
        built, monkeypatch):
    path = built("latent_trace")
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    ctx = ctx_of(tr.read_xplane(path, SPANS_NS))
    # Down 2, up 3, down again 1, up's backward 4, and the 4 ms of layer3's
    # down-projection that lie inside the window, over 2 steps.
    assert reader("moe_latent_ms")(ctx) == pytest.approx(7.0)
    # Forward 5, recomputed 6, backward 3.
    assert reader("moe_shared_ms")(ctx) == pytest.approx(7.0)
    # The accepted readers see the same file: neither is the routing's time
    # nor the kernels', both are the expert layer's.
    assert reader("moe_route_ms")(ctx) == pytest.approx(0.5)
    assert reader("moe_experts_ms")(ctx) == pytest.approx(2.0)
    assert reader("moe_ms")(ctx) == pytest.approx(16.5)
    assert reader("ssm_proj_ms")(ctx) == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["moe_trace", "window_trace",
                                  "prerouted_trace"])
def test_a_program_without_the_scopes_reads_nothing(built, monkeypatch, name):
    """The parent's programs (experts at the stream's width; no operation of
    these traces is under ``moe/shared``), and a rehearsal's trace (no
    device plane): None, never an error."""
    path = built(name)
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    for metric in ("moe_latent_ms", "moe_shared_ms"):
        assert reader(metric)(ctx_of(tr.read_xplane(path, SPANS_NS))) is None
        assert reader(metric)(ctx_of(tr.Trace({}, {}))) is None


def test_held_pairs_are_the_jobs_own_count():
    """The reader hands on what the job reckons from the counts its step
    returned, and reads nothing from a job that keeps none."""
    assert reader("moe_held_pairs_pct")(ctx_of(None)) is None
    assert reader("moe_held_pairs_pct")(
        ctx_of(None, held_pairs_pct=lambda: 1.5625)) == 1.5625
    # The job's reckoning, by hand: two blocks of a router 8 wide, experts 2
    # and 3 held; 6 of 24 pairs and 3 of 24: (25 + 12.5) / 2.
    from benchmarks.jobs import gpt_latent_moe_hybrid_dp as jobs

    job = types.SimpleNamespace(
        step=types.SimpleNamespace(last_counts=np.array(
            [[3, 3, 4, 2, 3, 3, 3, 3], [5, 4, 1, 2, 3, 3, 3, 3]])),
        cfg=types.SimpleNamespace(first_expert=2, experts_held=2))
    assert jobs.Job.held_pairs_pct(job) == pytest.approx(18.75)
    job.step.last_counts = None
    assert jobs.Job.held_pairs_pct(job) is None


def test_operation_counts_by_hand():
    """One token's forward operations of an expert block, the training
    count of a pattern and a pass of the two grouped matmuls, each written
    out."""
    block = flops_latent_moe.latent_expert_block_forward_flops(
        embed=8, latent=4, router=16, width=6, top_k=4, held=8,
        shared_width=10)
    # router 2*8*16, down and up 2 * 2*8*4, 4 * 8/16 = 2 experts of two
    # 4 x 6 matrices, the shared expert's two 8 x 10.
    assert block == 256 + 128 + 2 * 2 * 2 * 4 * 6 + 2 * 2 * 8 * 10 == 896
    ssm = dict(heads=2, head_dim=4, state=8, groups=1, chunk=16)
    # [z | x | B | C | dt] = 8 + 8 + 8 + 8 + 2 = 34 columns; the scan 2*16*8
    # + 2 * (2*16*4 + 4*4*8); the output projection.
    mamba = 2 * 8 * 34 + (256 + 2 * (128 + 128)) + 2 * 8 * 8
    # q and o 2 * 2*8*8, k and v 2 * 2*8*4, the scores and the values over
    # the (S + 1) / 2 keys a token sees at S = 32: 33 / 2 * 4 * 2 * 4.
    attention = 256 + 128 + 528
    total = flops_latent_moe.latent_moe_hybrid_train_flops(
        32, "ME*E", 8, heads=2, kv_heads=1, head_dim=4, vocab=64, ssm=ssm,
        experts=dict(latent=4, router=16, width=6, top_k=4, held=8,
                     shared_width=10))
    assert total == 3 * (mamba + attention + 2 * 896 + 2 * 8 * 64)
    cost = flops_latent_moe.grouped_matmul_pass_cost(
        rows=100, latent=4, width=6, experts=8)
    assert cost == {"ops": 2 * 2 * 100 * 4 * 6,
                    "bytes": 2 * (2 * 8 * 4 * 6 + 2 * 100 * 4)}
    # The cell's own count: 2.575 GFLOP a token trained.
    assert flops_latent_moe.latent_moe_hybrid_train_flops(
        8192, "MEMEMEM*EME", 4096, heads=4, kv_heads=1, head_dim=128,
        vocab=16384,
        ssm=dict(heads=16, head_dim=64, state=128, groups=1, chunk=128),
        experts=dict(latent=1024, router=512, width=2688, top_k=22, held=8,
                     shared_width=5376)) == pytest.approx(2.5750272e9)


def test_the_cell_in_rehearsal_reads_every_metric_it_lists():
    """The control flow of ``--workload nemotron-3-super-120b-a12b_s8192
    --trace 1`` at the twin's tiny sizes on 4 CPU devices: the check's four
    rows pass, and of the cell's metrics every one that needs no device
    trace is read, ``moe_held_pairs_pct`` among them."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    checks = [ln for ln in lines if "check: " in ln]
    assert len(checks) == 4 and all(ln.endswith(" ok") for ln in checks)
    for what in ("loss", "gradient norm after the exchange", "update norm",
                 "token-expert choices shared with the reference"):
        assert any(f"check: {what}" in ln for ln in checks), what
    read = [ln for ln in lines if "metrics read" in ln][0].split(": ")[-1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert {"moe_latent_ms", "moe_shared_ms", "moe_held_pairs_pct",
            "ssm_scan_ms", "moe_windows_per_step"} <= listed
    assert "flash_dq_ms" not in listed
    traced = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    # The window's drift needs three segments, which a loaded CPU may not
    # make of one second.
    assert set(read.split()) | {"tok_window_drift_pct"} == listed - traced

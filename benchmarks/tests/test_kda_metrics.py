"""The Ling-3.0-flash cell's readers (``layer_metrics/kda_ms.py``,
``kda_proj_ms.py``, ``kda_scan_ms.py``, ``kda_scan_roofline_pct.py``,
``moe_route_groups_ms.py``) against ``data/kda_trace.textproto``, whose
operations, names and expected sums are written out in the file;
``flops_kda`` against counts by hand; the configuration's published widths
and stated parameters; and the ``ling-3.0-flash_s8192`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks import flops, flops_kda, flops_mla
from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "ling-3.0-flash_s8192"
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}
NEW = {"kda_ms": 9.5, "kda_proj_ms": 3.0, "kda_scan_ms": 5.5,
       "moe_route_groups_ms": 1.0}


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
    costs = {"kda_scan": {"match": "^hvd_kda_", "ops": 1e9, "bytes": 1e6},
             "gdn_scan": {"match": "^hvd_gdn_", "ops": 1e9, "bytes": 1e6},
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
    path = built("kda_trace")
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    ctx = ctx_of(tr.read_xplane(path, SPANS_NS))
    assert reader(metric)(ctx) == pytest.approx(NEW[metric])


def test_the_scans_roofline_and_the_other_layers_readers(built, monkeypatch):
    path = built("kda_trace")
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    ctx = ctx_of(tr.read_xplane(path, SPANS_NS))
    # The least time 1 ms (1e9 operations at 1e12 a second; 1e6 bytes at 1e9)
    # over the scan's 5.5 ms.
    assert reader("kda_scan_roofline_pct")(ctx) == pytest.approx(100 / 5.5)
    # The gated delta rule's readers count their own kernel and none of
    # these; the MLA readers take the head-wise gate in; the grouped choice
    # is part of the routing.
    assert reader("gdn_scan_ms")(ctx) == pytest.approx(1.0)
    assert reader("mla_ms")(ctx) == pytest.approx(1.5)
    assert reader("mla_proj_ms")(ctx) == pytest.approx(0.5)
    assert reader("moe_route_ms")(ctx) == pytest.approx(1.5)
    bare = ctx_of(tr.read_xplane(path, SPANS_NS))
    del bare.job.kernel_costs["kda_scan"]
    assert reader("kda_scan_roofline_pct")(bare) is None


@pytest.mark.parametrize("name", ["gdn_trace", "mla_trace", "moe_trace",
                                  "sambay_trace"])
def test_a_program_without_the_scopes_reads_nothing(built, monkeypatch, name):
    """The parent's programs, and a rehearsal's trace (no device plane):
    None, never an error."""
    path = built(name)
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    for metric in (*NEW, "kda_scan_roofline_pct"):
        assert reader(metric)(ctx_of(tr.read_xplane(path, SPANS_NS))) \
            is None, metric
        assert reader(metric)(ctx_of(tr.Trace({}, {}))) is None


def test_operation_counts_by_hand():
    # The scan, a token, 2 heads of 4 by 8 in chunks of 16: a head the lower
    # halves of K K^T and Q K^T 2 * 16*4, the inverse 16*16 // 3 = 85, T on
    # the values and the attn product 2 * 16*8, T on the keys 16*4, three
    # products with the state 6 * 4*8.
    assert flops_kda.scan_forward_flops(2, 4, 8, 16) \
        == 2 * (128 + 85 + 256 + 64 + 192)
    # One mixer: projections 2*8*(3*8 + 16 + 2*2), the scan, out 2*16*8.
    assert flops_kda.kda_mixer_forward_flops(8, 2, 4, 8, 16) \
        == 2 * 8 * 44 + 1450 + 256
    kda = dict(heads=2, key_dim=4, value_dim=8, chunk=16)
    mla = dict(heads=2, nope_dim=4, rope_dim=2, value_dim=4, kv_rank=8)
    experts = dict(router=16, width=4, top_k=4, held=4, shared_width=4)
    block = 2 * 8 * 16 + 6 * 8 * 4 * 4 * 4 // 16 + 6 * 8 * 4
    fwd = 2 * (704 + 1450 + 256) \
        + flops_mla.mla_mixer_forward_flops(8, 8, **mla) + 2 * 8 * 2 \
        + 6 * 8 * 32 + 2 * block + 2 * 8 * 100
    assert flops_kda.kda_mla_moe_train_flops(
        8, ["kda", "kda", "mla"], 1, 8, kda=kda, mla=mla, mlp=32,
        experts=experts, vocab=100) == 3 * fwd
    # A pass of the scan over 8 tokens: q, k (4 each) and v, o (8 each) in
    # two bytes, the log decay a channel (4) and beta in four.
    assert flops_kda.scan_pass_cost(8, **kda) == {
        "ops": 8 * 1450, "bytes": 8 * 2 * (2 * 2 * 12 + 4 * 5)}
    # At the cell's size the scan is bound by its bytes on a v5e (4.5 MFLOP
    # and 49 KB a token a pass: the log decay a channel in float32 is a
    # third of them).
    cell = flops_kda.scan_pass_cost(8192, 32, 128, 128, 64)
    assert flops.roofline_seconds(
        cell, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})[1] \
        == "memory"


def test_the_configuration_keeps_the_published_widths():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "ling-3.0-flash.json")) as f:
        c = json.load(f)
    widths = dict(
        hidden_size=2560, num_attention_heads=32, head_dim=128,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=512, moe_intermediate_size=768,
        moe_shared_expert_intermediate_size=768, intermediate_size=6144,
        num_experts_per_tok=8, n_group=8, topk_group=4,
        short_conv_kernel_size=4, kda_lower_bound=-5, layer_group_size=6)
    assert {k: c[k] for k in widths} == widths
    assert c["published"] == {"num_hidden_layers": 42, "num_experts": 512,
                              "vocab_size": 157184,
                              "first_k_dense_replace": 2}
    assert sorted(c["reduced"]) == sorted(c["published"])
    assert c["held_layers"] == [0, 2, 3, 4, 5, 6]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["first_k_dense_replace"]) == (6, 8, 19648, 1)
    assert c["expert_parallel"] == {"chips": 64, "rank": 0}
    for key in ("assumed", "departures", "deployment", "parameters"):
        assert c[key], key
    for item in c["assumed"]["keys"]:
        assert c["assumed"][item], item
    # The stated parameters are ISSUE 63's table, counted again from the
    # shapes (and the five selection biases of 512 it leaves out).
    p = c["parameters"]
    assert p["kda_mixer"] == 52646048 and p["mla_mixer"] == 31965696
    assert p["total"] == 714987296 + 5 * 512
    assert p["bytes_at_16_a_parameter"] == 16 * p["total"]


def test_every_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in (*NEW, "kda_scan_roofline_pct"):
        assert entries[name]["workloads"] == [CELL], name
        assert entries[name]["moves"] == "tok_s_chip"
        assert entries[name]["source"] == "device_trace"
        assert callable(reader(name))
    # The cell reports the flash readers that find something in it, not the
    # one that has read nothing since PR 52 nor the window's; Moonlight's
    # MLA readers; no other scan's.
    for name, entry in entries.items():
        listed = CELL in entry.get("workloads", [])
        if name.startswith(("ssm_", "gdn_", "cca_", "s6_", "gmu_", "attn_",
                            "router_", "img_", "flash_window")) \
                or name in ("flash_dq_ms", "moe_latent_ms"):
            assert not listed, name
        elif name.startswith(("tok_", "flash_", "mla_", "kda_")) \
                or name == "moe_held_pairs_pct":
            assert listed, name
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling-3.0-flash", "s8192_b1", 1)


def test_the_cell_runs_in_rehearsal():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--rehearsal", "--trace", "1", "--seconds",
         "1", "--seed", "2147483999"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert len(line["compared"]) == 11
    read = next(l for l in done.stdout.splitlines() if "metrics read" in l)
    for metric in ("tok_mfu_pct", "moe_load_max_over_mean",
                   "moe_held_pairs_pct", "tok_kernels_unplaced"):
        assert metric in read

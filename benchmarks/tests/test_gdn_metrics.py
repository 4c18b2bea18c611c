"""The linear-attention layer's readers (``layer_metrics/gdn_*.py``) and the
expert layer's on a step with a shared expert, against
``data/gdn_trace.textproto``, whose operations, names and expected sums are
written out in the file; ``flops_gdn.py`` against a hand count; and the
``qwen3-next-80b-a3b_s4096`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks import flops, flops_gdn, flops_moe
from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}
NEW = ("gdn_ms", "gdn_scan_ms", "gdn_proj_ms", "gdn_scan_roofline_pct")
CELL = "qwen3-next-80b-a3b_s4096"
QWEN = dict(key_heads=16, value_heads=32, key_dim=128, value_dim=128,
            chunk=64)


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "data", "gdn_trace.textproto")) as f:
        built = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("gdn") / "gdn_trace.xplane.pb"
    path.write_bytes(built)
    return str(path)


def ctx_of(trace):
    costs = {"gdn_scan": {"match": "^hvd_gdn_", "ops": 1.3e9, "bytes": 1e6},
             "grouped_matmul": {"match": "^ragged-dot-", "ops": 2.5e8,
                                "bytes": 1e5}}
    return RunContext(
        job=types.SimpleNamespace(kernel_costs=costs), chips=1,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=2)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


@pytest.mark.parametrize("metric, want", [
    ("gdn_ms", 11.0), ("gdn_scan_ms", 6.5), ("gdn_proj_ms", 3.5),
    # The expert layer's readers on the same step: ``moe/shared`` is under
    # ``moe`` and counts in moe_ms alone; the grouped kernel is found by
    # XLA's name for it.
    ("moe_ms", 3.0), ("moe_route_ms", 1.0), ("moe_experts_ms", 1.0),
    # 2.5e8 operations at 1e12 a second over the kernel's 1 ms a step.
    ("moe_experts_roofline_pct", 25.0)])
def test_scope_readers(metric, want, trace_file, monkeypatch):
    monkeypatch.setattr(sr, "newest_xplane", lambda: trace_file)
    trace = tr.read_xplane(trace_file, SPANS_NS)
    assert reader(metric)(ctx_of(trace)) == pytest.approx(want)


def test_roofline_share_is_least_time_over_the_scan(trace_file, monkeypatch):
    monkeypatch.setattr(sr, "newest_xplane", lambda: trace_file)
    ctx = ctx_of(tr.read_xplane(trace_file, SPANS_NS))
    # 1.3e9 operations at 1e12 a second: 1.3 ms of the scan's 6.5 (the
    # bytes' 1 ms is the smaller bound).
    assert reader("gdn_scan_roofline_pct")(ctx) == pytest.approx(20.0)
    # A job that names no scan cost: the scan is still found by its scope
    # (the kernel's operation carries it too), and there is no share.
    ctx.job.kernel_costs.clear()
    assert reader("gdn_scan_ms")(ctx) == pytest.approx(6.5)
    assert reader("gdn_scan_roofline_pct")(ctx) is None


@pytest.mark.parametrize("other", ["scoped_trace.xplane.pb",
                                   "moe_trace.textproto",
                                   "ssm_trace.textproto"])
def test_readers_return_nothing_where_the_program_has_no_such_layer(
        other, monkeypatch, tmp_path):
    """A dense, a sparse or a state-space program's trace (the parent's,
    which the driver runs these readers on), a rehearsal's (no device
    plane): None, never an error."""
    path = os.path.join(HERE, "data", other)
    if other.endswith(".textproto"):
        from jax.profiler import ProfileData
        with open(path) as f:
            built = ProfileData.text_proto_to_serialized_xspace(f.read())
        path = str(tmp_path / "other.xplane.pb")
        with open(path, "wb") as f:
            f.write(built)
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    with_device = ctx_of(tr.read_xplane(path, SPANS_NS))
    without = ctx_of(tr.Trace({}, {}))
    for metric in NEW:
        assert reader(metric)(with_device) is None
        assert reader(metric)(without) is None


def test_flops_by_hand():
    # One token through the scan at the published shapes. A key head: the
    # lower halves of K K^T and Q K^T over the chunk's 64 tokens, 64 * 128
    # each. A value head: the inverse 64^2 / 3, T on the values and the
    # masked Q K^T on the corrected values 64 * 128 each, T on the keys
    # 64 * 128, three products with the 128 x 128 state 2 * 128 * 128 each.
    scan = 16 * 2 * 64 * 128 + 32 * (1365 + 2 * 8192 + 8192 + 6 * 16384)
    assert flops_gdn.scan_forward_flops(**QWEN) == scan == 4_237_984
    # The mixer: 2048 x 12288 (q, k 2048 each, v, z 4096 each) and 2048 x 64
    # in, 4096 x 2048 out.
    mixer = 2 * 2048 * (12288 + 64) + scan + 2 * 4096 * 2048
    assert flops_gdn.gdn_mixer_forward_flops(2048, **QWEN) == mixer
    # Gated attention at 16:2 heads of 256 and S=4096: q and its gate
    # 2048 x 4096 each, k and v 2048 x 512, o 4096 x 2048, the pairs a token
    # sees on average times 4 * 16 * 256.
    attention = 2 * 2048 * (2 * 4096 + 2 * 512) + 2 * 4096 * 2048 \
        + 4097 * 2 * 16 * 256
    assert flops_gdn.gated_attention_mixer_forward_flops(
        4096, 2048, 16, 2, 256) == attention
    # The expert block on a rank with 32 of 512 experts: the whole router,
    # 10 * 32 / 512 experts a token of three 2048 x 512 matrices, the shared
    # expert's three and its gate.
    experts = dict(router=512, width=512, top_k=10, held=32,
                   shared_width=512)
    block = 2 * 2048 * 512 + 0.625 * 6 * 2048 * 512 + 6 * 2048 * 512 \
        + 2 * 2048
    assert flops_gdn.expert_block_forward_flops(2048, **experts) == block
    assert flops_gdn.expert_block_forward_flops(
        2048, **{**experts, "shared_width": 0}) \
        == block - 6 * 2048 * 512 - 2 * 2048
    # A token trained: one period and the 2048 x 18992 head.
    kinds = ("gdn", "gdn", "gdn", "attention")
    total = flops_gdn.linear_moe_train_flops(
        4096, kinds, 2048, 16, 2, 256, vocab=18992, gdn=QWEN,
        experts=experts)
    assert total == 3 * (3 * mixer + attention + 4 * block
                         + 2 * 2048 * 18992)
    assert total == pytest.approx(1.29e9, rel=5e-3)
    # One pass of the cell's scans: 16384 tokens; q and k 16 x 128, v and o
    # 32 x 128 in bfloat16, the log decay and beta 32 each in float32.
    cost = flops_gdn.scan_pass_cost(16384, **QWEN)
    assert cost == {"ops": 16384 * scan,
                    "bytes": 16384 * (2 * (2 * 2048 + 2 * 4096) + 256)}
    seconds, bound = flops.roofline_seconds(
        cost, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and seconds == pytest.approx(4.967e-4, rel=1e-3)


def test_the_cell_in_rehearsal():
    """The control flow of ``--workload qwen3-next-80b-a3b_s4096 --trace 1``
    at the twin's tiny sizes on 4 CPU devices: the program (flash kernels
    interpreted, the chunked gated delta rule, 4 of 16 experts held, full
    recomputation) passes the five rows of the check against the reference's
    recurrence, and the readers run (a CPU run has no device plane: the trace
    readers are held to the fixture above)."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    checks = [ln for ln in lines if "check: " in ln]
    assert len(checks) == 5 and all(ln.endswith(" ok") for ln in checks)
    read = next(ln for ln in lines if "metrics read" in ln).split()
    assert "tok_mfu_pct" in read and "moe_load_max_over_mean" in read


def _job(traffic):
    import horovod_tpu as hvd
    import jax
    from benchmarks.jobs import gpt_linear_moe_dp

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        config = json.load(f)
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    try:
        return gpt_linear_moe_dp.Job(config, traffic, 0), config
    finally:
        hvd.shutdown()


def test_the_job_counts_what_the_step_runs():
    """One flash forward and one backward for the attention layer at heads
    of 256; three scan passes a linear layer; three grouped passes a layer
    over the rows the held experts multiply, an even routing's sixteenth of
    the T k until the check has counted them; the published shapes reach
    the model."""
    import numpy as np
    job, config = _job({"global_batch": 4, "seq_len": 4096, "log_every": 4})
    cfg = job.cfg
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "attention")
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert,
            cfg.experts_per_token) == (512, 32, 0, 10)
    assert cfg.renormalize_experts and cfg.shared_expert_dim == 512
    assert (cfg.rotary_dim, cfg.rope_theta) == (64, 1e7)
    assert cfg.vocab_size == 18992 and cfg.mlp_dim == 512
    shape = dict(heads=16, kv_heads=2, head_dim=256)
    fwd = flops.flash_forward_cost(4, 4096, **shape)
    bwd = flops.flash_backward_cost(4, 4096, **shape)
    assert job.kernel_costs["flash"]["ops"] == fwd["ops"] + bwd["ops"]
    scan = flops_gdn.scan_pass_cost(16384, **QWEN)
    assert job.kernel_costs["gdn_scan"]["ops"] == 9 * scan["ops"]
    one = flops_moe.grouped_matmul_pass_cost(
        16384 * 10 // 16, embed=2048, width=512, experts=32)
    assert job.kernel_costs["grouped_matmul"]["ops"] == 12 * one["ops"]
    # After the check: the share the counts show, here a quarter.
    job.expert_counts = np.ones((4, 512))
    job._grouped_matmul_cost(0.25)
    assert job.kernel_costs["grouped_matmul"]["ops"] \
        == 12 * 6 * 40960 * 2048 * 512
    # Tokens come from the rows of the vocabulary held here.
    tokens = job.host_batches(1)[0][0]
    assert tokens.shape == (4, 4096) and tokens.max() < 18992


def test_every_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["layer"] == "Linear-attention layer"
        assert entries[name]["moves"] == "tok_s_chip"
        assert entries[name]["workloads"][0] == CELL
        reader(name)
    # The cell reports what the other GPT cells and the sparse cell report.
    for name, entry in entries.items():
        listed = entry.get("workloads", [])
        if "starcoder2-3b_s4096" in listed or name.startswith("moe_"):
            assert CELL in listed, name


def test_configuration_keeps_every_published_number():
    """Every key of the catalog's config, copied here by hand, at its
    published value but the three that are this chip's share."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        config = json.load(f)
    published = dict(
        decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
        hidden_act="silu", hidden_size=2048, intermediate_size=5120,
        linear_conv_kernel_dim=4, linear_key_head_dim=128,
        linear_num_key_heads=16, linear_num_value_heads=32,
        linear_value_head_dim=128, max_position_embeddings=262144,
        mlp_only_layers=[], model_type="qwen3_next",
        moe_intermediate_size=512, norm_topk_prob=True,
        num_attention_heads=16, num_experts_per_tok=10,
        num_key_value_heads=2, partial_rotary_factor=0.25,
        rms_norm_eps=1e-6, rope_scaling=None, rope_theta=10000000,
        shared_expert_intermediate_size=512, tie_word_embeddings=False,
        use_sliding_window=False)
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 32, 18992)
    # The floors: a whole period, 8 routed experts, an eighth of the rows.
    assert config["num_hidden_layers"] % config["full_attention_interval"] \
        == 0 and config["num_experts"] >= 8
    assert config["vocab_size"] * 8 == 151936
    assert config["expert_parallel"] == {"chips": 16, "rank": 0}
    for key in ("assumed", "departures", "deployment"):
        assert config[key]

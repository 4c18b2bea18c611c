"""The state-space layer's readers (``layer_metrics/ssm_*.py``) against
``data/ssm_trace.textproto``, whose operations, names and expected sums are
written out in the file; ``flops_ssm.py`` against a hand count; and the
``granite-4.0-h-micro_s4096`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks import flops, flops_ssm
from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}
NEW = ("ssm_ms", "ssm_scan_ms", "ssm_proj_ms", "ssm_scan_roofline_pct")
CELL = "granite-4.0-h-micro_s4096"
GRANITE = dict(heads=64, head_dim=64, state=128, groups=1, chunk=256)


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "data", "ssm_trace.textproto")) as f:
        built = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("ssm") / "ssm_trace.xplane.pb"
    path.write_bytes(built)
    return str(path)


def ctx_of(trace):
    costs = {"ssm_scan": {"match": "^hvd_ssd_", "ops": 1.3e9, "bytes": 1e6}}
    return RunContext(
        job=types.SimpleNamespace(kernel_costs=costs), chips=1,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=2)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


@pytest.mark.parametrize("metric, want", [
    ("ssm_ms", 11.0), ("ssm_scan_ms", 6.5), ("ssm_proj_ms", 3.5)])
def test_scope_readers(metric, want, trace_file, monkeypatch):
    monkeypatch.setattr(sr, "newest_xplane", lambda: trace_file)
    trace = tr.read_xplane(trace_file, SPANS_NS)
    assert reader(metric)(ctx_of(trace)) == pytest.approx(want)


def test_roofline_share_is_least_time_over_the_scan(trace_file, monkeypatch):
    monkeypatch.setattr(sr, "newest_xplane", lambda: trace_file)
    ctx = ctx_of(tr.read_xplane(trace_file, SPANS_NS))
    # 1.3e9 operations at 1e12 a second: 1.3 ms of the scan's 6.5 (the
    # bytes' 1 ms is the smaller bound).
    assert reader("ssm_scan_roofline_pct")(ctx) == pytest.approx(20.0)
    # A job that names no scan cost: the scan is still found by its scope
    # (the kernel's operation carries it too), and there is no share.
    ctx.job.kernel_costs.clear()
    assert reader("ssm_scan_ms")(ctx) == pytest.approx(6.5)
    assert reader("ssm_scan_roofline_pct")(ctx) is None


@pytest.mark.parametrize("other", ["scoped_trace.xplane.pb",
                                   "moe_trace.textproto"])
def test_readers_return_nothing_where_the_program_has_no_such_layer(
        other, monkeypatch, tmp_path):
    """A dense or a sparse program's trace (the parent's, which the driver
    runs these readers on), a rehearsal's (no device plane): None, never an
    error."""
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
    # One token through the scan at the published shapes: C B^T over the
    # chunk's 256 tokens, 2 * 256 * 128, once (one group); a head: the
    # weighted product on x 2 * 256 * 64, its state and the entering
    # state's part 2 * 64 * 128 each.
    assert flops_ssm.scan_forward_flops(**GRANITE) \
        == 65_536 + 64 * (32_768 + 16_384 + 16_384) == 4_259_840
    # Two groups make C B^T twice.
    assert flops_ssm.scan_forward_flops(**{**GRANITE, "groups": 2}) \
        == 4_259_840 + 65_536
    # The mixer: 2048 x 8512 in (2 x 4096 + 2 x 128 + 64), 4096 x 2048 out.
    mixer = 2 * 2048 * 8512 + 4_259_840 + 2 * 4096 * 2048
    assert flops_ssm.ssm_mixer_forward_flops(2048, **GRANITE) == mixer
    # Attention at 32:8 heads of 64 and S=4096: q and o 2048 x 2048, k and v
    # 2048 x 512, the pairs a token sees on average times 4 * 32 * 64.
    attention = 2 * 2048 * (2048 + 2 * 512) + 2 * 2048 * 2048 \
        + 4097 * 2 * 32 * 64
    assert flops_ssm.attention_mixer_forward_flops(4096, 2048, 32, 8, 64) \
        == attention
    # A token trained: five state-space layers and one attention layer, each
    # with a gated feed-forward of three 2048 x 8192 matrices, and the
    # 2048 x 100352 head once.
    mlp = 6 * 2048 * 8192
    kinds = ("ssm",) * 5 + ("attention",)
    assert flops_ssm.hybrid_train_flops(
        4096, kinds, 2048, 32, 8, 64, mlp=8192, vocab=100352, ssm=GRANITE) \
        == 3 * (5 * mixer + attention + 6 * mlp + 2 * 2048 * 100352)
    # One pass of the cell's scan: 8192 tokens; x and y 64 x 64 and B and C
    # 128 each in bfloat16, dt 64 in float32.
    cost = flops_ssm.scan_pass_cost(8192, **GRANITE)
    assert cost == {"ops": 8192 * 4_259_840,
                    "bytes": 8192 * (2 * (2 * 4096 + 256) + 256)}
    seconds, bound = flops.roofline_seconds(
        cost, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and seconds == pytest.approx(1.771e-4, rel=1e-3)


def test_the_cell_in_rehearsal():
    """The control flow of ``--workload granite-4.0-h-micro_s4096 --trace 1``
    at the twin's tiny sizes on 4 CPU devices: the bfloat16 program (flash
    kernels interpreted, the chunked scan, full recomputation) passes the
    check against the float32 reference's recurrence, and the readers run (a
    CPU run has no device plane: the four trace readers are held to the
    fixture above)."""
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
    read = next(ln for ln in lines if "metrics read" in ln).split()
    assert "tok_mfu_pct" in read and "step_compiles" in read


def test_the_job_counts_what_the_step_runs():
    """One flash forward and one backward for each attention layer of the
    prefix that runs, at heads of 64; three scan passes a state-space
    layer."""
    import horovod_tpu as hvd
    from benchmarks.jobs import gpt_hybrid_dp

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    hvd.shutdown()
    import jax
    hvd.init(devices=jax.devices()[:1])
    try:
        job = gpt_hybrid_dp.Job(
            config, {"global_batch": 2, "seq_len": 4096, "log_every": 4}, 0)
    finally:
        hvd.shutdown()
    depth = config["num_hidden_layers"]
    kinds = job.cfg.layer_kinds
    assert kinds == tuple({"mamba": "ssm"}.get(k, k)
                          for k in config["layer_types"][:depth])
    assert kinds.count("attention") == 1 and kinds[5] == "attention"
    shape = dict(heads=32, kv_heads=8, head_dim=64)
    fwd = flops.flash_forward_cost(2, 4096, **shape)
    bwd = flops.flash_backward_cost(2, 4096, **shape)
    assert job.kernel_costs["flash"]["ops"] == fwd["ops"] + bwd["ops"]
    assert job.kernel_costs["flash"]["bytes"] == fwd["bytes"] + bwd["bytes"]
    scan = flops_ssm.scan_pass_cost(8192, **GRANITE)
    assert job.kernel_costs["ssm_scan"]["ops"] \
        == 3 * (depth - 1) * scan["ops"]


def test_every_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert entries[name]["layer"] == "State-space layer"
        assert entries[name]["moves"] == "tok_s_chip"
        assert entries[name]["workloads"][0] == CELL
        reader(name)
    # The cell reports what the other GPT cells report.
    for name, entry in entries.items():
        if "starcoder2-3b_s4096" in entry.get("workloads", []):
            assert CELL in entry["workloads"], name


def test_configuration_keeps_every_published_number():
    """Every key of the catalog's config, copied here by hand from the
    published ``config.json``, at its published value: only the depth is
    cut."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    published = dict(
        attention_multiplier=0.015625, embedding_multiplier=12,
        hidden_size=2048, intermediate_size=8192, logits_scaling=8,
        mamba_chunk_size=256, mamba_d_conv=4, mamba_d_head=64,
        mamba_d_state=128, mamba_expand=2, mamba_n_groups=1,
        mamba_n_heads=64, max_position_embeddings=131072,
        num_attention_heads=32, num_key_value_heads=8,
        residual_multiplier=0.22, rms_norm_eps=1e-5,
        shared_intermediate_size=8192, vocab_size=100352,
        tie_word_embeddings=True, position_embedding_type="nope")
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"]["num_hidden_layers"] == 40
    types_ = config["layer_types"]
    assert len(types_) == 40 and [i for i, t in enumerate(types_)
                                  if t == "attention"] == [5, 15, 25, 35]
    assert 6 <= config["num_hidden_layers"] < 40

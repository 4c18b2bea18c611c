"""The dense linear/full hybrid's yardstick: ``flops_linear.py`` against a hand
count at the published sizes, ``layer_metrics/gdn_lane_fill_pct.py`` on
hand-made counters, the ``olmo-hybrid-7b`` configuration's keys against the
catalog's, what the job counts, and the ``olmo-hybrid-7b_s8192`` cell in
rehearsal."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmarks import flops, flops_gdn, flops_linear

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "olmo-hybrid-7b_s8192"
QWEN_CELL = "qwen3-next-80b-a3b_s4096"
OLMO = dict(key_heads=30, value_heads=30, key_dim=96, value_dim=192,
            chunk=64)
KINDS = ("gdn", "gdn", "gdn", "attention")
# The catalog's ``config`` for allenai/Olmo-Hybrid-7B
# (model-configs/architectures.jsonl), numbers and flags at the top level.
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_flops_by_hand():
    # One token through the scan at the published sizes, by flops_gdn's
    # rules. A key head: the lower halves of K K^T and Q K^T over the
    # chunk's 64 tokens, 64 * 96 each. A value head: the inverse 64^2 / 3,
    # T on the values and the masked Q K^T on the corrected values 64 * 192
    # each, T on the keys 64 * 96, three products with the 96 x 192 state
    # 2 * 96 * 192 each.
    scan = 30 * 2 * 64 * 96 + 30 * (1365 + 2 * 64 * 192 + 64 * 96
                                    + 6 * 96 * 192)
    assert flops_gdn.scan_forward_flops(**OLMO) == scan == 4_648_950
    # The mixer: 3840 x 17280 (q, k 2880 each, v, z 5760 each) and
    # 3840 x 60 in, 5760 x 3840 out.
    mixer = 2 * 3840 * (17280 + 60) + scan + 2 * 5760 * 3840
    assert flops_gdn.gdn_mixer_forward_flops(3840, **OLMO) == mixer
    # Full attention at 30:30 heads of 128 and S=8192: four 3840 x 3840
    # projections, the pairs a token sees on average times 4 * 30 * 128.
    attention = 8 * 3840 * 3840 + 8193 * 2 * 30 * 128
    assert flops.gpt_layer_forward_flops(8192, 3840, 30, 30, 128, mlp=0) \
        == attention
    feed_forward = 6 * 3840 * 11008
    assert flops_linear.gated_ff_forward_flops(3840, 11008) == feed_forward
    forward = 3 * mixer + attention + 4 * feed_forward + 2 * 3840 * 12544
    args = (8192, KINDS, 3840, 30, 30, 128, 11008, 12544, OLMO)
    assert flops_linear.linear_forward_flops(*args) == forward
    assert forward == pytest.approx(1.838e9, rel=1e-3)
    assert flops_linear.linear_train_flops(*args) == 3 * forward
    assert 3 * forward == pytest.approx(5.51e9, rel=1e-3)
    # One pass of the cell's scans: 8192 tokens; q and k 30 x 96, v and o
    # 30 x 192 in bfloat16, the log decay and beta 30 each in float32: the
    # published sizes, not the lanes the kernels carry them on.
    cost = flops_gdn.scan_pass_cost(8192, **OLMO)
    assert cost == {"ops": 8192 * scan,
                    "bytes": 8192 * (2 * (2 * 2880 + 2 * 5760) + 240)}


def _metrics(layers, kernels):
    """``hvd.metrics()`` with the two counters' samples hand-made."""
    def family(samples):
        return {"samples": [("", {k: str(v) for k, v in labels.items()},
                             float(count)) for labels, count in samples]}
    return {"hvdtpu_spmd_gdn_layer_traces_total": family(layers),
            "hvdtpu_spmd_gdn_kernel_traces_total": family(kernels)}


def _layer(key_heads, heads, key_dim, width):
    return dict(key_heads=key_heads, value_heads=heads, key_dim=key_dim,
                value_dim=width, chunk=64, recurrence="kernel", chunks=64,
                beta_max=1)


def _kernels(key_lanes, value_lanes):
    return [(dict(kernel=name, chunk=64, heads_per_block=2,
                  operand_dtype="bfloat16", key_lanes=key_lanes,
                  value_lanes=value_lanes), traces)
            for name, traces in (("hvd_gdn_fwd", 1), ("hvd_gdn_bwd", 1),
                                 ("hvd_gdn_rec_fwd", 2),
                                 ("hvd_gdn_rec_bwd", 1))]


@pytest.mark.parametrize("layers, kernels, want", [
    # Whole lane tiles: every lane holds data.
    ([(_layer(16, 32, 128, 128), 2)], _kernels(128, 128), 100.0),
    # 96 -> 128 and 192 -> 256: three lanes in four.
    ([(_layer(30, 30, 96, 192), 2)], _kernels(128, 256), 75.0),
    # Two value heads a key head at 24 by 40 on a tile each:
    # (3 * 24 + 6 * 40) / (9 * 128).
    ([(_layer(3, 6, 24, 40), 1)], _kernels(128, 128),
     100.0 * 312 / 1152),
    # Layers of two shapes in one job, each on the lanes of its own size.
    ([(_layer(16, 32, 128, 128), 1), (_layer(30, 30, 96, 192), 1)],
     _kernels(128, 128) + _kernels(128, 256),
     100.0 * (6144 + 8640) / (6144 + 11520)),
], ids=["whole tiles", "96 by 192", "under a tile", "two shapes"])
def test_lane_fill_on_hand_made_counters(monkeypatch, layers, kernels, want):
    import horovod_tpu as hvd
    from benchmarks.layer_metrics import gdn_lane_fill_pct

    monkeypatch.setattr(hvd, "metrics", lambda: _metrics(layers, kernels))
    assert gdn_lane_fill_pct.read(None) == pytest.approx(want)


@pytest.mark.parametrize("metrics", [
    {}, _metrics([], []),
    # The parent commit's counters: a kernel sample has no lanes.
    _metrics([({k: v for k, v in _layer(16, 32, 128, 128).items()
                if k != "beta_max"}, 2)],
             [({k: v for k, v in labels.items() if "lanes" not in k}, n)
              for labels, n in _kernels(128, 128)])],
    ids=["no recorder", "no linear layer", "the parent's counters"])
def test_lane_fill_reads_nothing_where_there_is_nothing(monkeypatch, metrics):
    import horovod_tpu as hvd
    from benchmarks.layer_metrics import gdn_lane_fill_pct

    monkeypatch.setattr(hvd, "metrics", lambda: metrics)
    assert gdn_lane_fill_pct.read(None) is None


def test_the_configuration_is_the_published_one_cut_in_two_keys():
    config = _json("benchmarks", "configs", "olmo-hybrid-7b.json")
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == "olmo-hybrid-7b")
    assert entry["file"] == "benchmarks/configs/olmo-hybrid-7b.json"
    assert entry["source"] == config["source"] \
        == "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/" \
           "config.json"
    assert entry["reduced"] == config["reduced"] \
        == ["num_hidden_layers", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    # The published pattern whole: eight periods of three linear layers and
    # a full one; the job runs its first num_hidden_layers.
    assert config["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 8
    assert config["num_hidden_layers"] == 4 and config["vocab_size"] == 12544
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    stage = config["pipeline_parallel"]
    assert stage["stages"] * stage["layers_per_stage"] \
        == config["published"]["num_hidden_layers"]
    assert set(config["assumed"]["keys"]) <= set(config["assumed"])
    assert config["head_dim"] * config["num_attention_heads"] \
        == config["hidden_size"]
    # The rehearsal twin has every key the job reads of the real file.
    twin = _json("benchmarks", "tests", "data", "configs",
                 "olmo-hybrid-7b.json")
    assert (set(PUBLISHED) | {"job", "head_dim", "linear_chunk", "check",
                              "optimizer", "compute_dtype", "attention",
                              "remat", "layer_types"}) <= set(twin)
    assert twin["job"] == config["job"] == "gpt_linear_dp"


def test_the_cell_and_its_metrics_are_entries():
    bench = _json("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell == {"name": CELL, "config": "olmo-hybrid-7b",
                    "traffic": "s8192_b1", "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    assert _json("benchmarks", "traffic", "s8192_b1.json") == {
        "global_batch": 1, "seq_len": 8192, "log_every": 4}
    # Later cells are appended: the count and the lists only grow.
    assert len(bench["workloads"]) >= 9
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert {"gdn_ms", "gdn_scan_ms", "gdn_proj_ms", "gdn_scan_roofline_pct",
            "gdn_lane_fill_pct", "flash_ms", "flash_roofline_pct",
            "flash_fwd_ms", "flash_dkdv_ms", "flash_dq_ms", "tok_mfu_pct",
            "tok_compiler_remat", "tok_step_memory_gib"} <= listed
    assert not {m for m in listed if m.startswith(("moe_", "ssm_", "img_",
                                                   "flash_window"))}
    fill = next(m for m in bench["per_layer"]
                if m["name"] == "gdn_lane_fill_pct")
    assert fill == {
        "name": "gdn_lane_fill_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": fill["layer"],
        "moves": "tok_s_chip", "workloads": fill["workloads"]}
    assert fill["workloads"][:2] == [QWEN_CELL, CELL]
    assert fill["layer"] == next(m["layer"] for m in bench["per_layer"]
                                 if m["name"] == "gdn_ms")
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            importlib.import_module(f"benchmarks.layer_metrics.{m['name']}")


def test_the_job_counts_what_the_step_runs():
    """928.9M parameters (832.5M in the layers, 96.3M in the vocabulary's
    eighth); one flash forward and one backward for the attention layer;
    three scan passes a linear layer at the published 96 and 192; the
    published shapes reach ``GPTConfig``."""
    import horovod_tpu as hvd
    import jax
    import numpy as np
    from benchmarks.jobs import gpt_linear_dp

    config = _json("benchmarks", "configs", "olmo-hybrid-7b.json")
    traffic = _json("benchmarks", "traffic", "s8192_b1.json")
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    try:
        job = gpt_linear_dp.Job(config, traffic, 0)
    finally:
        hvd.shutdown()
    cfg = job.cfg
    assert (cfg.embed_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size) == (3840, 30, 30, 128, 11008, 12544)
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.gdn_conv, cfg.gdn_chunk) \
        == (30, 30, 96, 192, 4, 64)
    assert cfg.gdn_allow_neg_eigval and cfg.norms == "post" \
        and cfg.qk_norm and not cfg.rope and not cfg.tie_embeddings
    assert [s.mixer for s in cfg.plan] == list(KINDS)
    assert {s.ff for s in cfg.plan} == {"gated"}
    shapes = jax.eval_shape(job.init_params, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

    assert count(shapes) == 928_862_196
    assert count(shapes["layers"]) == 832_520_436
    assert count(shapes["embed"]) + count(shapes["lm_head"]) \
        == 2 * 12544 * 3840 == 96_337_920
    assert job.samples_per_step == 8192
    assert job.flops_per_sample == flops_linear.linear_train_flops(
        8192, KINDS, 3840, 30, 30, 128, 11008, 12544, OLMO)
    fwd = flops.flash_forward_cost(1, 8192, heads=30, kv_heads=30,
                                   head_dim=128)
    bwd = flops.flash_backward_cost(1, 8192, heads=30, kv_heads=30,
                                    head_dim=128)
    assert job.kernel_costs["flash"]["ops"] == fwd["ops"] + bwd["ops"]
    scan = flops_gdn.scan_pass_cost(8192, **OLMO)
    assert job.kernel_costs["gdn_scan"] == {
        "match": r"^hvd_gdn_", "ops": 9 * scan["ops"],
        "bytes": 9 * scan["bytes"]}


def test_the_cell_in_rehearsal():
    """The control flow of ``--workload olmo-hybrid-7b_s8192 --trace 1`` at
    the twin's tiny sizes on 4 CPU devices: the program (the four scan
    kernels interpreted at heads of 12 by 24 on a lane tile each, ``beta``
    in (0, 2), the norms after the branches, full recomputation) passes the
    five rows of the check against the reference's recurrence, and every
    metric the cell lists has a reader that runs (a CPU run has no device
    plane: the trace readers are held to ``test_gdn_metrics.py``'s
    fixture)."""
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
    assert {"tok_mfu_pct", "gdn_lane_fill_pct", "tok_step_memory_gib",
            "tok_compiler_remat"} <= set(read)
    assert not [w for w in read if w.startswith(("moe_", "ssm_"))]

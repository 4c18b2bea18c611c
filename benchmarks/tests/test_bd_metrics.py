"""The block-diffusion cell's yardstick: the two readers of the mask's
tiling (``layer_metrics/flash_bd_*.py``) against counters a hand can check,
``flops_bd.py`` against brute-force counts over the three clauses, the job's
kernel costs and a token's training cost by hand, and the
``sdar-30b-a3b-chat_s8192`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks import flops, flops_bd, program_counters
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "sdar-30b-a3b-chat_s8192"
NEW = ("flash_bd_tiles_kept_pct", "flash_bd_tile_fill_pct")


def ctx_of(seq=8192):
    return RunContext(
        job=types.SimpleNamespace(kernel_costs={}, seq=seq), chips=1,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=None, steps_traced=0)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


def keep(length, block):
    """The mask pair by pair, the three clauses."""
    def one(q, k):
        bq, bk = q % length // block, k % length // block
        return (q < length and k < length and bk == bq) \
            or (q < length and k >= length and bk < bq) \
            or (q >= length and k >= length and bk <= bq)
    return [[one(q, k) for k in range(2 * length)]
            for q in range(2 * length)]


def test_tiles_kept_is_the_counters_kept_over_the_rectangle(monkeypatch):
    asked = []

    def value(family, **labels):
        asked.append((family, labels))
        # Six layers, two kernels, 80 of 256 tiles each.
        return {"kept": 12 * 80.0, "skipped_block_diffusion": 12 * 176.0}[
            labels["tiles"]]

    monkeypatch.setattr(program_counters, "value", value)
    assert reader(NEW[0])(ctx_of()) == pytest.approx(100 * 80 / 256)
    # The step's rows, twice its data tokens, not another program's (the
    # check's 2048).
    assert asked[0] == ("hvdtpu_spmd_flash_tiles_total", {
        "mask": "block_diffusion", "tiles": "kept", "seq": "16384"})
    monkeypatch.setattr(program_counters, "value", lambda family, **labels: {
        "16384": None, "2048": 9.0}[labels["seq"]])
    assert reader(NEW[0])(ctx_of()) is None
    # 2 x 1000 rows are padded to 2048.
    monkeypatch.setattr(program_counters, "value", lambda family, **labels: {
        "2048": 3.0}.get(labels["seq"]))
    assert reader(NEW[0])(ctx_of(1000)) == pytest.approx(50.0)


def test_tile_fill_is_the_counters_kept_pairs_over_the_computed(monkeypatch):
    asked = []
    kept, computed = 8192 * (8192 + 4), 80 * 1024 * 1024

    def value(family, **labels):
        asked.append((family, labels))
        return 12.0 * {"kept": kept, "computed": computed}[labels["pairs"]]

    monkeypatch.setattr(program_counters, "value", value)
    assert reader(NEW[1])(ctx_of()) == pytest.approx(100 * kept / computed)
    assert reader(NEW[1])(ctx_of()) == pytest.approx(80.04, abs=0.01)
    assert asked[0] == ("hvdtpu_spmd_flash_pairs_total", {
        "mask": "block_diffusion", "pairs": "kept", "seq": "16384"})
    # A program without the family (the parent commit's): nothing to read.
    monkeypatch.setattr(program_counters, "value",
                        lambda family, **labels: None)
    assert reader(NEW[0])(ctx_of()) is None
    assert reader(NEW[1])(ctx_of()) is None


@pytest.mark.parametrize("length, block", [
    (8, 1), (8, 2), (16, 4), (24, 8), (32, 32), (48, 4)])
def test_pairs_against_a_brute_force(length, block):
    assert flops_bd.bd_pairs(length, block) \
        == sum(map(sum, keep(length, block))) == length * (length + block)


def test_pairs_refuse_a_block_that_does_not_divide():
    with pytest.raises(ValueError):
        flops_bd.bd_pairs(10, 4)


@pytest.mark.parametrize("length, block, block_q, block_k", [
    (16, 4, 8, 8), (16, 4, 16, 8), (24, 2, 8, 16), (32, 8, 8, 8),
    (32, 4, 64, 64), (24, 4, 16, 16), (48, 16, 8, 32)])
def test_tiles_against_a_brute_force(length, block, block_q, block_k):
    """A tile is computed where any pair of it is kept, the halves'
    boundary on a tile's edge or inside one."""
    mask = keep(length, block)
    rows = 2 * length
    brute = sum(
        any(mask[q][k] for q in range(i, i + block_q)
            for k in range(j, j + block_k))
        for i in range(0, rows, block_q) for j in range(0, rows, block_k))
    assert flops_bd.bd_tiles(length, block, block_q, block_k) \
        == (brute, (rows // block_q) * (rows // block_k))


def test_the_cells_tiles_and_fill():
    assert flops_bd.bd_tiles(8192, 4, 1024, 1024) == (80, 256)
    assert flops_bd.bd_pairs(8192, 4) / (80 * 1024 ** 2) \
        == pytest.approx(0.8004, abs=1e-4)


def test_flash_costs_by_hand():
    """One sequence of 8 data tokens in blocks of 2, 4:2 heads of 16:
    8 * 10 pairs a head; q and o of 16 rows at 4 heads, k and v at 2."""
    shape = dict(heads=4, kv_heads=2, head_dim=16)
    fwd = flops_bd.flash_forward_cost(1, 8, 2, **shape)
    bwd = flops_bd.flash_backward_cost(1, 8, 2, **shape)
    assert fwd["ops"] == 2 * 2 * 16 * 4 * 80
    assert bwd["ops"] == 5 * 2 * 16 * 4 * 80
    assert fwd["bytes"] == 16 * 16 * 2 * (2 * 4 + 2 * 2) + 16 * 4 * 4 \
        == flops.flash_forward_cost(1, 16, **shape)["bytes"]
    assert bwd["bytes"] == flops.flash_backward_cost(1, 16, **shape)["bytes"]


def test_a_data_tokens_training_cost_by_hand():
    """Two rows a data token through every layer's projections and expert
    block, the mask's pairs over L, the head once."""
    experts = dict(router=16, width=8, top_k=4, held=4)
    embed, heads, kv_heads, head_dim, vocab, layers = 32, 4, 2, 8, 100, 3
    proj = 2 * embed * (heads + 2 * kv_heads) * head_dim \
        + 2 * heads * head_dim * embed
    moe = 2 * embed * 16 + 3 * 2 * embed * 8 * 4 * 4 // 16
    attention = 16 * (16 + 4) * 4 * heads * head_dim // 16
    assert flops_bd.bd_moe_train_flops(
        16, 4, layers, embed, heads, kv_heads, head_dim, experts, vocab) \
        == 3 * (layers * (2 * (proj + moe) + attention) + 2 * embed * vocab)


def test_the_cell_in_rehearsal_reads_every_metric_it_lists():
    """The control flow of ``--workload sdar-30b-a3b-chat_s8192 --trace 1``
    at the twin's tiny sizes on 4 CPU devices: the check's six rows pass,
    and of the cell's metrics every one that needs no device trace is read,
    the two new ones among them."""
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
    assert len(checks) == 6 and all(ln.endswith(" ok") for ln in checks)
    for what in ("loss", "load-balance term",
                 "gradient norm after the exchange", "update norm",
                 "key and value gradients along the reference's",
                 "routers' outputs off the reference's on the same"):
        assert any(f"check: {what}" in ln for ln in checks), what
    read = [ln for ln in lines if "metrics read" in ln][0].split(": ")[-1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= listed and "flash_dq_ms" not in listed
    traced = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    assert set(read.split()) == listed - traced


def test_a_sample_is_a_data_token():
    """The job counts the L data tokens of a sequence, and its kernels'
    costs the 2 L rows and the mask's pairs."""
    import horovod_tpu as hvd
    import jax
    from benchmarks.jobs import gpt_bd_moe_dp

    with open(os.path.join(HERE, "data", "configs",
                           "sdar-30b-a3b-chat.json")) as f:
        config = json.load(f)
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    try:
        job = gpt_bd_moe_dp.Job(
            config, {"global_batch": 4, "seq_len": 128, "log_every": 2}, 0)
        assert job.samples_per_step == 4 * 128 and job.sample == "tok"
        tokens, targets, positions, weights = job.host_batches(2)[1]
        assert tokens.shape == positions.shape == (4, 256)
        assert targets.shape == weights.shape == (4, 128)
        shape = dict(heads=8, kv_heads=2, head_dim=16)
        assert job.kernel_costs["flash"]["ops"] == 2 * sum(
            cost(4, 128, 4, **shape)["ops"]
            for cost in (flops_bd.flash_forward_cost,
                         flops_bd.flash_backward_cost))
        # 4 x 256 rows, 4 experts a row, a quarter of them held: 3 passes
        # a layer through three 64 x 32 matrices an expert.
        assert job.kernel_costs["grouped_matmul"]["ops"] \
            == 2 * 3 * 3 * 2 * 1024 * 64 * 32
        assert job.flops_per_sample == flops_bd.bd_moe_train_flops(
            128, 4, 2, 64, vocab=256, experts=dict(
                router=16, width=32, top_k=4, held=4), **shape)
    finally:
        hvd.shutdown()

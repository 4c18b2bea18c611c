"""The looped cell's readers (``layer_metrics/loop_head_ms.py``,
``loop_exit_ms.py``, ``loop_block_calls.py``) against
``data/loop_trace.textproto``, whose operations, names and expected sums are
written out in the file, and against the program's own counter;
``flops_loop`` against a brute-force count and against ``flops.py`` at one
pass; and the ``ouro-2.6b_s4096`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks import flops, flops_loop
from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "ouro-2.6b_s4096"
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}
TRACED = {"loop_head_ms": 6.5, "loop_exit_ms": 3.5}
NEW = (*TRACED, "loop_block_calls")


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


def ctx_of(trace):
    return RunContext(
        job=types.SimpleNamespace(kernel_costs={"flash": {
            "match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$", "ops": 1e9,
            "bytes": 1e6}}), chips=1,
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=2)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


@pytest.mark.parametrize("metric", sorted(TRACED))
def test_scope_readers(metric, built, monkeypatch):
    path = built("loop_trace")
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    ctx = ctx_of(tr.read_xplane(path, SPANS_NS))
    assert reader(metric)(ctx) == pytest.approx(TRACED[metric])


def test_the_accepted_readers_find_their_scopes_inside_a_pass(
        built, monkeypatch):
    """``pass<t>`` lies around ``layer<i>``: a reader that looks for
    ``attn``, for a kernel's name or for what a checkpoint made again finds
    them as before."""
    path = built("loop_trace")
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    ctx = ctx_of(tr.read_xplane(path, SPANS_NS))
    assert sr.scope_of(
        "jit(step)/shard_map/transpose(jvp(pass2))/layer3/jvp(pass2)/layer3/"
        "checkpoint/rematted_computation/attn/dot_general") \
        == ["pass2", "layer3", "pass2", "layer3", "attn"]
    assert reader("flash_fwd_ms")(ctx) == pytest.approx(0.5)
    assert reader("flash_dkdv_ms")(ctx) == pytest.approx(1.0)
    assert reader("flash_ms")(ctx) == pytest.approx(1.5)
    assert reader("tok_remat_ms")(ctx) == pytest.approx(1.5)
    # The least time 1 ms over the kernels' 1.5 ms a step.
    assert reader("flash_roofline_pct")(ctx) == pytest.approx(100 / 1.5)


@pytest.mark.parametrize("name", ["sambay_trace", "window_trace"])
def test_a_program_without_the_scopes_reads_no_exit(built, monkeypatch, name):
    """The parent's programs, and a rehearsal's trace (no device plane):
    None, never an error."""
    path = built(name)
    monkeypatch.setattr(sr, "newest_xplane", lambda: path)
    assert reader("loop_exit_ms")(ctx_of(tr.read_xplane(path, SPANS_NS))) \
        is None
    for metric in TRACED:
        assert reader(metric)(ctx_of(tr.Trace({}, {}))) is None


def test_block_calls_are_the_counters_passes_times_layers(monkeypatch):
    import horovod_tpu as hvd

    def families(samples):
        return lambda: {"hvdtpu_spmd_loop_passes_total": {
            "type": "counter", "help": "", "samples": samples}}

    ctx = ctx_of(None)
    # Two traces of the one stack (the check's step and the timed step).
    monkeypatch.setattr(hvd, "metrics", families(
        [("", {"passes": "4", "layers": "6"}, 2.0)]))
    assert reader("loop_block_calls")(ctx) == 24.0
    # A program that counts no loop, as the parent commit's: nothing.
    monkeypatch.setattr(hvd, "metrics", lambda: {})
    assert reader("loop_block_calls")(ctx) is None
    # Two shapes of loop in one process are two numbers: nothing.
    monkeypatch.setattr(hvd, "metrics", families(
        [("", {"passes": "4", "layers": "6"}, 1.0),
         ("", {"passes": "3", "layers": "6"}, 1.0)]))
    assert reader("loop_block_calls")(ctx) is None


def test_each_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric in NEW:
        assert entries[metric]["workloads"] == [CELL]
        assert entries[metric]["moves"] == "tok_s_chip"
        assert entries[metric]["layer"] == "Looped stack"
        assert callable(reader(metric))
    assert entries["loop_block_calls"]["source"] == "program_counter"
    assert {entries[m]["source"] for m in TRACED} == {"device_trace"}


# ---- operations ------------------------------------------------------------

def brute_force_forward(seq, layers, passes, embed, heads, head_dim, mlp,
                        vocab) -> int:
    """Multiply-accumulates of one sequence's forward pass, product by
    product and token by token, times two."""
    macs = 0
    for _ in range(passes):
        for _ in range(layers):
            for token in range(seq):
                macs += 3 * embed * heads * head_dim        # q, k, v
                macs += 2 * heads * head_dim * (token + 1)  # scores, values
                macs += heads * head_dim * embed            # o
                macs += 3 * embed * mlp                     # gate, up, down
        macs += seq * (embed * vocab + embed)               # head, exit gate
    return 2 * macs


@pytest.mark.parametrize("passes", [1, 4])
def test_a_data_tokens_training_cost_against_a_brute_force(passes):
    shape = dict(seq=12, layers=3, passes=passes, embed=8, heads=2,
                 head_dim=4, mlp=20, vocab=32)
    got = flops_loop.loop_train_flops(
        12, 3, passes, 8, heads=2, kv_heads=2, head_dim=4, mlp=20, vocab=32)
    assert got * 12 == 3 * brute_force_forward(**shape)


def test_one_pass_is_flops_pys_decoder_and_the_third_matrix_and_the_gate():
    shape = dict(embed=2048, heads=16, kv_heads=16, head_dim=128, mlp=5632,
                 vocab=49152)
    assert flops_loop.loop_train_flops(4096, 6, 1, **shape) \
        == flops.gpt_train_flops(4096, 6, **shape) \
        + 3 * (6 * 2 * 2048 * 5632 + 2 * 2048)
    # Four passes are four times one.
    assert flops_loop.loop_train_flops(4096, 6, 4, **shape) \
        == 4 * flops_loop.loop_train_flops(4096, 6, 1, **shape)
    # The head is 22% of a pass at six layers, 3.6% at the model's 48.
    for layers, share in ((6, 0.219), (48, 0.034)):
        assert 2 * 2048 * 49152 / flops_loop.loop_pass_forward_flops(
            4096, layers, **shape) == pytest.approx(share, abs=2e-3)


# ---- the cell --------------------------------------------------------------

def test_the_cell_in_rehearsal_reads_every_metric_it_lists():
    """The control flow of ``--workload ouro-2.6b_s4096 --trace 1`` at the
    twin's tiny sizes on 4 CPU devices: the check's nine rows pass, and of
    the cell's metrics every one that needs no device trace is read,
    ``loop_block_calls`` among them."""
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
    assert len(checks) == 9 and all(ln.endswith(" ok") for ln in checks)
    for what in ("loss", "pass 1's mean cross-entropy",
                 "pass 4's mean cross-entropy", "entropy term",
                 "gradient norm after the exchange",
                 "exit gate's gradient norm", "update norm"):
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
    """The job counts the tokens of the batch, its operations and its
    kernels' costs every pass of them; it keeps the newest step's exit
    distribution."""
    import horovod_tpu as hvd
    import jax
    from benchmarks.jobs import gpt_loop_dp

    with open(os.path.join(HERE, "data", "configs", "ouro-2.6b.json")) as f:
        config = json.load(f)
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    try:
        job = gpt_loop_dp.Job(
            config, {"global_batch": 2, "seq_len": 128, "log_every": 2}, 0)
        assert job.samples_per_step == 2 * 128 and job.sample == "tok"
        shape = dict(heads=4, kv_heads=4, head_dim=16)
        assert job.flops_per_sample == flops_loop.loop_train_flops(
            128, 2, 4, 64, mlp=160, vocab=256, **shape)
        # One forward and one backward a block application, 4 x 2 of them.
        assert job.kernel_costs["flash"]["ops"] == 8 * sum(
            cost(2, 128, **shape)["ops"]
            for cost in (flops.flash_forward_cost,
                         flops.flash_backward_cost))
        assert job.mean_exit_step() is None
        state = job.state()
        *state, loss = job.step(*state, hvd.shard_batch(
            job.host_batches(1)[0]))
        assert len(state) == 2 and loss.shape == ()
        # A gate near a half: p = (1/2, 1/4, 1/8, 1/8), the mean 1.875.
        assert job.mean_exit_step() == pytest.approx(1.875, abs=0.2)
    finally:
        hvd.shutdown()

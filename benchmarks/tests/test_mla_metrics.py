"""The latent attention layer's readers (``layer_metrics/mla_*.py``) against
``data/mla_trace.textproto``, whose operations, names and expected sums are
written out in the file; ``flops_mla.py`` against hand counts; and the
``moonlight-16b-a3b_s8192`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks import flops, flops_mla
from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "moonlight-16b-a3b_s8192"
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}
NEW = ("mla_ms", "mla_proj_ms", "mla_rope_ms")


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
    costs = {"flash": {"match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
                       "ops": 1.8e9, "bytes": 1e6}}
    return RunContext(
        job=types.SimpleNamespace(kernel_costs=costs),
        chips=1, peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=2)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


def test_an_mla_layer_is_told_by_its_own_scopes(built, monkeypatch):
    trace_file = built("mla_trace")
    monkeypatch.setattr(sr, "newest_xplane", lambda: trace_file)
    ctx = ctx_of(tr.read_xplane(trace_file, SPANS_NS))
    # Everything under layer0's attn, its norm and its flash kernels
    # included; layer1's attn holds no mla scope and is left out.
    assert reader("mla_ms")(ctx) == pytest.approx(9.0)
    assert reader("mla_proj_ms")(ctx) == pytest.approx(3.0)
    assert reader("mla_rope_ms")(ctx) == pytest.approx(1.5)
    # The accepted readers see the same file: the three flash kernels by
    # name, both layers'; a step's 1.8e9 operations at 1e12 a second are
    # 1.8 ms of the 6 a step they took.
    assert reader("flash_ms")(ctx) == pytest.approx(6.0)
    assert reader("flash_roofline_pct")(ctx) == pytest.approx(30.0)
    assert reader("moe_route_ms")(ctx) == pytest.approx(1.0)
    # No CCA layer in it.
    assert reader("cca_ms")(ctx) is None


def test_readers_return_nothing_where_the_program_has_no_mla_layer(
        built, monkeypatch):
    """A program without the scopes (the parent's: every ``attn`` is a plain
    attention or a CCA layer's), a rehearsal's trace (no device plane):
    None, never an error."""
    dense = os.path.join(HERE, "data", "scoped_trace.xplane.pb")
    monkeypatch.setattr(sr, "newest_xplane", lambda: dense)
    with_device = ctx_of(tr.read_xplane(dense, SPANS_NS))
    without = ctx_of(tr.Trace({}, {}))
    for metric in NEW:
        assert reader(metric)(with_device) is None
        assert reader(metric)(without) is None
    for name in ("window_trace", "cca_trace"):
        plain = built(name)
        monkeypatch.setattr(sr, "newest_xplane", lambda plain=plain: plain)
        ctx = ctx_of(tr.read_xplane(plain, SPANS_NS))
        for metric in NEW:
            assert reader(metric)(ctx) is None, (name, metric)


def test_operations_and_bytes_by_hand():
    # A small shape: 1 sequence of 4 tokens (10 kept pairs), 2 heads, a
    # 3-wide query/key head beside a 2-wide value head, bfloat16.
    fwd = flops_mla.flash_forward_cost(1, 4, 2, 2, 3, 2)
    # Scores over 3 and values over 2, two operations each, 10 pairs a head;
    # a token's q, k (2 heads of 3 each) and v, o (2 heads of 2 each) are 20
    # elements of 2 bytes, over 4 tokens; and 8 float32 log-sum-exps.
    assert fwd == {"ops": 2 * (3 + 2) * 20, "bytes": 4 * 20 * 2 + 8 * 4}
    assert fwd == {"ops": 200, "bytes": 160 + 32}
    bwd = flops_mla.flash_backward_cost(1, 4, 2, 2, 3, 2)
    # Scores again, dQ, dK over 3; dP, dV over 2. q, dQ, k, dK (3 wide) and
    # v, dV, o, dO (2 wide) at 2 heads each.
    assert bwd == {"ops": 2 * (3 * 3 + 2 * 2) * 20,
                   "bytes": 4 * 40 * 2 + 8 * 4}
    assert bwd == {"ops": 520, "bytes": 320 + 32}
    # Equal widths are flops.py's own, grouped-query heads too.
    for shape in ((2, 4096, 24, 2, 128), (4, 512, 16, 16, 64)):
        assert flops_mla.flash_forward_cost(*shape, shape[-1]) \
            == flops.flash_forward_cost(*shape)
        assert flops_mla.flash_backward_cost(*shape, shape[-1]) \
            == flops.flash_backward_cost(*shape)
    # The cell's mixer: 16 heads of 128 + 64 beside 128, a latent of 512,
    # 2048 wide. One token forward, in operations:
    mla = dict(heads=16, nope_dim=128, rope_dim=64, value_dim=128,
               kv_rank=512)
    proj = 2 * 2048 * 3072 + 2 * 2048 * 576 + 2 * 512 * 4096 \
        + 2 * 2048 * 2048
    attn = (8192 * 8193 // 2) * 2 * (192 + 128) * 16 // 8192
    assert proj == 27_525_120 and attn == 41_948_160
    assert flops_mla.mla_mixer_forward_flops(8192, 2048, **mla) \
        == proj + attn
    # A token's 6 experts of three 2048 x 1408 matrices are held here with
    # probability 8 / 64 each; the shared pair is 2816 wide; the router 64;
    # the dense layer 11264; an eighth of the 163,840-row head.
    block = 2 * 2048 * 64 + 3 * 2 * 2048 * 1408 * 6 * 8 // 64 \
        + 3 * 2 * 2048 * 2816
    dense = 3 * 2 * 2048 * 11264
    head = 2 * 2048 * 20480
    want = 3 * (6 * (proj + attn) + dense + 5 * block + head)
    got = flops_mla.mla_moe_train_flops(
        8192, 6, 1, 2048, mla, mlp=11264, vocab=20480,
        experts=dict(router=64, width=1408, top_k=6, held=8,
                     shared_width=2816))
    assert got == want
    assert got == pytest.approx(2.635e9, rel=1e-3)
    # What the cell's kernels are asked for in a step: six layers, one
    # forward and one backward each, over 2 sequences: 14.8 TFLOP and
    # 1.2 GB, compute-bound on a v5e, 75 ms.
    parts = [f(2, 8192, 16, 16, 192, 128) for f in
             (flops_mla.flash_forward_cost, flops_mla.flash_backward_cost)]
    step = {key: 6 * sum(p[key] for p in parts) for key in ("ops", "bytes")}
    assert step["ops"] == 6 * 2 * 16 * (8192 * 8193 // 2) * (640 + 1664)
    seconds, bound = flops.roofline_seconds(
        step, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and seconds == pytest.approx(75.4e-3, rel=1e-2)


def test_the_cell_in_rehearsal_reads_every_metric_it_lists():
    """The control flow of ``--workload moonlight-16b-a3b_s8192 --trace 1``
    at the twin's tiny sizes on 4 CPU devices: the check's seven rows pass,
    and of the cell's metrics every one that needs no device trace is read
    (a CPU run has no device plane: the trace readers are held to the
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
    assert len(checks) == 7 and all(ln.endswith(" ok") for ln in checks)
    for what in ("loss", "gradient norm after the exchange", "update norm",
                 "token-expert choices shared with the reference",
                 "latent attention's key and value gradients along",
                 "selection biases' update weighed by the experts' load",
                 "routers' outputs off the reference's on the same"):
        assert any(f"check: {what}" in ln for ln in checks), what
    read = [ln for ln in lines if "metrics read" in ln][0].split(": ")[-1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | {"moe_windows_per_step", "flash_roofline_pct"} \
        <= listed
    assert not any(name.startswith("flash_window") for name in listed)
    traced = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    # The window's drift needs three segments, which a loaded CPU may not
    # make of one second.
    assert set(read.split()) | {"tok_window_drift_pct"} == listed - traced


@pytest.mark.parametrize("key, value, what", [
    ("n_group", 2, "n_group=2"), ("topk_group", 2, "topk_group=2"),
    ("q_lora_rank", 1536, "q_lora_rank=1536"),
    ("rope_scaling", {"type": "yarn", "factor": 40}, "rope_scaling="),
])
def test_the_job_refuses_what_it_does_not_implement_by_name(key, value,
                                                            what):
    import horovod_tpu as hvd
    import jax
    from benchmarks.jobs import gpt_mla_moe_dp

    with open(os.path.join(HERE, "data", "configs",
                           "moonlight-16b-a3b.json")) as f:
        config = dict(json.load(f), **{key: value})
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    try:
        with pytest.raises(ValueError, match=what):
            gpt_mla_moe_dp.Job(
                config, {"global_batch": 4, "seq_len": 128, "log_every": 2},
                0)
    finally:
        hvd.shutdown()

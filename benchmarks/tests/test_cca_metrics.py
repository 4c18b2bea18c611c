"""The CCA layer's readers (``layer_metrics/cca_*.py``, ``router_mlp_ms``)
against ``data/cca_trace.textproto``, whose operations, names and expected
sums are written out in the file; ``flops_cca.py`` against hand counts; and
the ``zaya1-8b_s4096`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks import flops, flops_cca
from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "zaya1-8b_s4096"
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}
NEW = ("cca_ms", "cca_mix_ms", "cca_mix_roofline_pct", "router_mlp_ms")


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


@pytest.fixture(scope="module")
def trace_file(built):
    return built("cca_trace")


def ctx_of(trace, router_kind="mlp", **job):
    costs = {"flash": {"match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
                       "ops": 1.8e9, "bytes": 1e6}}
    return RunContext(
        job=types.SimpleNamespace(
            kernel_costs=costs,
            cfg=types.SimpleNamespace(router_kind=router_kind), **job),
        chips=1, peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=2)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


def test_a_cca_layer_is_told_by_its_own_scopes(trace_file, monkeypatch):
    monkeypatch.setattr(sr, "newest_xplane", lambda: trace_file)
    ctx = ctx_of(tr.read_xplane(trace_file, SPANS_NS),
                 cca_mix_cost={"ops": 1e9, "bytes": 5e5})
    # Everything under layer0's attn, its flash kernel and residual scaling
    # included; layer1's attn holds no cca scope and is left out.
    assert reader("cca_ms")(ctx) == pytest.approx(8.5)
    assert reader("cca_mix_ms")(ctx) == pytest.approx(5.0)
    # 1e9 operations at 1e12 a second (the bytes take half of that): 1 ms of
    # the mix's 5.
    assert reader("cca_mix_roofline_pct")(ctx) == pytest.approx(20.0)
    # Bytes that take longer than the operations set the least time.
    ctx.job.cca_mix_cost = {"ops": 1e9, "bytes": 2e6}
    assert reader("cca_mix_roofline_pct")(ctx) == pytest.approx(40.0)
    assert reader("router_mlp_ms")(ctx) == pytest.approx(1.0)
    # The accepted readers see the same file: both flash kernels by name.
    assert reader("flash_ms")(ctx) == pytest.approx(4.5)
    assert reader("moe_route_ms")(ctx) == pytest.approx(1.5)
    # A job that says nothing of the mix's cost: the time alone.
    del ctx.job.cca_mix_cost
    assert reader("cca_mix_ms")(ctx) == pytest.approx(5.0)
    assert reader("cca_mix_roofline_pct")(ctx) is None
    # A linear router's time is moe_route_ms's, not this metric's.
    assert reader("router_mlp_ms")(ctx_of(ctx.trace, "linear")) is None


def test_readers_return_nothing_where_the_program_has_no_cca_layer(
        built, monkeypatch):
    """A program without the scopes (the parent's: every ``attn`` is a plain
    attention layer's, no job has a ``cfg.router_kind``), a rehearsal's
    trace (no device plane): None, never an error."""
    dense = os.path.join(HERE, "data", "scoped_trace.xplane.pb")
    monkeypatch.setattr(sr, "newest_xplane", lambda: dense)
    with_device = ctx_of(tr.read_xplane(dense, SPANS_NS), None)
    del with_device.job.cfg         # the parent's jobs have one without the
    without = ctx_of(tr.Trace({}, {}))          # field; some have none
    for metric in NEW:
        assert reader(metric)(with_device) is None
        assert reader(metric)(without) is None
    # attn and attn_window layers, flash kernels and all: no CCA layer.
    plain = built("window_trace")
    monkeypatch.setattr(sr, "newest_xplane", lambda: plain)
    ctx = ctx_of(tr.read_xplane(plain, SPANS_NS))
    assert reader("cca_ms")(ctx) is None
    assert reader("cca_mix_ms")(ctx) is None


def test_operations_and_bytes_by_hand():
    # The cell's mixer: 8 query and 2 key/value heads of 128, 2048 wide, two
    # taps a stage. One token forward, in operations:
    shape = dict(heads=8, kv_heads=2, head_dim=128)
    assert flops_cca.latent(**shape) == 1280
    conv = 2 * 2 * 1280 + 2 * 2 * 10 * 128 * 128
    assert conv == 5_120 + 655_360
    assert flops_cca.conv_forward_flops(taps=(2, 2), **shape) == conv
    proj = 2 * 2048 * (1024 + 256 + 256) + 2 * 1024 * 2048
    attn = (4096 * 4097 // 2) * 4 * 8 * 128 // 4096
    assert proj == 10_485_760 and attn == 8_390_656
    mixer = flops_cca.cca_mixer_forward_flops(4096, 2048, taps=(2, 2),
                                              **shape)
    assert mixer == proj + conv + attn == 19_536_896
    # The router: 2048 -> 256, two 256 x 256 layers, 256 -> 16.
    router = 2 * 2048 * 256 + 2 * 2 * 256 * 256 + 2 * 256 * 16
    assert router == 1_318_912
    assert flops_cca.router_mlp_forward_flops(2048, 256, 16) == router
    # A token's one expert of three 2048 x 2048 matrices is held here with
    # probability 8 / 16; an eighth of the tied 262,272-row head.
    expert = 3 * 2 * 2048 * 2048 * 1 * 8 // 16
    head = 2 * 2048 * 32784
    want = 3 * (5 * (mixer + router + expert) + head)
    got = flops_cca.cca_moe_train_flops(
        4096, 5, 2048, taps=(2, 2), router_dim=256, vocab=32784,
        experts=dict(router=16, width=2048, top_k=1, held=8), **shape)
    assert got == want
    assert got / 3 == pytest.approx(301.4e6, rel=1e-3)
    # One pass of the mix over the cell's 16,384 tokens: u read (1280
    # channels), q, k and v written (1024 + 256 + 256), in bfloat16; the
    # grouped stage's 655,360 operations a token.
    one = flops_cca.mix_pass_cost(16384, taps=(2, 2), **shape)
    assert one["bytes"] == 16384 * 2 * (1280 + 1536) == 92_274_688
    assert one["ops"] == 16384 * 655_360 == 10_737_418_240
    # Fifteen passes a step (five layers, three passes each): memory-bound
    # on a v5e, 1.69 ms.
    seconds, bound = flops.roofline_seconds(
        {key: 15 * one[key] for key in one},
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "memory" and seconds == pytest.approx(1.690e-3, rel=1e-3)


def test_the_cell_in_rehearsal_reads_every_metric_it_lists():
    """The control flow of ``--workload zaya1-8b_s4096 --trace 1`` at the
    twin's tiny sizes on 4 CPU devices: the check's seven rows pass, and of
    the cell's metrics every one that needs no device trace is read (a CPU
    run has no device plane: the trace readers are held to the fixture
    above)."""
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
                 "selection biases' update weighed by the experts' load",
                 "routers' outputs off the reference's on the same",
                 "gradient norm of the convolutions' and temperatures'"):
        assert any(f"check: {what}" in ln for ln in checks), what
    read = [ln for ln in lines if "metrics read" in ln][0].split(": ")[-1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert set(NEW) <= listed and "moe_windows_per_step" not in listed
    traced = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    # The window's drift needs three segments, which a loaded CPU may not
    # make of one second.
    assert set(read.split()) | {"tok_window_drift_pct"} == listed - traced


def _twin_job(seed=0):
    """The cell's job at the twin's sizes on one CPU device."""
    import horovod_tpu as hvd
    import jax
    from benchmarks.jobs import gpt_cca_moe_dp

    with open(os.path.join(HERE, "data", "configs", "zaya1-8b.json")) as f:
        config = json.load(f)
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    return gpt_cca_moe_dp.Job(
        config, {"global_batch": 4, "seq_len": 128, "log_every": 2}, seed)


def test_the_router_row_sees_a_product_in_bfloat16():
    """The sixth row: the program's routers' outputs against the reference's
    chain of routers on the activations the program's read. Exact outputs
    read nothing; routers whose down-projection was rounded to bfloat16
    (what one pass of the MXU does to it) read over the limit, and so do
    routers that were handed no state."""
    import horovod_tpu as hvd
    import jax.numpy as jnp
    from benchmarks.jobs import gpt_cca_moe_dp as jobs

    try:
        job = _twin_job()
        params = job._params
        routers = [p["moe"]["router"] for p in params["layers"]]
        tokens, eps = 256, job.cfg.norm_eps
        inputs = jnp.cos(jnp.arange(tokens * len(routers) * 64,
                                    dtype=jnp.float32)).reshape(
            tokens, len(routers), 64).astype(jnp.bfloat16)

        def outputs(round_to, carried=True):
            out, state = [], None
            for i, r in enumerate(routers):
                r = dict(r, down=r["down"].astype(round_to).astype(
                    jnp.float32))
                got, state = jobs.reference.router(
                    inputs[:, i], r, state if carried else None, eps)
                out.append(got)
            return jnp.stack(out, axis=1)

        assert jobs._routers_off(params, inputs, outputs(jnp.float32),
                                 eps) < 1e-6
        assert jobs._routers_off(params, inputs, outputs(jnp.bfloat16),
                                 eps) > 10 * jobs.ROUTER_RTOL
        assert jobs._routers_off(params, inputs,
                                 outputs(jnp.float32, carried=False),
                                 eps) > 100 * jobs.ROUTER_RTOL
    finally:
        hvd.shutdown()

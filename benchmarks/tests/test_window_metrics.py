"""The window attention layers' readers (``layer_metrics/flash_window_*.py``)
against ``data/window_trace.textproto``, whose operations, names and expected
sums are written out in the file; ``flops_window.py`` against hand counts;
and the ``trinity-mini_s8192`` cell in rehearsal."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks import flops, flops_window, program_counters
from benchmarks import scope_reduce as sr
from benchmarks import trace_reduce as tr
from benchmarks.context import RunContext

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
START_NS = 1_700_000_000 * 10**9
MS = 10**6
SPANS_NS = {"dispatch": [(START_NS + 10 * MS, START_NS + 11 * MS)],
            "fence": [(START_NS + 11 * MS, START_NS + 50 * MS)]}
NEW = ("flash_window_ms", "flash_window_roofline_pct",
       "flash_window_tiles_kept_pct")


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "data", "window_trace.textproto")) as f:
        built = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("window") / "window_trace.xplane.pb"
    path.write_bytes(built)
    return str(path)


def ctx_of(trace, **job):
    costs = {"flash": {"match": r"^hvd_flash_(fwd|dkdv|dq)(\.\d+)?$",
                       "ops": 4.6e9, "bytes": 1e6}}
    return RunContext(
        job=types.SimpleNamespace(kernel_costs=costs, seq=8192, **job),
        chips=1, peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        throughput=1.0, spans={}, first_step_s=1.0, step_compiles=1,
        memory_peak_bytes=0, trace=trace, steps_traced=2)


def reader(metric):
    return importlib.import_module(f"benchmarks.layer_metrics.{metric}").read


def test_window_kernels_are_told_from_full_ones_by_scope(trace_file,
                                                         monkeypatch):
    monkeypatch.setattr(sr, "newest_xplane", lambda: trace_file)
    ctx = ctx_of(tr.read_xplane(trace_file, SPANS_NS),
                 window_flash_cost={"ops": 1e9, "bytes": 1e6})
    assert reader("flash_window_ms")(ctx) == pytest.approx(4.0)
    # Every flash kernel, by name, as the accepted reader sums them; the
    # fusion under attn_window/post_norm is no kernel and in neither.
    assert reader("flash_ms")(ctx) == pytest.approx(11.5)
    # 1e9 operations at 1e12 a second: 1 ms of the window kernels' 4.
    assert reader("flash_window_roofline_pct")(ctx) == pytest.approx(25.0)
    # 4.6e9 for all of them: 4.6 ms of 11.5.
    assert reader("flash_roofline_pct")(ctx) == pytest.approx(40.0)
    # A job that says nothing of its window layers' cost: the time alone.
    del ctx.job.window_flash_cost
    assert reader("flash_window_ms")(ctx) == pytest.approx(4.0)
    assert reader("flash_window_roofline_pct")(ctx) is None


def test_readers_return_nothing_where_the_program_has_no_window_layer(
        monkeypatch):
    """A program without the scope (the parent's: every layer is ``attn``), a
    rehearsal's trace (no device plane), a program without the counter:
    None, never an error."""
    dense = os.path.join(HERE, "data", "scoped_trace.xplane.pb")
    monkeypatch.setattr(sr, "newest_xplane", lambda: dense)
    monkeypatch.setattr(program_counters, "value", lambda *a, **k: None)
    with_device = ctx_of(tr.read_xplane(dense, SPANS_NS))
    without = ctx_of(tr.Trace({}, {}))
    for metric in NEW:
        assert reader(metric)(with_device) is None
        assert reader(metric)(without) is None


def test_tiles_kept_is_the_counters_kept_over_the_triangles(monkeypatch):
    asked = []

    def value(family, **labels):
        asked.append((family, labels))
        return {"kept": 3 * 4 * 21.0, "skipped_band": 3 * 4 * 15.0}[
            labels["tiles"]]

    monkeypatch.setattr(program_counters, "value", value)
    assert reader("flash_window_tiles_kept_pct")(ctx_of(None)) \
        == pytest.approx(100 * 21 / 36)
    assert asked[0] == ("hvdtpu_spmd_flash_tiles_total",
                        {"mask": "window", "tiles": "kept", "seq": "8192"})
    # The step's padded length, not another program's (the check's).
    monkeypatch.setattr(program_counters, "value", lambda family, **labels: {
        "8192": None, "4096": 9.0}[labels["seq"]])
    assert reader("flash_window_tiles_kept_pct")(ctx_of(None)) is None
    # A band no tile lies wholly outside of: everything the triangle has.
    monkeypatch.setattr(program_counters, "value", lambda family, **labels:
                        10.0 if labels["tiles"] == "kept" else None)
    assert reader("flash_window_tiles_kept_pct")(ctx_of(None)) == 100.0


def test_pairs_tiles_and_costs_by_hand():
    # Eight tokens, a window of three: 1 + 2 + 3 x 6 pairs.
    assert flops_window.band_pairs(8, 3) == 21
    assert flops_window.band_pairs(8, None) == flops_window.band_pairs(8, 9) \
        == 36
    # The cell's: W (W + 1) / 2 + (S - W) W a head a sequence.
    pairs = 2048 * 2049 // 2 + (8192 - 2048) * 2048
    assert flops_window.band_pairs(8192, 2048) == pairs == 14_681_088
    assert 100 * pairs / flops.causal_pairs(8192) == pytest.approx(43.745,
                                                                   rel=1e-4)
    # 1024-wide tiles: row i keeps blocks i - 2 to i, 1 + 2 + 3 x 6.
    assert flops_window.band_tiles(8192, 2048, 1024, 1024) == (21, 36)
    assert flops_window.band_tiles(8192, None, 1024, 1024) == (36, 36)
    # A block's first query sees back window - 1 keys: one more than a
    # tile's width reaches the last key of the block before the last.
    assert flops_window.band_tiles(1024, 130, 128, 128) == (8 + 7 + 6, 36)
    assert flops_window.band_tiles(1024, 129, 128, 128) == (8 + 7, 36)
    assert flops_window.band_tiles(1024, 1, 128, 128) == (8, 36)
    # Forward: two products a kept pair; backward five; bytes the mask does
    # not change.
    shape = dict(heads=32, kv_heads=4, head_dim=128)
    fwd = flops_window.flash_forward_cost(2, 8192, window=2048, **shape)
    bwd = flops_window.flash_backward_cost(2, 8192, window=2048, **shape)
    assert fwd["ops"] == 4 * 128 * 2 * 32 * pairs
    assert bwd["ops"] == 10 * 128 * 2 * 32 * pairs
    full = flops.flash_forward_cost(2, 8192, **shape)
    assert fwd["bytes"] == full["bytes"]
    assert flops_window.flash_forward_cost(2, 8192, **shape) == full
    assert flops_window.flash_backward_cost(2, 8192, **shape) \
        == flops.flash_backward_cost(2, 8192, **shape)
    # The cell's four window layers, forward and backward, at the MXU's peak.
    seconds, bound = flops.roofline_seconds(
        {"ops": 4 * (fwd["ops"] + bwd["ops"]),
         "bytes": 4 * (fwd["bytes"] + bwd["bytes"])},
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute" and seconds == pytest.approx(34.19e-3, rel=1e-3)


def test_a_tokens_training_cost_by_hand():
    # One token of the cell forward, in operations: attention's projections
    # (q and gate 2 x 4096, k and v 2 x 512, o 4096 columns or rows of 2048),
    # the band's or the triangle's keys at 4 x 32 x 128 a key, the dense
    # layer's three 2048 x 6144 matrices, an expert layer's router, one
    # routed expert a token on average (8 of 128, 16 held) and the shared
    # one, the 25,024-wide head.
    proj = 2 * 2048 * (2 * 4096 + 2 * 512) + 2 * 4096 * 2048
    window = 14_681_088 * 4 * 32 * 128 // 8192
    full = flops.causal_pairs(8192) * 4 * 32 * 128 // 8192
    dense = 3 * 2 * 2048 * 6144
    block = 2 * 2048 * 128 + 3 * 2 * 2048 * 1024 * 8 * 16 // 128 \
        + 3 * 2 * 2048 * 1024
    head = 2 * 2048 * 25024
    want = 3 * (5 * proj + 4 * window + full + dense + 4 * block + head)
    got = flops_window.window_moe_train_flops(
        8192, (2048, 2048, None, 2048, 2048), 1, 2048, heads=32, kv_heads=4,
        head_dim=128, mlp=6144, vocab=25024, experts=dict(
            router=128, width=1024, top_k=8, held=16, shared_width=1024))
    assert got == want
    assert got / 3 == pytest.approx(738e6, rel=1e-3)
    # Attention's products are a quarter of it; the band saves a sixth of
    # what the step would do under full attention throughout.
    assert (4 * window + full) / (got / 3) == pytest.approx(0.25, abs=0.005)
    assert 4 * (full - window) / (got / 3 + 4 * (full - window)) \
        == pytest.approx(1 / 6, abs=0.005)


def test_the_cell_in_rehearsal_reads_every_metric_it_lists():
    """The control flow of ``--workload trinity-mini_s8192 --trace 1`` at the
    twin's tiny sizes on 4 CPU devices: the check's seven rows pass, and of
    the cell's metrics every one that needs no device trace is read (a CPU
    run has no device plane: the trace readers are held to the fixture
    above)."""
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "trinity-mini_s8192", "--seed", "2147483999",
         "--seconds", "1", "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    checks = [ln for ln in lines if "check: " in ln]
    assert len(checks) == 7 and all(ln.endswith(" ok") for ln in checks)
    for what in ("loss", "gradient norm after the exchange", "update norm",
                 "token-expert choices shared with the reference",
                 "window layers' key and value gradients along the",
                 "selection biases' update weighed by the experts' load",
                 "routers' outputs off the reference's on the same"):
        assert any(f"check: {what}" in ln for ln in checks), what
    read = [ln for ln in lines if "metrics read" in ln][0].split(": ")[-1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if "trinity-mini_s8192" in m.get("workloads",
                                               ["trinity-mini_s8192"])}
    assert set(NEW) <= listed
    traced = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    assert set(read.split()) == listed - traced
    assert "flash_window_tiles_kept_pct" in read.split()


def _twin_job(seed=0):
    """The cell's job at the twin's sizes on one CPU device."""
    import horovod_tpu as hvd
    import jax
    from benchmarks.jobs import gpt_window_moe_dp

    with open(os.path.join(HERE, "data", "configs",
                           "trinity-mini.json")) as f:
        config = json.load(f)
    hvd.shutdown()
    hvd.init(devices=jax.devices()[:1])
    return gpt_window_moe_dp.Job(
        config, {"global_batch": 4, "seq_len": 128, "log_every": 2}, seed)


def test_the_router_row_sees_a_product_in_bfloat16():
    """The seventh row: the program's routers' outputs against the
    reference's on the activations the program's routers read. Exact
    outputs read nothing; a router whose matrix was rounded to bfloat16
    (what one pass of the MXU does to it) reads over the limit."""
    import horovod_tpu as hvd
    import jax.numpy as jnp
    from benchmarks.jobs import gpt_window_moe_dp as jobs

    try:
        job = _twin_job()
        params = job._params
        routers = [p["moe"]["router"] for p in params["layers"]
                   if "moe" in p]
        tokens = 256
        inputs = jnp.cos(jnp.arange(tokens * len(routers) * 64,
                                    dtype=jnp.float32)).reshape(
            tokens, len(routers), 64).astype(jnp.bfloat16)

        def outputs(round_to):
            return jnp.stack([jobs.reference.router_logits(
                inputs[:, i], r.astype(round_to).astype(jnp.float32))
                for i, r in enumerate(routers)], axis=1)

        assert jobs._routers_off(params, inputs,
                                 outputs(jnp.float32)) < 1e-6
        assert jobs._routers_off(params, inputs, outputs(jnp.bfloat16)) \
            > 10 * jobs.ROUTER_RTOL
    finally:
        hvd.shutdown()

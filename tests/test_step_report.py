"""The compiled step accounts for itself (ISSUE 33): the reducer of an
optimized HLO's text on a canned module, ``hvd.compiled_step_report`` on a real
``run_step`` function over the virtual CPU mesh (the executable that ran, from
JAX's caches; the gauges behind ``hvd.metrics()`` only once asked), and the
``windows`` / ``window`` scopes a device trace counts an expert layer's windows
by. And places its Mosaic kernels (ISSUE 51): the pass of an ``op_name``, and
``kernel_calls`` on text cut from three cells' compiled steps (``data/hlo/``:
the lines a placement reads, the kernels' bodies and layout attributes taken
out), where XLA names the grouped matmuls itself."""

import collections
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import hlo_report
from horovod_tpu.observability import parse_prometheus_text, sample_value
from horovod_tpu.parallel.moe import moe_layer

# What XLA prints for a TPU step, cut down by hand: a fused computation, a
# loop's body with a Mosaic kernel in it, an asynchronous all-reduce, a
# parameter copied and one prefetched, the rematerialisation pass's clones
# (a fusion with the program's op_name, its tuple's reads, a compressed copy
# without metadata) beside JAX's own "remat2", which is no clone.
HLO = '''HloModule jit__train_step, is_scheduled=true

%fused_computation.81.clone (param_0.1: bf16[8,4]) -> bf16[8,4] {
  %param_0.1 = bf16[8,4]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %dot.5.clone = bf16[8,4]{1,0:T(8,128)(2,1)} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(_train_step)/jvp(head)/dot_general"}
}

%body.3 (arg.1: (s32[], f32[16,4])) -> (s32[], f32[16,4]) {
  %arg.1 = (s32[]{:T(128)}, f32[16,4]{1,0:T(8,128)}) parameter(0)
  %get-tuple-element.7 = f32[16,4]{1,0:T(8,128)} get-tuple-element(%arg.1), index=1
  %copy.9 = f32[16,4]{0,1:T(8,128)} copy(%get-tuple-element.7)
  %ragged-dot-none.2 = f32[16,4]{1,0:T(8,128)} custom-call(%copy.9), custom_call_target="tpu_custom_call", metadata={op_name="jit(_train_step)/jvp(layer0)/moe/windows/while/body/window/jit(_window)/ragged-dot-none"}
  %hvd_flash_fwd.3 = f32[16,4]{1,0:T(8,128)} custom-call(%ragged-dot-none.2), custom_call_target="tpu_custom_call", backend_config={"body":"TUzvUg"}
  %custom-call.4 = f32[16,4]{1,0:T(8,128)S(1)} custom-call(), custom_call_target="AllocateBuffer"
  ROOT %tuple.2 = (s32[]{:T(128)}, f32[16,4]{1,0:T(8,128)}) tuple(%get-tuple-element.7, %hvd_flash_fwd.3)
}

ENTRY %main.9 (params__w.1: f32[16,4], params__b.1: f32[4], data.1: bf16[8,4]) -> f32[16,4] {
  %params__w.1 = f32[16,4]{1,0:T(8,128)} parameter(0), metadata={op_name="params[\\'w\\']"}
  %params__b.1 = f32[4]{0:T(128)} parameter(1)
  %data.1 = bf16[8,4]{1,0:T(8,128)(2,1)} parameter(2)
  %copy.12 = f32[16,4]{0,1:T(8,128)S(1)} copy(%params__w.1), metadata={op_name="jit(_train_step)/transpose(jvp(layer0))/jvp(layer0)/remat2"}
  %copy-start.3 = (f32[4]{0:T(128)S(1)}, f32[4]{0:T(128)}, u32[]{:S(2)}) copy-start(%params__b.1)
  %copy-done.3 = f32[4]{0:T(128)S(1)} copy-done(%copy-start.3)
  %remat2.239 = bf16[8,4]{1,0:T(8,128)(2,1)} convert(%data.1), metadata={op_name="jit(_train_step)/jvp(layer1)/ssm/reduce_sum"}
  %fusion.11 = (f32[8]{0:T(128)}, bf16[8,4]{1,0:T(8,128)(2,1)}) fusion(%data.1), kind=kOutput, calls=%fused_computation.81.clone, metadata={op_name="jit(_train_step)/jvp(head)/bse,ve->bsv/dot_general" stack_frame_id=3}
  %all-reduce-start.1 = f32[16,4]{1,0:T(8,128)} all-reduce-start(%copy.12), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add.1
  %all-reduce-done.1 = f32[16,4]{1,0:T(8,128)} all-reduce-done(%all-reduce-start.1)
  %all-gather.2 = f32[64,4]{1,0:T(8,128)} all-gather(%all-reduce-done.1), dimensions={0}
  %tuple.5 = (s32[]{:T(128)}, f32[16,4]{1,0:T(8,128)}) tuple(%all-reduce-done.1, %all-reduce-done.1)
  %while.64 = (s32[]{:T(128)}, f32[16,4]{1,0:T(8,128)}) while(%tuple.5), condition=%cond.3, body=%body.3
  %fusion.11.remat = (f32[8]{0:T(128)}, bf16[8,4]{1,0:T(8,128)(2,1)}) fusion(%data.1), kind=kOutput, calls=%fused_computation.81.clone, metadata={op_name="jit(_train_step)/jvp(head)/bse,ve->bsv/dot_general" stack_frame_id=3}, backend_config={"flag_configs":[]}
  %gte.remat = f32[8]{0:T(128)} get-tuple-element(%fusion.11.remat), index=0
  %gte.remat.1 = bf16[8,4]{1,0:T(8,128)(2,1)} get-tuple-element(%fusion.11.remat), index=1
  %convert.7.remat2 = pred[8,4]{1,0:T(8,128)(4,1)} convert(%gte.remat.1), metadata={op_name="jit(_train_step)/jvp(embed)/convert_element_type"}
  %reduce_precision.24.remat_compressed = bf16[64,4]{1,0:T(8,128)(2,1)} copy(%gte.remat.1)
  ROOT %get-tuple-element.9 = f32[16,4]{1,0:T(8,128)} get-tuple-element(%while.64), index=1
}
'''


def test_the_reducer_counts_what_the_compiler_added():
    report = hlo_report.reduce_hlo(HLO.splitlines())
    assert report["rematerialized"] == [
        {"name": "fusion.11.remat", "opcode": "fusion",
         "bytes": 8 * 4 + 8 * 4 * 2,
         "op_name": "jit(_train_step)/jvp(head)/bse,ve->bsv/dot_general"},
        {"name": "convert.7.remat2", "opcode": "convert", "bytes": 8 * 4,
         "op_name": "jit(_train_step)/jvp(embed)/convert_element_type"},
        {"name": "reduce_precision.24.remat_compressed", "opcode": "copy",
         "bytes": 64 * 4 * 2, "op_name": ""}]
    # The entry's parameter copied, not the loop's own argument, and not the
    # prefetch; the pair of an asynchronous collective once.
    assert report["parameter_copies"] == {"count": 1, "bytes": 16 * 4 * 4}
    assert report["whiles"] == 1
    assert report["collectives"] == {"all-reduce": 1, "all-gather": 1}
    # Mosaic kernels by name; XLA's other custom calls are none.
    assert report["kernels"] == {"ragged-dot-none": 1, "hvd_flash_fwd": 1}
    assert report["instructions"] == 28
    # Each of them placed: XLA's kernel by the name the windowed layer's jit
    # composed for it, in the loop's body; the one without metadata by the
    # rows it reads.
    assert report["kernel_calls"] == [
        {"instruction": "ragged-dot-none.2", "kernel": "ragged-dot-none",
         "op_name": "jit(_train_step)/jvp(layer0)/moe/windows/while/body/"
                    "window/jit(_window)/ragged-dot-none",
         "pass": "forward", "placed_by": "own", "loop": True,
         "scope": "layer0/moe/windows/while/body/window/_window"},
        {"instruction": "hvd_flash_fwd.3", "kernel": "hvd_flash_fwd",
         "op_name": "jit(_train_step)/jvp(layer0)/moe/windows/while/body/"
                    "window/jit(_window)/ragged-dot-none",
         "pass": "forward", "placed_by": "operands", "loop": True,
         "scope": "layer0/moe/windows/while/body/window/_window"}]


@pytest.mark.parametrize("type_text, nbytes", [
    ("f32[2,4096,100352]{2,1,0:T(8,128)}", 2 * 4096 * 100352 * 4),
    ("(f32[2,4096]{1,0:T(2,128)S(1)}, bf16[2,4096,64]{2,1,0:T(8,128)(2,1)})",
     2 * 4096 * 4 + 2 * 4096 * 64 * 2),
    ("s32[]{:T(128)}", 4), ("pred[16]{0}", 16), ("f8e4m3fn[8,8]{1,0}", 64),
    ("token[]", 0)])
def test_result_bytes(type_text, nbytes):
    assert hlo_report._result_bytes(type_text) == nbytes


# ---- every Mosaic kernel in a pass and a scope -------------------------------

STEP = "jit(_train_step)/"
BLOCK_BACKWARD = STEP + "transpose(jvp(layer0))/jvp(layer0)/checkpoint/"
WINDOW = "moe/windows/while/body/window/"


@pytest.mark.parametrize("op_name, want", [
    (STEP + "jvp(layer0)/moe/dispatch/gather", "forward"),
    (BLOCK_BACKWARD + "rematted_computation/moe/dispatch/gather",
     "recomputation"),
    (BLOCK_BACKWARD + "moe/combine/gather", "backward"),
    # JAX's own primitive in the backward pass, on a copy of the matrices a
    # recomputed product reads: why a kernel goes by its rows.
    (STEP + "transpose(jvp(layer0))/jvp(layer0)/remat2", "backward"),
    (STEP + "transpose(jvp(head))/dot_general", "backward"),
    # A windowed layer's kernels carry names of their own: the forward rule's,
    # the backward rule's, and the backward rule's forward made again.
    (STEP + "jvp(layer0)/" + WINDOW + "jit(_window)/ragged-dot-none",
     "forward"),
    (BLOCK_BACKWARD + WINDOW + "transpose(jvp(jit(_window)))/ragged-dot-none",
     "backward"),
    (BLOCK_BACKWARD + WINDOW + "jvp(jit(_window))/ragged-dot-none",
     "recomputation"),
    ("ragged-dot-none", "none"), (STEP + "hvd_optimizer/mul", "none"),
    ("params['w']", "none"), ("", "none")])
def test_pass_of(op_name, want):
    assert hlo_report.pass_of(op_name) == want


def calls_of(fixture):
    with open(os.path.join(os.path.dirname(__file__), "data", "hlo",
                           fixture + ".hlo.txt")) as f:
        report = hlo_report.reduce_hlo(f)
    return {c["instruction"]: c for c in report["kernel_calls"]}, report


def passes(calls, kernel="ragged-dot-none", **where):
    return collections.Counter(
        c["pass"] for c in calls.values() if c["kernel"] == kernel
        and all(c[k] == v for k, v in where.items()))


def test_bare_kernels_are_placed_by_the_rows_they_read():
    """OLMoE's block (a text recorded before PR 59 kept the first
    products): XLA names all 11 grouped matmuls "ragged-dot-none" and
    nothing else; 3 forward, gate and up made again, 6 backward."""
    calls, report = calls_of("olmoe_block")
    assert report["kernels"] == {"ragged-dot-none": 11,
                                 "ragged-dot-metadata": 3}
    assert passes(calls) == {"forward": 3, "recomputation": 2, "backward": 6}
    assert {(c["placed_by"], c["loop"]) for c in calls.values()} \
        == {("operands", False)}
    first = calls["ragged-dot-none.9"]
    assert first["op_name"] == STEP + "jvp(layer0)/moe/dispatch/gather"
    assert (first["pass"], first["scope"]) == ("forward",
                                               "layer0/moe/dispatch")
    # A recomputed product whose matrices come through the "remat2" copy.
    assert calls["ragged-dot-none.7"]["pass"] == "recomputation"
    # The matrices' gradients read recomputed rows and are backward.
    for gradient in ("ragged-dot-none", "ragged-dot-none.1",
                     "ragged-dot-none.2"):
        assert calls[gradient]["pass"] == "backward", gradient
    assert "rematted_computation" in calls["ragged-dot-none.1"]["op_name"]
    # The kernels that lay out the groups have no rows: by their first operand.
    assert passes(calls, "ragged-dot-metadata") == {"forward": 1,
                                                    "recomputation": 2}


def test_a_windowed_layers_kernels_are_placed_by_their_own_names():
    """One layer of Moonlight's (a text recorded before PR 66 kept the
    window at 0's first products): the window at 0 and the loop's body, in
    the forward rule and in the backward rule, which made each window's
    forward again (``parallel/moe.py::_held_experts_bwd``)."""
    calls, _ = calls_of("moonlight_layer")
    assert {c["placed_by"] for c in calls.values()} == {"own"}
    for loop in (False, True):
        assert passes(calls, loop=loop) == {
            "forward": 3, "recomputation": 2, "backward": 6}, loop
        assert passes(calls, "ragged-dot-metadata", loop=loop) == {
            "forward": 1, "backward": 2}, loop
    assert {c["scope"] for c in calls.values()} == {
        "layer1/moe/windows/window/_window",
        "layer1/moe/windows/while/body/window/_window"}


def test_a_kept_window_at_0s_backward_kernels_carry_their_rules_name():
    """One layer of Moonlight's since PR 66 (``scripts/aot_step.py
    moonlight-16b-a3b_s8192 --hlo``, cut to layer 1's grouped matmuls): the
    backward rule's window at 0 is no ``_window`` made again but the jitted
    ``_kept_window_bwd``, whose name its kernels carry as the windows' carry
    ``_window``'s, six grouped matmuls and two layouts of the groups, all
    backward and none made again; the loop's body keeps its gate and up
    products' second run; nothing is placed by what it reads or left
    unplaced."""
    calls, report = calls_of("moonlight_layer_kept")
    assert report["kernels"] == {"ragged-dot-none": 20,
                                 "ragged-dot-metadata": 6}
    assert {c["placed_by"] for c in calls.values()} == {"own"}
    assert passes(calls, loop=False) == {"forward": 3, "backward": 6}
    assert passes(calls, loop=True) == {
        "forward": 3, "recomputation": 2, "backward": 6}
    for loop in (False, True):
        assert passes(calls, "ragged-dot-metadata", loop=loop) == {
            "forward": 1, "backward": 2}, loop
    assert {c["scope"] for c in calls.values()
            if not c["loop"] and c["pass"] == "backward"} == {
        "layer1/moe/windows/window/_kept_window_bwd"}


def test_a_read_of_a_fusion_goes_by_the_output_it_reads():
    """ZAYA1's layer 3: one fusion makes the recomputed rows and the backward
    product's, and it and both reads carry the first's name."""
    calls, _ = calls_of("zaya1_fusion")
    assert calls["ragged-dot-none.34"]["pass"] == "recomputation"
    assert calls["ragged-dot-none.36"]["pass"] == "backward"
    assert calls["ragged-dot-none.36"]["scope"] == "layer3/moe/combine/_where"


def test_a_kernel_nothing_places_reads_none():
    calls = hlo_report.reduce_hlo('''HloModule jit_step

ENTRY %main.3 (x.1: bf16[64,8]) -> bf16[64,8] {
  %x.1 = bf16[64,8]{1,0} parameter(0), metadata={op_name="x"}
  %copy.1 = bf16[64,8]{0,1} copy(%x.1)
  %ragged-dot-none.1 = bf16[64,8]{1,0} custom-call(%copy.1), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %hvd_flash_fwd.1 = bf16[64,8]{1,0} custom-call(%copy.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/layer0/attn/hvd_flash_fwd/pallas_call"}
}
'''.splitlines())["kernel_calls"]
    assert [(c["pass"], c["placed_by"], c["scope"], c["loop"])
            for c in calls] == [
        ("none", "operands", "", False),
        # A step that differentiates nothing: the kernel's own scopes, no pass.
        ("none", "operands", "layer0/attn/hvd_flash_fwd", False)]


# ---- a real run_step function on the virtual mesh ---------------------------

@pytest.fixture
def spmd4(make_runtime):
    return make_runtime(devices=jax.devices()[:4])


def reported_step():
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def _reported_step(params, opt_state, x):
        loss, grads = jax.value_and_grad(
            lambda p: jnp.mean((x @ p["w"] + p["b"]) ** 2))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, op=hvd.Average))

    step = hvd.run_step(
        _reported_step,
        in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
        out_specs=hvd.REPLICATED, donate_argnums=(0, 1))
    params = hvd.replicate({"w": jnp.ones((8, 8), jnp.float32),
                            "b": jnp.zeros((8,), jnp.float32)})
    return step, params, hvd.replicate(opt.init(params))


def batch(rows):
    return hvd.shard_batch(np.ones((rows, 8), np.float32))


def step_families():
    return {name for name in hvd.metrics()
            if name.startswith("hvdtpu_spmd_step_")}


def test_report_is_of_the_executable_that_ran(spmd4):
    step, params, opt_state = reported_step()
    params, opt_state, _ = step(params, opt_state, batch(16))
    step(params, opt_state, batch(16))
    # What run_step returned is still the jit object.
    assert step.__name__ == "_reported_step" and step._cache_size() == 1
    assert callable(step.lower)
    backend_compiles = sample_value(
        hvd.metrics(), "hvdtpu_spmd_compiles_total",
        function="_reported_step", stage="backend_compile")
    # hvd.metrics() makes no report by itself.
    assert not step_families()

    report = hvd.compiled_step_report(step)
    m = report["memory_bytes"]
    assert m["arguments"] > 0 and m["outputs"] > 0 and m["temporaries"] > 0
    assert m["aliased"] > 0                         # the donated state
    # Two gradients and the loss: what the combiner left of three psums.
    assert 1 <= report["collectives"]["all-reduce"] <= 3
    assert set(report["collectives"]) == {"all-reduce"}
    assert report["rematerialized"] == [] and report["kernels"] == {}
    assert report["instructions"] > 10 and report["seconds"] > 0
    # Nothing compiled, nothing added to the step's cache.
    assert step._cache_size() == 1
    fams = hvd.metrics()
    assert sample_value(fams, "hvdtpu_spmd_compiles_total",
                        function="_reported_step",
                        stage="backend_compile") == backend_compiles
    assert sample_value(fams, "hvdtpu_spmd_compile_cache_misses_total") == 0

    # The gauges, labelled by function, once a report was asked for.
    assert step_families() == {
        "hvdtpu_spmd_step_memory_bytes", "hvdtpu_spmd_step_instructions",
        "hvdtpu_spmd_step_instruction_bytes", "hvdtpu_spmd_step_kernels"}
    assert fams["hvdtpu_spmd_step_memory_bytes"]["type"] == "gauge"
    for kind, nbytes in m.items():
        assert sample_value(fams, "hvdtpu_spmd_step_memory_bytes",
                            function="_reported_step", kind=kind) == nbytes
    assert sample_value(
        fams, "hvdtpu_spmd_step_instructions", function="_reported_step",
        kind="all-reduce") == report["collectives"]["all-reduce"]
    for kind in ("rematerialized", "parameter_copy"):
        for family in ("hvdtpu_spmd_step_instructions",
                       "hvdtpu_spmd_step_instruction_bytes"):
            assert sample_value(fams, family, function="_reported_step",
                                kind=kind) == 0
    assert parse_prometheus_text(hvd.metrics_dump()) == fams


def test_report_is_kept_until_the_step_is_traced_anew(spmd4):
    step, params, opt_state = reported_step()
    params, opt_state, _ = step(params, opt_state, batch(16))
    first = hvd.compiled_step_report(step)
    assert hvd.compiled_step_report(step) is first          # from the cache
    params, opt_state, _ = step(params, opt_state, batch(32))   # new shapes
    assert step._cache_size() == 2
    second = hvd.compiled_step_report(step)
    assert second is not first and step._cache_size() == 2
    assert second["memory_bytes"]["arguments"] \
        == first["memory_bytes"]["arguments"] + 16 * 8 * 4 // 4
    # One function, one set of gauges: the newest report's.
    assert sample_value(
        hvd.metrics(), "hvdtpu_spmd_step_memory_bytes",
        function="_reported_step", kind="arguments") \
        == second["memory_bytes"]["arguments"]


def test_the_kernels_gauge_says_pass_and_placement(spmd4):
    """The family's labels, on the canned module handed in as an executable's
    text."""
    from horovod_tpu import runtime

    canned = types.SimpleNamespace(
        as_text=lambda: HLO, memory_analysis=lambda: types.SimpleNamespace(
            argument_size_in_bytes=0, output_size_in_bytes=0,
            alias_size_in_bytes=0, temp_size_in_bytes=0,
            generated_code_size_in_bytes=0))
    report = runtime.recorder().step_report("_canned", object(),
                                            lambda: canned)
    samples = hvd.metrics()["hvdtpu_spmd_step_kernels"]["samples"]
    assert [(labels, value) for _, labels, value in samples] == [
        ({"function": "_canned", "kernel": "hvd_flash_fwd",
          "pass": "forward", "placed_by": "operands"}, 1.0),
        ({"function": "_canned", "kernel": "ragged-dot-none",
          "pass": "forward", "placed_by": "own"}, 1.0)]
    # Summed over the two new labels it is the count by kernel it was.
    by_kernel = collections.Counter()
    for _, labels, value in samples:
        by_kernel[labels["kernel"]] += value
    assert by_kernel == report["kernels"]
    assert parse_prometheus_text(hvd.metrics_dump()) == hvd.metrics()


@pytest.mark.parametrize("what", ["never run", "not a run_step function"])
def test_report_of_nothing_traced_raises(spmd4, what):
    step = reported_step()[0] if what == "never run" \
        else jax.jit(lambda x: x)
    with pytest.raises(ValueError, match="compiled_step_report"):
        hvd.compiled_step_report(step)


# ---- the expert layer's windows carry the program's own names ---------------

T, D, M, E = 48, 16, 24, 16


def lowered_layer(held, grad):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    h = jax.random.normal(ks[0], (T, D))
    weights = (jax.random.normal(ks[1], (D, E)),
               jax.random.normal(ks[2], (held, D, M)),
               jax.random.normal(ks[3], (held, D, M)),
               jax.random.normal(ks[4], (held, M, D)))

    def layer(h, *w):
        with jax.named_scope("moe"):
            return jnp.sum(moe_layer(h, *w, top_k=2, dtype=jnp.float32)[0])

    # The value with the gradients: without it nothing needs the forward
    # rule's windows and JAX drops them.
    f = jax.value_and_grad(layer, argnums=(0, 1, 2, 3, 4)) if grad else layer
    text = jax.jit(f).lower(h, *weights).as_text(debug_info=True)
    return set(re.findall(r'loc\("(jit\(layer\)/[^"]*)"', text))


def some(names, *parts):
    return any(all(p in n for p in parts) for n in names)


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_a_windowed_layer_names_its_windows(moe_row_tile, grad):
    """Where ``moe_windows_per_step`` looks: the window at 0 under
    ``moe/windows/window``, the loop's under ``moe/windows/while/body/
    window``, in the forward rule and in the backward rule alike. The
    backward rule's window at 0 reads what the forward's made (PR 66): it is
    ``_kept_window_bwd`` and no ``_window`` made again; the loop's is."""
    moe_row_tile(8)         # 4 of 16 experts held: windows of 24 of 96 rows
    names = lowered_layer(4, grad)
    rules = ["jvp(moe)/", "transpose(jvp(moe))/"] if grad else ["moe/"]
    for rule in rules:
        backward = rule.startswith("transpose")
        assert some(names, rule + "windows/window/", "jit(_kept_window_bwd)"
                    if backward else "jit(_window)"), rule
        assert backward == (not some(names, rule + "windows/window/",
                                     "jit(_window)")), rule
        assert some(names, rule + "windows/while/body/window/",
                    "jit(_window)"), rule
        assert some(names, rule + "windows/while/cond"), rule
    # No window and no loop of the layer's outside the scope.
    assert not [n for n in names
                if ("_window" in n or "while" in n) and "/windows/" not in n]


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "backward"])
def test_a_layer_that_holds_every_expert_has_no_window(moe_row_tile, grad):
    moe_row_tile(8)
    names = lowered_layer(E, grad)
    assert some(names, "moe", "/experts/")
    assert not some(names, "window") and not some(names, "while")

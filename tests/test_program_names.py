"""The compiled step names its own parts, and the SPMD recorder behind
``hvd.metrics()`` and ``hvd.start_timeline`` (ISSUE 23): scopes and kernel
names reach the compiled program as metadata only; compile, start-up and
placement counters; host spans on the device trace's clock."""

import functools
import json
import re
import threading
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import spmd_recorder
from horovod_tpu.compression import pallas_kernels as pk
from horovod_tpu.models import gpt
from horovod_tpu.observability import parse_prometheus_text, sample_value
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import conv, gated_delta, kda, s6, ssd

CFG = dict(vocab_size=64, num_layers=2, num_heads=2, num_kv_heads=1,
           head_dim=16, embed_dim=32, mlp_dim=64, tp_axis=None, sp_axis=None,
           attention="flash", dtype=jnp.float32)
B, S = 4, 128


@pytest.fixture
def spmd4(make_runtime):
    return make_runtime(devices=jax.devices()[:4])


# A state-space mixer in layer 1 (the scan's kernels in interpret mode).
HYBRID = dict(layer_kinds=("attention", "ssm"), ssm_heads=4, ssm_head_dim=8,
              ssm_state=8, ssm_groups=1, ssm_conv=4, ssm_chunk=16, rope=False)
# An expert layer in every block.
SPARSE = dict(moe_every=1, num_experts=4, experts_per_token=2)


def gpt_step(remat: str, **more):
    """A tiny GPT training step under run_step + DistributedOptimizer, its
    state and a batch for the 4-device mesh."""
    cfg = gpt.GPTConfig(remat=remat, **{**CFG, **more})
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def _train_step(params, opt_state, data):
        loss, grads = jax.value_and_grad(
            lambda p: gpt.loss_fn(p, *data, cfg))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, op=hvd.Average))

    step = hvd.run_step(
        _train_step,
        in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
        out_specs=hvd.REPLICATED)
    params = hvd.replicate(gpt.init_params(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    positions = np.broadcast_to(np.arange(S, dtype=np.int32), (B, S)).copy()
    data = hvd.shard_batch((tokens, np.roll(tokens, -1, -1), positions))
    return step, params, hvd.replicate(opt.init(params)), data


def op_names(step, *args) -> set:
    text = step.lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


# ---- (a) scopes in the compiled step ----------------------------------------

@pytest.mark.parametrize("remat", ["none", "full"])
def test_compiled_step_carries_the_scopes(spmd4, remat):
    names = op_names(*gpt_step(remat))

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    assert some("hvd_exchange") and some("hvd_optimizer")
    assert some("hvd_exchange/hvd_allreduce/grads")
    for scope in ("layer0)/attn", "layer0)/mlp", "layer1)/attn", "jvp(head)",
                  "jvp(embed)", "jvp(loss)"):
        assert some(scope), scope
    # Every forward scope again in the backward pass ...
    for scope in ("layer0", "layer1", "head", "embed", "loss"):
        assert some(f"transpose(jvp({scope}))"), scope
    assert some("transpose(jvp(layer0))", "/attn/")
    assert some("transpose(jvp(layer0))", "/mlp/")
    # ... and, under full recomputation, in the recomputed copy of a block.
    assert some("rematted_computation/attn") == (remat == "full")
    assert some("rematted_computation/mlp") == (remat == "full")
    # A scope never carries a counter that changes between two traces.
    assert not some("noname")


@pytest.mark.parametrize("budget,rows,blocks", [
    (gpt._HEAD_LOSS_BLOCK_BYTES, B * S // 4, 1), (4 * 64 * 40, 32, 4)],
    ids=["one_block", "four_blocks"])
def test_head_and_loss_rule_keeps_its_scopes_and_counts_its_trace(
        spmd4, monkeypatch, budget, rows, blocks):
    """The rule that makes head and loss in blocks of rows
    (``gpt._head_loss``) leaves ``head`` on its three products and ``loss``
    on the log-sum-exp and ``softmax - onehot`` (all in the forward rule:
    ``jvp(``), and both scopes on the backward pass's scaling; the counter
    says which blocks a rank's 128 rows over a vocabulary of 64 got."""
    monkeypatch.setattr(gpt, "_HEAD_LOSS_BLOCK_BYTES", budget)
    assert gpt.head_loss_rows(B * S // 4, 64) == rows
    names = op_names(*gpt_step("full"))

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    for product in ("...e,ev->...v", "rv,ev->re", "rv,re->ev"):
        assert some(f"jvp(head)/{product}/dot_general"), product
        assert not some("transpose(", f"/{product}/"), product
    for primitive in ("exp", "log", "reduce_max", "eq"):
        assert some(f"jvp(loss)/{primitive}"), primitive
    assert some("transpose(jvp(head))/mul")
    assert some("transpose(jvp(loss))/div")
    assert some("jvp(head)/rsqrt")             # the norm before the rule
    samples = {tuple(sorted(labels.items())): count for _, labels, count in
               hvd.metrics()["hvdtpu_spmd_head_loss_traces_total"]["samples"]}
    assert samples == {(("blocks", str(blocks)), ("rows_per_block", str(rows)),
                        ("tied", "false"), ("vocab", "64")): 1.0}


def test_hybrid_step_holds_the_scan_kernels_under_their_scope(spmd4):
    """The scan's two kernels sit under ``layer<i>/ssm/scan`` (where
    ``ssm_scan_ms`` looks), the forward one in the forward pass alone: a
    checkpointed block that keeps the scan's output does not run it again.
    The counter says which tiling each got."""
    step, *args = gpt_step("full", **HYBRID)
    text = step.lower(*args).as_text(debug_info=True)
    scopes = set(re.findall(r'loc\("([^"]*)/hvd_ssd_(fwd|bwd)/', text))
    assert {kernel for _, kernel in scopes} == {"fwd", "bwd"}
    for scope, kernel in scopes:
        assert scope.endswith("/ssm/scan") and "layer1" in scope, scope
        assert "layer0" not in scope
        assert ("transpose(jvp(layer1))" in scope) == (kernel == "bwd"), scope
        assert "rematted_computation" not in scope, scope
    samples = {tuple(sorted(labels.items())): count for _, labels, count in
               hvd.metrics()["hvdtpu_spmd_ssd_kernel_traces_total"]["samples"]}
    # JAX traces the forward kernel again for the block's recomputed copy,
    # and drops it there: nothing in the backward pass needs its output.
    assert samples == {
        (("chunk", "16"), ("heads_per_block", "4"), ("kernel", kernel),
         ("operand_dtype", "float32")): traces
        for kernel, traces in ((ssd.KERNEL_FWD, 2.0), (ssd.KERNEL_BWD, 1.0))}


# A gated-delta-rule mixer in layer 0, an expert block with a shared expert
# and a share of the router's experts after both mixers.
LINEAR = dict(layer_kinds=("gdn", "attention"), gdn_key_heads=2,
              gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8, gdn_chunk=16,
              moe_every=1, num_experts=8, experts_per_token=2, experts_held=4,
              renormalize_experts=True, shared_expert_dim=16,
              attention_gate=True, qk_head_norm=True)


@functools.cache
def _linear_step_names():
    """The compiled linear-attention step's names, made once for the five
    cases that each look for one part of the mixer (under the runtime of
    the case that asks first; a set of strings outlives it)."""
    return op_names(*gpt_step("full", **LINEAR))


@pytest.mark.parametrize("inner", ["in_proj", "conv", "scan", "gate_norm",
                                   "out_proj"])
def test_linear_attention_step_carries_the_gdn_scopes(spmd4, inner):
    """Where ``gdn_ms``, ``gdn_scan_ms`` and ``gdn_proj_ms`` look: every part
    of the mixer under ``layer0/gdn/<part>`` in the forward pass, in the
    recomputed copy and in the backward pass; the shared expert under
    ``moe/shared``, beside the router, the dispatch and the experts."""
    names = _linear_step_names()

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    assert some("jvp(layer0)", f"/gdn/{inner}/")
    assert some("transpose(jvp(layer0))", f"/gdn/{inner}/")
    assert some(f"rematted_computation/gdn/{inner}/")
    assert not some("layer1", "/gdn/")
    for scope in ("shared", "router", "dispatch", "experts"):
        assert some("jvp(layer0)", f"/moe/{scope}/"), scope
        assert some("jvp(layer1)", f"/moe/{scope}/"), scope
    assert some("transpose(jvp(layer1))", "/moe/shared/")


def test_linear_attention_step_holds_the_gdn_kernels_under_their_scope(
        spmd4):
    """The scan's kernels, the chunk-local pair and the recurrence's, sit
    under ``layer<i>/gdn/scan`` (where ``gdn_scan_ms`` looks): the forward
    ones in the forward pass, the recurrence's in the recomputed copy too
    (its backward pass reads each chunk's entering state, which a block
    keeps only where no lane of it is padding: these heads of 8 ride 128;
    the chunk-local kernel's outputs are kept, and it does not run again),
    the backward ones in the backward pass. The counter says which
    tiling each got, with its six labels (heads of 8 ride one lane tile of
    128): each kernel is traced once for a
    shape (the calls are jitted inline, so the recomputed copy and further
    layers of the same shape re-bind the traced kernel; the recurrence's
    forward twice, as the forward pass runs it and as the rule's forward,
    which keeps the entering states), which is why this test scans at a
    chunk no other test of this file does."""
    step, *args = gpt_step("full", **{**LINEAR, "gdn_chunk": 32})
    text = step.lower(*args).as_text(debug_info=True)
    scopes = set(re.findall(
        r'loc\("([^"]*)/hvd_gdn_(fwd|bwd|rec_fwd|rec_bwd)/', text))
    assert {kernel for _, kernel in scopes} == {"fwd", "bwd", "rec_fwd",
                                                "rec_bwd"}
    for scope, kernel in scopes:
        assert scope.endswith("/gdn/scan") and "layer0" in scope, scope
        assert "layer1" not in scope
        assert ("transpose(jvp(layer0))" in scope
                and "rematted_computation" not in scope) \
            == kernel.endswith("bwd"), scope
    assert {name for scope, name in scopes
            if "rematted_computation" in scope} == {"rec_fwd"}
    family = hvd.metrics()["hvdtpu_spmd_gdn_kernel_traces_total"]
    samples = {tuple(sorted(labels.items())): count
               for _, labels, count in family["samples"]}
    assert samples == {
        (("chunk", "32"), ("heads_per_block", heads), ("kernel", kernel),
         ("key_lanes", "128"), ("operand_dtype", "float32"),
         ("value_lanes", "128")): traces
        for kernel, heads, traces in (
            (gated_delta.KERNEL_FWD, "2", 1.0),
            (gated_delta.KERNEL_BWD, "2", 1.0),
            (gated_delta.KERNEL_REC_FWD, "4", 2.0),
            (gated_delta.KERNEL_REC_BWD, "4", 1.0))}


# A state of 16 and value heads of 16: convolutions of 64 channels with a bias
# and of 96 without one, which no other test of this file traces (the calls
# are jitted inline: a kernel is traced, and counted, once for a shape).
@pytest.mark.parametrize("more, layer, mixer, labels", [
    ({**HYBRID, "ssm_state": 16}, "layer1", "ssm",
     dict(channels="64", bias="true", tile="128x64", minor="tokens")),
    ({**LINEAR, "gdn_value_dim": 16}, "layer0", "gdn",
     dict(channels="96", bias="false", tile="128x96", minor="tokens")),
], ids=["hybrid", "linear"])
def test_recurrent_step_holds_the_conv_kernels_under_its_scope(
        spmd4, more, layer, mixer, labels):
    """The convolution's two kernels sit under ``layer<i>/<mixer>/conv``
    (where ``ssm_ms``, ``gdn_ms`` and the passes' metrics find them, and
    where no scan's metric does): the forward one in the forward pass and in
    the recomputed copy (the scan's backward pass needs its output), the
    backward one in the backward pass. The counter says which form a step
    compiled: a state-space mixer asks for tokens on the lanes, and so does
    a gated-delta-rule mixer whose heads (8 and 16 wide here) are carried to
    whole lane tiles before its scan; one with heads of 128 asks for
    channels."""
    step, *args = gpt_step("full", **more)
    text = step.lower(*args).as_text(debug_info=True)
    scopes = set(re.findall(r'loc\("([^"]*)/hvd_conv_(fwd|bwd)/', text))
    assert {kernel for _, kernel in scopes} == {"fwd", "bwd"}
    for scope, kernel in scopes:
        assert scope.endswith(f"/{mixer}/conv") and layer in scope, scope
        assert ("transpose(jvp(" in scope
                and "rematted_computation" not in scope) \
            == (kernel == "bwd"), scope
    assert any("rematted_computation" in scope for scope, _ in scopes)
    assert any("transpose(jvp(" not in scope for scope, _ in scopes)
    family = hvd.metrics()["hvdtpu_spmd_conv_kernel_traces_total"]
    samples = {tuple(sorted(labels.items())): count
               for _, labels, count in family["samples"]}
    assert samples == {
        tuple(sorted(dict(labels, kernel=kernel, taps="4",
                          operand_dtype="float32").items())): 1.0
        for kernel in (conv.CONV_KERNEL_FWD, conv.CONV_KERNEL_BWD)}


@pytest.mark.parametrize("more", [{}, SPARSE], ids=["dense", "sparse"])
def test_a_step_without_a_state_space_layer_traces_no_scan_kernel(spmd4,
                                                                  more):
    step, *args = gpt_step("full", **more)
    text = step.lower(*args).as_text(debug_info=True)
    assert "hvd_ssd" not in text and "hvd_gdn" not in text
    assert "hvd_conv" not in text
    for family in ("hvdtpu_spmd_ssd_kernel_traces_total",
                   "hvdtpu_spmd_gdn_kernel_traces_total",
                   "hvdtpu_spmd_conv_kernel_traces_total"):
        assert not hvd.metrics()[family]["samples"]


def test_in_step_collective_scope_uses_the_callers_name(spmd4):
    def body(x):
        return (hvd.allreduce(x, name="loss_avg"), hvd.allgather(x),
                hvd.reducescatter(x, name="rs"))

    step = hvd.run_step(body, in_specs=hvd.batch_spec(0),
                        out_specs=(hvd.REPLICATED, hvd.REPLICATED,
                                   hvd.batch_spec(0)), check_vma=False)
    text = step.lower(jnp.ones((16, 4))).as_text(debug_info=True)
    for scope in ("hvd_allreduce/loss_avg", "hvd_allgather/unnamed",
                  "hvd_reducescatter/rs"):
        assert scope in text, scope


def test_window_step_holds_its_flash_kernels_under_a_scope_of_their_own(
        spmd4):
    """A window layer's flash kernels sit under ``layer<i>/attn_window``
    (where ``flash_window_ms`` looks) and a full layer's under
    ``layer<i>/attn``, forward and backward; the kernels' names are the same
    two, the backward pass one kernel under the dKdV kernel's name (no
    ``hvd_flash_dq`` where a head's dK and dV fit VMEM). A branch normed
    after it too has ``post_norm`` inside its scope."""
    plan = (gpt.LayerSpec(window=8), gpt.LayerSpec())
    step, *args = gpt_step("full", layers=plan, post_norm=True)
    text = step.lower(*args).as_text(debug_info=True)
    scopes = set(re.findall(r'loc\("([^"]*)/hvd_flash_(fwd|dkdv|dq)/', text))
    by_layer = {("layer0", "attn_window"): set(), ("layer1", "attn"): set()}
    for scope, kernel in scopes:
        layer = re.search(r"layer\d", scope).group(0)
        by_layer[(layer, scope.rsplit("/", 1)[-1])].add(kernel)
    assert all(kernels == {"fwd", "dkdv"}
               for kernels in by_layer.values()), by_layer
    for scope in ("attn_window/post_norm", "attn/post_norm",
                  "mlp/post_norm"):
        assert scope in text, scope


def test_block_diffusion_step_holds_its_flash_kernels_under_attn_bd(spmd4):
    """Under ``diffusion_block`` every attention mixer lies under
    ``layer<i>/attn_bd`` (a reader tells these flash kernels from a causal
    layer's by it), forward and backward, the same two kernel names; and
    the counters say what the mask's grids walk and how full their tiles
    are: where ``flash_bd_tiles_kept_pct`` and ``flash_bd_tile_fill_pct``
    look. The head multiplies the noised half's rows alone."""
    half, block = S // 2, 4
    cfg = gpt.GPTConfig(remat="full", diffusion_block=block, **CFG)
    opt = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def _train_step(params, opt_state, data):
        tokens, targets, positions, weights = data
        loss, grads = jax.value_and_grad(lambda p: gpt.loss_fn(
            p, tokens, targets, positions, cfg, -1, weights,
            targets.size))(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                hvd.allreduce(loss, op=hvd.Average))

    step = hvd.run_step(
        _train_step,
        in_specs=(hvd.REPLICATED, hvd.REPLICATED, hvd.batch_spec(0)),
        out_specs=hvd.REPLICATED)
    params = hvd.replicate(gpt.init_params(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    positions = np.tile(np.arange(half, dtype=np.int32), (B, 2))
    data = hvd.shard_batch((
        tokens, tokens[:, half:].copy(), positions,
        rng.uniform(1, 10, (B, half)).astype(np.float32)))
    text = step.lower(params, hvd.replicate(opt.init(params)),
                      data).as_text(debug_info=True)
    scopes = set(re.findall(r'loc\("([^"]*)/hvd_flash_(fwd|dkdv|dq)/', text))
    assert {kernel for _, kernel in scopes} == {"fwd", "dkdv"}
    assert all(scope.endswith("/attn_bd") for scope, _ in scopes), scopes
    assert {re.search(r"layer\d", scope).group(0)
            for scope, _ in scopes} == {"layer0", "layer1"}

    def samples(family, **want):
        return {tuple(sorted((k, v) for k, v in labels.items()
                             if k not in want)): count
                for _, labels, count in hvd.metrics()[family]["samples"]
                if all(labels[k] == v for k, v in want.items())}

    # One tile of 128 x 128 holds a head's whole 2 L x 2 L rectangle here;
    # a kernel is traced once or twice a shape (the forward under the rule
    # and under its recomputed copy), each trace one rectangle.
    tiles = samples("hvdtpu_spmd_flash_tiles_total", mask="block_diffusion")
    pairs = samples("hvdtpu_spmd_flash_pairs_total", mask="block_diffusion")
    for kernel in (fa.KERNEL_FWD, fa.KERNEL_DKDV):
        at = (("kernel", kernel), ("seq", str(S)))
        traces = tiles.pop((*at, ("tiles", "kept")))
        assert traces in (1.0, 2.0)
        assert tiles.pop((*at, ("tiles", "skipped_block_diffusion"))) == 0.0
        assert pairs.pop((at[0], ("pairs", "computed"), at[1])) \
            == traces * S * S
        assert pairs.pop((at[0], ("pairs", "kept"), at[1])) \
            == traces * half * (half + block)
    assert not tiles and not pairs
    assert not samples("hvdtpu_spmd_flash_tiles_total", mask="causal")
    # B / 4 sequences a rank, half of their rows each.
    assert samples("hvdtpu_spmd_head_loss_traces_total") == {
        (("blocks", "1"), ("rows_per_block", str(B // 4 * half)),
         ("tied", "false"), ("vocab", "64")): 1.0}


def test_looped_step_holds_its_passes_and_its_exit_under_their_scopes(spmd4):
    """A looped stack puts ``pass<t>`` around ``layer<i>`` and around the
    norm at the end of the pass (``head``): a reader that looks for ``attn``
    or ``mlp`` finds them as before (``scope_reduce.scope_of``), the flash
    kernels lie under ``pass<t>/layer<i>/attn``. The exit gate's product,
    the distribution, the entropy and their backward pass lie under ``exit``
    (where ``loop_exit_ms`` looks) and not under ``head`` or ``loss``
    (``loop_head_ms``); the counter says passes and layers
    (``loop_block_calls``); the head's rule is traced once, over passes x
    the rows."""
    from benchmarks import scope_reduce

    names = op_names(*gpt_step(
        "full", loop_passes=3, exit_entropy_coef=0.1, norms="pre_post",
        gated_mlp=True))

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    for t in range(3):
        for scope in ("layer0/attn", "layer1/mlp", "layer1/mlp/post_norm",
                      "head/"):
            assert some(f"jvp(pass{t})/{scope}"), (t, scope)
        assert some(f"transpose(jvp(pass{t}))/layer0", "/attn/")
        assert some(f"transpose(jvp(pass{t}))", "rematted_computation/mlp")
    assert not some("pass3")
    assert some("jvp(exit)/") and some("transpose(jvp(exit))")
    # (A reducer's own computation carries a name without the step's prefix,
    # and a parameter's is its path: neither is an operation's.)
    exits = [scope_reduce.scope_of(n) for n in names
             if n.startswith("jit(") and "(exit)" in n]
    assert exits and all(
        "exit" in scopes and not {"head", "loss"} & set(scopes)
        for scopes in exits), exits
    for primitive in ("log", "exp", "cumsum", "reduce_sum"):
        assert some("jvp(exit)", primitive), primitive
    assert some("jvp(head)/...e,ev->...v/dot_general")
    found = [scope_reduce.scope_of(n) for n in names
             if n.startswith("jit(") and "jvp(pass2)/layer1/attn" in n]
    assert found and all(scopes[:3] == ["pass2", "layer1", "attn"]
                         for scopes in found), found
    samples = {tuple(sorted(labels.items())): count for _, labels, count in
               hvd.metrics()["hvdtpu_spmd_loop_passes_total"]["samples"]}
    assert samples == {(("layers", "2"), ("passes", "3")): 1.0}
    # Three passes of a rank's 128 rows in the rule's one call.
    head = {tuple(sorted(labels.items())) for _, labels, _ in
            hvd.metrics()["hvdtpu_spmd_head_loss_traces_total"]["samples"]}
    assert head == {(("blocks", "1"), ("rows_per_block", str(3 * B * S // 4)),
                     ("tied", "false"), ("vocab", "64"))}


# A CCA mixer and an expert sublayer under an MLP router in both layers,
# each sublayer joined under the residual scaling.
CCA = dict(layers=(gpt.LayerSpec(mixer="cca", ff="experts"),) * 2,
           num_heads=4, num_kv_heads=2, num_experts=4, router_kind="mlp",
           router_dim=16, router_bias=True, residual_scaling=True,
           rotary_dim=8, tie_embeddings=True)


def test_cca_step_holds_its_parts_under_their_scopes(spmd4):
    """A CCA layer's mixer lies under ``layer<i>/attn`` with its own parts
    inside (where ``cca_ms`` and ``cca_mix_ms`` look): the latent
    projections and ``W_o`` under ``cca_proj``, the mix's two kernels
    (``hvd_cca_fwd`` in the forward pass and in the recomputed copy of a
    block, ``hvd_cca_bwd`` in the backward pass) and the value's shift under
    ``cca_mix``, the flash kernels under ``attn`` itself; the whole MLP router under ``moe/router``; the
    scaling of each sublayer's residual under ``res_scale``; forward, in
    the recomputed copy of a block and backward."""
    step, *args = gpt_step("full", **CCA)
    text = step.lower(*args).as_text(debug_info=True)
    scopes = set(re.findall(r'loc\("([^"]*)/hvd_cca_(fwd|bwd)/', text))
    assert all(scope.endswith("/attn/cca_mix") for scope, _ in scopes), scopes
    passes = {(kernel, "transpose(jvp(" in scope
               and "rematted_computation" not in scope,
               "rematted_computation" in scope) for scope, kernel in scopes}
    assert passes == {("fwd", False, False), ("fwd", False, True),
                      ("bwd", True, False)}, scopes
    assert "hvd_conv" not in text
    flash = set(re.findall(r'loc\("([^"]*)/hvd_flash_(?:fwd|dkdv|dq)/', text))
    assert flash and all(scope.endswith("/attn") for scope in flash), flash
    names = op_names(step, *args)

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    for layer in ("layer0", "layer1"):
        for scope in ("/attn/cca_proj/", "/attn/cca_mix/", "/attn/res_scale/",
                      "/moe/router/", "/moe/res_scale/"):
            assert some(f"jvp({layer})", scope), (layer, scope)
            assert some(f"transpose(jvp({layer}))", scope), (layer, scope)
            assert some(layer, "rematted_computation", scope), (layer, scope)
    # The mix's kernels and the router's products are under those scopes.
    for kernel in ("hvd_cca_fwd", "hvd_cca_bwd"):
        assert some("/attn/cca_mix/", kernel)
    assert some("/moe/router/", "dot_general")


def test_mla_step_holds_its_parts_under_their_scopes(spmd4):
    """A latent-attention layer's mixer lies under ``layer<i>/attn`` with
    its own parts inside (where ``mla_ms``, ``mla_proj_ms`` and
    ``mla_rope_ms`` look): the five projections and the latent's norm under
    ``mla_proj``, the two rotations, the shared key's broadcast and the
    concatenations under ``mla_rope``, the flash kernels under ``attn``
    itself; forward, in the recomputed copy of a block and backward."""
    step, *args = gpt_step(
        "full", layers=(gpt.LayerSpec(mixer="mla", ff="gated"),) * 2,
        num_heads=4, mla_rope_dim=4, mla_value_dim=16, mla_kv_rank=16)
    text = step.lower(*args).as_text(debug_info=True)
    flash = set(re.findall(r'loc\("([^"]*)/hvd_flash_(?:fwd|dkdv|dq)/', text))
    assert flash and all(scope.endswith("/attn") for scope in flash), flash
    scopes = set(re.findall(r'loc\("([^"]*/attn/mla_(?:proj|rope))/', text))
    for layer in ("layer0", "layer1"):
        for part in ("mla_proj", "mla_rope"):
            for where in (f"jvp({layer})", f"transpose(jvp({layer}))",
                          "rematted_computation"):
                assert any(layer in s and where in s
                           and s.endswith("/attn/" + part)
                           for s in scopes), (layer, part, where)
    # The five products (``W_o``'s too) and the shared key's joining.
    ops = set(re.findall(r'/attn/(mla_(?:proj|rope)/[^"]*)"', text))
    assert {"mla_proj/bse,ehd->bshd/dot_general",
            "mla_proj/bse,ef->bsf/dot_general",
            "mla_proj/bsr,rhd->bshd/dot_general",
            "mla_proj/bshd,hde->bse/dot_general", "mla_proj/rsqrt",
            "mla_rope/concatenate", "mla_rope/cos",
            "mla_rope/broadcast_in_dim"} <= ops, ops


# ---- (b) kernel names --------------------------------------------------------

def _flash(grad: bool, fused: bool = True):
    """``fused``: the backward pass as the one kernel the shape takes; not:
    as the pair a sequence too long for a head's dK and dV in VMEM keeps."""
    q = jnp.ones((1, 128, 2, 16), jnp.float32)
    if grad:
        with mock.patch.object(fa, "VMEM_LIMIT_BYTES",
                               fa.VMEM_LIMIT_BYTES if fused else 2 ** 18):
            return jax.make_jaxpr(jax.grad(
                lambda q: fa.flash_attention(q, q, q).sum()))(q)
    return jax.make_jaxpr(lambda q: fa.flash_attention(q, q, q))(q)


def _scan(grad: bool):
    x = jnp.ones((1, 32, 2, 8), jnp.float32)
    b = jnp.ones((1, 32, 1, 4), jnp.float32)

    def scan(x):
        return ssd.ssd_chunked(x, x[..., 0], -jnp.ones(2), b, b, jnp.ones(2),
                               chunk=16, dtype=jnp.float32)[0].sum()

    return jax.make_jaxpr(jax.grad(scan) if grad else scan)(x)


def _delta(grad: bool):
    q = jnp.ones((1, 32, 1, 8), jnp.float32)
    g = -jnp.ones((1, 32, 2), jnp.float32)

    def scan(v):
        return gated_delta.gated_delta_chunked(
            q, q, v, g, -g, chunk=16, dtype=jnp.float32)[0].sum()

    return jax.make_jaxpr(jax.grad(scan) if grad else scan)(
        jnp.ones((1, 32, 2, 8), jnp.float32))


def _selective(grad: bool):
    u = jnp.ones((1, 32, 8), jnp.float32)
    b = jnp.ones((1, 32, 8), jnp.float32)

    def scan(u):
        return s6.selective_scan(u, u, -jnp.ones((8, 8)), b, b, jnp.ones(8),
                                 chunk=16).sum()

    return jax.make_jaxpr(jax.grad(scan) if grad else scan)(u)


def _kimi(grad: bool):
    q = jnp.ones((1, 32, 2, 8), jnp.float32)

    def scan(q):
        return kda.kda_chunked(q, q, q, -q, q[..., 0], chunk=16, sub_chunk=8,
                               dtype=jnp.float32)[0].sum()

    return jax.make_jaxpr(jax.grad(scan) if grad else scan)(q)


def _conv(grad: bool):
    w = jnp.ones((4, 8), jnp.float32)

    def summed(u):
        return conv.causal_conv_silu(u, w, None).sum()

    return jax.make_jaxpr(jax.grad(summed) if grad else summed)(
        jnp.ones((1, 32, 8), jnp.float32))


FLAT = jnp.linspace(-1.0, 1.0, 512 * 4, dtype=jnp.float32)
LEVELS = jnp.linspace(0.0, 1.0, 8, dtype=jnp.float32)
Q8 = jnp.zeros((4, 512), jnp.uint8)
MN = jnp.zeros((4,), jnp.float32)


@pytest.mark.parametrize("name, make", [
    ("hvd_flash_fwd", lambda: _flash(False)),
    ("hvd_flash_dkdv", lambda: _flash(True)),
    ("hvd_flash_dq", lambda: _flash(True, fused=False)),
    ("hvd_ssd_fwd", lambda: _scan(False)),
    ("hvd_ssd_bwd", lambda: _scan(True)),
    ("hvd_gdn_fwd", lambda: _delta(False)),
    ("hvd_gdn_bwd", lambda: _delta(True)),
    ("hvd_conv_fwd", lambda: _conv(False)),
    ("hvd_conv_bwd", lambda: _conv(True)),
    ("hvd_s6_fwd", lambda: _selective(False)),
    ("hvd_s6_bwd", lambda: _selective(True)),
    ("hvd_kda_fwd", lambda: _kimi(False)),
    ("hvd_kda_rec_fwd", lambda: _kimi(False)),
    ("hvd_kda_bwd", lambda: _kimi(True)),
    ("hvd_kda_rec_bwd", lambda: _kimi(True)),
    ("hvd_maxmin_quantize", lambda: jax.make_jaxpr(
        lambda x: pk.maxmin_quantize_pallas(x, 4, 512, True))(FLAT)),
    # TPU-only (pltpu.prng_* has no CPU lowering), but it traces anywhere.
    ("hvd_maxmin_quantize_stochastic", lambda: jax.make_jaxpr(
        lambda x, seed: pk.maxmin_quantize_stochastic_pallas(
            x, 4, 512, seed))(FLAT, jnp.int32(1))),
    ("hvd_maxmin_dequantize", lambda: jax.make_jaxpr(
        lambda q: pk.maxmin_dequantize_pallas(q, MN, MN, 512, True))(Q8)),
    ("hvd_maxmin_dequantize_sum", lambda: jax.make_jaxpr(
        lambda q: pk.maxmin_dequantize_sum_pallas(
            q, jnp.stack([MN, MN]), jnp.stack([MN, MN]), True))(
                jnp.stack([Q8, Q8]))),
    ("hvd_norm_quantize", lambda: jax.make_jaxpr(
        lambda x: pk.norm_quantize_pallas(x, LEVELS, 512, True, True))(FLAT)),
    ("hvd_norm_dequantize", lambda: jax.make_jaxpr(
        lambda q: pk.norm_dequantize_pallas(q, LEVELS, MN, True))(Q8)),
])
def test_kernel_names(name, make):
    """The twenty-one names the benchmark's readers match as strings, or
    (the convolution's) must not."""
    assert re.search(rf"\bname={name}\b", str(make())), name


def test_kernel_name_constants():
    assert (fa.KERNEL_FWD, fa.KERNEL_DKDV, fa.KERNEL_DQ) == (
        "hvd_flash_fwd", "hvd_flash_dkdv", "hvd_flash_dq")
    # benchmarks/jobs/gpt_hybrid_dp.py matches ``^hvd_ssd_``.
    assert (ssd.KERNEL_FWD, ssd.KERNEL_BWD) == ("hvd_ssd_fwd", "hvd_ssd_bwd")
    # benchmarks/jobs/gpt_linear_moe_dp.py matches ``^hvd_gdn_``.
    assert (gated_delta.KERNEL_FWD, gated_delta.KERNEL_BWD) == (
        "hvd_gdn_fwd", "hvd_gdn_bwd")
    # The convolution's sit under ``ssm/conv`` and ``gdn/conv`` and belong to
    # no scan: a name under either prefix above would be counted into
    # ``ssm_scan_ms`` or ``gdn_scan_ms`` and their rooflines.
    assert (conv.CONV_KERNEL_FWD, conv.CONV_KERNEL_BWD) == (
        "hvd_conv_fwd", "hvd_conv_bwd")
    for name in (conv.CONV_KERNEL_FWD, conv.CONV_KERNEL_BWD):
        assert not re.match(r"^hvd_(ssd|gdn|s6)_", name)
    # benchmarks/jobs/gpt_sambay_dp.py matches ``^hvd_s6_``; no reader of
    # ``^hvd_ssd_`` (Mamba-2's scan) or of the convolution's names may count
    # the selective scan's kernels into another scan.
    assert (s6.KERNEL_FWD, s6.KERNEL_BWD) == ("hvd_s6_fwd", "hvd_s6_bwd")
    for name in (s6.KERNEL_FWD, s6.KERNEL_BWD):
        assert not re.match(r"^hvd_(ssd|gdn|conv|flash)_", name)
    assert {v for k, v in vars(pk).items() if k.startswith("KERNEL_")} == {
        "hvd_maxmin_quantize", "hvd_maxmin_quantize_stochastic",
        "hvd_maxmin_dequantize", "hvd_maxmin_dequantize_sum",
        "hvd_norm_quantize", "hvd_norm_dequantize"}


# A Kimi-delta-attention mixer in layer 0, a gated latent-attention mixer in
# layer 1, expert blocks whose router chooses inside 2 of 4 groups.
KIMI = dict(
    num_kv_heads=2, kda_heads=2, kda_key_dim=8, kda_value_dim=8, kda_chunk=16,
    mla_rope_dim=8, mla_value_dim=16, mla_kv_rank=16, mla_head_gate=True,
    num_experts=8, experts_per_token=2, experts_held=4,
    renormalize_experts=True, shared_expert_dim=16, shared_expert_gate=False,
    router_score="sigmoid", router_bias=True, router_groups=4,
    router_groups_kept=2,
    layers=(gpt.LayerSpec(mixer="kda", ff="experts"),
            gpt.LayerSpec(mixer="mla", ff="experts")))


def test_kimi_step_holds_its_parts_under_their_scopes(spmd4):
    """Where ``kda_ms``, ``kda_proj_ms``, ``kda_scan_ms`` and
    ``moe_route_groups_ms`` look: a KDA mixer's parts under ``layer0/kda/
    <part>`` in the forward pass, the recomputed copy and the backward pass,
    its four kernels under ``kda/kda_scan`` (none in the recomputed copy:
    the block keeps every output of the forward pair by name), the
    convolution's under ``kda/kda_conv``; the MLA layer's gate under
    ``attn/mla_gate``; the grouped choice under ``moe/router/groups``; and
    the three counters say what was traced."""
    step, *args = gpt_step("full", **KIMI)
    text = step.lower(*args).as_text(debug_info=True)
    scans = set(re.findall(
        r'loc\("([^"]*)/hvd_kda_(fwd|bwd|rec_fwd|rec_bwd)/', text))
    assert {kernel for _, kernel in scans} == {"fwd", "bwd", "rec_fwd",
                                               "rec_bwd"}
    for scope, kernel in scans:
        assert scope.endswith("/kda/kda_scan") and "layer0" in scope, scope
        assert ("transpose(jvp(layer0))" in scope
                and "rematted_computation" not in scope) \
            == kernel.endswith("bwd"), scope
    assert not [kernel for scope, kernel in scans
                if "rematted_computation" in scope]
    assert all(scope.endswith("/kda/kda_conv") for scope in re.findall(
        r'loc\("([^"]*)/hvd_conv_(?:fwd|bwd)/', text))
    names = set(re.findall(r'loc\("([^"]*)"', text))     # as lowered

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    for inner in ("kda_proj", "kda_conv", "kda_scan", "kda_gate"):
        assert some("jvp(layer0)", f"/kda/{inner}/"), inner
        assert some("transpose(jvp(layer0))", f"/kda/{inner}/"), inner
        assert some(f"rematted_computation/kda/{inner}/"), inner
    assert not some("layer1", "/kda/")
    assert some("jvp(layer1)", "/attn/mla_gate/")
    assert some("transpose(jvp(layer1))", "/attn/mla_gate/")
    assert not some("layer0", "/mla_gate/")
    for layer in ("layer0", "layer1"):
        assert some(f"jvp({layer})", "/moe/router/groups/"), layer
    fams = hvd.metrics()
    assert sample_value(
        fams, "hvdtpu_spmd_kda_traces_total", heads="2", key_dim="8",
        value_dim="8", chunk="16", sub_chunk="16", lower_bound="-5.0") >= 1
    assert sample_value(
        fams, "hvdtpu_spmd_mla_traces_total", heads="2", nope_dim="16",
        rope_dim="8", value_dim="16", kv_rank="16", q_rank="none",
        gate="head") >= 1
    assert sample_value(
        fams, "hvdtpu_spmd_moe_layer_traces_total", experts="8", top_k="2",
        held="4", score="sigmoid", bias="1", groups="4",
        groups_kept="2") >= 1
    # benchmarks/jobs/gpt_kda_mla_moe_dp.py matches ``^hvd_kda_``; the
    # gated delta rule's reader (``^hvd_gdn_``) must not count these.
    for name in (kda.KERNEL_FWD, kda.KERNEL_BWD, kda.KERNEL_REC_FWD,
                 kda.KERNEL_REC_BWD):
        assert re.match(r"^hvd_kda_", name)
        assert not re.match(r"^hvd_(ssd|gdn|s6|conv)_", name)


# Mamba-1, a window layer, the two producers and their readers: the six
# kinds of layer of the decoder-hybrid-decoder stack, two heads in one pair.
SAMBAY = dict(
    num_layers=6, num_heads=2, num_kv_heads=2, rope=None, tie_embeddings=True,
    norm_kind="layer", s6_inner=16, s6_dt_rank=2, ssm_state=8, ssm_conv=4,
    layers=(
        gpt.LayerSpec(mixer="s6", rope=False, ff="gated"),
        gpt.LayerSpec(mixer="diff_attention", window=8, rope=False,
                      ff="gated", depth=1),
        gpt.LayerSpec(mixer="s6", rope=False, ff="gated",
                      publishes=("s6_scan",)),
        gpt.LayerSpec(mixer="diff_attention", rope=False, ff="gated",
                      depth=17, publishes=("diff_kv",)),
        gpt.LayerSpec(mixer="gmu", rope=False, ff="gated",
                      reads=("s6_scan",)),
        gpt.LayerSpec(mixer="diff_cross", rope=False, ff="gated", depth=19,
                      reads=("diff_kv",))))


def test_sambay_step_holds_its_parts_under_their_scopes(spmd4):
    """Where ``s6_ms``, ``s6_scan_ms``, ``gmu_ms``, ``attn_cross_ms`` and
    ``attn_diff_ms`` look: a Mamba-1 mixer's parts under ``layer<i>/s6/
    <part>`` with the scan's kernels under ``s6/scan`` (the forward one in
    the forward pass alone: the block keeps the scan's output and the
    entering states) and the convolution's under ``s6/conv``; a Gated
    Memory Unit under ``gmu``; a differential layer's two flash calls
    straight under ``attn_window``, ``attn`` or ``attn_cross`` and its
    combination under ``diff`` inside each; and the counters say what was
    traced and what crosses the layers."""
    more = {k: v for k, v in SAMBAY.items() if k != "rope"}
    step, *args = gpt_step("full", **more)
    text = step.lower(*args).as_text(debug_info=True)
    scans = set(re.findall(r'loc\("([^"]*)/hvd_s6_(fwd|bwd)/', text))
    assert {kernel for _, kernel in scans} == {"fwd", "bwd"}
    for scope, kernel in scans:
        assert scope.endswith("/s6/scan"), scope
        assert re.search(r"layer[02]\b", scope), scope
        assert ("transpose(jvp(" in scope) == (kernel == "bwd"), scope
        assert "rematted_computation" not in scope, scope
    assert all(scope.endswith("/s6/conv") for scope in re.findall(
        r'loc\("([^"]*)/hvd_conv_(?:fwd|bwd)/', text))
    flash = set(re.findall(r'loc\("([^"]*)/hvd_flash_(fwd|dkdv)/', text))
    by_layer = {}
    for scope, kernel in flash:
        assert "rematted_computation" not in scope, scope
        by_layer.setdefault(re.search(r"layer\d", scope).group(0),
                            set()).add(scope.rsplit("/", 1)[-1])
    assert by_layer == {"layer1": {"attn_window"}, "layer3": {"attn"},
                        "layer5": {"attn_cross"}}
    names = set(re.findall(r'loc\("([^"]*)"', text))     # as lowered

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    for part in ("in_proj", "x_proj", "scan", "gate", "out_proj"):
        assert some("jvp(layer0)", f"/s6/{part}/"), part
        assert some("transpose(jvp(layer2))", f"/s6/{part}/"), part
    assert some("jvp(layer4)", "/gmu/") \
        and some("transpose(jvp(layer4))", "/gmu/")
    for layer, scope in ((1, "attn_window"), (3, "attn"), (5, "attn_cross")):
        assert some(f"jvp(layer{layer})", f"/{scope}/diff/"), scope
        assert some(f"transpose(jvp(layer{layer}))", f"/{scope}/diff/"), scope
    assert not some("layer4", "/s6/") and not some("layer5", "/attn/")

    def samples(family):
        return {tuple(sorted(labels.items())): count for _, labels, count
                in hvd.metrics()[family]["samples"]}

    assert samples("hvdtpu_spmd_s6_traces_total") == {
        (("channels", "16"), ("conv", "4"), ("dt_rank", "2"),
         ("publishes", publishes), ("state", "8")): 1.0
        for publishes in ("false", "true")}
    # Each kernel traced once for the shape: the calls are jitted inline.
    assert samples("hvdtpu_spmd_s6_kernel_traces_total") == {
        (("channels", "128"), ("chunk", "128"), ("kernel", kernel),
         ("operand_dtype", "float32"), ("state", "8"), ("tokens", "128")): 1.0
        for kernel in (s6.KERNEL_FWD, s6.KERNEL_BWD)}
    assert samples("hvdtpu_spmd_diff_attention_traces_total") == {
        (("cross", cross), ("head_dim", "16"), ("kv_pairs", "1"),
         ("pairs", "1"), ("window", window)): 1.0
        for cross, window in (("false", "8"), ("false", "0"), ("true", "0"))}
    assert samples("hvdtpu_spmd_shared_values_total") == {
        (("producer", "2"), ("readers", "1"), ("value", "s6_scan")): 1.0,
        (("producer", "3"), ("readers", "1"), ("value", "diff_kv")): 1.0}


# ---- (c) hvd.metrics() in SPMD mode -----------------------------------------

def compiles(function: str, stage: str = "backend_compile") -> float:
    return sample_value(hvd.metrics(), "hvdtpu_spmd_compiles_total",
                        function=function, stage=stage) or 0.0


def test_spmd_metrics_count_compiles_by_function(spmd4):
    def _counted_step(x):
        return hvd.allreduce(x.sum(), name="s")

    step = hvd.run_step(_counted_step, in_specs=hvd.batch_spec(0),
                        out_specs=hvd.REPLICATED)
    assert compiles("_counted_step") == 0
    step(hvd.shard_batch(np.ones((8, 3), np.float32)))
    assert compiles("_counted_step") == 1
    assert compiles("_counted_step", "trace") == 1
    assert compiles("_counted_step", "lower") == 1
    step(hvd.shard_batch(np.ones((8, 3), np.float32)))
    assert compiles("_counted_step") == 1          # the same step: no compile
    step(hvd.shard_batch(np.ones((16, 3), np.float32)))
    assert compiles("_counted_step") == 2          # a new shape: one more
    m = hvd.metrics()
    assert sample_value(m, "hvdtpu_spmd_compile_seconds_total",
                        function="_counted_step", stage="trace") > 0
    assert sample_value(m, "hvdtpu_spmd_shard_batch_calls_total") == 3
    assert sample_value(m, "hvdtpu_spmd_shard_batch_bytes_total") \
        == (8 + 8 + 16) * 3 * 4
    phases = {lb["phase"] for _, lb, _ in
              m["hvdtpu_spmd_init_seconds"]["samples"]}
    assert phases == {"backend", "mesh", "compile_cache"}
    assert sample_value(m, "hvdtpu_spmd_compile_cache_misses_total") == 0


def test_spmd_metrics_dump_round_trips(spmd4):
    hvd.run_step(lambda x: hvd.allreduce(x.sum()),
                 in_specs=hvd.batch_spec(0), out_specs=hvd.REPLICATED)(
                     hvd.shard_batch(np.ones((4, 2), np.float32)))
    text = hvd.metrics_dump()
    assert "# TYPE hvdtpu_spmd_compiles_total counter" in text
    assert parse_prometheus_text(text) == hvd.metrics()


def test_listeners_leave_with_shutdown(make_runtime):
    from jax._src import monitoring

    before = (len(monitoring.get_event_listeners()),
              len(monitoring.get_event_time_span_listeners()))
    make_runtime(devices=jax.devices()[:4])
    assert len(monitoring.get_event_listeners()) == before[0] + 1
    hvd.shutdown()
    assert (len(monitoring.get_event_listeners()),
            len(monitoring.get_event_time_span_listeners())) == before
    assert not hvd.is_initialized()


# ---- (d) hvd.start_timeline / hvd.stop_timeline ------------------------------

def watcher_threads() -> list:
    return [t for t in threading.enumerate()
            if t.name == spmd_recorder.WATCHER_THREAD]


def test_timeline_writes_spans_on_the_wall_clock(spmd4, tmp_path,
                                                 monkeypatch):
    monkeypatch.setattr(spmd_recorder, "SPAN_RING", 16)
    step = hvd.run_step(lambda x: hvd.allreduce(x.sum()),
                        in_specs=hvd.batch_spec(0), out_specs=hvd.REPLICATED)
    batch = np.ones((8, 3), np.float32)
    step(hvd.shard_batch(batch))
    assert not watcher_threads()            # no timeline: no extra thread
    assert hvd.runtime.recorder().spans is None
    path = str(tmp_path / "timeline.json")
    t0 = time.time_ns()
    hvd.start_timeline(path)
    assert len(watcher_threads()) == 1
    for _ in range(12):
        step(hvd.shard_batch(batch)).block_until_ready()
    step(hvd.shard_batch(np.ones((16, 3), np.float32)))    # a compile
    assert len(hvd.runtime.recorder().spans) == 16         # the ring's bound
    hvd.stop_timeline()
    t1 = time.time_ns()
    assert not watcher_threads()
    assert hvd.runtime.recorder().spans is None

    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    assert {"init/backend", "init/mesh", "init/compile_cache", "shard_batch",
            "batch_ready", "compile/trace", "compile/lower",
            "compile/backend_compile"} <= set(by_name)
    for e in by_name["shard_batch"] + by_name["batch_ready"]:
        a, b = e["args"]["start_ns"], e["args"]["end_ns"]
        assert t0 <= a <= b <= t1                   # time.time_ns, in ns
        assert e["ph"] == "X" and e["ts"] == pytest.approx(a / 1e3)
        assert e["dur"] == pytest.approx((b - a) / 1e3)
    # A batch is ready after shard_batch returned it, from that moment on
    # (the ring may have dropped an older one of a pair).
    assert by_name["batch_ready"][-1]["args"]["start_ns"] \
        == by_name["shard_batch"][-1]["args"]["end_ns"]
    compiled = by_name["compile/backend_compile"][-1]["args"]
    assert compiled["function"] == "<lambda>"
    assert compiled["cause"] == "new shapes"
    # The device trace lies beside it, and the file says when it started.
    meta = doc["metadata"]
    assert meta["xplane"].startswith(path + ".xplane")
    assert meta["xplane"].endswith(".xplane.pb")
    assert t0 <= meta["profile_start_time"] <= t1


def test_batch_ready_waits_for_the_restored_batch(spmd4, tmp_path,
                                                  monkeypatch):
    """A leaf that crosses flat (rank 3 or more) is ready when the array the
    step takes is: the watcher is handed shard_batch's result, not the
    ``[N, rest]`` array that crossed."""
    waited = []
    wait = jax.block_until_ready

    def waits(tree):
        out = wait(tree)
        if threading.current_thread().name == spmd_recorder.WATCHER_THREAD:
            waited.append((tree, time.time_ns()))
        return out

    monkeypatch.setattr(jax, "block_until_ready", waits)
    images = np.arange(8 * 4 * 4 * 3, dtype=np.uint8).reshape(8, 4, 4, 3)
    labels = np.arange(8, dtype=np.int32)
    path = tmp_path / "timeline.json"
    hvd.start_timeline(str(path))
    placed = hvd.shard_batch((images, labels))
    hvd.stop_timeline()
    (tree, ready_ns), = waited
    assert tree is placed
    assert placed[0].shape == images.shape
    m = hvd.metrics()
    assert sample_value(m, "hvdtpu_spmd_shard_batch_leaves_total",
                        path="flat") == 1
    assert sample_value(m, "hvdtpu_spmd_shard_batch_leaves_total",
                        path="direct") == 1
    spans = {e["name"]: e["args"] for e in
             json.loads(path.read_text())["traceEvents"]}
    assert spans["batch_ready"]["start_ns"] == spans["shard_batch"]["end_ns"]
    assert spans["batch_ready"]["end_ns"] >= ready_ns


def test_compile_causes(spmd4, tmp_path):
    def _caused(x):
        return hvd.allreduce(x.sum())

    step = hvd.run_step(_caused, in_specs=hvd.batch_spec(0),
                        out_specs=hvd.REPLICATED)
    x = np.ones((8, 3), np.float32)
    hvd.start_timeline(str(tmp_path / "t.json"))
    step(x)                                     # un-placed
    step(hvd.shard_batch(x))                    # the same shapes, placed
    step(hvd.shard_batch(np.ones((16, 3), np.float32)))
    hvd.stop_timeline()
    with open(tmp_path / "t.json") as f:
        causes = [e["args"]["cause"] for e in json.load(f)["traceEvents"]
                  if e["name"] == "compile/backend_compile"
                  and e["args"]["function"] == "_caused"]
    assert causes == ["first call", "new shardings", "new shapes"]


def test_shutdown_stops_a_running_timeline(make_runtime, tmp_path):
    make_runtime(devices=jax.devices()[:4])
    path = tmp_path / "left_running.json"
    hvd.start_timeline(str(path))
    hvd.shutdown()
    assert not watcher_threads()
    assert "traceEvents" in json.loads(path.read_text())


# ---- (e) metadata only --------------------------------------------------------

def test_scopes_change_no_computation(spmd4, monkeypatch):
    import contextlib

    def run():
        step, params, opt_state, data = gpt_step("full")
        jaxpr = jax.make_jaxpr(step)(params, opt_state, data)
        return str(jaxpr), step(params, opt_state, data)

    with_scopes, out = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without, out_plain = run()
    assert not any("hvd_optimizer" in n for n in op_names(*gpt_step("full")))
    # A jaxpr prints no name stack: the two are the same text, kernel names
    # apart, and the same numbers.
    strip = functools.partial(re.sub, r"name=hvd_\w+", "name=k")
    assert strip(with_scopes) == strip(without)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(out_plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

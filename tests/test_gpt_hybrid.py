"""``models/gpt.py`` with layers that differ in kind: state-space mixers
beside attention, a gated feed-forward, no rotary embedding, a tied head and
the four scalars, held to the plain float32 reference
(``benchmarks/reference/gpt_hybrid_dp.py``: the recurrence one token a step,
nothing imported from the program); and the default configuration held to
the parent's parameter tree.

Tolerance of the comparison with the reference: both sides are float32 at
the highest matmul precision and differ by the order of sums (chunks against
tokens, flash-free dense attention on both sides), so each gradient leaf
agrees to 2e-4 of its largest element (seen: 3e-5 on ``A_log``, whose
gradient sums a whole sequence of small terms, under 1e-6 elsewhere); a
dropped ``D x`` or gate term, a missing multiplier or a rotary embedding
left on moves the logits by 0.3 of the largest or more (the last tests).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.models.decoder.mixers import ssm
from benchmarks.reference import gpt_hybrid_dp as reference
from benchmarks.reference import gpt_latent_moe_hybrid_dp as grouped_reference

SCALARS = dict(embedding_multiplier=12.0, attention_multiplier=1 / 64,
               residual_multiplier=0.22, logits_scaling=8.0)
HYBRID = dict(
    vocab_size=128, num_layers=3, num_heads=4, num_kv_heads=2, head_dim=8,
    embed_dim=32, mlp_dim=64, dtype=jnp.float32, tp_axis=None, sp_axis=None,
    attention="dense", norm_eps=1e-5,
    layer_kinds=("ssm", "attention", "ssm"), ssm_heads=4, ssm_head_dim=16,
    ssm_state=8, ssm_groups=1, ssm_conv=4, ssm_chunk=16, gated_mlp=True,
    rope=False, tie_embeddings=True, **SCALARS)
B, S = 2, 40            # two chunks and a half


def _data(seed=0, vocab=128, shape=(B, S)):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, shape, dtype=np.int32)
    targets = np.roll(tokens, -1, axis=-1)
    targets[..., -1] = -1
    positions = np.broadcast_to(np.arange(shape[-1], dtype=np.int32),
                                shape).copy()
    return tokens, targets, positions


# One program a configuration and shape, not one an operation.
@functools.partial(jax.jit, static_argnums=0)
def _loss_and_grad(cfg, params, data):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(gpt.loss_fn)(params, *data, cfg)


def _reference(cfg, params, data):
    with jax.default_matmul_precision("highest"):
        # A program of its own a call: a test may swap the reference's mixer.
        return jax.jit(jax.value_and_grad(lambda p: reference.shard_loss(
            p, *data[:2], norm_eps=cfg.norm_eps, ssm_state=cfg.ssm_state,
            **SCALARS)))(params)


@pytest.mark.parametrize("groups", [1, 2])
def test_hybrid_model_matches_the_reference(groups, monkeypatch):
    # The gated norm runs over each group's channels (PR 55). The Granite
    # reference has the one group its model has and norms the whole inner
    # width; at two groups it gets the by-group mixer of the reference that
    # has them, the same lines but for the norm.
    if groups > 1:
        monkeypatch.setattr(reference, "_ssm_mixer",
                            grouped_reference.mamba_mixer)
    cfg = gpt.GPTConfig(**{**HYBRID, "ssm_groups": groups})
    params = gpt.init_params(jax.random.PRNGKey(1), cfg)
    data = _data()
    loss, grads = _loss_and_grad(cfg, params, data)
    want, want_grads = _reference(cfg, params, data)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=2e-4 * float(jnp.abs(w).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("remat", ["full"])
def test_remat_leaves_loss_and_gradients_alone(remat):
    cfg = gpt.GPTConfig(**HYBRID)
    params = gpt.init_params(jax.random.PRNGKey(2), cfg)
    data = _data(1)
    loss, grads = _loss_and_grad(cfg, params, data)
    loss1, grads1 = _loss_and_grad(dataclasses.replace(cfg, remat=remat),
                                   params, data)
    np.testing.assert_allclose(loss, loss1, rtol=1e-6)
    for g, g1 in zip(jax.tree.leaves(grads), jax.tree.leaves(grads1)):
        np.testing.assert_allclose(g, g1, rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(g).max()))


def test_flash_kernel_serves_the_attention_layer():
    """The attention multiplier goes onto q before the kernel, whose own
    scale is one over the square root of head_dim: the flash path and the
    dense reference path agree."""
    cfg = gpt.GPTConfig(**HYBRID)
    params = gpt.init_params(jax.random.PRNGKey(3), cfg)
    data = _data(2)
    loss, grads = _loss_and_grad(cfg, params, data)
    loss1, grads1 = _loss_and_grad(
        dataclasses.replace(cfg, attention="flash"), params, data)
    np.testing.assert_allclose(loss, loss1, rtol=1e-5)
    for g, g1 in zip(jax.tree.leaves(grads), jax.tree.leaves(grads1)):
        np.testing.assert_allclose(g, g1, rtol=0,
                                   atol=1e-4 * float(jnp.abs(g).max()))


def test_tied_head_receives_both_gradients():
    cfg = gpt.GPTConfig(**HYBRID)
    params = gpt.init_params(jax.random.PRNGKey(4), cfg)
    assert "lm_head" not in params and "lm_head" not in gpt.param_specs(cfg)
    data = _data(3)
    # The same matrix under two names, differentiated apart: the tied
    # parameter's gradient is the sum of the gather's and the head's.
    untied = dataclasses.replace(cfg, tie_embeddings=False)
    split = dict(params, lm_head=params["embed"].T)
    _, apart = _loss_and_grad(untied, split, data)
    _, grads = _loss_and_grad(cfg, params, data)
    for part in (apart["embed"], apart["lm_head"].T):
        assert float(jnp.abs(part).max()) > 0
    np.testing.assert_allclose(
        grads["embed"], apart["embed"] + apart["lm_head"].T, rtol=1e-5,
        atol=1e-6 * float(jnp.abs(grads["embed"]).max()))


@pytest.mark.parametrize("change", [
    dict(embedding_multiplier=6.0), dict(attention_multiplier=None),
    dict(residual_multiplier=1.0), dict(logits_scaling=1.0),
    dict(rope=True)])
def test_each_scalar_and_the_rotary_switch_move_the_logits(change):
    cfg = gpt.GPTConfig(**HYBRID)
    if "rope" in change:
        # At one sixty-fourth the tiny model's attention is nearly uniform
        # and where a key sits hardly matters: the usual scale for this one.
        cfg = dataclasses.replace(cfg, attention_multiplier=None)
    params = gpt.init_params(jax.random.PRNGKey(5), cfg)
    tokens, _, positions = _data(4)
    with jax.default_matmul_precision("highest"):
        logits, other = (
            jax.jit(lambda p: gpt.forward(p, tokens, positions, c))(params)
            for c in (cfg, dataclasses.replace(cfg, **change)))
    # Float32 noise is 1e-6 of the largest logit; the least of the five
    # changes (the rotary embedding, one layer in three) moves them by 0.3.
    assert float(jnp.abs(other - logits).max()) \
        > 5e-2 * float(jnp.abs(logits).max()), change


@pytest.mark.parametrize("leaf", ["D", "norm", "conv_b", "dt_bias", "A_log"])
def test_every_small_parameter_of_the_mixer_reaches_the_loss(leaf):
    """A dropped ``D x``, gate norm, convolution bias or decay would leave
    its parameter without a gradient."""
    cfg = gpt.GPTConfig(**HYBRID)
    params = gpt.init_params(jax.random.PRNGKey(6), cfg)
    _, grads = _loss_and_grad(cfg, params, _data(5))
    for layer in (0, 2):
        assert float(jnp.abs(grads["layers"][layer]["ssm"][leaf]).max()) > 0


def test_default_configuration_keeps_the_parents_tree_and_specs():
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, head_dim=8, embed_dim=32, mlp_dim=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"embed", "out_norm", "lm_head", "layers"}
    for layer in params["layers"]:
        assert set(layer) == {"attn_norm", "wq", "wk", "wv", "wo",
                              "mlp_norm", "w_up", "w_down"}
    specs = gpt.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(params)
    assert specs["layers"][0]["wq"] == P(None, "tp", None)
    assert specs["layers"][0]["w_down"] == P("tp", None)
    assert cfg.kind(0) == cfg.kind(1) == "attention"
    # The hybrid's specs follow its tree too, a state-space mixer replicated.
    hybrid = gpt.GPTConfig(**HYBRID)
    specs = gpt.param_specs(hybrid)
    tree = gpt.init_params(jax.random.PRNGKey(0), hybrid)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(tree)
    assert all(s == P() for s in specs["layers"][0]["ssm"].values())
    assert tree["layers"][0]["ssm"]["in_proj"].shape == (32, 64 + 80 + 4)
    assert set(tree["layers"][1]) == {"attn_norm", "wq", "wk", "wv", "wo",
                                      "mlp_norm", "w_gate", "w_up", "w_down"}


def test_initialisation_is_the_published_one():
    cfg = gpt.GPTConfig(**{**HYBRID, "ssm_heads": 64, "ssm_head_dim": 2})
    ssm = gpt.init_params(jax.random.PRNGKey(7), cfg)["layers"][0]["ssm"]
    a = np.exp(ssm["A_log"])
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.max() - a.min() > 8
    dt = np.asarray(jax.nn.softplus(ssm["dt_bias"]))
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    assert np.log10(dt.max() / dt.min()) > 1.2          # log-uniform
    np.testing.assert_array_equal(ssm["D"], 1.0)
    np.testing.assert_array_equal(ssm["norm"], 1.0)
    assert np.abs(ssm["conv_w"]).max() <= 0.5


@pytest.mark.parametrize("kinds", [("ssm",), ("attention", "mamba")])
def test_layer_kinds_must_name_every_layer(kinds):
    cfg = gpt.GPTConfig(**{**HYBRID, "num_layers": 2, "layer_kinds": kinds})
    with pytest.raises(ValueError, match="layer_kinds"):
        gpt.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="layer_kinds"):
        gpt.param_specs(cfg)


@pytest.mark.parametrize("axis", ["tp", "sp"])
def test_state_space_layer_refuses_a_bound_tp_or_sp_axis(make_runtime, axis):
    """No silent fallback: under sp a rank would scan its sequence shard
    from a zero state, under tp norm a shard of the heads."""
    make_runtime(mesh_shape={"dp": 4, axis: 2})
    cfg = gpt.GPTConfig(**{**HYBRID, f"{axis}_axis": axis})
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens, _, positions = _data(0, shape=(4, 32))
    data = P("dp", "sp") if axis == "sp" else P("dp")
    step = hvd.run_step(
        lambda p, t, pos: gpt.forward(p, t, pos, cfg),
        in_specs=(hvd.REPLICATED, data, data), out_specs=data)
    with pytest.raises(ValueError, match=f"'{axis}' axis is bound"):
        step(params, tokens, positions)


def test_hybrid_step_under_data_parallel_shard_map(make_runtime):
    """The normal path: ``hvd.run_step`` over a dp mesh, the scan's carry
    entering its loop with the batch's varying type; the dp-averaged loss and
    the gradient equal the single-device ones."""
    make_runtime(mesh_shape={"dp": 8})
    cfg = gpt.GPTConfig(**HYBRID, remat="full")
    params = gpt.init_params(jax.random.PRNGKey(8), cfg)
    data = _data(6, shape=(8, 32))

    def body(p, batch):
        loss, grads = jax.value_and_grad(gpt.loss_fn)(p, *batch, cfg)
        return hvd.allreduce(loss, op=hvd.Average), grads

    with jax.default_matmul_precision("highest"):
        loss, grads = hvd.run_step(
            body, in_specs=(hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)(params, hvd.shard_batch(data))
    # Each rank's loss is the mean over its own sequence's 31 targets; all
    # ranks hold as many, so the mean of means is the global mean.
    want, want_grads = _loss_and_grad(cfg, params, data)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g / 8, w, rtol=0,
                                   atol=1e-4 * float(jnp.abs(w).max()))
    fams = hvd.metrics()
    sample = [s for s in fams["hvdtpu_spmd_ssm_layer_traces_total"]["samples"]
              if s[1] == {"heads": "4", "head_dim": "16", "state": "8",
                          "groups": "1", "chunk": "16"}]
    assert sample and sample[0][2] >= 2


def test_full_remat_keeps_the_scans_output(make_runtime):
    """What a checkpointed state-space block hands to its backward pass
    beside its input: the scan's output, 4 H P bytes a token in float32, and
    the feed-forward's up product; the attention block the flash pair."""
    make_runtime(devices=jax.devices()[:1])
    cfg = gpt.GPTConfig(**{**HYBRID, "attention": "flash", "remat": "full",
                           "layer_kinds": ("ssm", "attention", "attention")})
    # Shapes no other test of this file traces: JAX splits a block it has
    # split before from its cache, without asking the policy.
    data = _data(7, shape=(3, 128))    # the flash kernels pad to 128
    jax.make_jaxpr(lambda p: jax.value_and_grad(gpt.loss_fn)(p, *data, cfg))(
        gpt.init_params(jax.random.PRNGKey(0), cfg))
    family = hvd.metrics()["hvdtpu_spmd_remat_saved_bytes_total"]
    kept = {labels["name"]: value for _, labels, value in family["samples"]
            if labels["mode"] == "full"}
    tokens = 3 * 128
    assert kept == {
        "ssm_scan_out": tokens * ssm.inner(cfg) * 4,
        # One state-space and one attention block split (the second
        # attention block shares the first's), an up product each.
        "ffn_pre_activation": 2 * tokens * cfg.mlp_dim * 4,
        "flash_out": tokens * cfg.num_heads * cfg.head_dim * 4,
        "flash_lse": tokens * cfg.num_heads * 4}

"""The decoder-hybrid-decoder stack (``phi-4-mini-flash-reasoning``) through
``models/gpt.py`` against its plain reference
(``benchmarks/reference/gpt_sambay_dp.py``) on seeded random weights: values
published by one layer and read by later ones through the one carry, across
checkpointed blocks; Mamba-1 through the selective scan's kernels; Gated
Memory Units; differential attention under a window, whole and as
cross-attention; LayerNorm. And the test that ties the benchmark's cut (six
of the 32 layers, an eighth of the vocabulary) to the model."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import gpt
from horovod_tpu.models.gpt import LayerSpec
from horovod_tpu.models.decoder import parts
from horovod_tpu.models.decoder.mixers import diff_attention
from horovod_tpu.ops.attention import default_attention, repeat_kv_heads

from benchmarks.jobs import gpt_sambay_dp as job
from benchmarks.reference import gpt_sambay_dp as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, VOCAB, EPS = 4, 32, 1e-5
KEPT = (0, 1, 16, 17, 18, 19)       # the benchmark's six layers, of 32


def sambay(layers, **over):
    """``layers``: the reference's ``Layer``s, or ``LayerSpec``s outright."""
    base = dict(
        vocab_size=VOCAB, num_layers=len(layers), num_heads=4, num_kv_heads=2,
        head_dim=4, embed_dim=16, mlp_dim=32, dtype=jnp.float32,
        tp_axis=None, sp_axis=None, attention="flash", remat="full",
        layers=tuple(layer if isinstance(layer, LayerSpec)
                     else job.layer_spec(layer) for layer in layers),
        tie_embeddings=True, norm_kind="layer", norm_eps=EPS, s6_inner=24,
        s6_dt_rank=2, ssm_state=8, ssm_conv=4)
    base.update(over)
    return gpt.GPTConfig(**base)


def sambay12():
    """Twelve layers by the published rule (``test_decoder_modules``)."""
    return sambay(reference.published_layers(12, 2, WINDOW))


def seeded(cfg, seed=0):
    """Random values of deviation 0.3 in the parameter tree's shapes, made
    on the host (the tree's own seeded values cost a compile of 150 random
    draws): biases, lambdas and norm weights that are not zeros and ones."""
    shapes = jax.eval_shape(lambda: gpt.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    rng = np.random.default_rng(seed)
    return jax.tree.map(lambda leaf: jnp.asarray(
        0.3 * rng.standard_normal(leaf.shape), leaf.dtype), shapes)


def batch(shape=(1, 16), vocab=VOCAB, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, shape).astype(np.int32)
    targets = np.roll(tokens, -1, axis=-1)
    targets[:, -1] = -1
    positions = np.broadcast_to(np.arange(shape[1], dtype=np.int32), shape)
    return tokens, targets, positions


def held_to_the_reference(cfg, layers, params, data, **model):
    loss, grad = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_fn(p, *data, cfg)))(params)
    with jax.default_matmul_precision("highest"):
        want, ref = jax.jit(jax.value_and_grad(
            lambda p: reference.shard_loss(p, *data[:2], layers=layers,
                                           norm_eps=EPS, **model)))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    got = jax.tree_util.tree_leaves_with_path(grad)
    for (path, g), r in zip(got, jax.tree.leaves(ref), strict=True):
        np.testing.assert_allclose(
            g, r, rtol=2e-3, atol=2e-4 * float(jnp.max(jnp.abs(r))) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
    return grad


def test_twelve_layers_by_the_published_rule():
    """All five kinds, each published value read by two layers, so that a
    producer's gradient is a sum of its readers' cotangents, through
    checkpointed blocks and the kernels."""
    layers = reference.published_layers(12, 2, WINDOW)
    assert [layer.kind for layer in layers] == [
        "mamba", "window", "mamba", "window", "mamba", "window", "mamba",
        "full", "gmu", "cross", "gmu", "cross"]
    cfg = sambay(layers, attention="dense")
    grad = held_to_the_reference(cfg, layers, seeded(cfg), batch())
    # The producers' own parameters have a gradient that is not nought.
    assert float(jnp.max(jnp.abs(grad["layers"][6]["s6"]["A_log"]))) > 0
    assert float(jnp.max(jnp.abs(grad["layers"][7]["wk"]))) > 0


def test_the_six_kept_layers_over_the_held_rows():
    """The benchmark's selection (the published layers 0, 1, 16, 17, 18, 19
    with their published indices) through the flash kernels, and the cut of
    the vocabulary: the program holds the embedding's first ``VOCAB`` rows,
    the reference the whole tied matrix with the other rows' logits left
    out; tokens are drawn from the held rows. Loss and every gradient
    agree, and no gradient reaches a row that is not held."""
    layers = tuple(reference.published_layers(32, 2, WINDOW)[i] for i in KEPT)
    cfg = sambay(layers)
    whole = seeded(dataclasses.replace(cfg, vocab_size=2 * VOCAB))
    held = {**whole, "embed": whole["embed"][:VOCAB]}
    data = batch()
    loss, grad = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_fn(p, *data, cfg)))(held)
    with jax.default_matmul_precision("highest"):
        want, ref = jax.jit(jax.value_and_grad(
            lambda p: reference.shard_loss(
                p, *data[:2], layers=layers, norm_eps=EPS, vocab=VOCAB)))(
                    whole)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    assert not np.any(ref["embed"][VOCAB:])
    ref = {**ref, "embed": ref["embed"][:VOCAB]}
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grad),
                            jax.tree.leaves(ref), strict=True):
        np.testing.assert_allclose(
            g, r, rtol=2e-3, atol=2e-4 * float(jnp.max(jnp.abs(r))) + 1e-9,
            err_msg=jax.tree_util.keystr(path))


def test_the_cut_is_the_models():
    """The rule at the published depth, the kept indices' kinds and
    ``lambda_init``, the configuration file's account of itself, and the
    orders ``layer_plan`` refuses."""
    layers = reference.published_layers(32, 2, 512)
    kinds = [layer.kind for layer in layers]
    assert [kinds.count(kind) for kind in reference.KINDS] == [9, 8, 1, 7, 7]
    assert [layer.depth for layer in layers if layer.publishes] == [16, 17]
    assert [(layers[i].kind, layers[i].window) for i in KEPT] == [
        ("mamba", None), ("window", 512), ("mamba", None), ("full", None),
        ("gmu", None), ("cross", None)]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        config = json.load(f)
    assert tuple(config["layer_indices"]) == KEPT
    specs = tuple(job.layer_spec(layers[i]) for i in KEPT)
    assert [spec.depth for spec in specs] == [None, 1, None, 17, None, 19]
    for spec in specs[1::2]:
        assert diff_attention.lambda_init(spec.depth) == pytest.approx(
            0.8 - 0.6 * np.exp(-0.3 * spec.depth))
        assert reference.lambda_init(spec.depth) \
            == diff_attention.lambda_init(spec.depth)
    # The parameters, reckoned again from the shapes.
    mamba = config["mamba"]
    cfg = gpt.GPTConfig(
        vocab_size=config["vocab_size"], num_layers=6,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], head_dim=64,
        embed_dim=config["hidden_size"], mlp_dim=config["intermediate_size"],
        tp_axis=None, sp_axis=None, layers=specs, tie_embeddings=True,
        norm_kind="layer", s6_inner=mamba["d_inner"],
        s6_dt_rank=mamba["dt_rank"], ssm_state=mamba["d_state"],
        ssm_conv=mamba["d_conv"])
    shapes = jax.eval_shape(
        lambda: gpt.init_params(jax.random.PRNGKey(0), cfg))

    def count(tree):
        return sum(leaf.size for leaf in jax.tree.leaves(tree))

    counted = config["parameters"]
    assert count(shapes) == counted["total"] == 697_073_792
    assert [count(layer) for layer in shapes["layers"]] == [
        counted["layers"][kind] for kind in (
            "mamba", "differential attention", "mamba",
            "differential attention", "gated memory unit", "cross")]
    assert count(shapes["layers"][0]["s6"]) == counted["mamba mixer"]["sum"]
    assert count(shapes["layers"][4]["gmu"]) \
        == counted["gated memory unit"]["sum"]
    # A reader before its producer, a value published twice, names that are
    # not the mixer's: refused before a parameter is made.
    gmu, s6, cross = specs[4], specs[2], specs[5]
    for plan, match in (
            ((gmu, s6), "reads .* no layer before it"),
            ((s6, gmu, s6), "already does"),
            ((specs[3], dataclasses.replace(cross, reads=())), "reads"),
            ((dataclasses.replace(specs[0], publishes=("diff_kv",)),),
             "may publish")):
        with pytest.raises(ValueError, match=match):
            gpt.param_specs(sambay(plan))


def test_differential_attention_is_two_maps_and_the_formula():
    """The mixer against ``default_attention`` twice and the combination
    written out, under a window and whole; a cross layer given the same keys
    and values computes what the layer that made them does."""
    cfg = sambay((), attention="dense")
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.standard_normal((2, 12, cfg.embed_dim)), jnp.float32)
    for window, depth in ((WINDOW, 1), (None, 17)):
        spec = LayerSpec(mixer="diff_attention", window=window, rope=False,
                         depth=depth, publishes=("diff_kv",))
        lp = jax.tree.map(lambda leaf: jnp.asarray(
            0.3 * rng.standard_normal(leaf.shape), jnp.float32),
            jax.eval_shape(lambda: diff_attention.SELF.init(
                jax.random.split(jax.random.PRNGKey(0), 4), cfg,
                lambda key, shape, fan_in: jnp.zeros(shape), None)))
        out, published = diff_attention.SELF.apply(cfg, spec, lp, h, None)
        q = jnp.einsum("bse,ehd->bshd", h, lp["wq"])
        k = jnp.einsum("bse,ehd->bshd", h, lp["wk"])
        v = jnp.einsum("bse,ehd->bshd", h, lp["wv"])
        v = jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], axis=-1)
        maps = [default_attention(
            q[:, :, i::2], repeat_kv_heads(k[:, :, i::2], 2),
            repeat_kv_heads(v, 2), causal=True, window=window)
            for i in (0, 1)]
        init = 0.8 - 0.6 * np.exp(-0.3 * depth)
        lam = np.exp(np.dot(lp["lambda_q1"], lp["lambda_k1"])) \
            - np.exp(np.dot(lp["lambda_q2"], lp["lambda_k2"])) + init
        o = maps[0] - lam * maps[1]
        o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + EPS) \
            * lp["subln"] * (1 - init)
        np.testing.assert_allclose(
            out, jnp.einsum("bshd,hde->bse", o, lp["wo"]), rtol=1e-4,
            atol=1e-5)
        cross = LayerSpec(mixer="diff_cross", rope=False, depth=depth,
                          window=None, reads=("diff_kv",))
        if window is None:
            again = diff_attention.CROSS.apply(
                cfg, cross, {k_: v_ for k_, v_ in lp.items()
                             if k_ not in ("wk", "wv")}, h, None,
                published["diff_kv"])
            np.testing.assert_allclose(again, out, rtol=1e-6)


def test_layer_norms_have_a_weight_and_a_bias():
    cfg = sambay((LayerSpec(mixer="attention", ff="gated"),))
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    for norm in (params["out_norm"], params["layers"][0]["attn_norm"],
                 params["layers"][0]["mlp_norm"]):
        assert set(norm) == {"weight", "bias"}
        assert np.all(norm["weight"] == 1) and not np.any(norm["bias"])
    assert jax.tree.structure(gpt.param_specs(cfg)) \
        == jax.tree.structure(params)
    rng = np.random.default_rng(0)
    x, w, b = (rng.standard_normal(shape).astype(np.float32)
               for shape in ((3, 5, 16), (16,), (16,)))
    centred = x - x.mean(-1, keepdims=True)
    np.testing.assert_allclose(
        parts._norm(cfg, x, {"weight": w, "bias": b}),
        centred / np.sqrt((centred ** 2).mean(-1, keepdims=True) + EPS) * w
        + b, rtol=1e-5, atol=1e-6)
    # The default is the RMSNorm it was: one vector a norm.
    rms = gpt.init_params(jax.random.PRNGKey(0), dataclasses.replace(
        cfg, norm_kind="rms"))
    assert rms["out_norm"].shape == (16,)
    with pytest.raises(ValueError, match="norm_kind"):
        gpt.init_params(jax.random.PRNGKey(0), dataclasses.replace(
            cfg, norm_kind="batch"))

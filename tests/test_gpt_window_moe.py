"""``models/gpt.py`` as Trinity-Mini's block (window attention beside full,
the rotary embedding on the window layers alone, a dense feed-forward before
expert blocks, a norm after each branch, a sigmoid router under a selection
bias, an ungated shared expert, a rank's share of the experts) against the
plain reference the benchmark keeps (``benchmarks/reference/
gpt_window_moe_dp.py``): float32, tiny sizes, seeded. And the per-layer
description itself: what ``layer_plan`` makes of the older inputs.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.models.gpt import LayerSpec

from benchmarks.reference import gpt_window_moe_dp as reference

B, S, WINDOW = 2, 32, 8
WINDOWS = (WINDOW, WINDOW, None, WINDOW)
DENSE_LAYERS = 1
RATE = 0.001


def trinity(**kw):
    plan = tuple(LayerSpec(window=w, rope=w is not None,
                           ff="gated" if i < DENSE_LAYERS else "experts")
                 for i, w in enumerate(WINDOWS))
    return gpt.GPTConfig(**{**dict(
        vocab_size=64, num_layers=len(WINDOWS), num_heads=4, num_kv_heads=2,
        head_dim=8, embed_dim=32, mlp_dim=64, expert_dim=16,
        dtype=jnp.float32, tp_axis=None, sp_axis=None, attention="dense",
        layers=plan, num_experts=8, experts_per_token=2, experts_held=4,
        first_expert=4, renormalize_experts=True, shared_expert_dim=16,
        shared_expert_gate=False, router_score="sigmoid", router_bias=True,
        route_scale=2.826, qk_head_norm=True, norm_eps=1e-5, post_norm=True,
        attention_gate=True, embedding_multiplier=math.sqrt(32)), **kw})


def seeded(cfg, seed=0):
    """Parameters with norm weights off one and biases off zero, so that a
    norm or a bias left out shows."""
    params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
    key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for layer in params["layers"]:
        for name in ("attn_norm", "mlp_norm", "mixer_post_norm",
                     "mlp_post_norm", "q_norm", "k_norm"):
            layer[name] = 1 + 0.2 * jax.random.normal(next(key),
                                                      layer[name].shape)
        if "moe" in layer:
            layer["moe"]["router_bias"] = 0.1 * jax.random.normal(
                next(key), layer["moe"]["router_bias"].shape)
    return params


def batch(cfg, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return tokens, targets, positions


def reference_loss(cfg, params, data):
    return reference.shard_loss(
        params, *data, windows=WINDOWS, dense_layers=DENSE_LAYERS,
        top_k=cfg.experts_per_token, route_scale=cfg.route_scale,
        first_expert=cfg.first_expert, rope_theta=cfg.rope_theta,
        norm_eps=cfg.norm_eps)


def assert_trees_close(got, want, rtol=2e-4, atol=2e-6):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("attention,remat", [
    ("dense", "none"), ("flash", "none"), ("flash", "full")])
def test_decoder_matches_the_reference(attention, remat):
    cfg = trinity(attention=attention, remat=remat)
    params, data = seeded(cfg), batch(trinity())
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True))(params)
    with jax.default_matmul_precision("highest"):
        (ref_loss, ref), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(cfg, p, data), has_aux=True))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_array_equal(aux["counts"],
                                  np.asarray(ref["counts"], np.int32))
    assert aux["counts"].shape == (len(WINDOWS) - DENSE_LAYERS, 8)
    # No auxiliary term is in the loss: both coefficients are zero.
    np.testing.assert_allclose(loss, aux["cross_entropy"], rtol=0)
    assert_trees_close(grads, ref_grads)
    for layer in grads["layers"][DENSE_LAYERS:]:
        assert not np.any(np.asarray(layer["moe"]["router_bias"]))


# What the reference must notice: each of these is one of the configuration's
# own mechanisms left out of the program.
@pytest.mark.parametrize("change", [
    dict(layers=tuple(dataclasses.replace(s, window=None)
                      for s in trinity().plan)),
    dict(layers=tuple(dataclasses.replace(s, rope=True)
                      for s in trinity().plan)),
    dict(layers=tuple(dataclasses.replace(s, rope=False)
                      for s in trinity().plan)),
    dict(post_norm=False), dict(route_scale=1.0), dict(router_bias=False),
    dict(shared_expert_dim=0), dict(router_score="softmax"),
    dict(renormalize_experts=False), dict(embedding_multiplier=1.0),
], ids=["no-window", "rope-everywhere", "rope-nowhere", "no-post-norm",
        "no-route-scale", "no-bias", "no-shared-expert", "softmax",
        "not-renormalised", "no-multiplier"])
def test_each_mechanism_left_out_misses_the_reference(change):
    cfg = trinity(**change)
    params, data, ref_loss = _shipped_case()
    loss = jax.jit(lambda p: gpt.loss_fn(p, *data, cfg))(params)
    assert abs(float(loss) - float(ref_loss)) > 1e-4 * float(ref_loss)


@functools.cache
def _shipped_case():
    """The shipped configuration's weights and batch and the reference's
    loss on them, which no change of the test above moves: made once."""
    params, data = seeded(trinity()), batch(trinity())
    with jax.default_matmul_precision("highest"):
        ref_loss, _ = jax.jit(
            lambda p: reference_loss(trinity(), p, data))(params)
    return params, data, ref_loss


def test_bias_is_state_adamw_leaves_alone_and_the_step_updates():
    """AdamW masked by ``gpt.trainable`` neither moves nor decays the
    biases; ``update_router_bias`` moves them as the reference does; every
    other leaf takes AdamW's first step."""
    cfg = trinity()
    params, data = seeded(cfg), batch(cfg)
    lr, decay, eps = 1e-2, 0.1, 1e-8
    opt = optax.masked(optax.adamw(lr, eps=eps, weight_decay=decay),
                       gpt.trainable)
    (_, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True))(params)
    stepped = jax.jit(lambda g, p: optax.apply_updates(
        p, opt.update(g, opt.init(p), p)[0]))(grads, params)
    before, after = reference.biases(params), reference.biases(stepped)
    assert len(before) == len(WINDOWS) - DENSE_LAYERS
    for b, a in zip(before, after):
        np.testing.assert_array_equal(a, b)
    # An unmasked AdamW would have decayed them.
    plain = optax.adamw(lr, eps=eps, weight_decay=decay)
    moved, _ = jax.jit(lambda g, p: plain.update(g, plain.init(p), p))(
        grads, params)
    assert np.any(np.asarray(reference.biases(moved)[0]))
    np.testing.assert_allclose(
        optax.global_norm(jax.tree.map(jnp.subtract, stepped, params)),
        reference.adamw_first_update_norm(params, grads, lr, decay, eps),
        rtol=1e-5)
    updated = gpt.update_router_bias(stepped, aux["counts"], RATE)
    want = reference.updated_biases(params, aux["counts"], RATE)
    for got, ref, was in zip(reference.biases(updated), want, before):
        np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-9)
        # Over the mean it lost a rate's worth, under it gained one, and
        # the biases' sum stays.
        assert np.abs(np.asarray(got - was)).max() <= 2 * RATE + 1e-9
        np.testing.assert_allclose(jnp.sum(got), jnp.sum(was), atol=1e-6)
    counts = np.asarray(aux["counts"][0], np.float32)
    step = np.asarray(reference.biases(updated)[0] - before[0])
    over = counts > counts.mean()
    assert (step[over] < step[~over].min()).all()
    # Nothing else moved.
    for name in ("embed", "lm_head"):
        np.testing.assert_array_equal(updated[name], stepped[name])
    np.testing.assert_array_equal(updated["layers"][1]["moe"]["router"],
                                  stepped["layers"][1]["moe"]["router"])


def test_trainable_marks_the_biases_alone():
    cfg = trinity()
    marks = gpt.trainable(gpt.init_params(jax.random.PRNGKey(0), cfg))
    flat, _ = jax.tree_util.tree_flatten_with_path(marks)
    off = [jax.tree_util.keystr(path) for path, keep in flat if not keep]
    assert off == [f"['layers'][{i}]['moe']['router_bias']"
                   for i in range(DENSE_LAYERS, len(WINDOWS))]


def test_specs_follow_the_tree_and_the_plan():
    cfg = trinity(tp_axis="tp")
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    specs = gpt.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(params)
    dense, sparse = params["layers"][0], params["layers"][1]
    assert set(dense) == {"attn_norm", "wq", "wk", "wv", "wo", "q_norm",
                          "k_norm", "mlp_norm", "mixer_post_norm",
                          "mlp_post_norm", "w_gate", "w_up", "w_down"}
    assert dense["w_up"].shape == (32, 64)
    assert set(sparse["moe"]) == {"router", "router_bias", "w_gate", "w_up",
                                  "w_down", "shared"}
    assert set(sparse["moe"]["shared"]) == {"w_gate", "w_up", "w_down"}
    # An expert is expert_dim wide where the dense layer is mlp_dim, and
    # the rank holds four of the router's eight.
    assert sparse["moe"]["w_up"].shape == (4, 32, 16)
    assert sparse["moe"]["router"].shape == (32, 8)
    assert sparse["moe"]["router_bias"].shape == (8,)
    assert specs["layers"][1]["moe"]["router_bias"] == P()
    assert specs["layers"][0]["mixer_post_norm"] == P()


def test_older_inputs_resolve_into_the_plan_in_one_place():
    base = dict(vocab_size=64, num_layers=4, num_heads=4, head_dim=8,
                embed_dim=32, mlp_dim=64)
    assert gpt.GPTConfig(**base).plan == (LayerSpec(),) * 4
    sparse = gpt.GPTConfig(**base, moe_every=2, gated_mlp=True, rope=False)
    assert [s.ff for s in sparse.plan] == ["gated", "experts"] * 2
    assert not any(s.rope for s in sparse.plan)
    hybrid = gpt.GPTConfig(**base, layer_kinds=("ssm", "gdn", "attention",
                                                "ssm"), moe_every=1)
    assert [s.mixer for s in hybrid.plan] == ["ssm", "gdn", "attention",
                                              "ssm"]
    assert {s.ff for s in hybrid.plan} == {"experts"}
    assert hybrid.kind(1) == "gdn" and hybrid.plan[2].window is None
    # The plan is the configuration's own: equal configurations share it.
    assert gpt.GPTConfig(**base, moe_every=2, gated_mlp=True,
                         rope=False).plan is sparse.plan


@pytest.mark.parametrize("bad,words", [
    (dict(layers=(LayerSpec(),) * 3), "for each of the 4"),
    (dict(layers=(LayerSpec(mixer="conv"),) * 4), "mixer one of"),
    (dict(layers=(LayerSpec(ff="experts_shared"),) * 4), "feed-forward one"),
    (dict(layers=(LayerSpec(mixer="ssm", window=4),) * 4),
         "on one of .*attention.* alone"),
    (dict(layers=(LayerSpec(window=0),) * 4), "at least one"),
    (dict(layers=(LayerSpec(),) * 4, moe_every=1), "leave"),
    (dict(layers=(LayerSpec(),) * 4, layer_kinds=("attention",) * 4),
     "leave"),
    (dict(layer_kinds=("attention",) * 3), "layer_kinds must name"),
])
def test_a_plan_that_cannot_be_is_refused_by_name(bad, words):
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=4, num_heads=4, head_dim=8,
                        embed_dim=32, mlp_dim=64, **bad)
    with pytest.raises(ValueError, match=words):
        gpt.init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_router_probe_hands_out_what_each_router_read_and_gave(remat):
    """Under ``router_probe`` the loss's parts hold every expert block's
    router input and float32 outputs, and the outputs are the reference's
    product on those inputs; the loss and its gradient are as without."""
    cfg = trinity(remat=remat)
    probed = dataclasses.replace(cfg, router_probe=True)
    params, data = seeded(cfg), batch(cfg)
    (loss, aux), grad = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, probed), has_aux=True))(params)
    (plain, plain_aux), plain_grad = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True))(params)
    assert set(aux) - set(plain_aux) == {"router_inputs", "router_logits"}
    np.testing.assert_array_equal(loss, plain)
    assert_trees_close(grad, plain_grad, rtol=0, atol=0)
    blocks = len(WINDOWS) - DENSE_LAYERS
    assert aux["router_inputs"].shape == (blocks, B * S, cfg.embed_dim)
    assert aux["router_logits"].shape == (blocks, B * S, cfg.num_experts)
    assert aux["router_logits"].dtype == jnp.float32
    routers = [p["moe"]["router"] for p in params["layers"] if "moe" in p]
    for router, h, got in zip(routers, aux["router_inputs"],
                              aux["router_logits"], strict=True):
        np.testing.assert_allclose(got, reference.router_logits(h, router),
                                   rtol=1e-6, atol=1e-6)
    # The blocks read different activations.
    assert not np.allclose(aux["router_inputs"][0], aux["router_inputs"][1])


def test_a_checkpointed_block_makes_its_shared_experts_products_once(
        products_like):
    """Under ``remat="full"`` an expert block keeps its shared expert's gate
    and up products before the activation (``moe_shared_pre_activation``,
    PR 59): the differentiated step holds two a block, the forward pass's,
    and none in what the backward pass makes again (before PR 59 two; the
    norm after the branch keeps the sublayer's output, not what the shared
    expert's own backward pass reads)."""
    # A width no other product of the step has.
    cfg = trinity(remat="full", shared_expert_dim=24)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: gpt.loss_fn(p, *batch(cfg), cfg)))(params).jaxpr
    assert products_like(jaxpr, (B, S, cfg.embed_dim), (cfg.embed_dim, 24)) \
        == (2 * (len(WINDOWS) - DENSE_LAYERS), 0)


def test_config_field_count():
    # CHANGES.md says how many there were and are; a new one is said there.
    assert len(dataclasses.fields(gpt.GPTConfig)) == 84
    assert len(dataclasses.fields(LayerSpec)) == 7


def _traced(attention, sp_bound, window):
    cfg = gpt.GPTConfig(
        vocab_size=64, num_layers=1, num_heads=4, num_kv_heads=2, head_dim=8,
        embed_dim=32, mlp_dim=64, dtype=jnp.float32, tp_axis=None,
        sp_axis="sp", attention=attention,
        layers=(LayerSpec(window=window),))
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 256), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(256), (2, 256))
    forward = lambda p, t, pos: gpt.forward(p, t, pos, cfg)
    if sp_bound:
        seq = P(None, "sp")
        forward = jax.shard_map(forward, mesh=hvd.mesh(),
                                in_specs=(P(), seq, seq), out_specs=seq)
    return str(jax.make_jaxpr(forward)(params, tokens, positions))


def test_ring_refuses_a_window_layer_under_a_bound_sp_axis(make_runtime):
    make_runtime(mesh_shape={"dp": 1, "sp": 2},
                 devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="'ring'.*no window.*window=64"):
        _traced("ring", True, 64)
    # Without the axis "ring" is the flash kernel, window and all, and a
    # full layer still rides the ring.
    assert "hvd_flash_fwd" in _traced("ring", False, 64)
    assert "ppermute" in _traced("ring", True, None)


def test_ulysses_hands_the_window_to_the_kernel_it_calls(make_runtime):
    make_runtime(mesh_shape={"dp": 1, "sp": 2},
                 devices=jax.devices()[:2])
    banded, full = (_traced("ulysses", True, w) for w in (64, None))
    assert "all_to_all" in banded and "hvd_flash_fwd" in banded
    # The band is in the kernel: the two programs differ there alone.
    assert "hvd_flash_fwd" in full and banded != full


def test_window_layers_carry_scopes_of_their_own():
    cfg = trinity(attention="flash", remat="full")
    params, data = seeded(cfg), batch(cfg)
    text = jax.jit(jax.grad(lambda p: gpt.loss_fn(p, *data, cfg))).lower(
        params).as_text(debug_info=True)
    for scope in ("layer0)/attn_window", "layer2)/attn/",
                  "attn_window/post_norm", "moe/post_norm", "mlp/post_norm",
                  "attn/post_norm", "rematted_computation/attn_window"):
        assert scope in text, scope
    assert "layer2)/attn_window" not in text
    assert "layer1)/attn/" not in text

"""``models/gpt.py`` as Nemotron-H's stack (blocks of one sublayer each: a
Mamba-2 mixer whose gated norm runs a group at a time, a grouped-query
attention mixer without a position embedding, or an expert feed-forward
whose un-gated squared-ReLU experts run in a latent narrower than the stream
beside a shared expert on the stream, routed by a sigmoid under a selection
bias) against the plain reference the benchmark keeps (``benchmarks/
reference/gpt_latent_moe_hybrid_dp.py``): float32, tiny sizes, seeded, the
normal ``loss_and_aux`` path. And the cuts a chip's share of the model
makes, tied to the whole: a group of Mamba heads, a key/value head with its
query heads.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import gpt
from horovod_tpu.models.gpt import LayerSpec
from horovod_tpu.models.decoder.mixers import attention, ssm

from benchmarks.reference import gpt_latent_moe_hybrid_dp as reference

B, S = 2, 32
PATTERN = "MEM*E"
BLOCKS = {"M": LayerSpec(mixer="ssm", ff=None),
          "*": LayerSpec(mixer="attention", rope=False, ff=None),
          "E": LayerSpec(mixer=None, ff="experts")}
ROUTE_SCALE = 2.5


def nemotron(pattern=PATTERN, **kw):
    """All three kinds of block; 4 Mamba heads in 2 groups, 4 query heads on
    2 key/value heads, 6 of 16 experts a token in a latent of 16 under a
    stream of 32."""
    return gpt.GPTConfig(**{**dict(
        vocab_size=64, num_layers=len(pattern), num_heads=4, num_kv_heads=2,
        head_dim=8, embed_dim=32, mlp_dim=64, dtype=jnp.float32,
        tp_axis=None, sp_axis=None, attention="dense",
        layers=tuple(BLOCKS[c] for c in pattern), norm_eps=1e-5,
        ssm_heads=4, ssm_head_dim=8, ssm_state=8, ssm_groups=2, ssm_conv=4,
        ssm_chunk=16, num_experts=16, experts_per_token=6, expert_dim=24,
        moe_latent_dim=16, shared_expert_dim=40, shared_expert_gate=False,
        expert_activation="relu2", renormalize_experts=True,
        router_score="sigmoid", router_bias=True, route_scale=ROUTE_SCALE),
        **kw})


def seeded(cfg, seed=0):
    """Parameters with every norm's weight off one (a norm left out, or one
    over other channels, shows), selection biases that are not all alike and
    an embedding of the stream's own size."""
    params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
    params["embed"] = params["embed"] * 50.0
    key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 32))

    def off_one(w):
        return 1 + 0.2 * jax.random.normal(next(key), w.shape)

    for layer in params["layers"]:
        for name in ("ssm_norm", "attn_norm", "mlp_norm"):
            if name in layer:
                layer[name] = off_one(layer[name])
        if "ssm" in layer:
            layer["ssm"]["norm"] = off_one(layer["ssm"]["norm"])
        if "moe" in layer:
            layer["moe"]["router_bias"] = 0.05 * jax.random.normal(
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
        params, *data[:2], top_k=cfg.experts_per_token,
        route_scale=cfg.route_scale, first_expert=cfg.first_expert,
        ssm_state=cfg.ssm_state, norm_eps=cfg.norm_eps)


def assert_trees_close(got, want, rtol=2e-4, atol=2e-6):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("attention,remat,held", [
    ("dense", "none", None), ("dense", "none", 4), ("flash", "full", 4)],
    ids=["every-expert", "a-share", "a-share-flash-remat"])
def test_decoder_matches_the_reference(attention, remat, held):
    """Loss, counts and every gradient leaf (the two latent projections',
    the un-gated experts' two tensors', the shared expert's, the grouped
    norm's among them), with every expert held and with experts 4 to 8 of
    the 16; nothing reaches the selection bias."""
    cfg = nemotron(attention=attention, remat=remat, experts_held=held,
                   first_expert=4 if held else 0)
    params, data = seeded(cfg), batch(cfg)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True))(params)
    with jax.default_matmul_precision("highest"):
        (ref_loss, ref), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(cfg, p, data), has_aux=True))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_array_equal(aux["counts"],
                                  np.asarray(ref["counts"], np.int32))
    assert aux["counts"].shape == (PATTERN.count("E"), 16)
    assert_trees_close(grads, ref_grads)
    for layer in grads["layers"]:
        if "moe" in layer:
            assert not np.any(np.asarray(layer["moe"]["router_bias"]))
            for name in ("router", "latent_down", "latent_up", "w_up"):
                assert np.any(np.asarray(layer["moe"][name])), name


@pytest.mark.parametrize("kind,keys", [
    ("M", {"ssm", "ssm_norm"}),
    ("*", {"wq", "wk", "wv", "wo", "attn_norm"}),
    ("E", {"moe", "mlp_norm"})])
def test_a_block_of_one_sublayer_has_one_norm_and_no_other_parameter(
        kind, keys):
    """A block's parameters are its sublayer's and one norm's, in the tree
    and in the specs alike; an un-gated expert is two matrices, the shared
    one too, and the latent's projections lie beside them."""
    cfg = nemotron(pattern=kind)
    layer, = jax.eval_shape(lambda: gpt.init_params(
        jax.random.PRNGKey(0), cfg))["layers"]
    spec, = gpt.param_specs(cfg)["layers"]
    assert set(layer) == set(spec) == keys
    assert jax.tree.structure(layer) == jax.tree.structure(
        spec, is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec))
    if kind == "E":
        moe = layer["moe"]
        assert set(moe) == {"router", "router_bias", "w_up", "w_down",
                            "latent_down", "latent_up", "shared"}
        assert set(moe["shared"]) == {"w_up", "w_down"}
        assert moe["w_up"].shape == (16, 16, 24)
        assert moe["w_down"].shape == (16, 24, 16)
        assert moe["latent_down"].shape == (32, 16)
        assert moe["shared"]["w_up"].shape == (32, 40)


def test_residual_scalings_follow_the_sublayers():
    cfg = nemotron(pattern="ME", residual_scaling=True)
    mixer, experts = gpt.init_params(jax.random.PRNGKey(0), cfg)["layers"]
    assert "mixer_res" in mixer and "mlp_res" not in mixer
    assert "mlp_res" in experts and "mixer_res" not in experts
    data = batch(cfg)
    assert np.isfinite(jax.jit(lambda p: gpt.loss_fn(p, *data, cfg))(
        {**seeded(cfg), "layers": [mixer, experts]}))


def test_a_layer_with_neither_sublayer_is_refused():
    with pytest.raises(ValueError, match="either of them None but not both"):
        nemotron(layers=(LayerSpec(mixer=None, ff=None),) * len(PATTERN)).plan
    with pytest.raises(ValueError, match="expert_activation must be one of"):
        gpt.init_params(jax.random.PRNGKey(0),
                        nemotron(expert_activation="relu3"))


def test_a_checkpointed_latent_block_routes_once(equations_of):
    """Under ``remat="full"`` what the backward pass makes again of an
    expert block holds no router's product, no top-k and no sort (PR 54's
    kept routing, with the experts' operand apart from the router's)."""
    cfg = nemotron(pattern="EE", remat="full", experts_held=4,
                   first_expert=4)
    params, data = seeded(cfg), batch(cfg)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: gpt.loss_fn(p, *data, cfg)))(params).jaxpr
    tokens, stream = B * S, cfg.embed_dim
    again = {"sort": 0, "top_k": 0, "router": 0}
    for eqn, inside in equations_of(jaxpr):
        if not inside:
            continue
        name = eqn.primitive.name
        shapes = [v.aval.shape for v in eqn.invars]
        if name in ("sort", "top_k"):
            again[name] += 1
        elif name == "dot_general" and shapes == [
                (tokens, stream), (stream, cfg.num_experts)]:
            again["router"] += 1
    assert again == {"sort": 0, "top_k": 0, "router": 0}


@pytest.mark.parametrize("held", [16, 4])
def test_a_checkpointed_latent_block_makes_its_first_products_once(
        products_like, held):
    """Under ``remat="full"`` an expert block keeps the latent's
    down-projection and the shared expert's pre-activation
    (``moe_latent_in``, ``moe_shared_pre_activation``, PR 59): the
    differentiated step holds one of each a block, the forward pass's, and
    what the backward pass makes again holds neither (before PR 59 it held
    both, ``(2, 2)``); every expert held or a share of them."""
    cfg = nemotron(pattern="EE", remat="full", experts_held=held,
                   first_expert=0 if held == 16 else 4)
    params, data = seeded(cfg), batch(cfg)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: gpt.loss_fn(p, *data, cfg)))(params).jaxpr
    stream = (B, S, cfg.embed_dim)
    assert products_like(jaxpr, stream, (cfg.embed_dim, cfg.moe_latent_dim)) \
        == (2, 0)
    assert products_like(jaxpr, stream,
                         (cfg.embed_dim, cfg.shared_expert_dim)) == (2, 0)


# What the reference must notice: each of these is one of the model's
# mechanisms left out or another in its place.
@pytest.mark.parametrize("change", [
    dict(expert_activation="silu"), dict(route_scale=1.0),
    dict(renormalize_experts=False), dict(rope=True)],
    ids=lambda c: next(iter(c)))
def test_a_mechanism_left_out_is_another_model(change):
    cfg = nemotron()
    params, data = seeded(cfg), batch(cfg)
    other = nemotron(**change)
    if "rope" in change:
        other = nemotron(layers=tuple(
            dataclasses.replace(s, rope=True) for s in cfg.plan))
    if "expert_activation" in change:
        # A gated form wants a gate matrix: the up matrix in its place.
        for layer in params["layers"]:
            if "moe" in layer:
                layer["moe"]["w_gate"] = layer["moe"]["w_up"]
                layer["moe"]["shared"]["w_gate"] = \
                    layer["moe"]["shared"]["w_up"]
    loss = jax.jit(lambda p: gpt.loss_fn(p, *data, other))(params)
    want = jax.jit(lambda p: gpt.loss_fn(p, *data, cfg))(seeded(cfg))
    assert abs(float(loss) - float(want)) > 1e-3 * abs(float(want))


# The cut: a chip's share of the heads is a smaller model whose parameters
# are slices of the whole's.

def _mixer_inputs(cfg, seed=3):
    h = jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.embed_dim))
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return h, positions


def mamba_share(cfg, p, group):
    """``(configuration, parameters)`` of one group of a Mamba mixer's heads:
    the group's columns of ``[z | x | B | C | dt]``, its convolution
    channels, its heads' ``dt_bias``, ``A_log`` and ``D``, its channels of
    the gated norm and its rows of ``W_out``."""
    per = cfg.ssm_heads // cfg.ssm_groups
    inner, n = ssm.inner(cfg), cfg.ssm_state
    wide = per * cfg.ssm_head_dim
    gn = cfg.ssm_groups * n
    chan = np.arange(group * wide, (group + 1) * wide)
    state = np.arange(group * n, (group + 1) * n)
    heads = np.arange(group * per, (group + 1) * per)
    conv = np.concatenate([chan, inner + state, inner + gn + state])
    cols = np.concatenate([chan, inner + conv,
                           2 * inner + 2 * gn + heads])
    share = {"in_proj": p["in_proj"][:, cols],
             "conv_w": p["conv_w"][:, conv], "conv_b": p["conv_b"][conv],
             "dt_bias": p["dt_bias"][heads], "A_log": p["A_log"][heads],
             "D": p["D"][heads], "norm": p["norm"][chan],
             "out_proj": p["out_proj"][chan]}
    return dataclasses.replace(cfg, ssm_heads=per, ssm_groups=1), share


def test_the_mamba_heads_shares_add_up_to_the_uncut_mixer():
    """One group of heads each, the outputs of all the shares add up to the
    uncut mixer's and to the uncut reference's: the gated norm runs over a
    group's channels, so a group is a mixer of its own up to ``W_out``'s
    sum (under a norm over the whole inner width, the mixer's before
    PR 55, the uncut mixer is not the reference's and no sum of shares is
    it)."""
    cfg = nemotron()
    p = seeded(cfg)["layers"][0]["ssm"]
    h, _ = _mixer_inputs(cfg)
    whole = jax.jit(lambda p, h: ssm.apply(cfg, None, p, h, None))(p, h)
    with jax.default_matmul_precision("highest"):
        want = reference.mamba_mixer(h, p, cfg.ssm_state, cfg.norm_eps)
    np.testing.assert_allclose(whole, want, rtol=2e-5, atol=2e-5)
    share_cfg = mamba_share(cfg, p, 0)[0]
    one = jax.jit(lambda p, h: ssm.apply(share_cfg, None, p, h, None))
    total = sum(one(mamba_share(cfg, p, g)[1], h)
                for g in range(cfg.ssm_groups))
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # The share's tree is what a model of that many heads initialises.
    share = mamba_share(cfg, p, 1)[1]
    made = jax.eval_shape(lambda: ssm.init(
        jax.random.PRNGKey(0)[None], share_cfg,
        lambda k, s, f: jnp.zeros(s), None))
    assert jax.tree.map(lambda a: a.shape, share) \
        == jax.tree.map(lambda a: a.shape, made)


def test_the_attention_heads_shares_add_up_to_the_uncut_mixer():
    """A key/value head with its query heads each (no position embedding,
    so nothing but the heads' own columns enters), the shares' outputs add
    up to the uncut mixer's and the uncut reference's."""
    cfg = nemotron()
    spec = BLOCKS["*"]
    lp = seeded(cfg)["layers"][PATTERN.index("*")]
    h, positions = _mixer_inputs(cfg)
    with jax.default_matmul_precision("highest"):
        want = reference.attention_mixer(h, lp)
    np.testing.assert_allclose(
        attention.apply(cfg, spec, lp, h, positions), want, rtol=2e-5,
        atol=2e-5)
    group = cfg.num_heads // cfg.kv_heads
    share_cfg = dataclasses.replace(cfg, num_heads=group, num_kv_heads=1)
    total = 0.0
    for g in range(cfg.kv_heads):
        q = slice(g * group, (g + 1) * group)
        total = total + attention.apply(share_cfg, spec, {
            "wq": lp["wq"][:, q], "wk": lp["wk"][:, g:g + 1],
            "wv": lp["wv"][:, g:g + 1], "wo": lp["wo"][q]}, h, positions)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)

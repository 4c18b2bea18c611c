"""``models/gpt.py`` as ZAYA1's layer (a CCA attention sublayer: latent q and
k mixed by two stacked causal convolutions, a q/k mean, an L2 norm a head
under a key temperature, half of the value from the token before; an expert
sublayer with one expert a token chosen by an MLP router that carries a
state from layer to layer under a selection bias; a learned scaling where a
sublayer joins the stream; a rank's share of the experts) against the plain
reference the benchmark keeps (``benchmarks/reference/gpt_cca_moe_dp.py``):
float32, tiny sizes, seeded.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.models.gpt import LayerSpec
from horovod_tpu.models.decoder import experts
from horovod_tpu.models.decoder.mixers import cca

from benchmarks.reference import gpt_cca_moe_dp as reference

B, S, LAYERS = 2, 32, 2
HEADS, KV_HEADS, DIM, ROTARY = 4, 2, 16, 8
EXPERTS, HELD = 4, 2
RATE = 0.001


def zaya(**kw):
    plan = tuple(LayerSpec(mixer="cca", ff="experts") for _ in range(LAYERS))
    return gpt.GPTConfig(**{**dict(
        vocab_size=64, num_layers=LAYERS, num_heads=HEADS,
        num_kv_heads=KV_HEADS, head_dim=DIM, embed_dim=32, mlp_dim=32,
        dtype=jnp.float32, tp_axis=None, sp_axis=None, attention="dense",
        layers=plan, num_experts=EXPERTS, experts_per_token=1,
        experts_held=HELD, first_expert=0, router_kind="mlp", router_dim=16,
        router_bias=True, residual_scaling=True, rope_theta=5e6,
        rotary_dim=ROTARY, tie_embeddings=True, norm_eps=1e-5), **kw})


@functools.lru_cache(maxsize=None)
def _seeded(held, seed):
    cfg = zaya(experts_held=held)

    def make():
        params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
        key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 256))

        def off(leaf, by):
            return leaf + by * jax.random.normal(next(key), leaf.shape)

        for layer in params["layers"]:
            for name in ("cca_norm", "mlp_norm"):
                layer[name] = off(layer[name], 0.2)
            layer["cca"]["temp"] = off(layer["cca"]["temp"], 0.3)
            for name in ("mixer_res", "mlp_res"):
                layer[name] = {k: off(v, 0.2)
                               for k, v in layer[name].items()}
            router = layer["moe"]["router"]
            for name in ("down_b", "norm", "b1", "b2", "carry"):
                if name in router:
                    router[name] = off(router[name], 0.2)
            layer["moe"]["router_bias"] = off(layer["moe"]["router_bias"],
                                              0.05)
        return params

    return jax.jit(make)()


def seeded(cfg, seed=0):
    """Parameters with every vector that starts at one or zero moved off
    it (norm weights, the residual scaling, the routers' biases and carried
    state's weights, the key temperatures, the selection biases), so that
    one left out shows. A tree of the caller's own: the leaves are made
    once for each number of experts held (nothing else of ``zaya``'s
    arguments shapes a parameter)."""
    return jax.tree.map(lambda x: x, _seeded(cfg.experts_held, seed))


def batch(cfg, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return tokens, targets, positions


def model(cfg):
    return dict(heads=cfg.num_heads, kv_heads=cfg.kv_heads,
                rope_theta=cfg.rope_theta, rotary_dim=cfg.rotary_dim,
                first_expert=cfg.first_expert, norm_eps=cfg.norm_eps)


def reference_loss(cfg, params, data):
    return reference.shard_loss(params, *data, **model(cfg))


@functools.lru_cache(maxsize=None)
def reference_side():
    """``(loss, parts, gradient)`` of the reference on the seeded
    parameters and batch, made once for the tests that compare with it."""
    cfg = zaya()
    with jax.default_matmul_precision("highest"):
        (loss, parts), grads = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(cfg, p, batch(cfg)), has_aux=True))(
                seeded(cfg))
    return loss, parts, grads


@functools.lru_cache(maxsize=None)
def program_side(**kw):
    """The same of the program under ``zaya(**kw)``."""
    cfg = zaya(**kw)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *batch(cfg), cfg), has_aux=True))(
            seeded(cfg))
    return loss, aux, grads


def worst_leaf(got, want, atol=2e-6):
    """The largest miss over two trees' leaves, relative to the element
    over a floor of ``atol / GRAD_RTOL``."""
    return float(jax.jit(lambda got, want: jnp.max(jnp.stack([
        jnp.max(jnp.abs(g - w) / (jnp.abs(w) + atol / GRAD_RTOL))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        strict=True)])))(got, want))


# The tolerances: float32 on both sides, so what is left is the order of
# sums (the kernels' tiles, the sorted rows): 1e-5 on the loss, 2e-4 a
# gradient's element over a floor of 1e-2 of that.
LOSS_RTOL, GRAD_RTOL = 1e-5, 2e-4


# The program as the cell runs it: the flash kernels, blocks checkpointed
# (the router's state crosses their boundary).
SHIPPED = dict(attention="flash", remat="full")


def test_decoder_matches_the_reference():
    loss, aux, grads = program_side(**SHIPPED)
    ref_loss, ref, ref_grads = reference_side()
    np.testing.assert_allclose(loss, ref_loss, rtol=LOSS_RTOL)
    np.testing.assert_array_equal(aux["counts"],
                                  np.asarray(ref["counts"], np.int32))
    assert aux["counts"].shape == (LAYERS, EXPERTS)
    # One expert a token, and no auxiliary term in the loss.
    assert int(aux["counts"].sum()) == LAYERS * B * S
    np.testing.assert_allclose(loss, aux["cross_entropy"], rtol=0)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(ref_grads), strict=True):
        np.testing.assert_allclose(g, w, rtol=GRAD_RTOL, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))
    for layer in grads["layers"]:
        assert not np.any(np.asarray(layer["moe"]["router_bias"]))
        # Every new leaf has a gradient: none is dead weight.
        for leaf in jax.tree.leaves(
                [layer["cca"], layer["mixer_res"], layer["mlp_res"],
                 layer["moe"]["router"]]):
            assert np.any(np.asarray(leaf))


def test_bfloat16_fails_the_tolerances_float32_passes():
    """The control: the same comparison with the program in bfloat16 misses
    the loss's tolerance, and the gradients' by far."""
    loss, _, grads = program_side(dtype=jnp.bfloat16)
    ref_loss, _, ref_grads = reference_side()
    assert abs(float(loss) - float(ref_loss)) > 3 * LOSS_RTOL * float(
        ref_loss)
    assert worst_leaf(grads, ref_grads) > 100 * GRAD_RTOL
    assert worst_leaf(program_side(**SHIPPED)[2], ref_grads) < GRAD_RTOL


def test_first_adamw_step_and_the_bias_update_match_the_reference():
    params = seeded(zaya())
    lr, decay, eps = 1e-2, 0.1, 1e-8
    opt = optax.masked(optax.adamw(lr, eps=eps, weight_decay=decay),
                       gpt.trainable)
    (_, aux, grads), (_, ref, ref_grads) = program_side(**SHIPPED), \
        reference_side()

    @jax.jit
    def step(params, grads, counts):
        updates, _ = opt.update(grads, opt.init(params), params)
        return gpt.update_router_bias(optax.apply_updates(params, updates),
                                      counts, RATE)

    stepped = step(params, grads, aux["counts"])
    moved = jax.tree.map(jnp.subtract, stepped, params)
    for layer in moved["layers"]:
        layer["moe"].pop("router_bias")
    want = reference.adamw_first_update_norm(params, ref_grads, lr, decay,
                                             eps)
    np.testing.assert_allclose(optax.global_norm(moved), want, rtol=1e-4)
    for got, ref_bias in zip(
            reference.biases(stepped),
            reference.updated_biases(params, ref["counts"], RATE),
            strict=True):
        np.testing.assert_allclose(got, ref_bias, rtol=0, atol=1e-7)


@pytest.mark.parametrize("change", [
    dict(residual_scaling=False), dict(router_bias=False),
    dict(rotary_dim=None), dict(cca_taps=(1, 2)), dict(cca_taps=(2, 1)),
    dict(layers=tuple(LayerSpec(mixer="cca", rope=False, ff="experts")
                      for _ in range(LAYERS))),
], ids=["no-residual-scaling", "no-bias", "whole-head-rotary",
        "no-depthwise-tap", "no-grouped-tap", "no-rope"])
def test_each_mechanism_left_out_misses_the_reference(change):
    whole = zaya()
    params, data = seeded(whole), batch(whole)
    if "cca_taps" in change:    # the tap of the token before, cut off
        for layer in params["layers"]:
            for name, taps in zip(("conv0_w", "conv1_w"),
                                  change["cca_taps"]):
                layer["cca"][name] = layer["cca"][name][-taps:]
    loss = jax.jit(lambda p: gpt.loss_fn(p, *data, zaya(**change)))(params)
    ref_loss = reference_side()[0]
    assert abs(float(loss) - float(ref_loss)) > 1e-4 * float(ref_loss)


def test_the_router_state_reaches_the_next_layer():
    """Layer 1's router reads layer 0's down-projection through its
    ``carry``: at zero its outputs, and with them the logits, change; layer
    0 has no such vector."""
    cfg = zaya()
    params, data = seeded(cfg), batch(cfg)
    assert "carry" not in params["layers"][0]["moe"]["router"]
    cut = jax.tree.map(lambda x: x, params)
    cut["layers"][1]["moe"]["router"]["carry"] = jnp.zeros_like(
        params["layers"][1]["moe"]["router"]["carry"])
    tokens, _, positions = data
    forward = jax.jit(lambda p: gpt.forward(p, tokens, positions, cfg))
    assert float(jnp.max(jnp.abs(forward(params) - forward(cut)))) > 1e-4
    # And the state is layer 0's z itself: the reference fed the same.
    with jax.default_matmul_precision("highest"):
        ref_loss = jax.jit(lambda p: reference_loss(cfg, p, data)[0])(cut)
    np.testing.assert_allclose(
        jax.jit(lambda p: gpt.loss_fn(p, *data, cfg))(cut), ref_loss,
        rtol=LOSS_RTOL)


def mixer_inputs(cfg, seed=3):
    p = seeded(cfg)["layers"][0]["cca"]
    h = jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.embed_dim))
    return p, h, jnp.broadcast_to(jnp.arange(S), (B, S))


def test_q_k_v_are_the_references_and_half_the_value_is_the_token_before(
        monkeypatch):
    cfg = zaya()
    p, h, positions = mixer_inputs(cfg)
    seen = []
    monkeypatch.setattr(cca, "_attention",
                        lambda cfg, q, k, v, window=None: seen.append(
                            (q, k, v)) or q)
    q, k, v = jax.jit(lambda p, h: (cca.apply(
        cfg, cfg.plan[0], p, h, positions), seen[-1])[1])(p, h)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda h, p: reference.cca_qkv(
            h, p, positions, heads=HEADS, kv_heads=KV_HEADS,
            rope_theta=cfg.rope_theta, rotary_dim=ROTARY))(h, p)
        own = jnp.einsum("bse,ef->bsf", h, p["wv"])
    for got, ref in zip((q, k, v), want, strict=True):
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)
    # Head 0 of the two value heads: the token's own; head 1: token t - 1's
    # (nothing at the start).
    np.testing.assert_allclose(v[:, :, 0], own[..., :DIM], rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(v[:, 1:, 1], own[:, :-1, DIM:], rtol=2e-5,
                               atol=2e-6)
    assert not np.any(np.asarray(v[:, 0, 1]))
    # A head of q has length sqrt(D); of k, that times exp(temp).
    np.testing.assert_allclose(jnp.linalg.norm(q, axis=-1), np.sqrt(DIM),
                               rtol=1e-5)
    np.testing.assert_allclose(
        jnp.linalg.norm(k, axis=-1),
        np.sqrt(DIM) * jnp.broadcast_to(jnp.exp(p["temp"]), k.shape[:3]),
        rtol=1e-5)


def test_the_mixer_is_causal():
    """Token 20 moved: no output before it moves, the one at it and the
    one after it (the convolutions' and the value's second tap) do."""
    cfg = zaya()
    p, h, positions = mixer_inputs(cfg)
    moved = h.at[:, 20].add(1.0)
    mixer = jax.jit(lambda h: cca.apply(cfg, cfg.plan[0], p, h,
                                             positions))
    out, out_moved = mixer(h), mixer(moved)
    np.testing.assert_array_equal(out[:, :20], out_moved[:, :20])
    assert float(jnp.min(jnp.max(jnp.abs(out - out_moved)[:, 20:],
                                 axis=-1))) > 1e-6


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-1 and 2-3 of one expert sublayer, each run as a rank's
    share by the program, sum to what the reference gives holding all
    four; the router (its state included), the bias, the choice and the
    counts are the whole router's in each."""
    uncut = seeded(zaya(experts_held=None))["layers"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(5), (B, S, 32))
    state = jax.random.normal(jax.random.PRNGKey(6), (B, S, 16))
    total, counts = 0.0, []
    for first in (0, HELD):
        cfg = zaya(first_expert=first)
        share = dict(uncut, **{name: uncut[name][first:first + HELD]
                               for name in ("w_gate", "w_up", "w_down")})
        y, aux, z = jax.jit(lambda m, cfg=cfg: experts.apply(
            cfg, None, m, h, state))(share)
        total = total + y
        counts.append(aux["counts"])
    with jax.default_matmul_precision("highest"):
        want, ref_counts, ref_z = jax.jit(
            lambda m: reference.expert_block(
                h.reshape(-1, 32), m, state.reshape(-1, 16), 1e-5))(uncut)
    np.testing.assert_allclose(total.reshape(-1, 32), want, rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(z.reshape(-1, 16), ref_z, rtol=2e-5,
                               atol=2e-6)
    for c in counts:
        np.testing.assert_array_equal(c, np.asarray(ref_counts, np.int32))
    # Both shares had work: the sum is no single share's output.
    assert min(int(counts[0][:HELD].sum()), int(counts[0][HELD:].sum())) > 0


@pytest.mark.parametrize("change,what", [
    (dict(tp_axis="dp"), "'dp' axis is bound"),
    (dict(sp_axis="dp", attention="ring"), "'dp' axis is bound"),
])
def test_a_bound_tp_or_sp_axis_is_refused_by_name(spmd8, change, what):
    cfg = zaya(**change)
    p, h, positions = mixer_inputs(zaya())

    def body(h):
        return cca.apply(cfg, cfg.plan[0], p, h, positions)

    with pytest.raises(ValueError, match=what):
        hvd.run_step(body, in_specs=hvd.REPLICATED,
                     out_specs=hvd.REPLICATED).lower(h)


def test_a_window_on_a_cca_layer_is_refused():
    with pytest.raises(ValueError, match="a CCA layer has none yet"):
        zaya(layers=(LayerSpec(mixer="cca", window=8, ff="experts"),) * 2
             ).plan


def test_the_step_counts_its_cca_and_router_traces(spmd8):
    cfg = zaya(remat="full")
    params, data = seeded(cfg), batch(cfg)
    jax.jit(lambda p: gpt.loss_fn(p, *data, cfg)).lower(params)
    fams = hvd.metrics()
    cca, = {tuple(sorted(labels.items())) for _, labels, _ in
            fams["hvdtpu_spmd_cca_traces_total"]["samples"]}
    assert dict(cca) == dict(heads=str(HEADS), kv_heads=str(KV_HEADS),
                             head_dim=str(DIM), taps0="2", taps1="2",
                             rotary_dim=str(ROTARY))
    routers = {(labels["router"], labels["state"]) for _, labels, _ in
               fams["hvdtpu_spmd_moe_layer_traces_total"]["samples"]}
    assert routers == {("mlp", "0"), ("mlp", "1")}

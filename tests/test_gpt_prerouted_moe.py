"""``models/gpt.py`` as SmallThinker's block (every block's router on the
block's own un-normed input, before attention; ReLU-gated experts of which
the rank holds a share under a softmax over the chosen; a full layer without
position embedding before three window layers with the rotary one) against
the plain reference the benchmark keeps (``benchmarks/reference/
gpt_prerouted_moe_dp.py``): float32, tiny sizes, seeded, the normal
``loss_and_aux`` path.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.models.gpt import LayerSpec
from horovod_tpu.models.decoder import experts
from horovod_tpu.observability import sample_value

from benchmarks.reference import gpt_prerouted_moe_dp as reference

B, S, WINDOW = 2, 32, 8
WINDOWS = (None, WINDOW, WINDOW, WINDOW)        # the published order
ROPES = (False, True, True, True)


def smallthinker(**kw):
    """6 heads over 2 (a group of 3: no power of two), 4 of 8 experts held,
    3 a token."""
    plan = tuple(LayerSpec(window=w, rope=r, ff="experts")
                 for w, r in zip(WINDOWS, ROPES))
    return gpt.GPTConfig(**{**dict(
        vocab_size=64, num_layers=len(WINDOWS), num_heads=6, num_kv_heads=2,
        head_dim=8, embed_dim=32, mlp_dim=16, dtype=jnp.float32,
        tp_axis=None, sp_axis=None, attention="dense", layers=plan,
        num_experts=8, experts_per_token=3, experts_held=4, first_expert=4,
        renormalize_experts=True, router_reads="block_input",
        expert_activation="relu", rope_theta=1.5e6), **kw})


def seeded(cfg, seed=0):
    """Parameters with norm weights off one, so that a norm left out (or a
    router that read the normed stream) shows, and an embedding of the
    stream's own size."""
    params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
    params["embed"] = params["embed"] * 50.0
    key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    for layer in params["layers"]:
        for name in ("attn_norm", "mlp_norm"):
            layer[name] = 1 + 0.2 * jax.random.normal(next(key),
                                                      layer[name].shape)
    return params


def batch(cfg, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return tokens, targets, positions


def reference_loss(cfg, params, data):
    return reference.shard_loss(
        params, *data, windows=WINDOWS, ropes=ROPES,
        top_k=cfg.experts_per_token, first_expert=cfg.first_expert,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps)


def assert_trees_close(got, want, rtol=2e-4, atol=2e-6):
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(g, w, rtol=rtol, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("attention,remat", [
    ("dense", "none"), ("flash", "none"), ("flash", "full")])
def test_decoder_matches_the_reference(attention, remat):
    """Loss, counts and every gradient leaf: the router's, which reaches the
    stream before attention, among them; under ``remat="full"`` the
    recomputed router reads the block's kept input."""
    cfg = smallthinker(attention=attention, remat=remat)
    params, data = seeded(cfg), batch(cfg)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True))(params)
    with jax.default_matmul_precision("highest"):
        (ref_loss, ref), ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference_loss(cfg, p, data), has_aux=True))(params)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_array_equal(aux["counts"],
                                  np.asarray(ref["counts"], np.int32))
    assert aux["counts"].shape == (len(WINDOWS), 8)
    np.testing.assert_allclose(loss, aux["cross_entropy"], rtol=0)
    assert_trees_close(grads, ref_grads)
    for layer in grads["layers"]:
        assert np.any(np.asarray(layer["moe"]["router"]))


def test_the_references_blocks_of_rows_change_no_number(monkeypatch):
    """The reference makes its attention logits a block of query rows at a
    time and its head's logits a block of token rows at a time (what lets the
    cell's check run at the timed 16,384 tokens): four blocks of each give
    the loss and every gradient leaf one block gives."""
    cfg = smallthinker(num_layers=2, layers=smallthinker().plan[:2])
    params, data = seeded(cfg), batch(cfg)

    def loss_and_grad():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda p: reference.shard_loss(
                    p, *data, windows=WINDOWS[:2], ropes=ROPES[:2],
                    top_k=cfg.experts_per_token,
                    first_expert=cfg.first_expert, rope_theta=cfg.rope_theta,
                    norm_eps=cfg.norm_eps)[0]))(params)

    whole, whole_grads = loss_and_grad()
    monkeypatch.setattr(reference, "LOGIT_ELEMENTS", S * (S // 4))
    monkeypatch.setattr(reference, "HEAD_ROWS", B * S // 4)
    blocked, blocked_grads = loss_and_grad()
    np.testing.assert_allclose(blocked, whole, rtol=1e-6)
    assert_trees_close(blocked_grads, whole_grads, rtol=1e-5, atol=1e-7)
    monkeypatch.setattr(reference, "LOGIT_ELEMENTS", S * 5)
    with pytest.raises(ValueError, match="no whole number of blocks"):
        loss_and_grad()


# What the reference must notice: each of these is one of the configuration's
# own mechanisms left out of the program, or another model's in its place.
@pytest.mark.parametrize("change", [
    dict(router_reads="ff_input"), dict(expert_activation="silu"),
    dict(layers=tuple(dataclasses.replace(s, rope=True)
                      for s in smallthinker().plan)),
    dict(layers=tuple(dataclasses.replace(s, window=None)
                      for s in smallthinker().plan)),
    dict(renormalize_experts=False), dict(first_expert=0),
], ids=["router-fed-the-normed-stream", "silu", "rope-on-the-full-layer",
        "no-window", "not-renormalised", "another-share"])
def test_each_mechanism_left_out_misses_the_reference(change):
    cfg = smallthinker(**change)
    params, data, ref_loss = _shipped_case()
    loss = jax.jit(lambda p: gpt.loss_fn(p, *data, cfg))(params)
    assert abs(float(loss) - float(ref_loss)) > 1e-4 * float(ref_loss)


@functools.cache
def _shipped_case():
    """The shipped configuration's weights and batch and the reference's
    loss on them, which no change of the test above moves: made once."""
    params, data = seeded(smallthinker()), batch(smallthinker())
    with jax.default_matmul_precision("highest"):
        ref_loss, _ = jax.jit(
            lambda p: reference_loss(smallthinker(), p, data))(params)
    return params, data, ref_loss


def test_router_probe_hands_out_the_blocks_input():
    """Under ``router_probe`` a checkpointed block's ``router_input`` is the
    stream as it entered the block, in the stream's type, and its outputs
    the reference's product on that; the loss and its gradient are as
    without."""
    cfg = smallthinker(remat="full")
    probed = dataclasses.replace(cfg, router_probe=True)
    params, data = seeded(cfg), batch(cfg)
    (loss, aux), grad = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, probed), has_aux=True))(params)
    plain, plain_grad = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_fn(p, *data, cfg)))(params)
    np.testing.assert_array_equal(loss, plain)
    assert_trees_close(grad, plain_grad, rtol=0, atol=0)
    assert aux["router_inputs"].shape == (len(WINDOWS), B * S, cfg.embed_dim)
    assert aux["router_logits"].dtype == jnp.float32
    # Layer 0's router read the embedding's rows themselves.
    np.testing.assert_array_equal(
        aux["router_inputs"][0], params["embed"][data[0]].reshape(B * S, -1))
    for layer, h, got in zip(params["layers"], aux["router_inputs"],
                             aux["router_logits"], strict=True):
        np.testing.assert_allclose(
            got, reference.router_logits(h, layer["moe"]["router"]),
            rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("bad,words", [
    (dict(router_reads="mixer_output"), "router_reads must be one of"),
    (dict(router_kind="mlp"), "'mlp' router that reads the block's input"),
    (dict(expert_activation="gelu"), "activation 'gelu' is none of"),
])
def test_what_is_not_built_is_refused_by_name(bad, words):
    cfg = smallthinker(**bad)
    params = gpt.init_params(jax.random.PRNGKey(0), smallthinker())
    if bad.get("router_kind") == "mlp":
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match=words):
        gpt.loss_fn(params, *batch(cfg), cfg)


def test_the_early_router_has_a_scope_in_every_pass_and_a_trace_record(
        make_runtime):
    make_runtime(devices=jax.devices()[:1])
    # A configuration no other test traces: the record is made at trace
    # time, and JAX keeps a block's trace.
    cfg = smallthinker(attention="flash", remat="full", norm_eps=2e-6)
    params, data = seeded(cfg), batch(cfg)
    text = jax.jit(jax.grad(lambda p: gpt.loss_fn(p, *data, cfg))).lower(
        params).as_text(debug_info=True)
    for scope in ("jvp(layer0)/moe/router_early",
                  "jvp(layer3)/moe/router_early",
                  "transpose(jvp(layer0))/jvp(layer0)/checkpoint/moe/"
                  "router_early", "layer0)/attn/", "layer1)/attn_window/"):
        assert scope in text, scope
    # Forward and backward: the block keeps the router's outputs
    # (``moe_router_logits``) and no pass makes the product again.
    assert "rematted_computation/moe/router_early" not in text
    # The product is made before the mixer, outside its scope.
    assert "attn/moe/router_early" not in text
    assert "attn_window/moe/router_early" not in text
    assert sample_value(
        hvd.metrics(), "hvdtpu_spmd_moe_layer_traces_total", experts="8",
        held="4", router="linear_early", activation="relu",
        score="softmax") >= 1.0


def test_a_shared_expert_takes_the_experts_gate():
    """``expert_activation`` is said once: a shared expert beside ReLU-gated
    experts is ReLU-gated too."""
    cfg = smallthinker(shared_expert_dim=16, shared_expert_gate=False,
                       num_layers=1, layers=smallthinker().plan[:1])
    params = seeded(cfg)
    h = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.embed_dim))
    s = params["layers"][0]["moe"]["shared"]
    want = jnp.dot(jax.nn.relu(jnp.dot(h, s["w_gate"]))
                   * jnp.dot(h, s["w_up"]), s["w_down"])
    np.testing.assert_allclose(experts._shared_expert(cfg, s, h), want,
                               rtol=1e-5, atol=1e-5)


def test_an_early_router_under_a_bound_ep_axis_is_the_unsharded_block(
        make_runtime):
    """Experts over ``ep`` alone (every rank's group is the whole batch):
    the caller's outputs are gathered with the tokens, and the loss, the
    counts and every gradient are the unsharded block's (the experts' are
    ep shards)."""
    from jax.sharding import PartitionSpec as P

    make_runtime(mesh_shape={"ep": 4}, devices=jax.devices()[:4])
    whole = smallthinker(experts_held=None, first_expert=0, num_layers=2,
                         layers=smallthinker().plan[:2])
    cfg = dataclasses.replace(whole, ep_axis="ep")
    params = seeded(whole)
    tokens, targets, positions = (jnp.concatenate([x, x[::-1]])
                                  for x in batch(whole))
    value_and_grad = jax.value_and_grad(
        lambda p, *d: gpt.loss_and_aux(p, *d, cfg), has_aux=True)
    (want, want_aux), want_grads = jax.jit(jax.value_and_grad(
        lambda p, *d: gpt.loss_and_aux(p, *d, whole), has_aux=True))(
            params, tokens, targets, positions)
    specs = gpt.param_specs(cfg)
    (loss, aux), grads = hvd.run_step(
        value_and_grad, in_specs=(specs, P("ep"), P("ep"), P("ep")),
        out_specs=((P(), P()), specs))(params, tokens, targets, positions)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    np.testing.assert_array_equal(aux["counts"], want_aux["counts"])
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(w).max()))

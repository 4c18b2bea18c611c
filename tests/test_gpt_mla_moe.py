"""``models/gpt.py`` as Moonlight's layer (latent attention: a query/key head
of a no-position and a rotary part beside a value head of another width,
keys and values from a normed latent, one rotary key a token for all heads;
a dense SiLU-gated feed-forward, then expert sublayers under a sigmoid
router with a selection bias, renormalised and scaled weights and an ungated
shared expert; a rank's share of the experts) against the plain reference
the benchmark keeps (``benchmarks/reference/gpt_mla_moe_dp.py``): float32,
tiny sizes, seeded.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.models.gpt import LayerSpec
from horovod_tpu.models.decoder import experts
from horovod_tpu.models.decoder.mixers import mla

from benchmarks.reference import gpt_mla_moe_dp as reference

B, S, EMBED = 2, 32, 32
HEADS, NOPE, ROT, VALUE, RANK = 4, 8, 4, 8, 16
EXPERTS, HELD, TOP_K, SCALE = 16, 2, 3, 2.446
DENSE_LAYERS, LAYERS = 1, 3
RATE = 0.001


def moonlight(**kw):
    plan = tuple(LayerSpec(mixer="mla", ff="gated" if i < DENSE_LAYERS
                           else "experts") for i in range(LAYERS))
    return gpt.GPTConfig(**{**dict(
        vocab_size=64, num_layers=LAYERS, num_heads=HEADS, head_dim=NOPE,
        mla_rope_dim=ROT, mla_value_dim=VALUE, mla_kv_rank=RANK,
        embed_dim=EMBED, mlp_dim=48, expert_dim=16, shared_expert_dim=32,
        shared_expert_gate=False, dtype=jnp.float32, tp_axis=None,
        sp_axis=None, attention="dense", layers=plan, num_experts=EXPERTS,
        experts_per_token=TOP_K, experts_held=HELD, first_expert=0,
        router_score="sigmoid", router_bias=True, renormalize_experts=True,
        route_scale=SCALE, rope_theta=50000.0, norm_eps=1e-5), **kw})


@functools.lru_cache(maxsize=None)
def _seeded(held, seed):
    cfg = moonlight(experts_held=held)

    def make():
        params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
        key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

        def off(leaf, by):
            return leaf + by * jax.random.normal(next(key), leaf.shape)

        for layer in params["layers"]:
            for name in ("mla_norm", "mlp_norm"):
                layer[name] = off(layer[name], 0.2)
            layer["mla"]["kv_norm"] = off(layer["mla"]["kv_norm"], 0.2)
            if "moe" in layer:
                layer["moe"]["router_bias"] = off(
                    layer["moe"]["router_bias"], 0.05)
        return params

    return jax.jit(make)()


def seeded(cfg, seed=0):
    """Parameters with every vector that starts at one or zero moved off it
    (the norms' weights, the latent's norm, the selection biases), so that
    one left out shows; a tree of the caller's own."""
    return jax.tree.map(lambda x: x, _seeded(cfg.experts_held, seed))


def batch(cfg, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=-1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return tokens, targets, positions


def model(cfg):
    return dict(dense_layers=DENSE_LAYERS, top_k=cfg.experts_per_token,
                route_scale=cfg.route_scale, first_expert=cfg.first_expert,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps)


@functools.lru_cache(maxsize=None)
def reference_side():
    """``(loss, parts, gradient)`` of the reference on the seeded
    parameters and batch, made once for the tests that compare with it."""
    cfg = moonlight()
    with jax.default_matmul_precision("highest"):
        (loss, parts), grads = jax.jit(jax.value_and_grad(
            lambda p: reference.shard_loss(p, *batch(cfg), **model(cfg)),
            has_aux=True))(seeded(cfg))
    return loss, parts, grads


@functools.lru_cache(maxsize=None)
def program_side(**kw):
    """The same of the program under ``moonlight(**kw)``."""
    cfg = moonlight(**kw)
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *batch(cfg), cfg), has_aux=True))(
            seeded(cfg))
    return loss, aux, grads


# The tolerances: float32 on both sides, so what is left is the order of
# sums (the kernels' tiles, the sorted rows): 1e-5 on the loss, 2e-4 a
# gradient's element over a floor of 1e-2 of that.
LOSS_RTOL, GRAD_RTOL = 1e-5, 2e-4


def worst_leaf(got, want, atol=2e-6):
    """The largest miss over two trees' leaves, relative to the element
    over a floor of ``atol / GRAD_RTOL``."""
    return float(jax.jit(lambda got, want: jnp.max(jnp.stack([
        jnp.max(jnp.abs(g - w) / (jnp.abs(w) + atol / GRAD_RTOL))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                        strict=True)])))(got, want))


# The program as the cell runs it: the flash kernels at two widths, blocks
# checkpointed.
SHIPPED = dict(attention="flash", remat="full")


def test_decoder_matches_the_reference():
    loss, aux, grads = program_side(**SHIPPED)
    ref_loss, ref, ref_grads = reference_side()
    np.testing.assert_allclose(loss, ref_loss, rtol=LOSS_RTOL)
    np.testing.assert_array_equal(aux["counts"],
                                  np.asarray(ref["counts"], np.int32))
    assert aux["counts"].shape == (LAYERS - DENSE_LAYERS, EXPERTS)
    assert int(aux["counts"].sum()) == (LAYERS - DENSE_LAYERS) * B * S * TOP_K
    # No auxiliary term in the loss.
    np.testing.assert_allclose(loss, aux["cross_entropy"], rtol=0)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(ref_grads), strict=True):
        np.testing.assert_allclose(g, w, rtol=GRAD_RTOL, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))
    for layer in grads["layers"]:
        # Every leaf of the mixer has a gradient: none is dead weight.
        for leaf in jax.tree.leaves(layer["mla"]):
            assert np.any(np.asarray(leaf))
        if "moe" in layer:
            assert not np.any(np.asarray(layer["moe"]["router_bias"]))


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
    params = seeded(moonlight())
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
        if "moe" in layer:
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
    dict(router_bias=False), dict(route_scale=1.0),
    dict(renormalize_experts=False), dict(shared_expert_dim=0),
    dict(layers=tuple(LayerSpec(mixer="mla", rope=False,
                                ff="gated" if i < DENSE_LAYERS else "experts")
                      for i in range(LAYERS))),
], ids=["no-bias", "no-scale", "no-renormalisation", "no-shared-expert",
        "no-rope"])
def test_each_mechanism_left_out_misses_the_reference(change):
    whole = moonlight()
    params, data = seeded(whole), batch(whole)
    if "shared_expert_dim" in change:
        for layer in params["layers"][DENSE_LAYERS:]:
            layer["moe"].pop("shared")
    loss = jax.jit(lambda p: gpt.loss_fn(p, *data, moonlight(**change)))(
        params)
    ref_loss = reference_side()[0]
    assert abs(float(loss) - float(ref_loss)) > 1e-4 * float(ref_loss)


def mixer_inputs(cfg, seed=3):
    p = seeded(cfg)["layers"][0]["mla"]
    h = jax.random.normal(jax.random.PRNGKey(seed), (B, S, EMBED))
    return p, h, jnp.broadcast_to(jnp.arange(S), (B, S))


def _qkv(monkeypatch, cfg, p, h, positions):
    """What the mixer hands ``_attention``."""
    seen = []
    monkeypatch.setattr(
        mla, "_attention", lambda cfg, q, k, v, **layout: seen.append(
            (q, k, v)) or v)
    mla.apply(cfg, cfg.plan[0], p, h, positions)
    return seen[-1]


def test_q_k_v_are_the_references_and_the_rotary_key_is_one_a_token(
        monkeypatch):
    cfg = moonlight()
    p, h, positions = mixer_inputs(cfg)
    q, k, v = _qkv(monkeypatch, cfg, p, h, positions)
    assert q.shape == k.shape == (B, S, HEADS, NOPE + ROT)
    assert v.shape == (B, S, HEADS, VALUE)
    with jax.default_matmul_precision("highest"):
        want = reference.mla_qkv(h, p, positions, rope_theta=cfg.rope_theta,
                                 norm_eps=cfg.norm_eps)
    for got, ref in zip((q, k, v), want, strict=True):
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)
    # Every head's rotary key is the same vector.
    np.testing.assert_array_equal(k[:, :, :1, NOPE:].repeat(HEADS, axis=2),
                                  k[..., NOPE:])

    def scores(p):
        q, k, _ = _qkv(monkeypatch, cfg, p, h, positions)
        return jnp.einsum("bqhd,bkhd->bhqk", q, k)

    # The shared key's columns of W_kv_a moved: every head's scores move.
    base = scores(p)
    moved = scores(dict(p, wkv_a=p["wkv_a"].at[:, RANK:].add(0.1)))
    assert float(jnp.min(jnp.max(jnp.abs(moved - base), axis=(0, 2, 3)))) \
        > 1e-3
    # One head's no-position key columns of W_kv_b moved: its scores alone.
    moved = scores(dict(p, wkv_b=p["wkv_b"].at[:, 2, :NOPE].add(0.1)))
    per_head = np.asarray(jnp.max(jnp.abs(moved - base), axis=(0, 2, 3)))
    assert per_head[2] > 1e-3 and not per_head[[0, 1, 3]].any()


def test_the_mixer_is_the_references_scaled_by_the_whole_key_width():
    """The flash kernels at a query/key head of ``NOPE + ROT`` beside a
    value head of ``VALUE``: the reference's mixer, whose logits are over
    the root of ``NOPE + ROT``; over the root of ``NOPE`` it is another."""
    cfg = moonlight(attention="flash")
    p, h, positions = mixer_inputs(cfg)
    out = jax.jit(lambda p, h: mla.apply(cfg, cfg.plan[0], p, h,
                                              positions))(p, h)
    shape = dict(rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps)
    with jax.default_matmul_precision("highest"):
        want = reference.mla(h, p, positions, **shape)
        q, k, v = reference.mla_qkv(h, p, positions, **shape)
        other = jnp.einsum(
            "bshd,hde->bse", reference.causal_attention(
                q * np.sqrt((NOPE + ROT) / NOPE), k, v), p["wo"])
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-6)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-3


def test_the_mixer_is_causal():
    cfg = moonlight()
    p, h, positions = mixer_inputs(cfg)
    mixer = jax.jit(lambda h: mla.apply(cfg, cfg.plan[0], p, h,
                                             positions))
    out, out_moved = mixer(h), mixer(h.at[:, 20].add(1.0))
    np.testing.assert_array_equal(out[:, :20], out_moved[:, :20])
    assert float(jnp.min(jnp.max(jnp.abs(out - out_moved)[:, 20:],
                                 axis=-1))) > 1e-6


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3 ... 14-15 of one expert sublayer, each run as a
    rank's share by the program, with the shared expert counted once, sum
    to what the reference gives holding all sixteen; the router, the bias,
    the choice, the renormalisation and the counts are the whole router's
    in each."""
    uncut = seeded(moonlight(experts_held=None))["layers"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(5), (B, S, EMBED))
    shared = experts._shared_expert(moonlight(), uncut["shared"], h)
    total, counts = shared, []

    @functools.partial(jax.jit, static_argnums=0)
    def run(first, m):
        y, aux, _ = experts.apply(moonlight(first_expert=first), None, m, h)
        return y, aux["counts"]

    for first in range(0, EXPERTS, HELD):
        share = dict(uncut, **{name: uncut[name][first:first + HELD]
                               for name in ("w_gate", "w_up", "w_down")})
        y, c = run(first, share)
        total = total + (y - shared)
        counts.append(c)
    with jax.default_matmul_precision("highest"):
        want, ref_counts = jax.jit(lambda m: reference.expert_block(
            h.reshape(-1, EMBED), m, TOP_K, SCALE))(uncut)
    np.testing.assert_allclose(total.reshape(-1, EMBED), want, rtol=2e-5,
                               atol=5e-6)
    for c in counts:
        np.testing.assert_array_equal(c, np.asarray(ref_counts, np.int32))
    # Every share had work: the sum is no single share's output.
    assert min(int(counts[0][i:i + HELD].sum())
               for i in range(0, EXPERTS, HELD)) > 0


def test_specs_follow_the_tree_and_shard_the_heads():
    cfg = moonlight(tp_axis="tp")
    params = jax.eval_shape(lambda: gpt.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    specs = gpt.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(params)
    mla = params["layers"][0]["mla"]
    assert {k: v.shape for k, v in mla.items()} == {
        "wq": (EMBED, HEADS, NOPE + ROT), "wkv_a": (EMBED, RANK + ROT),
        "kv_norm": (RANK,), "wkv_b": (RANK, HEADS, NOPE + VALUE),
        "wo": (HEADS, VALUE, EMBED)}
    assert specs["layers"][0]["mla"] == {
        "wq": P(None, "tp", None), "wkv_a": P(), "kv_norm": P(),
        "wkv_b": P(None, "tp", None), "wo": P("tp", None, None)}
    assert set(params["layers"][0]) == {"mla", "mla_norm", "mlp_norm",
                                        "w_gate", "w_up", "w_down"}


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
def test_heads_over_tp_and_the_sequence_over_sp_give_the_whole_mixer(
        make_runtime, attention):
    """A bound tp axis holds two of the four heads a rank (the latent and
    the shared key made whole on each), a bound sp axis half of the
    sequence: ring attention and Ulysses take the two widths, and the
    output is the unsharded mixer's."""
    make_runtime(mesh_shape={"dp": 2, "tp": 2, "sp": 2})
    whole = moonlight()
    cfg = moonlight(tp_axis="tp", sp_axis="sp", attention=attention)
    p, h, positions = mixer_inputs(whole)
    want = mla.apply(whole, whole.plan[0], p, h, positions)
    seq = P(None, "sp")
    got = jax.jit(jax.shard_map(
        lambda p, h, pos: mla.apply(cfg, cfg.plan[0], p, h, pos),
        mesh=hvd.mesh(), in_specs=(gpt.param_specs(cfg)["layers"][0]["mla"],
                                   seq, seq), out_specs=seq))(p, h, positions)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_a_window_on_an_mla_layer_is_refused():
    with pytest.raises(ValueError, match="nor an MLA layer"):
        moonlight(layers=(LayerSpec(mixer="mla", window=8, ff="experts"),)
                  * LAYERS).plan


def test_a_checkpointed_block_makes_its_shared_experts_products_once(
        products_like):
    """Under ``remat="full"`` an expert block keeps its shared expert's gate
    and up products before the activation (``moe_shared_pre_activation``,
    PR 59): the differentiated step holds two a block, the forward pass's,
    and none in what the backward pass makes again (before PR 59 two)."""
    # A width no other product of the step has.
    cfg = moonlight(**SHIPPED, shared_expert_dim=40)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: gpt.loss_fn(p, *batch(cfg), cfg)))(params).jaxpr
    assert products_like(jaxpr, (B, S, EMBED), (EMBED, 40)) \
        == (2 * (LAYERS - DENSE_LAYERS), 0)


def test_the_step_counts_its_mla_and_flash_traces(spmd8):
    # An eps of its own: JAX keeps what it traced of a checkpointed block by
    # the block's configuration, and a kept trace notes nothing.
    cfg = moonlight(**SHIPPED, norm_eps=2e-5)
    params, data = seeded(cfg), batch(cfg)
    jax.jit(jax.grad(lambda p: gpt.loss_fn(p, *data, cfg))).lower(params)
    fams = hvd.metrics()
    mla, = {tuple(sorted(labels.items())) for _, labels, _ in
            fams["hvdtpu_spmd_mla_traces_total"]["samples"]}
    assert dict(mla) == dict(heads=str(HEADS), nope_dim=str(NOPE),
                             rope_dim=str(ROT), value_dim=str(VALUE),
                             kv_rank=str(RANK), q_rank="none", gate="none")
    widths = {(labels["kernel"], labels["key_dim"], labels["value_dim"],
               labels["dq"])
              for _, labels, _ in
              fams["hvdtpu_spmd_flash_kernel_traces_total"]["samples"]}
    # The backward pass is the one kernel that makes dQ too.
    assert widths == {(kernel, str(NOPE + ROT), str(VALUE), dq)
                      for kernel, dq in (("hvd_flash_fwd", "none"),
                                         ("hvd_flash_dkdv", "fused"))}
    # A checkpointed block keeps the flash output at the value head's
    # width, the sequence padded to 128 rows (a block traced once counts
    # once, however many layers share its trace).
    kept = {labels["name"]: value for _, labels, value in
            fams["hvdtpu_spmd_remat_saved_bytes_total"]["samples"]}
    traces, rest = divmod(kept["flash_out"], B * HEADS * 128 * VALUE * 4)
    assert 1 <= traces <= LAYERS and not rest

"""``models/gpt.py`` as a Qwen3-Next-like decoder at a tiny size: gated-delta-
rule linear-attention layers beside a gated softmax-attention layer (a norm a
head, rotary embedding on a quarter of a head at base 1e7, a sigmoid output
gate), zero-centred norms, and after every mixer an expert block that holds a
share of its router's experts, renormalises its weights and adds a gated
shared expert, held to the plain float32 reference
(``benchmarks/reference/gpt_linear_moe_dp.py``: the recurrence one token a
step, every held expert on every token, nothing imported from the program).

Tolerance of the comparison with the reference: both sides are float32 at
the highest matmul precision and differ by the order of sums (chunks against
tokens, sorted rows against every expert on every token). The loss agrees to
1e-6 and no routing choice differs; each gradient leaf agrees to 2e-3 of its
largest element (seen over six seeds of weights: 3e-5 to 1e-4 on four, 3e-4
and 1e-3 on two, always from a linear layer's input side down: at heads of 8
the L2 norms of q and k work near their eps; the scan alone agrees to 2e-6,
``tests/test_gated_delta.py``). A dropped term misses by percents; each
switch turned off moves the loss by 2e-5 of itself or more, against
float32's 1e-6 (the switches' test).
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
from horovod_tpu.models.decoder.mixers import gdn
from horovod_tpu.parallel import moe as moe_module
from benchmarks.reference import gpt_linear_moe_dp as reference

TINY = dict(
    vocab_size=96, num_layers=4, num_heads=4, num_kv_heads=2, head_dim=16,
    embed_dim=32, mlp_dim=16, dtype=jnp.float32, tp_axis=None, sp_axis=None,
    attention="dense", norm_eps=1e-6, norm_zero_centered=True,
    layer_kinds=("gdn", "gdn", "gdn", "attention"),
    gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=8,
    gdn_conv=4, gdn_chunk=16,
    qk_head_norm=True, rope_theta=1e7, rotary_dim=4, attention_gate=True,
    moe_every=1, num_experts=16, experts_per_token=4, experts_held=4,
    first_expert=4, renormalize_experts=True, shared_expert_dim=16,
    load_balance_coef=0.001)
B, S = 2, 40            # two chunks and a half


def _data(seed=0, vocab=96, shape=(B, S)):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, shape, dtype=np.int32)
    targets = np.roll(tokens, -1, axis=-1)
    targets[..., -1] = -1
    positions = np.broadcast_to(np.arange(shape[-1], dtype=np.int32),
                                shape).copy()
    return tokens, targets, positions


def _params(cfg, seed):
    """Seeded weights with every norm's zero-centred weight moved off zero,
    so that ``1 + w`` against ``w`` shows."""
    params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def off_zero(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith("norm']") and "gdn']['norm" not in name:
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape,
                                                  leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(off_zero, params)


# One program a configuration and shape, not one an operation: no test that
# calls these two sets the expert layer's tile, which ``jit`` would not see.
@functools.partial(jax.jit, static_argnums=0)
def _loss_and_grad(cfg, params, data):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True)(params)


@functools.partial(jax.jit, static_argnums=0)
def _loss(cfg, params, data):
    """The loss alone, for a case that holds no gradient: no backward pass
    to compile."""
    with jax.default_matmul_precision("highest"):
        return gpt.loss_and_aux(params, *data, cfg)[0]


@functools.partial(jax.jit, static_argnums=0)
def _reference(cfg, params, data):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: reference.shard_loss(
            p, *data, load_balance_coef=cfg.load_balance_coef,
            norm_eps=cfg.norm_eps, top_k=cfg.experts_per_token,
            first_expert=cfg.first_expert, key_dim=cfg.gdn_key_dim,
            rope_theta=cfg.rope_theta, rotary_dim=cfg.rotary_dim),
            has_aux=True)(params)


@pytest.fixture(scope="module")
def two_shards():
    """What neither the tile nor ``remat`` changes of the ``run_step``
    test: the weights, the batch, and the reference a shard at a time,
    averaged as the exchange does."""
    cfg = gpt.GPTConfig(**TINY)
    params, data = _params(cfg, 1), _data(0)
    want_loss, want_lb, want_counts, want_grads = 0.0, 0.0, 0.0, None
    for s in range(2):
        shard = tuple(x[s:s + 1] for x in data)
        (l, parts), g = _reference(cfg, params, shard)
        want_loss += float(l) / 2
        want_lb += float(parts["load_balance"]) / 2
        want_counts = want_counts + parts["counts"]
        want_grads = g if want_grads is None else jax.tree.map(
            jnp.add, want_grads, g)
    return params, data, (want_loss, want_lb, want_counts, want_grads)


def _assert_grads_agree(grads, want, tol=2e-3):
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=tol * float(jnp.abs(w).max()) + 1e-12,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("tile", [512, 8])
@pytest.mark.parametrize("remat", ["none", "full"])
def test_model_matches_the_reference_through_run_step(make_runtime,
                                                      moe_row_tile,
                                                      two_shards, remat,
                                                      tile):
    """The normal path: ``hvd.run_step`` over a dp mesh, each rank its own
    sequences; loss, auxiliary term and every gradient leaf. A rank's 160
    token-expert rows are under the grouped matmul's tile of 512, so its
    expert layers work on all of them at once; at a tile of 8 on a window
    of 80 (``moe.share_rows``), and on the next where the held experts draw
    more."""
    moe_row_tile(tile, fresh=True)
    rows = moe_module.share_rows(S, 4, 4, 16)
    assert rows == (4 * S if tile == 512 else 2 * S)
    make_runtime(devices=jax.devices()[:2], mesh_shape={"dp": 2})
    cfg = gpt.GPTConfig(**TINY, remat=remat)
    params, data, (want_loss, want_lb, want_counts, want_grads) = two_shards

    def body(p, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda q: gpt.loss_and_aux(q, *batch, cfg), has_aux=True)(p)
        return (hvd.allreduce(loss, op=hvd.Average),
                hvd.allreduce(aux["load_balance"], op=hvd.Average),
                hvd.allreduce(aux["counts"], op=hvd.Sum), grads)

    with jax.default_matmul_precision("highest"):
        loss, load_balance, counts, grads = hvd.run_step(
            body, in_specs=(hvd.REPLICATED, hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)(params, hvd.shard_batch(data))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    np.testing.assert_allclose(load_balance, want_lb, rtol=1e-6)
    np.testing.assert_array_equal(counts, want_counts)
    # Autodiff's psum of a replicated parameter's gradient sums the shards.
    _assert_grads_agree(grads, want_grads)
    fams = hvd.metrics()
    gdn = [s for s in fams["hvdtpu_spmd_gdn_layer_traces_total"]["samples"]
           if s[1] == {"key_heads": "2", "value_heads": "4", "key_dim": "8",
                       "value_dim": "8", "chunk": "16",
                       "recurrence": "kernel", "chunks": "3",
                       "beta_max": "1", "qk_norm": "kernel"}]
    # A checkpointed block of a shape traced before comes from JAX's cache.
    assert gdn and gdn[0][2] >= 1
    moe = [s for s in fams["hvdtpu_spmd_moe_layer_traces_total"]["samples"]
           if s[1]["experts"] == "16" and s[1]["held"] == "4"
           and s[1]["top_k"] == "4" and s[1]["rows"] == str(rows)]
    assert moe and moe[0][2] >= 1


def test_flash_kernel_serves_the_gated_attention_layer():
    cfg = gpt.GPTConfig(**TINY)
    params = _params(cfg, 2)
    data = _data(1)
    (loss, _), grads = _loss_and_grad(cfg, params, data)
    (loss1, _), grads1 = _loss_and_grad(
        dataclasses.replace(cfg, attention="flash"), params, data)
    np.testing.assert_allclose(loss, loss1, rtol=1e-5)
    _assert_grads_agree(grads1, grads, tol=1e-4)


@pytest.mark.parametrize("change", [
    dict(rotary_dim=None), dict(rope_theta=10000.0), dict(qk_head_norm=False),
    dict(attention_gate=False), dict(norm_zero_centered=False),
    dict(renormalize_experts=False), dict(shared_expert_dim=0),
    dict(first_expert=0)])
def test_each_switch_turned_off_misses_the_reference(change):
    """Partial rotary, the base, the per-head norm, the output gate, the
    zero-centred norm, the renormalised weights, the shared expert, which
    experts are held: the reference's loss is met with all of them and
    missed by 2e-5 of itself or more without any one (float32 noise is 1e-6;
    the attention layer is one in four and its logits are small at
    initialisation, so the base and the head norm move the loss least,
    the head norm by 6e-5)."""
    cfg = gpt.GPTConfig(**TINY)
    params = _params(cfg, 3)
    data = _data(2)
    (want, _), _ = _reference(cfg, params, data)
    (loss, _), _ = _loss_and_grad(cfg, params, data)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    other = dataclasses.replace(cfg, **change)
    # The tree the changed configuration reads: the same weights, without
    # those the switch brought.
    tree = jax.tree.map(lambda x: x, params)
    for layer in tree["layers"]:
        if "attention_gate" in change and "wq" in layer:
            layer["wq"] = layer["wq"][..., :cfg.head_dim]
        if "qk_head_norm" in change:
            layer.pop("q_norm", None), layer.pop("k_norm", None)
        if "shared_expert_dim" in change:
            layer["moe"].pop("shared")
    missed = _loss(other, tree, data)
    assert abs(float(missed) - float(want)) > 2e-5 * abs(float(want)), change


@pytest.mark.parametrize("leaf", ["in_proj_ba", "conv_w", "dt_bias", "A_log",
                                  "norm"])
def test_every_small_parameter_of_the_mixer_reaches_the_loss(leaf):
    cfg = gpt.GPTConfig(**TINY)
    params = _params(cfg, 4)
    _, grads = _loss_and_grad(cfg, params, _data(3))
    for layer in (0, 1, 2):
        assert float(jnp.abs(grads["layers"][layer]["gdn"][leaf]).max()) > 0


def test_tree_specs_and_initialisation():
    cfg = gpt.GPTConfig(**{**TINY, "gdn_value_heads": 64, "gdn_key_heads": 32,
                           "gdn_key_dim": 2, "gdn_value_dim": 2})
    tree = gpt.init_params(jax.random.PRNGKey(7), cfg)
    specs = gpt.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(tree)
    gdn = tree["layers"][0]["gdn"]
    assert all(s == P() for s in specs["layers"][0]["gdn"].values())
    assert gdn["in_proj"].shape == (32, 64 + 64 + 128 + 128)
    assert gdn["in_proj_ba"].shape == (32, 128)
    assert gdn["conv_w"].shape == (4, 256) and "conv_b" not in gdn
    a = np.exp(gdn["A_log"])
    assert a.min() > 0 and a.max() <= 16.0 and a.max() - a.min() > 8
    np.testing.assert_array_equal(gdn["dt_bias"], 1.0)
    np.testing.assert_array_equal(gdn["norm"], 1.0)
    # Zero-centred norms start at zero; the attention layer's wq is doubled.
    np.testing.assert_array_equal(tree["layers"][0]["gdn_norm"], 0.0)
    np.testing.assert_array_equal(tree["out_norm"], 0.0)
    attn = tree["layers"][3]
    assert attn["wq"].shape == (32, 4, 32) and attn["q_norm"].shape == (16,)
    moe = attn["moe"]
    assert moe["router"].shape == (32, 16) and moe["w_up"].shape == (4, 32, 16)
    assert set(moe["shared"]) == {"w_gate", "w_up", "w_down", "gate"}


def test_held_experts_refuse_a_named_ep_axis():
    cfg = gpt.GPTConfig(**{**TINY, "ep_axis": "ep"})
    with pytest.raises(ValueError, match="experts_held"):
        gpt.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="experts_held"):
        gpt.param_specs(cfg)


@pytest.mark.parametrize("axis", ["tp", "sp"])
def test_gdn_layer_refuses_a_bound_tp_or_sp_axis(make_runtime, axis):
    make_runtime(mesh_shape={"dp": 4, axis: 2})
    cfg = gpt.GPTConfig(**{**TINY, f"{axis}_axis": axis,
                           "layer_kinds": ("gdn",) * 4})
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens, _, positions = _data(0, shape=(4, 32))
    data = P("dp", "sp") if axis == "sp" else P("dp")
    step = hvd.run_step(
        lambda p, t, pos: gpt.forward(p, t, pos, cfg),
        in_specs=(hvd.REPLICATED, data, data), out_specs=data)
    with pytest.raises(ValueError, match=f"'{axis}' axis is bound"):
        step(params, tokens, positions)


def test_full_remat_keeps_the_gdn_scans_output(make_runtime):
    make_runtime(devices=jax.devices()[:1])
    cfg = gpt.GPTConfig(**{**TINY, "remat": "full", "num_layers": 1,
                           "layer_kinds": ("gdn",)})
    data = _data(7, shape=(3, 48))
    jax.make_jaxpr(lambda p: jax.value_and_grad(gpt.loss_fn)(p, *data, cfg))(
        gpt.init_params(jax.random.PRNGKey(0), cfg))
    family = hvd.metrics()["hvdtpu_spmd_remat_saved_bytes_total"]
    kept = {labels["name"]: value for _, labels, value in family["samples"]
            if labels["mode"] == "full"}
    experts = 3 * cfg.experts_held * cfg.embed_dim * cfg.mlp_dim * 4
    tokens, pairs = 3 * 48, 3 * 48 * cfg.experts_per_token
    # What fixes the routing beside them; a share's windows need no inverse
    # of the order (whose indices are 8 bytes under this suite's x64).
    window = moe_module.share_rows(tokens, cfg.experts_per_token,
                                   cfg.experts_held, cfg.num_experts)
    index = jnp.argsort(jnp.zeros(1)).dtype.itemsize
    # What ``hvd_gdn_fwd`` writes, a chunk a turn ``[c, B, Hv, Q, .]``:
    # ``u_own`` on the value lanes, ``w``, ``q G`` and ``k G_last / G`` on
    # the key lanes, ``attn`` on the chunk, and for its backward kernel
    # alone ``T``, a chunk's width a row too (PR 68). A head of 8 by 8
    # rides 128 lanes, so no entering state is kept.
    rows = (48 // cfg.gdn_chunk) * 3 * cfg.gdn_value_heads * cfg.gdn_chunk
    assert kept == {"gdn_scan_out": tokens * gdn.value_inner(cfg) * 4,
                    "gdn_scan_operands":
                        rows * (128 + 3 * 128 + 2 * cfg.gdn_chunk) * 4,
                    "moe_expert_matrices": experts,
                    "moe_router_logits": tokens * cfg.num_experts * 4,
                    "moe_top_experts": pairs * 4,
                    "moe_top_weights": pairs * 4,
                    "moe_order": pairs * index,
                    # The shared expert's gate and up products (PR 59).
                    "moe_shared_pre_activation":
                        2 * tokens * cfg.shared_expert_dim * 4,
                    # The sorted rows and their gate and up products: all
                    # of them where the layer works on all its rows at once,
                    # with the order's inverse, else the window at 0's.
                    "moe_rows": window * cfg.embed_dim * 4,
                    "moe_pre_activation": 2 * window * cfg.mlp_dim * 4,
                    **({"moe_order_inverse": pairs * index}
                       if window == pairs else {})}

"""``models/gpt.py`` as an Olmo-Hybrid-like decoder at a tiny size: a dense
3:1 stack of gated-delta-rule linear-attention layers and a full softmax-
attention layer, linear heads that are neither square nor a lane multiple
(12 keys by 24 values, one value head a key head, five heads), ``beta = 2
sigmoid(b)``, an RMSNorm over the whole of q and of k and no position
embedding, a SiLU-gated feed-forward after every mixer, and each branch
normed **after** it and not before (``norms="post"``), held to the plain
float32 reference (``benchmarks/reference/gpt_linear_dp.py``: the recurrence
one token a step at the head sizes given, nothing imported from the program).

Tolerance of the comparison with the reference: both sides are float32 at
the highest matmul precision and differ by the order of sums (chunks against
tokens, padded lanes that hold zeros). The loss agrees to 1e-6; each
gradient leaf agrees to 2e-3 of its largest element (seen over three seeds of
weights against the reference in float64: 7e-5 to 2e-4; the bound is
``tests/test_gpt_linear_moe.py``'s). Each mechanism left out
moves the loss by 1e-4 of itself or more (the switches' test).
"""

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.models.decoder.mixers import gdn
from horovod_tpu.ops import gated_delta
from benchmarks.reference import gpt_linear_dp as reference

TINY = dict(
    vocab_size=96, num_layers=4, num_heads=5, num_kv_heads=5, head_dim=12,
    embed_dim=60, mlp_dim=32, dtype=jnp.float32, tp_axis=None, sp_axis=None,
    attention="dense", norm_eps=1e-6, norms="post", qk_norm=True, rope=False,
    gated_mlp=True, layer_kinds=("gdn", "gdn", "gdn", "attention"),
    gdn_key_heads=5, gdn_value_heads=5, gdn_key_dim=12, gdn_value_dim=24,
    gdn_conv=4, gdn_chunk=16, gdn_allow_neg_eigval=True)
B, S = 2, 40            # two chunks and a half
ADAMW = dict(lr=1e-4, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4)


def _data(seed=0, vocab=96, shape=(B, S)):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, vocab, shape, dtype=np.int32)
    targets = np.roll(tokens, -1, axis=-1)
    targets[..., -1] = -1
    positions = np.broadcast_to(np.arange(shape[-1], dtype=np.int32),
                                shape).copy()
    return tokens, targets, positions


def _params(cfg, seed):
    """Seeded weights with every norm's weight moved off one, so that a norm
    in the wrong place or with another's weight shows, and the embedding at
    ten times its initial 0.02: with no norm before a branch the first
    layer's mixer otherwise works on inputs so small that its gated norm
    sits at its eps and the gradients of ``A_log``, ``dt_bias`` and that
    norm are sums that cancel (float32 against float64 then differ by 6e-3
    of the leaf's largest element, in the reference alone as well)."""
    params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
    params["embed"] = 10.0 * params["embed"]
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def off_one(path, leaf):
        if jax.tree_util.keystr(path).endswith("norm']"):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape,
                                                  leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(off_one, params)


# One program a configuration and shape, not one an operation.
@functools.partial(jax.jit, static_argnums=0)
def _loss_and_grad(cfg, params, data):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: gpt.loss_fn(p, *data, cfg))(params)


@functools.partial(jax.jit, static_argnums=0)
def _loss(cfg, params, data):
    """The loss alone, for a case that holds no gradient: no backward pass
    to compile."""
    with jax.default_matmul_precision("highest"):
        return gpt.loss_fn(params, *data, cfg)


@functools.partial(jax.jit, static_argnums=0)
def _reference(cfg, params, data):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: reference.shard_loss(
            p, *data[:2], norm_eps=cfg.norm_eps, key_dim=cfg.gdn_key_dim,
            beta_max=2.0 if cfg.gdn_allow_neg_eigval else 1.0))(params)


def _assert_grads_agree(grads, want, tol=2e-3):
    assert jax.tree.structure(grads) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=tol * float(jnp.abs(w).max()) + 1e-12,
            err_msg=jax.tree_util.keystr(path))


@pytest.fixture(scope="module")
def two_shards():
    """What ``remat`` does not change of the ``run_step`` test: the weights,
    the batch, and the reference a shard at a time, averaged as the exchange
    does."""
    cfg = gpt.GPTConfig(**TINY)
    params, data = _params(cfg, 1), _data(0)
    want_loss, want_grads = 0.0, None
    for s in range(2):
        l, g = _reference(cfg, params, tuple(x[s:s + 1] for x in data))
        want_loss += float(l) / 2
        want_grads = g if want_grads is None else jax.tree.map(
            jnp.add, want_grads, g)
    return params, data, want_loss, jax.tree.map(lambda g: g / 2, want_grads)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_model_matches_the_reference_through_run_step(make_runtime,
                                                      two_shards, remat):
    """The normal path: ``hvd.run_step`` over a dp mesh, each rank its own
    sequence, ``hvd.DistributedOptimizer`` over AdamW: the loss, every
    gradient leaf as the optimizer received it (its first moment after the
    first step is ``1 - b1`` times the gradient) and the norm of AdamW's
    first step; and what the program counted of its scans."""
    make_runtime(devices=jax.devices()[:2], mesh_shape={"dp": 2})
    cfg = gpt.GPTConfig(**TINY, remat=remat)
    params, data, want_loss, want_grads = two_shards
    opt = hvd.DistributedOptimizer(optax.adamw(
        ADAMW["lr"], b1=ADAMW["b1"], b2=ADAMW["b2"], eps=ADAMW["eps"],
        weight_decay=ADAMW["weight_decay"]))

    def body(p, state, batch):
        loss, grads = jax.value_and_grad(
            lambda q: gpt.loss_fn(q, *batch, cfg))(p)
        updates, state = opt.update(grads, state, p)
        return (hvd.allreduce(loss, op=hvd.Average), state[0].mu,
                optax.global_norm(updates))

    with jax.default_matmul_precision("highest"):
        loss, mu, moved = hvd.run_step(
            body, in_specs=(hvd.REPLICATED, hvd.REPLICATED,
                            hvd.batch_spec(0)),
            out_specs=hvd.REPLICATED)(
                params, opt.init(params), hvd.shard_batch(data))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    _assert_grads_agree(jax.tree.map(lambda m: m / (1 - ADAMW["b1"]), mu),
                        want_grads)
    np.testing.assert_allclose(moved, reference.adamw_first_update_norm(
        params, want_grads, ADAMW["lr"], ADAMW["weight_decay"],
        ADAMW["eps"]), rtol=1e-4)
    fams = hvd.metrics()
    layers = [s for s in
              fams["hvdtpu_spmd_gdn_layer_traces_total"]["samples"]
              if s[1] == {"key_heads": "5", "value_heads": "5",
                          "key_dim": "12", "value_dim": "24", "chunk": "16",
                          "recurrence": "kernel", "chunks": "3",
                          "beta_max": "2", "qk_norm": "kernel"}]
    assert layers and layers[0][2] >= 1
    # A head of 12 by 24 rides a lane tile each way in the kernels (those
    # this runtime saw traced: a kernel's inline-jitted call is traced once
    # for a shape, tests/test_program_names.py holds all four's labels).
    kernels = fams["hvdtpu_spmd_gdn_kernel_traces_total"]["samples"]
    assert {(s[1]["key_lanes"], s[1]["value_lanes"]) for s in kernels} \
        <= {("128", "128")}


# One key and one value head of a whole lane tile each way: nothing padded.
WHOLE = dict(gdn_key_heads=1, gdn_value_heads=1, gdn_key_dim=128,
             gdn_value_dim=128)


def _kernels_in(jaxpr, out=None):
    """Pallas kernels by name in a jaxpr and every jaxpr under it, and
    under ``"rounded"`` the ``reduce_precision`` passes ``jax.checkpoint``
    puts on tensors in the recurrence's order ``[c, B, Hv, ., .]``."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] += 1
            continue
        if eqn.primitive.name == "reduce_precision" \
                and eqn.outvars[0].aval.ndim == 5:
            out["rounded"] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernels_in(sub, out)
    return out


@pytest.mark.parametrize("heads", ["padded", "whole"])
def test_a_checkpointed_block_runs_the_scans_forward_kernels_once(
        make_runtime, heads):
    """``remat="full"`` keeps what ``hvd_gdn_fwd`` writes (its five
    operands and, for ``hvd_gdn_bwd`` alone, ``T``), so the
    recomputed copy does not run it, and each chunk's entering state where
    no lane of it is padding, so ``hvd_gdn_rec_fwd`` runs once too; a head
    carried with zeros makes its entering states again, from the kept
    operands: only there does the forward pass read a kept tensor, and
    only there does ``jax.checkpoint`` copy the five through a
    ``reduce_precision`` (a pass over every kept byte on the chip)."""
    make_runtime(devices=jax.devices()[:1])
    cfg = gpt.GPTConfig(**{**TINY, **(WHOLE if heads == "whole" else {}),
                           "num_layers": 2, "layer_kinds": ("gdn", "gdn")},
                        remat="full")
    layers = 2
    data = _data(3)
    jaxpr = jax.make_jaxpr(
        lambda p: jax.value_and_grad(gpt.loss_fn)(p, *data, cfg))(
            gpt.init_params(jax.random.PRNGKey(0), cfg))
    kernels = _kernels_in(jaxpr.jaxpr)
    assert {name: n for name, n in kernels.items()
            if name.startswith("hvd_gdn_")} == {
        gated_delta.KERNEL_FWD: layers, gated_delta.KERNEL_BWD: layers,
        gated_delta.KERNEL_REC_BWD: layers,
        gated_delta.KERNEL_REC_FWD: layers * (1 if heads == "whole" else 2)}
    assert kernels["rounded"] == (0 if heads == "whole" else 5 * layers)
    family = hvd.metrics()["hvdtpu_spmd_remat_saved_bytes_total"]
    kept = {labels["name"]: value for _, labels, value in family["samples"]}
    # A block's bytes, float32 here: [c, B, Hv, Q, .] with u_own on the
    # value lanes, w, q G and k G_last / G on the key lanes, attn and T a
    # chunk's width each. T is no operand of the forward pass, so the five
    # ``rounded`` above stay five.
    chunks, hv, q = -(-S // cfg.gdn_chunk), cfg.gdn_value_heads, cfg.gdn_chunk
    assert kept["gdn_scan_operands"] \
        == chunks * B * hv * q * (4 * 128 + 2 * q) * 4
    assert ("gdn_scan_entering" in kept) == (heads == "whole")
    if heads == "whole":
        # [c, B, Hv, K, V] in the operand dtype, a layer.
        assert kept["gdn_scan_entering"] == 3 * B * 128 * 128 * 4


def test_full_remat_is_no_remat_to_the_last_gradient_leaf():
    """The kept tensors are the tensors that would have been made again:
    the loss and every gradient leaf under ``remat="full"`` against
    ``remat="none"``."""
    # (One linear layer, jitted: lowering the kernels' interpret mode is
    # most of this test's time.)
    tiny = {**TINY, "num_layers": 1, "layer_kinds": ("gdn",)}
    params, data = _params(gpt.GPTConfig(**tiny), 4), _data(5)
    (want_loss, want), (loss, grads) = (
        jax.jit(lambda p, cfg=gpt.GPTConfig(**tiny, remat=remat):
                _loss_and_grad(cfg, p, data))(params)
        for remat in ("none", "full"))
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    _assert_grads_agree(grads, want)


def test_flash_kernel_serves_the_attention_layer():
    cfg = gpt.GPTConfig(**TINY)
    params = _params(cfg, 2)
    data = _data(1)
    loss, grads = _loss_and_grad(cfg, params, data)
    loss1, grads1 = _loss_and_grad(
        dataclasses.replace(cfg, attention="flash"), params, data)
    np.testing.assert_allclose(loss, loss1, rtol=1e-5)
    _assert_grads_agree(grads1, grads, tol=1e-4)


def _without(params, cfg, change):
    """The tree the changed configuration reads: the same weights, the
    norms' under the keys the changed placement looks for, without those
    the switch brought."""
    tree = jax.tree.map(lambda x: x, params)
    for spec, layer in zip(cfg.plan, tree["layers"], strict=True):
        if "qk_norm" in change:
            layer.pop("q_norm", None), layer.pop("k_norm", None)
        if "norms" in change:
            mixer = "attn" if spec.mixer == "attention" else spec.mixer
            layer[mixer + "_norm"] = layer.pop("mixer_post_norm")
            layer["mlp_norm"] = layer.pop("mlp_post_norm")
        if "gated_mlp" in change:
            layer.pop("w_gate")
    return tree


@pytest.mark.parametrize("change", [
    dict(gdn_allow_neg_eigval=False), dict(norms="pre"), dict(qk_norm=False),
    dict(rope=True), dict(gated_mlp=False)],
    ids=["beta in (0, 1)", "norms before", "no q/k norm", "rotary", "gelu"])
def test_each_mechanism_left_out_misses_the_reference(change):
    """``beta``'s factor, the norm after the branch (the same weights
    applied before it instead), the q/k norm, no position embedding, the
    gated feed-forward: the reference's loss is met with all of them and
    missed by 1e-4 of itself or more without any one (float32 noise is
    1e-6)."""
    cfg = gpt.GPTConfig(**TINY)
    params = _params(cfg, 3)
    data = _data(2)
    want, _ = _reference(cfg, params, data)
    loss, _ = _loss_and_grad(cfg, params, data)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    other = dataclasses.replace(cfg, **change)
    missed = _loss(other, _without(params, cfg, change), data)
    assert abs(float(missed) - float(want)) > 1e-4 * abs(float(want)), change


@pytest.mark.parametrize("leaf", ["in_proj_ba", "conv_w", "dt_bias", "A_log",
                                  "norm"])
def test_every_small_parameter_of_the_mixer_reaches_the_loss(leaf):
    cfg = gpt.GPTConfig(**TINY)
    _, grads = _loss_and_grad(cfg, _params(cfg, 4), _data(3))
    for layer in (0, 1, 2):
        assert float(jnp.abs(grads["layers"][layer]["gdn"][leaf]).max()) > 0


def _mixer_and_input(dtype, seed=5):
    # (A stream of 48: no other tensor of the mixer is as wide as q.)
    cfg = gpt.GPTConfig(**{**TINY, "dtype": dtype, "embed_dim": 48})
    p = _params(cfg, seed)["layers"][0]["gdn"]
    h = jax.random.normal(jax.random.PRNGKey(seed), (B, S, cfg.embed_dim),
                          dtype)
    return cfg, p, h


def _shapes_in(jaxpr):
    """``(shape, dtype)`` of every value a jaxpr makes, at any depth but a
    kernel's own body (its values are blocks in VMEM)."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield tuple(var.aval.shape), var.aval.dtype
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _shapes_in(sub)


@pytest.mark.parametrize("passes", ["forward", "backward"])
def test_the_mixer_holds_no_float32_copy_of_q_or_k(passes):
    """Since PR 50 the scan's kernels norm ``q`` and ``k``: between the
    convolution and the scan, and between the scan's backward kernel and the
    convolution's, a bfloat16 mixer makes no float32 ``[B, S, Hk, K]``
    tensor (nor one flattened to ``[B, S, Hk K]``), forward or backward."""
    cfg, p, h = _mixer_and_input(jnp.bfloat16)

    def mixer(p, h):
        return gdn.apply(cfg, None, p, h, None).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(
        mixer if passes == "forward" else jax.grad(mixer, argnums=(0, 1)))(
            p, h).jaxpr
    made = set(_shapes_in(jaxpr))
    heads = (B, S, cfg.gdn_key_heads, cfg.gdn_key_dim)
    assert (heads, jnp.dtype(jnp.bfloat16)) in made   # the kernels' q and k
    for shape in (heads, (B, S, gdn.key_inner(cfg))):
        assert (shape, jnp.dtype(jnp.float32)) not in made, shape
    assert "name=hvd_gdn_" + ("bwd" if passes == "backward" else "fwd") \
        in str(jaxpr)


def test_the_kernels_norm_is_the_parents_formula(monkeypatch):
    """The mixer with the norm in the scan's kernels against the formula it
    had before PR 50 (``q`` and ``k`` to heads, L2-normalised in float32,
    ``q`` over the root of the head's 12, then the scan on normed rows): the
    layer's output and the gradient of every parameter and of the input, to
    the file's tolerances."""
    cfg, p, h = _mixer_and_input(jnp.float32)
    co = jax.random.normal(jax.random.PRNGKey(6), h.shape, jnp.float32)

    def both():
        def form(p, h):
            out = gdn.apply(cfg, None, p, h, None)
            return jnp.sum(out * co), out

        with jax.default_matmul_precision("highest"):
            (_, out), grads = jax.jit(jax.value_and_grad(
                form, argnums=(0, 1), has_aux=True))(p, h)
        return out, grads

    out, grads = both()
    shipped = gdn.gated_delta_chunked

    def normed_outside(q, k, *rest, norm_qk, **kw):
        assert norm_qk
        return shipped(
            gated_delta.unit_rows(q, cfg.gdn_key_dim ** -0.5).astype(q.dtype),
            gated_delta.unit_rows(k).astype(k.dtype), *rest, **kw)

    monkeypatch.setattr(gdn, "gated_delta_chunked", normed_outside)
    want_out, want = both()
    _assert_grads_agree(out, want_out, tol=1e-5)
    _assert_grads_agree(grads, want)


BASE = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
            embed_dim=32, mlp_dim=64, layer_kinds=("gdn", "attention"))
PRE = {"gdn_norm", "attn_norm", "mlp_norm"}
POST = {"mixer_post_norm", "mlp_post_norm"}


@pytest.mark.parametrize("given, before, after", [
    (dict(), True, False), (dict(post_norm=True), True, True),
    (dict(norms="pre"), True, False), (dict(norms="pre_post"), True, True),
    (dict(norms="post"), False, True)],
    ids=["default", "post_norm", "pre", "pre_post", "post"])
def test_norm_placement_is_resolved_in_one_place(given, before, after):
    """``norm_placement`` is what ``init_params``, ``param_specs`` and the
    block read; the older ``post_norm`` resolves to before-and-after, the
    placement its one user's program (a norm either side of each branch)
    had, with the same parameter names."""
    cfg = gpt.GPTConfig(**BASE, **given)
    assert gpt.norm_placement(cfg) == (before, after)
    tree = gpt.init_params(jax.random.PRNGKey(0), cfg)
    specs = gpt.param_specs(cfg)
    assert jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P)) \
        == jax.tree.structure(tree)
    names = {k for layer in tree["layers"] for k in layer
             if k.endswith("norm")}
    assert names == (PRE if before else set()) | (POST if after else set())
    assert "out_norm" in tree
    data = _data(0, vocab=64, shape=(1, 16))
    text = jax.jit(lambda p: gpt.loss_fn(p, *data, dataclasses.replace(
        cfg, tp_axis=None, sp_axis=None, attention="dense"))).lower(
            tree).as_text(debug_info=True)
    assert ("/post_norm/" in text) == after


def test_post_norm_and_pre_post_are_one_program():
    """The legacy field and the description it resolves into lower to the
    same text: ``post_norm=True`` is ``norms="pre_post"``."""
    data = _data(0, vocab=64, shape=(1, 16))
    kw = dict(tp_axis=None, sp_axis=None, attention="dense", remat="full")
    old = gpt.GPTConfig(**BASE, **kw, post_norm=True)
    new = gpt.GPTConfig(**BASE, **kw, norms="pre_post")
    tree = gpt.init_params(jax.random.PRNGKey(0), old)
    lowered = [jax.jit(lambda p, cfg=cfg: gpt.loss_fn(p, *data, cfg))
               .lower(tree).as_text() for cfg in (old, new)]
    assert lowered[0] == lowered[1]


@pytest.mark.parametrize("bad", [dict(norms="after"),
                                 dict(norms="post", post_norm=True)])
def test_a_placement_that_cannot_be_is_refused_by_name(bad):
    with pytest.raises(ValueError, match="norms must be one of"):
        gpt.init_params(jax.random.PRNGKey(0), gpt.GPTConfig(**BASE, **bad))

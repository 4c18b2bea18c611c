"""The expert layer (``parallel/moe.py``) and the sparse decoder built on it
(``models/gpt.py`` with OLMoE's block) against the plain every-expert-on-
every-token reference the benchmark keeps (``benchmarks/reference/
gpt_moe_dp.py``): float32, tiny sizes, seeded."""

import collections
import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.models import gpt  # noqa: E402
from horovod_tpu.observability import sample_value  # noqa: E402
from horovod_tpu.parallel import moe  # noqa: E402
from horovod_tpu.parallel.moe import moe_layer  # noqa: E402

from benchmarks import flops_moe  # noqa: E402
from benchmarks.reference import gpt_bd_moe_dp as bd_reference  # noqa: E402
from benchmarks.reference import gpt_linear_moe_dp as share_reference  # noqa: E402,E501
from benchmarks.reference import gpt_moe_dp as reference  # noqa: E402

T, D, M, E = 48, 16, 24, 16


def layer_inputs(seed=0, experts=E):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (T, D)),
            jax.random.normal(ks[1], (D, experts)),
            jax.random.normal(ks[2], (experts, D, M)) / 4,
            jax.random.normal(ks[3], (experts, D, M)) / 4,
            jax.random.normal(ks[4], (experts, M, D)) / 5)


def program(top_k):
    def f(h, *w):
        y, aux = moe_layer(h, *w, top_k=top_k, dtype=jnp.float32)
        # A loss that weighs every output and both auxiliary terms.
        return (jnp.sum(y * jnp.cos(y)) + aux["load_balance"]
                + aux["router_z"]), (y, aux)
    return f


def plain(top_k):
    def f(h, *w):
        y, load_balance, router_z, counts = reference.expert_layer(
            h, *w, top_k)
        return (jnp.sum(y * jnp.cos(y)) + load_balance + router_z), (
            y, {"load_balance": load_balance, "router_z": router_z,
                "counts": counts})
    return f


def assert_layer_is_the_reference(args, top_k):
    wrt = tuple(range(5))
    (_, (y, aux)), grads = jax.jit(jax.value_and_grad(
        program(top_k), argnums=wrt, has_aux=True))(*args)
    (_, (y_ref, aux_ref)), grads_ref = jax.jit(jax.value_and_grad(
        plain(top_k), argnums=wrt, has_aux=True))(*args)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux["load_balance"], aux_ref["load_balance"],
                               rtol=1e-5)
    np.testing.assert_allclose(aux["router_z"], aux_ref["router_z"],
                               rtol=1e-5)
    np.testing.assert_array_equal(aux["counts"],
                                  np.asarray(aux_ref["counts"], np.int32))
    assert int(aux["counts"].sum()) == T * top_k        # nothing dropped
    for name, g, g_ref in zip(("h", "W_r", "W_gate", "W_up", "W_down"),
                              grads, grads_ref):
        np.testing.assert_allclose(
            g, g_ref, rtol=1e-5, atol=1e-5 * float(jnp.abs(g_ref).max()),
            err_msg=name)
    return aux["counts"]


@pytest.mark.parametrize("top_k", [1, 2, 8])
def test_layer_matches_the_reference(top_k):
    assert_layer_is_the_reference(layer_inputs(top_k), top_k)


def test_one_expert_taking_every_token_drops_none():
    """A router whose columns are equal ties every probability, ties go to
    the lower index, so every token's two experts are experts 0 and 1: a
    capacity would overflow; the grouped layer computes all 96 rows."""
    h, router, *w = layer_inputs(3)
    router = jnp.broadcast_to(router[:, :1], router.shape)
    counts = assert_layer_is_the_reference((h, router, *w), 2)
    assert counts.tolist() == [T, T] + [0] * (E - 2)


@pytest.mark.parametrize("top_k, skew", [(1, False), (2, False), (8, False),
                                         (2, True)])
def test_down_and_combine_has_autodiffs_gradients(top_k, skew):
    """The layer's tail (down projection, rows back in token order, weighted
    sum) has a backward pass of its own, which takes the weights' gradient
    from the hidden rows and never from the experts' outputs: held here to
    autodiff of the plain formula, a matrix a row and no grouped matmul.

    What that buys shows in ``test_full_remat_keeps_what_is_dear_to_make_
    again[sparse-adds]``: the backward pass has no use for the recomputed down
    projection, JAX drops it from the checkpointed block's jaxpr as dead code
    (the rule's forward is inlined there like any other code), and a layer's
    count of grouped matmuls is 11 in the jaxpr as in the compiled step."""
    ks = jax.random.split(jax.random.PRNGKey(top_k), 5)
    f32 = jnp.float32
    scores = jax.random.normal(ks[0], (T, E), f32)
    if skew:        # every token to experts 0 and 1: ties to the lower index
        scores = jnp.zeros((T, E), f32)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(scores), top_k)
    top_p = top_p * jax.random.uniform(ks[1], top_p.shape, f32, minval=0.5)
    order = jnp.argsort(top_e.reshape(-1), stable=True)
    inv = jnp.argsort(order)
    expert_of_row = top_e.reshape(-1)[order]
    counts = jnp.bincount(expert_of_row, length=E).astype(jnp.int32)
    if skew:
        assert counts.tolist() == [T, T] + [0] * (E - 2)
    hidden = jax.random.normal(ks[2], (T * top_k, M), f32)
    w_down = jax.random.normal(ks[3], (E, M, D), f32) / 5
    weigh = jax.random.normal(ks[4], (T, D), f32)

    def program(hidden, w_down, top_p):
        return moe._down_and_combine(hidden, w_down, top_p, order, inv,
                                     counts, None)

    def plain(hidden, w_down, top_p):
        out_rows = jnp.einsum("rm,rmd->rd", hidden, w_down[expert_of_row])
        return jnp.sum(out_rows[inv].reshape(T, top_k, D)
                       * top_p[:, :, None], axis=1)

    wrt = (0, 1, 2)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * weigh)       # noqa: E731
    with jax.default_matmul_precision("highest"):
        y = program(hidden, w_down, top_p)
        y_ref = plain(hidden, w_down, top_p)
        grads = jax.jit(jax.grad(loss(program), argnums=wrt))(
            hidden, w_down, top_p)
        grads_ref = jax.jit(jax.grad(loss(plain), argnums=wrt))(
            hidden, w_down, top_p)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    for name, g, g_ref in zip(("hidden", "w_down", "top_p"), grads,
                              grads_ref):
        assert g.shape == g_ref.shape and g.dtype == g_ref.dtype, name
        np.testing.assert_allclose(
            g, g_ref, rtol=1e-5, atol=1e-5 * float(jnp.abs(g_ref).max()),
            err_msg=name)


def olmoe(**kw):
    base = dict(vocab_size=64, num_layers=2, num_heads=4, num_kv_heads=4,
                head_dim=8, embed_dim=32, mlp_dim=16, dtype=jnp.float32,
                tp_axis=None, sp_axis=None, attention="dense", moe_every=1,
                num_experts=8, experts_per_token=2, load_balance_coef=0.01,
                router_z_coef=0.001, qk_norm=True, norm_eps=1e-5)
    return gpt.GPTConfig(**{**base, **kw})


def olmoe_batch(cfg, batch=2, seq=32, seed=1):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    targets = np.roll(tokens, -1, -1)
    targets[:, -1] = -1
    positions = np.broadcast_to(np.arange(seq, dtype=np.int32),
                                (batch, seq)).copy()
    return tokens, targets, positions


def olmoe_params(cfg, seed=0):
    params = gpt.init_params(jax.random.PRNGKey(seed), cfg)
    # Norm weights off one, so that a norm applied to the wrong axis shows.
    key = jax.random.PRNGKey(seed + 1)
    for lp in params["layers"]:
        for name in ("q_norm", "k_norm", "attn_norm", "mlp_norm"):
            key, sub = jax.random.split(key)
            lp[name] = 1.0 + 0.1 * jax.random.normal(sub, lp[name].shape)
    return params


def traced_loss_and_grads(cfg, params, data):
    return jax.value_and_grad(
        lambda p: gpt.loss_and_aux(p, *data, cfg), has_aux=True)(params)


# One program a configuration; what sets the expert layer's tile, which
# ``jit`` would not see, takes ``traced_loss_and_grads`` under a trace of
# its own.
loss_and_grads = jax.jit(traced_loss_and_grads, static_argnums=0)


def test_sparse_decoder_matches_the_reference():
    cfg = olmoe()
    params, data = olmoe_params(cfg), olmoe_batch(cfg)
    (loss, aux), grads = loss_and_grads(cfg, params, data)
    (want, parts), grads_ref = jax.jit(jax.value_and_grad(
        lambda p: reference.shard_loss(
            p, *data, top_k=2, norm_eps=1e-5, load_balance_coef=0.01,
            router_z_coef=0.001), has_aux=True))(params)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for key in ("cross_entropy", "load_balance", "router_z"):
        np.testing.assert_allclose(aux[key], parts[key], rtol=1e-5)
    np.testing.assert_array_equal(aux["counts"], parts["counts"])
    # The auxiliary terms are in the loss, under their coefficients.
    np.testing.assert_allclose(
        loss, aux["cross_entropy"] + 0.01 * aux["load_balance"]
        + 0.001 * aux["router_z"], rtol=1e-6)
    assert float(aux["load_balance"]) > 1.5     # two layers, about 1 each
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), g_ref in zip(flat, jax.tree.leaves(grads_ref)):
        np.testing.assert_allclose(
            g, g_ref, rtol=2e-4, atol=2e-5 * float(jnp.abs(g_ref).max()),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("change", [dict(attention="flash"),
                                    dict(remat="full")])
def test_sparse_decoder_flash_and_remat_change_nothing(change):
    base, other = olmoe(), olmoe(**change)
    params, data = olmoe_params(base), olmoe_batch(base, seq=128)
    (l0, a0), g0 = loss_and_grads(base, params, data)
    (l1, a1), g1 = loss_and_grads(other, params, data)
    np.testing.assert_allclose(l1, l0, rtol=1e-5)
    np.testing.assert_array_equal(a1["counts"], a0["counts"])
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=5e-4,
                                   atol=5e-5 * float(jnp.abs(b).max()))


def ops_of(jaxpr, out=None, outside=()):
    """Equations by primitive in a jaxpr and every jaxpr under it (but those
    of the primitives ``outside``, which are not counted either); a Pallas
    kernel under its own name, a matmul or a gather under its output's shape
    too."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in outside:
            continue
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] += 1
            continue
        out[eqn.primitive.name] += 1
        if eqn.primitive.name in ("dot_general", "gather"):
            out[eqn.primitive.name, eqn.outvars[0].aval.shape] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            ops_of(sub, out, outside)
    return out


def step_ops(cfg, params, data):
    """``ops_of`` the differentiated step of ``cfg``."""
    return ops_of(jax.make_jaxpr(
        lambda p: traced_loss_and_grads(cfg, p, data))(params).jaxpr)


def assert_remat_changes_no_number(full, none, params, data):
    (l0, a0), g0 = loss_and_grads(none, params, data)
    (l1, a1), g1 = loss_and_grads(full, params, data)
    np.testing.assert_allclose(l1, l0, rtol=1e-6)
    for a, b in zip(jax.tree.leaves((a1, g1)), jax.tree.leaves((a0, g0))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# What a block does with a branch beside adding it to the stream: nothing
# (OLMoE's), a norm after it (Trinity's, Olmo's), a learned scale (ZAYA1's).
# Under the last two the block's own backward pass reads the branch's value.
BLOCKS = {"adds": {}, "post_norm": dict(post_norm=True),
          "residual_scaling": dict(residual_scaling=True)}


@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("feed_forward", ["dense", "sparse"])
def test_full_remat_keeps_what_is_dear_to_make_again(feed_forward, block):
    """Under ``remat="full"`` a block keeps the flash kernel's output and
    log-sum-exp and the dense feed-forward's pre-activation: the
    differentiated step holds no second flash forward and no second up
    projection. The expert layer keeps its matrices in the compute dtype,
    what fixes its routing (PR 54) and, where it works on all its rows at
    once as here, what lies in the sort's order and is dear to make again:
    the sorted rows and the gate and up products before the activation
    (``moe_rows``, ``moe_pre_activation``, PR 59; safe because the order
    they lie in is kept with them), so the gate and up products do not run
    again; nor does the down product, because the backward pass of
    ``moe._down_and_combine`` has no use for it. Where the block's own
    backward pass has a use for a branch's value (a norm after the branch,
    a learned residual scale) the block keeps that value (``branch_out``),
    so no branch's last product runs again there either: 9 grouped matmuls
    a layer (3 forward, 6 backward: what a step without checkpointing
    holds; 11 before PR 59, gate and up again), and as many products ``[B,
    S, E]`` (the down product, the output projection and the backward
    pass's) as a step without checkpointing holds. The numbers are
    ``"none"``'s."""
    kind = dict(moe_every=0, mlp_dim=48) if feed_forward == "dense" else {}
    full = olmoe(attention="flash", remat="full", **kind, **BLOCKS[block])
    none = dataclasses.replace(full, remat="none")
    batch, seq = 2, 128
    params, data = olmoe_params(full), olmoe_batch(full, batch, seq)

    ops, plain = (step_ops(cfg, params, data) for cfg in (full, none))
    assert ops["hvd_flash_fwd"] == full.num_layers
    # The backward pass is one kernel a layer, under the dKdV kernel's name.
    assert ops["hvd_flash_dkdv"] == full.num_layers
    assert ops["hvd_flash_dq"] == 0
    if feed_forward == "dense":
        # The up projection, and the backward pass's product with the down
        # projection's matrix, which has the same shape.
        assert ops["dot_general", (batch, seq, full.mlp_dim)] \
            == 2 * full.num_layers
    else:
        # Three forward, two each backward, none again: ``"none"``'s.
        assert ops["ragged_dot_general"] == 9 * full.num_layers \
            == plain["ragged_dot_general"]
    # The stream's own shape: a block that only adds its branches makes the
    # mixer's output projection again (the feed-forward reads the stream
    # behind it) and not the feed-forward's last product; one that keeps
    # its branches makes neither again.
    again = full.num_layers if block == "adds" else 0
    stream = "dot_general", (batch, seq, full.embed_dim)
    assert ops[stream] == plain[stream] + again

    if block != "adds":
        # What these cases' numbers hold is the policy, not the kernels
        # (interpreted, 2 s a run: the ``adds`` cases hold them).
        full, none = (dataclasses.replace(cfg, attention="dense")
                      for cfg in (full, none))
    assert_remat_changes_no_number(full, none, params, data)


def test_a_kept_branch_leaves_no_window_in_the_recomputed_copy(
        moe_row_tile):
    """A share's windows under a norm after the branch: the forward pass
    takes them, the backward rule takes them again by design
    (``moe._held_experts_bwd``), and the recomputed copy between the two
    holds none, because the block keeps the expert sublayer's output, shared
    expert and all: two loops a layer, as without checkpointing, and the
    grouped matmuls of two. (A shared expert under a sigmoid gate of its own
    would make its down product again for the gate's gradient.) That the
    numbers are ``"none"``'s: ``test_a_share_matches_its_reference`` for a
    window under checkpointing, ``tests/test_gpt_window_moe.py`` for the
    decoder under a norm after the branch."""
    moe_row_tile(8)
    full = olmoe(attention="dense", remat="full", post_norm=True,
                 experts_held=2, first_expert=2, shared_expert_dim=16,
                 shared_expert_gate=False, num_layers=1)
    none = dataclasses.replace(full, remat="none")
    batch, seq = 2, 32
    assert moe.share_rows(batch * seq, 2, 2, 8) < batch * seq * 2
    params, data = olmoe_params(full), olmoe_batch(full, batch, seq)

    ops, plain = (step_ops(cfg, params, data) for cfg in (full, none))
    assert ops["while"] == plain["while"] == 2 * full.num_layers
    assert ops["ragged_dot_general"] == plain["ragged_dot_general"]
    # Nor the shared expert's three products or any branch's last one.
    stream = "dot_general", (batch, seq, full.embed_dim)
    assert ops[stream] == plain[stream]


@pytest.mark.parametrize("block", BLOCKS)
def test_metrics_count_what_a_checkpointed_block_keeps(make_runtime, block):
    make_runtime(devices=jax.devices()[:1])
    family = "hvdtpu_spmd_remat_saved_bytes_total"
    # Shapes no other test of this file traces: JAX splits a block it has
    # split before from its cache, without asking the policy.
    batch, seq = 3, 256
    sparse = olmoe(attention="flash", remat="none", num_layers=1,
                   num_experts=4, **BLOCKS[block])
    data = olmoe_batch(sparse, batch, seq)

    def trace(cfg):
        jax.make_jaxpr(lambda p: traced_loss_and_grads(cfg, p, data))(
            gpt.init_params(jax.random.PRNGKey(0), cfg))

    trace(sparse)
    assert hvd.metrics()[family]["samples"] == []

    def kept():
        return {labels["name"]: value for _, labels, value
                in hvd.metrics()[family]["samples"]
                if labels["mode"] == "full"}

    # A dense block keeps nothing under the expert layer's names.
    trace(dataclasses.replace(sparse, remat="full", moe_every=0))
    assert not [name for name in kept() if name.startswith("moe_")]
    trace(dataclasses.replace(sparse, remat="full"))
    fams = hvd.metrics()
    assert fams[family]["type"] == "counter"
    tokens, f32, i32 = batch * seq, 4, 4
    pairs = tokens * sparse.experts_per_token
    heads = sparse.num_heads * sparse.head_dim
    # (``jnp.argsort``'s indices are as wide as the process's integers: this
    # suite runs with ``jax_enable_x64``, a job does not.)
    index = jnp.argsort(jnp.zeros(1)).dtype.itemsize
    # Two blocks split, a flash pair each; the dense block's up projection;
    # the expert block's three matrices an expert (float32 here: the cast
    # that carries the name is to the compute dtype) and what fixes its
    # routing: the router's outputs, a token's chosen experts and their
    # scores, the sort's order and its inverse (every expert is held), and
    # in that order the rows the experts read and their gate and up products.
    want = {"flash_out": 2 * tokens * heads * f32,
            "flash_lse": 2 * tokens * sparse.num_heads * f32,
            "ffn_pre_activation": tokens * sparse.mlp_dim * f32,
            "moe_expert_matrices": (3 * sparse.num_experts * sparse.embed_dim
                                    * sparse.mlp_dim * f32),
            "moe_router_logits": tokens * sparse.num_experts * f32,
            "moe_top_experts": pairs * i32,
            "moe_top_weights": pairs * f32,
            "moe_order": pairs * index,
            "moe_order_inverse": pairs * index,
            "moe_rows": pairs * sparse.embed_dim * f32,
            "moe_pre_activation": 2 * pairs * sparse.mlp_dim * f32}
    if block != "adds":
        # Two branches a block, where the block's backward pass reads them.
        want["branch_out"] = 2 * 2 * tokens * sparse.embed_dim * f32
    # Neither block has a recurrent mixer: nothing is kept under their names
    # (tests/test_gpt_hybrid.py and test_gpt_linear_moe.py count those).
    assert kept() == want


def test_dense_decoder_has_no_auxiliary_terms():
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=1, num_heads=2, head_dim=8,
                        embed_dim=16, mlp_dim=32, dtype=jnp.float32,
                        tp_axis=None, sp_axis=None, attention="dense")
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    data = olmoe_batch(cfg, seq=16)
    loss, aux = gpt.loss_and_aux(params, *data, cfg)
    assert set(aux) == {"cross_entropy"}
    assert float(loss) == float(gpt.loss_fn(params, *data, cfg))
    assert "moe" not in params["layers"][0]
    assert "q_norm" not in params["layers"][0]


def test_qk_norm_over_tensor_parallel_heads(make_runtime):
    """The q/k norm is over all heads together: with the heads over tp its
    mean square is summed across the ranks."""
    make_runtime(mesh_shape={"tp": 2}, devices=jax.devices()[:2])
    cfg = olmoe(tp_axis="tp")
    params, data = olmoe_params(cfg), olmoe_batch(cfg)
    want = gpt.forward(params, data[0], data[2], olmoe())
    got = hvd.run_step(
        lambda p, t, pos: gpt.forward(p, t, pos, cfg),
        in_specs=(gpt.param_specs(cfg), P(), P()), out_specs=P())(
            params, data[0], data[2])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def sparse_step(remat):
    cfg = olmoe(attention="flash", remat=remat, num_layers=1)
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
    data = hvd.shard_batch(olmoe_batch(cfg, batch=4, seq=128))
    return step, params, hvd.replicate(opt.init(params)), data


def test_compiled_step_carries_the_expert_layers_scopes(make_runtime):
    make_runtime(devices=jax.devices()[:4])
    step, *args = sparse_step("full")
    # The grouped matmul is one primitive (JAX expands it when it lowers for
    # the CPU; the TPU's compiler makes it a kernel).
    assert "ragged_dot" in str(jax.make_jaxpr(step)(*args))
    names = set(re.findall(r'op_name="([^"]*)"',
                           step.lower(*args).compile().as_text()))

    def some(*parts):
        return any(all(p in n for p in parts) for n in names)

    for scope in ("router", "dispatch", "experts", "combine"):
        assert some("jvp(layer0)", f"/moe/{scope}/"), scope
        assert some("transpose(jvp(layer0))", f"/moe/{scope}/"), scope
    # The recomputed pass: the router's scores from its kept outputs and the
    # activation on the kept gate and up products. Nothing of the dispatch
    # (the order and the sorted rows are kept, PR 54 and PR 59) and nothing
    # of the combine: its backward pass needs no expert's output.
    for scope in ("router", "experts"):
        assert some(f"rematted_computation/moe/{scope}/"), scope
    for scope in ("dispatch", "combine"):
        assert not some(f"rematted_computation/moe/{scope}/"), scope
    assert some("jvp(aux_loss)")
    assert not some("/mlp/")


def _shared(seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"w_gate": jax.random.normal(ks[0], (D, M)) / 4,
            "w_up": jax.random.normal(ks[1], (D, M)) / 4,
            "w_down": jax.random.normal(ks[2], (M, D)) / 5,
            "gate": jax.random.normal(ks[3], (D,)) / 4}


SHARES = (0, 4, 8, 12)
# How many of the T k rows a share's experts draw, against the window R the
# layer works on at a time (``moe.share_rows``): well under it, exactly R,
# and all of them (two windows here).
ROUTINGS = ("under", "exact", "over")


@pytest.fixture
def small_tile(moe_row_tile):
    """At 48 tokens a window rounded up to the grouped matmul's tile of 512
    rows is all the rows; with a tile of 8 a share of 4 experts of 16 at 4 a
    token works on 96 rows of 192 at a time."""
    moe_row_tile(8)
    assert moe.share_rows(T, 4, 4, E) == 96 == T * 4 // 2


def routed_inputs(seed, routing, first, held=4):
    """``layer_inputs`` with the router the identity, so that the tokens are
    the router's logits, and the share's columns raised or lowered: ``under``
    leaves the routing to chance (the even share of the rows held on
    average), ``exact`` sends the first half of the tokens to the held
    experts alone and the others past them, ``over`` sends every token to
    them."""
    h, router, *w = layer_inputs(seed)
    assert D == E
    # Tokens of the usual size; the logits are eight times them.
    h, lift = 0.25 * h, jnp.zeros((T, E)).at[:, first:first + held].set(2.5)
    if routing == "exact":
        h = h + jnp.where(jnp.arange(T)[:, None] < T // 2, lift, -lift)
    elif routing == "over":
        h = h + lift
    return (h, 8 * jnp.eye(D, E), *w)


def assert_rows_held(counts, routing, first, held=4, top_k=4):
    rows = int(counts[first:first + held].sum())
    window = moe.share_rows(T, top_k, held, E)
    assert {"under": rows < window * 3 // 4, "exact": rows == window,
            "over": rows == T * min(top_k, held)}[routing], (rows, window)
    return -(-rows // window)


def as_a_block_runs_it(f, remat):
    """``f`` plain, or checkpointed as a ``remat="full"`` block is."""
    return jax.checkpoint(f, policy=gpt._full_policy) if remat == "full" \
        else f


# The cuts whose shares are added up: Qwen3-Next's and Trinity's (four
# shares of 4 of 16 experts beside a shared expert that every chip computes
# alike, counted once) and SDAR's (eight shares, 2 of 16 experts each, no
# shared expert), each against the reference its benchmark job keeps.
CUTS = {"four-shares-and-a-shared-expert": (4, True, share_reference),
        "eight-shares": (2, False, bd_reference)}


@pytest.mark.parametrize("cut", CUTS)
@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_shares_add_up_to_the_uncut_layer(small_tile, routing, cut):
    """The cut a configuration with more experts than a chip makes: at 16
    experts, 4 a token, renormalised, the outputs of the shares (four of 4,
    or eight of 2) with the shared expert, where there is one, counted once
    add up to what the uncut reference
    gives for the whole layer, and so do the gradients of the router (each
    share sees the whole router) and of the tokens; each share's own
    experts' gradients are the uncut layer's rows. Whatever the routing: the
    share whose experts are favoured finds its rows in one window, fills it
    exactly, or takes two, and the other shares one."""
    top_k = 4
    held, has_shared, reference = CUTS[cut]
    h, router, w_gate, w_up, w_down = routed_inputs(11, routing, first=4,
                                                    held=held)
    shared = _shared(12)
    weigh = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)

    def whole(h, router, w_gate, w_up, w_down):
        m = {"router": router, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down, **({"shared": shared} if has_shared else {})}
        y, load_balance, counts = reference.expert_block(h, m, top_k)
        return jnp.sum(y * weigh), (y, load_balance, counts)

    def share(first):
        def f(h, router, w_gate, w_up, w_down):
            y, aux = moe_layer(
                h, router, w_gate[first:first + held],
                w_up[first:first + held], w_down[first:first + held],
                top_k=top_k, dtype=jnp.float32, first_expert=first,
                renormalize=True)
            return jnp.sum(y * weigh), (y, aux)
        return f

    def once(h):
        return share_reference.shared_expert(h, shared) if has_shared \
            else jnp.zeros_like(h)

    args = (h, router, w_gate, w_up, w_down)
    (_, (y_ref, lb_ref, counts_ref)), g_ref = jax.jit(jax.value_and_grad(
        whole, argnums=range(5), has_aux=True))(*args)
    assert_rows_held(counts_ref, routing, first=4, held=held)
    total, grads = once(h), None
    for first in range(0, E, held):
        (_, (y, aux)), g = jax.jit(jax.value_and_grad(
            share(first), argnums=range(5), has_aux=True))(*args)
        # The router's terms are the whole router's on every share.
        np.testing.assert_allclose(aux["load_balance"], lb_ref, rtol=1e-5)
        np.testing.assert_array_equal(aux["counts"],
                                      np.asarray(counts_ref, np.int32))
        total = total + y
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    np.testing.assert_allclose(total, y_ref, rtol=1e-5, atol=1e-5)
    # The uncut loss's gradient has the shared expert's part too: through h.
    g_once = jax.jit(jax.grad(lambda h: jnp.sum(once(h) * weigh)))(h)
    for name, got, want in zip(
            ("h", "W_r", "W_gate", "W_up", "W_down"),
            (grads[0] + g_once,) + tuple(grads[1:]), g_ref):
        np.testing.assert_allclose(
            got, want, rtol=1e-5, atol=1e-5 * float(jnp.abs(want).max()),
            err_msg=name)


def assert_share_is_its_reference(inputs, first, held, top_k, remat):
    """One share against the benchmark's reference given the same share:
    output, load-balance term, counts, every gradient (tokens, router, the
    three expert tensors). Returns the tokens every expert got."""
    h, router, *w = inputs
    share = tuple(t[first:first + held] for t in w)

    def got(h, router, *w):
        y, aux = moe_layer(h, router, *w, top_k=top_k, dtype=jnp.float32,
                           first_expert=first, renormalize=True)
        return jnp.sum(y * jnp.cos(y)) + aux["load_balance"], (y, aux)

    def want(h, router, *w):
        m = dict(zip(("router", "w_gate", "w_up", "w_down"), (router, *w)))
        y, load_balance, counts = share_reference.expert_block(
            h, m, top_k, first)
        return jnp.sum(y * jnp.cos(y)) + load_balance, (y, counts)

    (_, (y, aux)), grads = jax.jit(jax.value_and_grad(
        as_a_block_runs_it(got, remat), argnums=range(5), has_aux=True))(
            h, router, *share)
    (_, (y_ref, counts)), grads_ref = jax.jit(jax.value_and_grad(
        want, argnums=range(5), has_aux=True))(h, router, *share)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(aux["counts"], np.asarray(counts, np.int32))
    for g, g_ref in zip(grads, grads_ref):
        np.testing.assert_allclose(
            g, g_ref, rtol=1e-5, atol=1e-5 * float(jnp.abs(g_ref).max()))
    return counts


@pytest.mark.parametrize("remat", ["none", "full"])
@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("first", SHARES)
def test_a_share_matches_its_reference(small_tile, first, routing, remat):
    """Every share of 4 of 16 experts at 4 a token, its rows in one window
    of the order, filling it exactly, and in two, plain and checkpointed as
    a block is."""
    counts = assert_share_is_its_reference(
        routed_inputs(13 + first, routing, first), first, 4, 4, remat)
    assert_rows_held(counts, routing, first)


@pytest.mark.parametrize("remat", ["none", "full"])
def test_a_routing_of_four_windows_matches_its_reference(small_tile, remat):
    """Two of 16 experts held at 2 a token: a window is 24 rows of 96, and a
    router that sends every token to those two fills four, the last to its
    end."""
    top_k, first, held = 2, 6, 2
    assert moe.share_rows(T, top_k, held, E) == 24
    counts = assert_share_is_its_reference(
        routed_inputs(31, "over", first, held), first, held, top_k, remat)
    assert assert_rows_held(counts, "over", first, held, top_k) == 4


def test_a_share_at_the_real_tile_matches_its_reference():
    """No constant patched: 2048 tokens, 4 of 16 experts held, 4 a token:
    the layer works on 4096 of 8192 rows at a time."""
    top_k, first, tokens = 4, 8, 2048
    assert moe.share_rows(tokens, top_k, 4, E) == 4096
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    h = jax.random.normal(ks[0], (tokens, D))
    router = jax.random.normal(ks[1], (D, E))
    w = tuple(t[first:first + 4] for t in layer_inputs(6)[2:])

    def got(h, router, *w):
        y, aux = moe_layer(h, router, *w, top_k=top_k, dtype=jnp.float32,
                           first_expert=first, renormalize=True)
        return jnp.sum(y * jnp.cos(y)), aux["counts"]

    def want(h, router, *w):
        m = dict(zip(("router", "w_gate", "w_up", "w_down"), (router, *w)))
        y, _, counts = share_reference.expert_block(h, m, top_k, first)
        return jnp.sum(y * jnp.cos(y)), counts

    (loss, counts), grads = jax.jit(jax.value_and_grad(
        got, argnums=range(5), has_aux=True))(h, router, *w)
    (loss_ref, _), grads_ref = jax.jit(jax.value_and_grad(
        want, argnums=range(5), has_aux=True))(h, router, *w)
    assert 0 < int(counts[first:first + 4].sum()) < 4096
    np.testing.assert_allclose(loss, loss_ref, rtol=1e-5)
    for g, g_ref in zip(grads, grads_ref):
        np.testing.assert_allclose(
            g, g_ref, rtol=1e-4, atol=1e-5 * float(jnp.abs(g_ref).max()))


@pytest.mark.parametrize("routing", ["under", "exact"])
def test_a_windows_down_and_combine_has_autodiffs_gradients(routing):
    """``test_down_and_combine_has_autodiffs_gradients`` for the rule that
    serves a share's window: 96 rows of the order's 192, of which the held
    experts (0 to 3) draw fewer, or all 96."""
    top_k, held, window_rows = 4, 4, 96
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    f32 = jnp.float32
    scores = jax.random.normal(ks[0], (T, E), f32)
    if routing == "exact":
        lift = jnp.zeros((T, E), f32).at[:, :held].set(10.0)
        scores = scores + jnp.where(jnp.arange(T)[:, None] < T // 2, lift,
                                    -lift)
    top_p, top_e = jax.lax.top_k(jax.nn.softmax(scores), top_k)
    order = jnp.argsort(top_e.reshape(-1), stable=True)
    pair_of_row = order[:window_rows]
    expert_of_row = top_e.reshape(-1)[pair_of_row]
    counts = jnp.bincount(top_e.reshape(-1), length=E).astype(
        jnp.int32)[:held]
    n = int(counts.sum())
    assert n == window_rows if routing == "exact" else n < window_rows * 3 // 4
    mine = (jnp.arange(window_rows) < n)[:, None]
    hidden = jax.random.normal(ks[2], (window_rows, M), f32)
    w_down = jax.random.normal(ks[3], (held, M, D), f32) / 5
    weigh = jax.random.normal(ks[4], (T, D), f32)

    def program(hidden, w_down, top_p):
        return moe._down_and_combine_window(hidden, w_down, top_p, pair_of_row,
                                         counts, mine)

    def plain(hidden, w_down, top_p):
        out_rows = jnp.einsum("rm,rmd->rd", hidden,
                              w_down[jnp.minimum(expert_of_row, held - 1)])
        p_rows = top_p.reshape(-1)[pair_of_row][:, None]
        return jax.ops.segment_sum(jnp.where(mine, out_rows * p_rows, 0),
                                   pair_of_row // top_k, num_segments=T)

    wrt = (0, 1, 2)
    loss = lambda f: lambda *a: jnp.sum(f(*a) * weigh)       # noqa: E731
    with jax.default_matmul_precision("highest"):
        y = program(hidden, w_down, top_p)
        y_ref = plain(hidden, w_down, top_p)
        grads = jax.jit(jax.grad(loss(program), argnums=wrt))(
            hidden, w_down, top_p)
        grads_ref = jax.jit(jax.grad(loss(plain), argnums=wrt))(
            hidden, w_down, top_p)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    for name, g, g_ref in zip(("hidden", "w_down", "top_p"), grads,
                              grads_ref):
        assert g.shape == g_ref.shape and g.dtype == g_ref.dtype, name
        if name == "hidden":      # rows that are nobody's get no cotangent
            g_ref = jnp.where(mine, g_ref, 0)
        np.testing.assert_allclose(
            g, g_ref, rtol=1e-5, atol=1e-5 * float(jnp.abs(g_ref).max()),
            err_msg=name)


def test_share_rows_by_hand():
    # The cell's shape: 16,384 tokens, 10 a token, 32 of 512 experts: twice
    # the even share of 10,240 rows, already a multiple of 512.
    assert moe.share_rows(16384, 10, 32, 512) == 20480
    # Up to the tile: 2 x 1000 x 8 x 3 / 64 = 750.
    assert moe.share_rows(1000, 8, 3, 64) == 1024
    # Never more than all the rows: every expert held; half of them (an ep
    # axis of 2); a batch whose rows are fewer than a tile.
    assert moe.share_rows(8192, 8, 64, 64) == 8192 * 8
    assert moe.share_rows(8192, 8, 32, 64) == 8192 * 8
    assert moe.share_rows(48, 4, 4, 16) == 48 * 4


def _shapes(jaxpr, out):
    """Every array shape in a jaxpr and the jaxprs under it."""
    for eqn in jaxpr.eqns:
        out.update(v.aval.shape for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, out)
    return out


def test_a_share_has_no_tensor_of_all_the_rows():
    """The cell's shape (16,384 tokens of 2048, 10 of 512 experts a token of
    width 512, 32 held), differentiated as a checkpointed block: no tensor
    has ``T k`` rows of the tokens' width or the experts', whatever the
    routing (one that sends the held experts more than a window's rows takes
    more windows); with every expert held the layer has both."""
    tokens, d, m, experts, held, top_k = 16384, 2048, 512, 512, 32, 10
    pairs = tokens * top_k
    f32, bf16 = jnp.float32, jnp.bfloat16

    def shapes(held):
        args = (jax.ShapeDtypeStruct((tokens, d), bf16),
                jax.ShapeDtypeStruct((d, experts), f32),
                jax.ShapeDtypeStruct((held, d, m), f32),
                jax.ShapeDtypeStruct((held, d, m), f32),
                jax.ShapeDtypeStruct((held, m, d), f32))

        def loss(h, *w):
            y, aux = moe_layer(h, *w, top_k=top_k, renormalize=True)
            return jnp.sum(y.astype(f32)) + aux["load_balance"]

        return _shapes(jax.make_jaxpr(jax.grad(
            as_a_block_runs_it(loss, "full"), argnums=range(5)))(
                *args).jaxpr, set())

    wide = {(pairs, d), (pairs, m)}
    share = shapes(held)
    assert not wide & share
    assert {(moe.share_rows(tokens, top_k, held, experts), w)
            for w in (d, m)} <= share
    assert wide <= shapes(experts)


@pytest.mark.parametrize("top_k", [1, 2, 8])
def test_renormalised_weights(top_k):
    """``renormalize``: a token's weights sum to one, so with every expert
    the same matrix the layer is that expert; the gradient flows through the
    sum (the router's gradient equals autodiff of the plain formula)."""
    h, router, w_gate, w_up, w_down = layer_inputs(20 + top_k)
    same = tuple(jnp.broadcast_to(w[:1], w.shape)
                 for w in (w_gate, w_up, w_down))
    y, _ = moe_layer(h, router, *same, top_k=top_k, dtype=jnp.float32,
                     renormalize=True)
    one = (jax.nn.silu(h @ w_gate[0]) * (h @ w_up[0])) @ w_down[0]
    np.testing.assert_allclose(y, one, rtol=1e-4, atol=1e-5)

    def got(router):
        y, _ = moe_layer(h, router, w_gate, w_up, w_down, top_k=top_k,
                         dtype=jnp.float32, renormalize=True)
        return jnp.sum(y * jnp.cos(y))

    def want(router):
        m = {"router": router, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down}
        y = share_reference.expert_block(h, m, top_k)[0]
        return jnp.sum(y * jnp.cos(y))

    g, g_ref = jax.jit(jax.grad(got))(router), jax.jit(jax.grad(want))(router)
    # (At one expert a token the weight is the constant 1: both are zero.)
    np.testing.assert_allclose(
        g, g_ref, rtol=1e-4, atol=1e-5 * float(jnp.abs(g_ref).max()) + 2e-6)
    # Not renormalised, the same layer's weights sum to less than one.
    plain_y, _ = moe_layer(h, router, *same, top_k=top_k, dtype=jnp.float32)
    if top_k < E:
        assert float(jnp.abs(plain_y - one).max()) > 1e-3


@pytest.mark.parametrize("first, held", [(-1, 4), (13, 4), (1, 16)])
def test_a_share_outside_the_router_raises(first, held):
    h, router, w_gate, w_up, w_down = layer_inputs()
    with pytest.raises(ValueError, match="expert layer: experts"):
        moe_layer(h, router, w_gate[:held], w_up[:held], w_down[:held],
                  top_k=2, first_expert=first)


def test_metrics_count_a_shares_held_experts(make_runtime):
    make_runtime(devices=jax.devices()[:1])
    h, router, *w = layer_inputs()
    jax.jit(lambda h, r, *w: moe_layer(
        h, r, *w, top_k=2, dtype=jnp.float32, first_expert=8)[0])(
            h, router, *(t[8:12] for t in w))
    assert sample_value(
        hvd.metrics(), "hvdtpu_spmd_moe_layer_traces_total", experts=str(E),
        top_k="2", ep="1", grouped_matmul="ragged_dot", held="4",
        rows=str(T * 2)) == 1.0


def test_metrics_count_the_expert_layers_trace(make_runtime):
    make_runtime(devices=jax.devices()[:1])
    h, *w = layer_inputs()
    jax.jit(lambda h, *w: moe_layer(h, *w, top_k=2, dtype=jnp.float32)[0])(
        h, *w)
    fams = hvd.metrics()
    assert fams["hvdtpu_spmd_moe_layer_traces_total"]["type"] == "counter"
    assert sample_value(
        fams, "hvdtpu_spmd_moe_layer_traces_total", experts=str(E),
        top_k="2", ep="1", grouped_matmul="ragged_dot",
        held=str(E), rows=str(T * 2)) == 1.0


def test_operation_count_by_hand():
    # One token: the router's 16x8 matrix and 2 experts of three 16x24
    # matrices, two operations a multiply-accumulate.
    assert flops_moe.expert_layer_forward_flops(16, 8, 24, 2) \
        == 2 * 16 * 8 + 2 * 3 * 2 * 16 * 24 == 4864
    # A token trained at S=3 with one head of 4: q, k, v, o 4 * 2 * 16 * 4;
    # attention over (3 + 1) / 2 keys, 4 * 4 operations a key; the head
    # 2 * 16 * 10; forward and backward three times that.
    assert flops_moe.moe_train_flops(
        3, 1, 16, 1, 1, 4, experts=8, width=24, top_k=2, vocab=10) \
        == 3 * (512 + 32 + 4864 + 320)


# ---- the sigmoid router under a selection bias (Trinity-Mini's) -------------

from benchmarks.reference import gpt_window_moe_dp as window_reference  # noqa: E402

ROUTE_SCALE = 2.826


def _sigmoid_inputs(seed):
    h, router, w_gate, w_up, w_down = layer_inputs(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 4)
    block = {"router": router, "w_gate": w_gate, "w_up": w_up,
             "w_down": w_down,
             # Wide enough to change a third of the tokens' choices.
             "router_bias": 0.2 * jax.random.normal(ks[0], (E,)),
             "shared": {"w_gate": jax.random.normal(ks[1], (D, M)) / 4,
                        "w_up": jax.random.normal(ks[2], (D, M)) / 4,
                        "w_down": jax.random.normal(ks[3], (M, D)) / 5}}
    return h, block


def _sigmoid_layer(h, block, top_k, first=0, held=E, bias=True):
    return moe_layer(
        h, block["router"], *(block[k][first:first + held]
                              for k in ("w_gate", "w_up", "w_down")),
        top_k=top_k, dtype=jnp.float32, first_expert=first, renormalize=True,
        score="sigmoid", bias=block["router_bias"] if bias else None,
        scale=ROUTE_SCALE)


@pytest.mark.parametrize("top_k", [1, 4])
def test_sigmoid_router_with_bias_and_constant_matches_the_reference(top_k):
    """``s = sigmoid(h W_r)``, the choice on ``s + b``, the weights ``scale
    s / (sum of the chosen s + 1e-20)``: outputs, counts and every gradient
    against the plain every-expert-on-every-token reference; nothing reaches
    the bias."""
    h, block = _sigmoid_inputs(21)
    weigh = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)

    def program(h, block):
        y, aux = _sigmoid_layer(h, block, top_k)
        return jnp.sum(y * weigh), (y, aux["counts"])

    def plain(h, block):
        # The reference's block adds its shared expert; the layer has none.
        y, counts = window_reference.expert_block(h, block, top_k,
                                                  ROUTE_SCALE)
        y = y - window_reference.gated_ff(
            h, *(block["shared"][k] for k in ("w_gate", "w_up", "w_down")))
        return jnp.sum(y * weigh), (y, counts)

    (_, (y, counts)), g = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(h, block)
    (_, (y_ref, counts_ref)), g_ref = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True))(h, block)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(counts, np.asarray(counts_ref, np.int32))
    g[1].pop("shared"), g_ref[1].pop("shared")
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=2e-4, atol=2e-6), g, g_ref)
    assert not np.any(np.asarray(g[1]["router_bias"]))
    # The bias is in the choice: without it other experts are chosen ...
    plain_counts = _sigmoid_layer(h, block, top_k, bias=False)[1]["counts"]
    assert np.abs(np.asarray(plain_counts) - np.asarray(counts)).sum() > 0
    # ... and in nothing else: a token's weights are its scores', and add
    # up to the constant.
    scores = jax.nn.sigmoid(h @ block["router"])
    _, chosen = jax.lax.top_k(scores + block["router_bias"], top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    np.testing.assert_allclose(
        jnp.sum(ROUTE_SCALE * picked / jnp.sum(picked, -1, keepdims=True),
                axis=-1), ROUTE_SCALE, rtol=1e-6)


def test_eight_shares_of_a_sigmoid_router_add_up_to_the_uncut_layer():
    """Trinity-Mini's cut: eight ranks hold two of the 16 experts each, every
    rank routes over all 16 under the same bias, and the ranks' partial sums
    with the ungated shared expert counted once add up to the uncut layer's,
    as do the gradients of the tokens and of the router."""
    top_k = 4
    h, block = _sigmoid_inputs(31)
    weigh = jnp.sin(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)

    def whole(h, block):
        y, counts = window_reference.expert_block(h, block, top_k,
                                                  ROUTE_SCALE)
        return jnp.sum(y * weigh), (y, counts)

    def share(first):
        def f(h, block):
            y, aux = _sigmoid_layer(h, block, top_k, first=first, held=2)
            return jnp.sum(y * weigh), (y, aux["counts"])
        return f

    def shared_once(h, block):
        y = window_reference.gated_ff(
            h, *(block["shared"][k] for k in ("w_gate", "w_up", "w_down")))
        return jnp.sum(y * weigh), y

    (_, (y_ref, counts_ref)), g_ref = jax.jit(jax.value_and_grad(
        whole, argnums=(0, 1), has_aux=True))(h, block)
    (_, total), grads = jax.jit(jax.value_and_grad(
        shared_once, argnums=(0, 1), has_aux=True))(h, block)
    for first in range(0, E, 2):
        (_, (y, counts)), g = jax.jit(jax.value_and_grad(
            share(first), argnums=(0, 1), has_aux=True))(h, block)
        np.testing.assert_array_equal(counts,
                                      np.asarray(counts_ref, np.int32))
        total = total + y
        grads = jax.tree.map(jnp.add, grads, g)
    np.testing.assert_allclose(total, y_ref, rtol=2e-5, atol=2e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=5e-4, atol=2e-5), grads, g_ref)


from benchmarks.reference import gpt_kda_mla_moe_dp as grouped_reference  # noqa: E402,E501


@pytest.mark.parametrize("groups, kept, top_k, first, held", [
    (4, 2, 3, 0, E), (4, 2, 3, 4, 4), (8, 3, 4, 0, E), (2, 1, 8, 8, 8),
    (4, 4, 3, 0, E)])
def test_a_choice_limited_to_groups_matches_the_reference(groups, kept,
                                                          top_k, first, held):
    """The experts in ``groups`` groups of neighbours, a group's score the
    sum of its two largest leaning scores, a token's experts chosen inside
    the ``kept`` best groups: outputs, counts and every gradient against the
    plain reference (every held expert on every token), whole and as a
    rank's share; every chosen expert lies in a kept group; with every group
    kept it is the plain choice."""
    h, block = _sigmoid_inputs(41)
    weigh = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)
    mine = {**block, **{k: block[k][first:first + held]
                        for k in ("w_gate", "w_up", "w_down")}}

    def program(h, block):
        y, aux = moe_layer(
            h, block["router"], block["w_gate"], block["w_up"],
            block["w_down"], top_k=top_k, dtype=jnp.float32,
            first_expert=first, renormalize=True, score="sigmoid",
            bias=block["router_bias"], scale=ROUTE_SCALE,
            router_groups=groups, router_groups_kept=kept)
        return jnp.sum(y * weigh), (y, aux["counts"])

    def plain(h, block):
        y, counts = grouped_reference.expert_block(
            h, block, top_k, ROUTE_SCALE, first, groups, kept)
        y = y - grouped_reference.gated_ff(
            h, *(block["shared"][k] for k in ("w_gate", "w_up", "w_down")))
        return jnp.sum(y * weigh), (y, counts)

    (_, (y, counts)), g = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(h, mine)
    (_, (y_ref, counts_ref)), g_ref = jax.jit(jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True))(h, mine)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-6)
    np.testing.assert_array_equal(counts, np.asarray(counts_ref, np.int32))
    g[1].pop("shared"), g_ref[1].pop("shared")
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=2e-4, atol=2e-6), g, g_ref)
    assert not np.any(np.asarray(g[1]["router_bias"]))
    assert int(np.sum(counts)) == T * top_k
    ungrouped = _sigmoid_layer(h, block, top_k)[1]["counts"]
    assert (kept == groups) == bool(
        np.array_equal(np.asarray(ungrouped), np.asarray(counts)))


def test_groups_that_do_not_fit_the_router_raise_by_name():
    h, block = _sigmoid_inputs(1)
    for groups, kept, top_k in ((3, 1, 2), (4, 5, 2), (4, 1, 5), (16, 8, 2),
                                (4, 0, 2)):
        with pytest.raises(ValueError, match="groups of a router"):
            moe_layer(h, block["router"], block["w_gate"], block["w_up"],
                      block["w_down"], top_k=top_k, dtype=jnp.float32,
                      score="sigmoid", router_groups=groups,
                      router_groups_kept=kept)


def test_metrics_say_the_groups_a_layer_chose_in(make_runtime):
    make_runtime(devices=jax.devices()[:1])
    h, router, *w = layer_inputs()
    jax.jit(lambda h, r, *w: moe_layer(
        h, r, *w, top_k=3, dtype=jnp.float32, score="sigmoid",
        router_groups=4, router_groups_kept=2)[0])(h, router, *w)
    jax.jit(lambda h, r, *w: moe_layer(
        h, r, *w, top_k=3, dtype=jnp.float32, score="sigmoid")[0])(
            h, router, *w)
    for groups, kept in (("4", "2"), ("1", "1")):
        assert sample_value(
            hvd.metrics(), "hvdtpu_spmd_moe_layer_traces_total",
            experts=str(E), top_k="3", score="sigmoid", groups=groups,
            groups_kept=kept) == 1.0


def test_probe_returns_what_the_router_read_and_gave():
    """``probe``: the router's product's float32 operand (the activations,
    flattened to tokens) and its float32 outputs; without it ``aux`` has
    neither."""
    h, block = _sigmoid_inputs(23)
    args = (h.astype(jnp.bfloat16).reshape(2, T // 2, D), block["router"],
            block["w_gate"], block["w_up"], block["w_down"])
    kw = dict(top_k=2, score="sigmoid", bias=block["router_bias"])
    y, aux = moe_layer(*args, probe=True, **kw)
    y_plain, plain = moe_layer(*args, **kw)
    assert set(aux) - set(plain) == {"router_input", "router_logits"}
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y_plain, np.float32))
    assert aux["router_input"].dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(aux["router_input"]),
        np.asarray(args[0].reshape(T, D), np.float32))
    assert aux["router_logits"].dtype == jnp.float32
    np.testing.assert_allclose(
        aux["router_logits"], window_reference.router_logits(
            aux["router_input"], block["router"]), rtol=1e-6, atol=1e-6)


def test_an_unknown_score_raises():
    h, block = _sigmoid_inputs(1)
    with pytest.raises(ValueError, match="neither 'softmax' nor 'sigmoid'"):
        moe_layer(h, block["router"], block["w_gate"], block["w_up"],
                  block["w_down"], top_k=2, dtype=jnp.float32, score="tanh")


def test_metrics_name_the_routers_kind(make_runtime):
    make_runtime(devices=jax.devices()[:1])
    h, block = _sigmoid_inputs(2)
    jax.jit(lambda h, b: _sigmoid_layer(h, b, 2)[0])(h, block)
    assert sample_value(
        hvd.metrics(), "hvdtpu_spmd_moe_layer_traces_total", experts=str(E),
        top_k="2", score="sigmoid", bias="1") == 1.0


# --- ReLU-gated experts and a router whose outputs the caller made ---------
# (SmallThinker's layer: the block's input gives the outputs, before
# attention; the layer takes them as ``logits``.)

from benchmarks.reference import gpt_prerouted_moe_dp as prerouted_reference  # noqa: E402,E501


def _prerouted(seed, routing="under", first=4):
    """``(what the experts read, the router's outputs made elsewhere, the
    block)``: the outputs are from other activations than the experts
    read."""
    logits, _, *w = routed_inputs(seed, routing, first)
    h = jax.random.normal(jax.random.PRNGKey(seed + 100), (T, D))
    return h, 8 * logits, dict(zip(("w_gate", "w_up", "w_down"), w))


def _matrices(block, first=0, held=E):
    return tuple(block[k][first:first + held]
                 for k in ("w_gate", "w_up", "w_down"))


def _relu_layer(h, logits, block, top_k, first=0, held=E, **kw):
    return moe_layer(
        h, None, *_matrices(block, first, held), top_k=top_k,
        dtype=jnp.float32, first_expert=first, renormalize=True,
        logits=logits, activation="relu", **kw)


@pytest.mark.parametrize("remat", ["none", "full"])
@pytest.mark.parametrize("first,held,routing", [
    (0, E, "under"), (4, 4, "under"), (4, 4, "over")],
    ids=["un-windowed", "one-window", "two-windows"])
def test_relu_experts_match_the_dense_sum(small_tile, first, held, routing,
                                          remat):
    """``activation="relu"`` on all the rows at once and on a share's
    windows (one, and two through the loop's body and the backward rule's)
    against every held expert on every token: the output and the gradients
    of the tokens, of the caller's outputs and of the three expert
    tensors."""
    top_k = 4
    h, logits, block = _prerouted(41, routing, first)
    share = {k: v[first:first + held] for k, v in block.items()}

    kw = dict(top_k=top_k, dtype=jnp.float32, first_expert=first,
              renormalize=True)

    def got(h, logits, share):
        y, aux = moe_layer(h, None, *_matrices(share), logits=logits,
                           activation="relu", **kw)
        return jnp.sum(y * jnp.cos(y)), (y, aux["counts"])

    def want(h, logits, share):
        y, counts = prerouted_reference.expert_block(h, logits, share, top_k,
                                                     first)
        return jnp.sum(y * jnp.cos(y)), (y, counts)

    (_, (y, counts)), grads = jax.jit(jax.value_and_grad(
        as_a_block_runs_it(got, remat), argnums=(0, 1, 2), has_aux=True))(
            h, logits, share)
    (_, (y_ref, counts_ref)), grads_ref = jax.jit(jax.value_and_grad(
        want, argnums=(0, 1, 2), has_aux=True))(h, logits, share)
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(counts, np.asarray(counts_ref, np.int32))
    if held < E:
        assert_rows_held(counts, routing, first)
    jax.tree.map(lambda g, r: np.testing.assert_allclose(
        g, r, rtol=1e-5, atol=1e-5 * float(jnp.abs(r).max())),
        grads, grads_ref)
    # SiLU in its place is another layer.
    silu = moe_layer(h, None, *_matrices(share), logits=logits, **kw)[0]
    assert not np.allclose(silu, y_ref, rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("routing", ROUTINGS)
def test_four_relu_shares_of_a_callers_router_add_up_to_the_uncut_layer(
        small_tile, routing):
    """SmallThinker's cut: four ranks hold four of the 16 experts each,
    every rank is handed the same router outputs (made from the block's
    input) and weighs its experts by the softmax over all the chosen, held
    or not; the ranks' partial sums add up to the uncut reference's layer,
    as do the gradients of the tokens and of the outputs."""
    top_k = 4
    h, logits, block = _prerouted(43, routing)
    weigh = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)

    def whole(h, logits):
        y, counts = prerouted_reference.expert_block(h, logits, block, top_k)
        return jnp.sum(y * weigh), (y, counts)

    (_, (y_ref, counts_ref)), g_ref = jax.jit(jax.value_and_grad(
        whole, argnums=(0, 1), has_aux=True))(h, logits)
    total, grads = 0.0, None
    for first in SHARES:
        def share(h, logits):
            y, aux = _relu_layer(h, logits, block, top_k, first, 4)
            return jnp.sum(y * weigh), (y, aux["counts"])

        (_, (y, counts)), g = jax.jit(jax.value_and_grad(
            share, argnums=(0, 1), has_aux=True))(h, logits)
        np.testing.assert_array_equal(counts,
                                      np.asarray(counts_ref, np.int32))
        total = total + y
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    np.testing.assert_allclose(total, y_ref, rtol=1e-5, atol=1e-5)
    for got, want in zip(grads, g_ref):
        np.testing.assert_allclose(
            got, want, rtol=1e-5, atol=1e-5 * float(jnp.abs(want).max()))


def test_relus_derivative_at_nought_is_nought():
    """A gate product of exactly 0 passes no gradient, in the layer as in
    the reference (``jnp.maximum``'s would pass a half)."""
    h, logits, block = _prerouted(45)
    block = dict(block, w_gate=jnp.zeros_like(block["w_gate"]))

    def layer(w_gate):
        return jnp.sum(_relu_layer(h, logits, dict(block, w_gate=w_gate),
                                   2)[0])

    def plain(w_gate):
        return jnp.sum(prerouted_reference.expert_block(
            h, logits, dict(block, w_gate=w_gate), 2)[0])

    assert not np.any(np.asarray(jax.grad(layer)(block["w_gate"])))
    assert not np.any(np.asarray(jax.grad(plain)(block["w_gate"])))


def test_an_unknown_activation_raises_and_the_record_names_the_gate(
        make_runtime):
    make_runtime(devices=jax.devices()[:1])
    h, logits, block = _prerouted(47)
    with pytest.raises(ValueError, match="activation 'gelu' is none of"):
        moe_layer(h, None, *_matrices(block), top_k=2, logits=logits,
                  activation="gelu")
    aux = jax.jit(lambda: _relu_layer(h, logits, block, 2, probe=True)[1])()
    np.testing.assert_array_equal(aux["router_logits"],
                                  logits.astype(jnp.float32))
    assert sample_value(
        hvd.metrics(), "hvdtpu_spmd_moe_layer_traces_total", experts=str(E),
        top_k="2", activation="relu") == 1.0


# The routing is made once a step (PR 54). A checkpointed block keeps what
# fixes it: the router's outputs, the chosen experts and their scores, the
# sort's order (``gpt.SAVED_NAMES``'s ``moe_router_logits``,
# ``moe_top_experts``, ``moe_top_weights``, ``moe_order``,
# ``moe_order_inverse``), and its backward pass reads them.

ROUTING_CASES = [(rows, router) for rows in ("all_rows", "windowed")
                 for router in ("softmax", "sigmoid_bias", "callers_logits")]
ROUTED_WIDTH = 20           # no tensor's other extent: a product's shape tells


def routed_once(rows, router, small_tile_of, activation="silu",
                reads_output=True):
    """``(f, args, tie, leaning)``: a layer of 16 experts at 3 a token on 48 tokens
    of 20, every expert held or experts 4 to 8 with a window of half their
    even share's twice (72 of 144 rows; ``"share_all_rows"``: the same share
    at the real tile, all 144 rows at once with the other experts' held at
    zero); the router the layer's own softmax, a sigmoid under a bias, or
    the caller's product. ``f(x, W_r, W_gate, W_up, W_down)`` is a loss over
    the output and both auxiliary terms (no ``W_gate`` under an un-gated
    ``activation``; :func:`names_of` names the arguments).

    The router's columns come in pairs a constant vector ``v`` apart (and a
    pair's biases are equal), so a token's two scores of a pair differ by
    ``x_t . v``, which ``args`` hold at ``+-1e-3``: with three chosen, the
    better pair whole and the better of the next, **every token's third
    choice is a near-tie**, and ``x + tie`` turns every one the other way
    (``tie`` = ``-+2e-3 v / |v|^2`` a token; ``leaning(x)`` ``[T, E]`` is
    what the choice is made on, up to a rising function)."""
    top_k, d = 3, ROUTED_WIDTH
    first, held = (0, E) if rows == "all_rows" else (4, 4)
    if rows == "windowed":
        small_tile_of(8)
    assert moe.share_rows(T, top_k, held, E) == (
        72 if rows == "windowed" else T * top_k)
    ks = jax.random.split(jax.random.PRNGKey(54), 7)
    v = jax.random.normal(ks[0], (d,), jnp.float32)
    pairs = jax.random.normal(ks[1], (d, E // 2), jnp.float32)
    router_w = jnp.stack([pairs, pairs + v[:, None]], axis=-1).reshape(d, E)
    bias = jnp.repeat(0.2 * jax.random.normal(ks[2], (E // 2,), jnp.float32), 2)
    x = jax.random.normal(ks[3], (T, d), jnp.float32)
    gap = jnp.where(jnp.arange(T) % 2 == 0, 1e-3, -1e-3).astype(jnp.float32)
    x = x + ((gap - x @ v) / (v @ v))[:, None] * v
    tie = (-2 * gap / (v @ v))[:, None] * v
    w = (jax.random.normal(ks[4], (held, d, M), jnp.float32) / 4,
         jax.random.normal(ks[5], (held, d, M), jnp.float32) / 4,
         jax.random.normal(ks[6], (held, M, d), jnp.float32) / 5)
    how = dict(top_k=top_k, dtype=jnp.float32, first_expert=first,
               renormalize=True, activation=activation)
    if router == "sigmoid_bias":
        how.update(score="sigmoid", bias=bias, scale=1.5)
    if activation in moe.UNGATED:
        w = w[1:]

    def f(x, router_w, *w):
        if activation in moe.UNGATED:
            w = (None, *w)
        if router == "callers_logits":
            y, aux = moe_layer(
                x, None, *w, **how, logits=jnp.dot(
                    x.astype(jnp.float32), router_w,
                    precision=jax.lax.Precision.HIGHEST))
        else:
            y, aux = moe_layer(x, router_w, *w, **how)
        by = jnp.cos(y) if reads_output else jnp.cos(
            jnp.arange(y.size, dtype=y.dtype).reshape(y.shape))
        return jnp.sum(y * by) + aux["load_balance"] + aux["router_z"]

    def leaning(x):
        r = x @ router_w
        return jax.nn.sigmoid(r) + bias if router == "sigmoid_bias" else r

    return f, (x, router_w, *w), tie, leaning


def names_of(args):
    """:func:`routed_once`'s arguments by name: two matrices an expert in an
    un-gated form."""
    return ("x", "W_r", "W_gate", "W_up", "W_down") if len(args) == 5 \
        else ("x", "W_r", "W_up", "W_down")


def routing_work(equations, rematted=False):
    """How many sorts, top-k choices and router's products (``[T, d] x [d,
    E]``) a jaxpr's ``equations`` (``conftest.py::equations_of``) hold, all
    of them or those under its checkpoint equations (what a backward pass
    makes again) alone."""
    work = collections.Counter()
    for eqn, inside in equations:
        if rematted and not inside:
            continue
        if eqn.primitive.name in ("sort", "top_k"):
            work[eqn.primitive.name] += 1
        if eqn.primitive.name == "dot_general" and [
                v.aval.shape for v in eqn.invars] == [(T, ROUTED_WIDTH),
                                                      (ROUTED_WIDTH, E)]:
            work["router_product"] += 1
    return dict(work)


@pytest.mark.parametrize("rows, router", ROUTING_CASES)
def test_a_checkpointed_layer_routes_once(moe_row_tile, equations_of, rows,
                                          router):
    """The gradient's jaxpr under ``remat="full"``'s policy holds the sorts,
    the top-k and the router's product the forward pass alone needs (one
    argsort a share's windows, two with the inverse; no rule of the backward
    pass sorts or chooses), and none of them in the part the backward pass
    makes again."""
    f, args, _, _ = routed_once(rows, router, moe_row_tile)
    forward = routing_work(equations_of(jax.make_jaxpr(f)(*args).jaxpr))
    assert forward == {"sort": 1 if rows == "windowed" else 2, "top_k": 1,
                       "router_product": 1}
    wrt = tuple(range(5))
    kept = jax.make_jaxpr(jax.grad(as_a_block_runs_it(
        lambda *a: f(*a), "full"), argnums=wrt))(*args).jaxpr
    assert routing_work(equations_of(kept)) == forward
    assert routing_work(equations_of(kept), rematted=True) == {}
    # The census sees a routing that is made again: with nothing named.
    nothing = jax.make_jaxpr(jax.grad(jax.checkpoint(
        lambda *a: f(*a)), argnums=wrt))(*args).jaxpr
    assert routing_work(equations_of(nothing), rematted=True) == forward


def assert_checkpointing_changes_no_gradient(f, args):
    """Every argument's gradient under ``remat="full"``'s policy is the
    plain layer's."""
    wrt = tuple(range(len(args)))
    loss, grads = jax.jit(jax.value_and_grad(as_a_block_runs_it(
        lambda *a: f(*a), "full"), argnums=wrt))(*args)
    want_loss, want = jax.jit(jax.value_and_grad(f, argnums=wrt))(*args)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for name, g, g_ref in zip(names_of(args), grads, want):
        assert float(jnp.abs(g_ref).max()) > 0, name
        np.testing.assert_allclose(
            g, g_ref, rtol=1e-5, atol=1e-6 * float(jnp.abs(g_ref).max()),
            err_msg=name)


@pytest.mark.parametrize("rows, router", ROUTING_CASES)
def test_kept_routing_changes_no_gradient(moe_row_tile, rows, router):
    """Tokens, router and the three expert tensors: checkpointed with the
    routing kept, the gradients are the plain layer's."""
    f, args, _, _ = routed_once(rows, router, moe_row_tile)
    assert_checkpointing_changes_no_gradient(f, args)


@pytest.mark.parametrize("rows, router", ROUTING_CASES)
def test_the_two_passes_cannot_choose_otherwise(moe_row_tile, rows, router):
    """The backward pass of a checkpointed layer whose recomputation reads
    tokens a hair off the forward's (what XLA's other rounding of the
    router's input did on the chip, PERF.md, Findings, PR 53; here the kept
    input is moved in the pullback's residuals, by as much as turns every
    token's near-tie) gives the forward's gradients: it differentiates the
    forward's routing, whatever a router made again would choose. Before
    PR 54 every token's third expert changed and the gradients were off by
    their own size."""
    assert_turned_ties_change_no_gradient(
        *routed_once(rows, router, moe_row_tile))


def assert_turned_ties_change_no_gradient(f, args, tie, leaning):
    x = args[0]
    # The tie does turn the choice, to the other of one pair (for every
    # token but the few whose pair's sigmoids both round to one).
    third, turned = (np.asarray(jnp.argsort(-leaning(t), axis=-1)[:, 2])
                     for t in (x, x + tie))
    assert (third != turned).mean() > 0.9
    assert (third // 2 == turned // 2).all()

    kept = as_a_block_runs_it(lambda *a: f(*a), "full")
    _, pullback = jax.vjp(kept, *args)
    want = pullback(jnp.ones((), jnp.float32))
    leaves, tree = jax.tree_util.tree_flatten(pullback)
    is_x = [getattr(leaf, "shape", None) == x.shape
            and bool((leaf == x).all()) for leaf in leaves]
    assert sum(is_x) >= 1
    moved = jax.tree_util.tree_unflatten(tree, [
        leaf + tie if hit else leaf for leaf, hit in zip(leaves, is_x)])
    got = moved(jnp.ones((), jnp.float32))
    for name, g, g_ref in zip(names_of(args), got, want):
        np.testing.assert_allclose(
            g, g_ref, rtol=0, atol=5e-3 * float(jnp.abs(g_ref).max()),
            err_msg=name)


# The expert layer's first products are made once a step (PR 59; a share's
# window at 0 since PR 66). A checkpointed block keeps the sorted rows and the
# gate and up products before the activation (``moe_rows``,
# ``moe_pre_activation``), in the sort's order, which is kept with them: all
# the rows of a layer that works on them at once, the window at 0's of a
# share (the residuals of ``moe._held_experts``' rule). The loop's windows,
# which no even routing runs, name nothing: their rule makes each again.

KEPT_ROWS_CASES = [(rows, router, "silu")
                   for rows in ("all_rows", "windowed")
                   for router in ("softmax", "sigmoid_bias", "callers_logits")
                   ] + [("share_all_rows", "softmax", "silu"),
                        ("all_rows", "softmax", "relu2"),
                        ("windowed", "softmax", "relu2")]
ROWS_NAMES = {"moe_rows", "moe_pre_activation"}
WINDOW = 72                 # ``routed_once``'s, where it is windowed


def names_in(equations):
    return {eqn.params["name"] for eqn, _ in equations
            if eqn.primitive.name == "name"}


def first_window_work(jaxpr):
    """``(grouped matmuls, gathers of a window's rows: the tokens' rows, the
    output's cotangent's)`` of a jaxpr outside its loops: of a windowed layer
    the window at 0's, of any other all it has."""
    ops = ops_of(jaxpr, outside=("while",))
    return ops["ragged_dot_general"], ops["gather", (WINDOW, ROUTED_WIDTH)]


@pytest.mark.parametrize("rows, router, activation", KEPT_ROWS_CASES)
def test_a_checkpointed_layer_makes_its_first_products_once(
        moe_row_tile, equations_of, rows, router, activation):
    """The gradient's jaxpr under ``remat="full"``'s policy holds the grouped
    matmuls of a layer that is not checkpointed, 9 (6 un-gated: three, or
    two, forward and twice that backward; of a windowed layer those of the
    window at 0), and for this loss alone, which reads the layer's output in
    its backward pass, what makes the output again: the down product, from
    the kept products, where the layer works on all its rows at once, and
    the whole window where it does not (a block that keeps its branch has
    not even that: ``test_full_remat_keeps_what_is_dear_to_make_again``);
    the rows and products carry the two names. With nothing named the gate
    and up products are made again too, all rows at once."""
    f, args, _, _ = routed_once(rows, router, moe_row_tile, activation)
    first = 1 if activation in moe.UNGATED else 2
    wrt = tuple(range(len(args)))

    def step(g):
        return jax.make_jaxpr(jax.grad(g, argnums=wrt))(*args).jaxpr

    def grouped_calls(g):
        return first_window_work(step(g))[0]

    assert grouped_calls(f) == 3 * (first + 1)
    kept = as_a_block_runs_it(lambda *a: f(*a), "full")
    assert ROWS_NAMES <= names_in(equations_of(step(kept)))
    again = first + 1 if rows == "windowed" else 1
    assert grouped_calls(kept) == 3 * (first + 1) + again
    if rows != "windowed":
        assert grouped_calls(jax.checkpoint(lambda *a: f(*a))) \
            == 3 * (first + 1) + 1 + first


@pytest.mark.parametrize("rows, router, activation", KEPT_ROWS_CASES)
def test_kept_rows_change_no_gradient(moe_row_tile, rows, router,
                                      activation):
    """Checkpointed with the sorted rows and the pre-activations kept, every
    gradient is the plain layer's (a share's rows of other ranks' experts,
    held at zero, among them)."""
    f, args, _, _ = routed_once(rows, router, moe_row_tile, activation)
    assert_checkpointing_changes_no_gradient(f, args)


@pytest.mark.parametrize("rows, router, activation", KEPT_ROWS_CASES)
def test_kept_rows_are_read_in_the_order_they_were_written(
        moe_row_tile, rows, router, activation):
    """A buffer kept in the forward's order and read in another gives
    gradients wrong by their own size (PERF.md, Findings, PR 28): with every
    token's near-tie turned the other way between the passes, the kept rows
    and products are read under the kept order and the gradients are the
    forward's."""
    assert_turned_ties_change_no_gradient(
        *routed_once(rows, router, moe_row_tile, activation))


@pytest.mark.parametrize("router, activation", [
    ("softmax", "silu"), ("sigmoid_bias", "silu"), ("callers_logits", "silu"),
    ("softmax", "relu2")])
def test_a_shares_window_at_0_is_made_once(moe_row_tile, equations_of,
                                           router, activation):
    """A share that works a window at a time, in a block that only adds its
    output to its stream: under ``remat="full"``'s policy the gradient's
    jaxpr names the window at 0's rows and products beside what fixes the
    routing, and outside the loop it holds the grouped matmuls of a layer
    that is not checkpointed (9, 6 un-gated) and two gathers of a window's
    rows, the tokens' in the forward pass and the output's cotangent's in
    the backward pass: no rows gathered and no gate or up product made again
    (with nothing named: a third gather and the products again). The loop's
    body, which no even routing runs, still makes its own forward again."""
    f, args, _, _ = routed_once("windowed", router, moe_row_tile, activation,
                                reads_output=False)
    first = 1 if activation in moe.UNGATED else 2
    wrt = tuple(range(len(args)))

    def step(g):
        return jax.make_jaxpr(jax.grad(g, argnums=wrt))(*args).jaxpr

    plain = 3 * (first + 1), 2
    assert first_window_work(step(f)) == plain
    kept = step(as_a_block_runs_it(lambda *a: f(*a), "full"))
    assert names_in(equations_of(kept)) == {
        "moe_expert_matrices", "moe_router_logits", "moe_top_experts",
        "moe_top_weights", "moe_order"} | ROWS_NAMES
    assert first_window_work(kept) == plain
    assert first_window_work(step(jax.checkpoint(lambda *a: f(*a)))) == (
        3 * (first + 1) + first, 3)
    loops = [eqn for eqn, _ in equations_of(kept)
             if eqn.primitive.name == "while"]
    assert len(loops) == 2
    again = ops_of(loops[1].params["body_jaxpr"].jaxpr)["ragged_dot_general"]
    assert again >= 2 * (first + 1) + first


# --- Un-gated squared-ReLU experts in a latent narrower than the stream ----
# (Nemotron 3's LatentMoE: the router reads the stream, the experts a
# down-projection of it, the caller's; the layer takes it as ``expert_in``
# and returns the weighted sum in the latent.)

from benchmarks.reference import gpt_latent_moe_hybrid_dp as latent_reference  # noqa: E402,E501

LATENT = 8


def _latent_inputs(seed, routing="under", first=4):
    """``(tokens [T, D], the block)``: a sigmoid router over the stream under
    a bias, a down-projection to ``LATENT`` and experts of two matrices
    there, ``routed_inputs``'s routing."""
    h, router, _, _, _ = routed_inputs(seed, routing, first)
    ks = jax.random.split(jax.random.PRNGKey(seed + 200), 5)
    return h, {
        "router": router,
        "router_bias": 0.02 * jax.random.normal(ks[0], (E,)),
        "latent_down": jax.random.normal(ks[1], (D, LATENT)) / 4,
        "w_up": jax.random.normal(ks[2], (E, LATENT, M)) / 3,
        "w_down": jax.random.normal(ks[3], (E, M, LATENT)) / 5,
        "latent_up": jax.random.normal(ks[4], (LATENT, D)) / 3}


def _latent_layer(h, block, top_k, first=0, held=E):
    """The layer as ``models/decoder/experts.py::apply`` calls it, up to the
    up-projection: ``(the held experts' sum in the latent, aux)``."""
    return moe_layer(
        h, block["router"], None, block["w_up"][first:first + held],
        block["w_down"][first:first + held], top_k=top_k, dtype=jnp.float32,
        first_expert=first, renormalize=True, score="sigmoid",
        bias=block["router_bias"], scale=ROUTE_SCALE, activation="relu2",
        expert_in=h @ block["latent_down"])


@pytest.mark.parametrize("remat", ["none", "full"])
@pytest.mark.parametrize("first,held,routing", [
    (0, E, "under"), (4, 4, "under"), (4, 4, "over")],
    ids=["un-windowed", "one-window", "two-windows"])
def test_latent_squared_relu_experts_match_the_dense_sum(
        small_tile, first, held, routing, remat):
    """``activation="relu2"`` (two matrices, no gate) on an operand of its
    own, on all the rows at once and on a share's windows (one, and two
    through the loop's body and the backward rule's), against every held
    expert on every token: the sum in the latent, the counts, and the
    gradients of the tokens (through the router and through the
    projection), the router, the projection and the two expert tensors."""
    top_k = 6
    h, block = _latent_inputs(61, routing, first)
    share = {**block, "w_up": block["w_up"][first:first + held],
             "w_down": block["w_down"][first:first + held]}
    weigh = jnp.cos(jnp.arange(T * LATENT, dtype=jnp.float32)).reshape(
        T, LATENT)

    def got(h, share):
        y, aux = moe_layer(
            h, share["router"], None, share["w_up"], share["w_down"],
            top_k=top_k, dtype=jnp.float32, first_expert=first,
            renormalize=True, score="sigmoid", bias=share["router_bias"],
            scale=ROUTE_SCALE, activation="relu2",
            expert_in=h @ share["latent_down"])
        return jnp.sum(y * weigh), (y, aux["counts"])

    def want(h, share):
        y, counts = latent_reference.routed_latent(h, share, top_k,
                                                   ROUTE_SCALE, first)
        return jnp.sum(y * weigh), (y, counts)

    (_, (y, counts)), grads = jax.jit(jax.value_and_grad(
        as_a_block_runs_it(got, remat), argnums=(0, 1), has_aux=True))(
            h, share)
    (_, (y_ref, counts_ref)), grads_ref = jax.jit(jax.value_and_grad(
        want, argnums=(0, 1), has_aux=True))(h, share)
    assert y.shape == (T, LATENT)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(counts, np.asarray(counts_ref, np.int32))
    if held < E:
        windows = assert_rows_held(counts, routing, first, top_k=top_k)
        assert windows == (2 if routing == "over" else 1)
    grads[1].pop("latent_up"), grads_ref[1].pop("latent_up")
    jax.tree.map(lambda g, r: np.testing.assert_allclose(
        g, r, rtol=2e-4, atol=2e-5 * float(jnp.abs(r).max()) + 1e-7),
        grads, grads_ref)
    assert not np.any(np.asarray(grads[1]["router_bias"]))


@pytest.mark.parametrize("routing", ROUTINGS)
def test_four_latent_shares_add_up_through_the_up_projection(small_tile,
                                                             routing):
    """Nemotron 3's cut: four ranks hold four of the 16 experts each, every
    rank routes over all 16 on the stream and projects the stream to the
    latent itself; the ranks' partial sums **in the latent**, through the
    up-projection, with the shared expert (on the stream, un-gated) and
    nothing else counted once, add up to the uncut reference's block, as do
    the gradients of the tokens."""
    top_k = 6
    h, block = _latent_inputs(63, routing)
    ks = jax.random.split(jax.random.PRNGKey(64), 2)
    block["shared"] = {"w_up": jax.random.normal(ks[0], (D, 2 * M)) / 4,
                       "w_down": jax.random.normal(ks[1], (2 * M, D)) / 6}
    weigh = jnp.cos(jnp.arange(T * D, dtype=jnp.float32)).reshape(T, D)

    def whole(h):
        y, counts = latent_reference.expert_block(h, block, top_k,
                                                  ROUTE_SCALE)
        return jnp.sum(y * weigh), (y, counts)

    def shared_once(h):
        y = latent_reference.relu2_expert(h, block["shared"]["w_up"],
                                          block["shared"]["w_down"])
        return jnp.sum(y * weigh), y

    (_, (y_ref, counts_ref)), g_ref = jax.jit(jax.value_and_grad(
        whole, has_aux=True))(h)
    (_, total), grad = jax.jit(jax.value_and_grad(
        shared_once, has_aux=True))(h)
    for first in SHARES:
        def share(h):
            latent, aux = _latent_layer(h, block, top_k, first, 4)
            y = latent @ block["latent_up"]
            return jnp.sum(y * weigh), (y, aux["counts"])

        (_, (y, counts)), g = jax.jit(jax.value_and_grad(
            share, has_aux=True))(h)
        np.testing.assert_array_equal(counts,
                                      np.asarray(counts_ref, np.int32))
        total, grad = total + y, grad + g
    np.testing.assert_allclose(total, y_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        grad, g_ref, rtol=2e-4, atol=2e-5 * float(jnp.abs(g_ref).max()))


def test_an_expert_is_two_matrices_or_three():
    """An un-gated form takes no gate matrix and a gated one wants it; the
    record names the form."""
    h, block = _latent_inputs(65)
    with pytest.raises(ValueError, match="takes no gate matrix"):
        moe_layer(h @ block["latent_down"], None, block["w_up"],
                  block["w_up"], block["w_down"], top_k=2, logits=h,
                  activation="relu2")
    with pytest.raises(ValueError, match="takes a gate matrix"):
        moe_layer(h @ block["latent_down"], None, None, block["w_up"],
                  block["w_down"], top_k=2, logits=h)
    # 0 squared has the derivative 0, as ReLU's at 0 is 0.
    np.testing.assert_array_equal(
        jax.grad(moe.ACTIVATIONS["relu2"])(jnp.zeros(())), 0.0)

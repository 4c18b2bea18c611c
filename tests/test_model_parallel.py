"""Tensor/expert/pipeline parallelism + the explicitly-parallel GPT model:
parity against single-device (unsharded) execution of the same math.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.parallel import moe
from horovod_tpu.parallel.moe import moe_layer
from horovod_tpu.parallel.pipeline import pipeline_apply, stage_partition


def _checkpointed(f):
    """``f`` as a ``remat="full"`` block runs it: what ``gpt.SAVED_NAMES``
    lists kept, the rest made again for the backward pass."""
    return jax.checkpoint(f, policy=gpt._full_policy)


@pytest.mark.parametrize("tile", [512, 8])
@pytest.mark.parametrize("remat", ["none", "full"])
@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_layer_expert_parallel_matches_local(make_runtime, moe_row_tile,
                                                 top_k, remat, tile):
    """Experts over ep=4, the batch over ep: output, both auxiliary terms,
    counts and every gradient equal the all-experts-local layer's on the
    whole batch. Dropless either way, whatever the routing; checkpointed
    (the rows outside every group are zero in the combine's own backward
    pass too) or not. At the grouped matmul's real tile these 32 tokens'
    rows are under one tile and every rank works on all of them at once; at
    a tile of 8 a rank works on a window of half of them
    (``moe.share_rows``), and on the next where its two experts draw more
    than that: each rank's loop is its own."""
    moe_row_tile(tile)
    assert moe.share_rows(32, top_k, 2, 8) == (
        32 * top_k if tile == 512 else 16 * top_k)
    make_runtime(mesh_shape={"ep": 4}, devices=jax.devices()[:4])
    d, m, n_exp = 16, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (4, 8, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, n_exp), jnp.float32)
    w_gate = jax.random.normal(ks[4], (n_exp, d, m), jnp.float32) / 4
    w_up = jax.random.normal(ks[2], (n_exp, d, m), jnp.float32) / 4
    w_down = jax.random.normal(ks[3], (n_exp, m, d), jnp.float32) / 6
    args = (x, router, w_gate, w_up, w_down)

    def loss(axis):
        def f(x, *w):
            y, aux = moe_layer(x, *w, top_k=top_k, axis=axis,
                               dtype=jnp.float32)
            total = jnp.sum(y * jnp.cos(y))
            if axis is not None:
                total = jax.lax.psum(total, axis)
                # Every rank of the group holds the same terms.
                aux = {k: jax.lax.pmax(v, axis) if k == "counts"
                       else jax.lax.pmean(v, axis) for k, v in aux.items()}
            return total + aux["load_balance"] + aux["router_z"], (y, aux)
        return _checkpointed(f) if axis and remat == "full" else f

    grad = lambda axis: jax.value_and_grad(  # noqa: E731
        loss(axis), argnums=(0, 1, 2, 3, 4), has_aux=True)
    (want_loss, (want_y, want_aux)), want_grads = jax.jit(grad(None))(*args)
    experts = P("ep")
    (got_loss, (got_y, got_aux)), got_grads = jax.jit(jax.shard_map(
        grad("ep"), mesh=hvd.mesh(),
        in_specs=(P("ep"), P(), experts, experts, experts),
        out_specs=((P(), (P("ep"), P())),
                   (P("ep"), P(), experts, experts, experts))))(*args)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got_aux["counts"], want_aux["counts"])
    assert int(got_aux["counts"].sum()) == 32 * top_k
    for key in ("load_balance", "router_z"):
        np.testing.assert_allclose(got_aux[key], want_aux[key], rtol=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("tile", [512, 8])
def test_a_bound_ep_axis_keeps_the_groups_routing(make_runtime, moe_row_tile,
                                                  equations_of, tile):
    """Under a bound ``ep`` axis the checkpointed layer names what fixes the
    routing after the gather: the policy keeps the router's outputs, the
    chosen experts and scores and the sort's order of the group's 32 tokens
    (each rank routes all of them), with the inverse, the sorted rows and
    their gate and up products (PR 59) where a rank works on all the rows
    at once, the window at 0's rows and products (PR 66) where it works a
    window at a time, and the part the backward pass makes again holds no sort, no
    top-k and no router's product."""
    moe_row_tile(tile)
    make_runtime(mesh_shape={"ep": 4}, devices=jax.devices()[:4])
    d, m, n_exp, top_k, tokens = 12, 32, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    args = (jax.random.normal(ks[0], (4, 8, d), jnp.float32),
            jax.random.normal(ks[1], (d, n_exp), jnp.float32),
            jax.random.normal(ks[4], (n_exp, d, m), jnp.float32) / 4,
            jax.random.normal(ks[2], (n_exp, d, m), jnp.float32) / 4,
            jax.random.normal(ks[3], (n_exp, m, d), jnp.float32) / 6)

    def f(x, *w):
        y, aux = moe_layer(x, *w, top_k=top_k, axis="ep", dtype=jnp.float32)
        return jax.lax.psum(jnp.sum(y * jnp.cos(y)), "ep") \
            + aux["load_balance"] + aux["router_z"]

    experts = P("ep")
    specs = (P("ep"), P(), experts, experts, experts)
    jaxpr = jax.make_jaxpr(jax.shard_map(
        jax.grad(_checkpointed(f), argnums=(0, 1, 2, 3, 4)), mesh=hvd.mesh(),
        in_specs=specs, out_specs=specs))(*args).jaxpr

    assert [eqn.primitive.name for eqn, made_again in equations_of(jaxpr)
            if made_again and (
                eqn.primitive.name in ("sort", "top_k")
                or eqn.primitive.name == "dot_general"
                and eqn.outvars[0].aval.shape == (tokens, n_exp))] == []
    kept = {labels["name"]: value for _, labels, value in hvd.metrics()[
        "hvdtpu_spmd_remat_saved_bytes_total"]["samples"]}
    index = jnp.argsort(jnp.zeros(1)).dtype.itemsize    # 8 under x64
    want = {"moe_router_logits": tokens * n_exp * 4,
            "moe_top_experts": tokens * top_k * 4,
            "moe_top_weights": tokens * top_k * 4,
            "moe_order": tokens * top_k * index,
            "moe_expert_matrices": 3 * (n_exp // 4) * d * m * 4}
    rows = moe.share_rows(tokens, top_k, n_exp // 4, n_exp)
    assert rows == (tokens * top_k if tile == 512 else 32)
    want.update(moe_rows=rows * d * 4, moe_pre_activation=2 * rows * m * 4)
    if tile == 512:
        want.update(moe_order_inverse=tokens * top_k * index)
    assert kept == want


@pytest.mark.parametrize("remat", ["none", "full"])
@pytest.mark.parametrize("ep, tile", [(2, 512), (4, 8)],
                         ids=["all_rows", "windowed"])
def test_moe_layer_tensor_parallel_expert_width(make_runtime, moe_row_tile,
                                                remat, ep, tile):
    """ep x tp=2: the experts over ep, their width over tp, a rank working
    on all the rows at once (ep=2) or a window of 32 of the 64 at a time
    (ep=4; the window at 0's backward rule reads its kept rows and products,
    PR 66). The output and every gradient are the local layer's: the routing
    weights' is a sum over the whole width, which each tp rank holds a part
    of, and so is the tokens' through a window's rows."""
    moe_row_tile(tile)
    make_runtime(mesh_shape={"ep": ep, "tp": 2},
                 devices=jax.devices()[:2 * ep])
    d, m, n_exp = 16, 32, 2 * ep
    assert (moe.share_rows(32, 2, 2, n_exp) < 64) == (ep == 4)
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(ks[0], (4, 8, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, n_exp), jnp.float32)
    w_gate = jax.random.normal(ks[4], (n_exp, d, m), jnp.float32) / 4
    w_up = jax.random.normal(ks[2], (n_exp, d, m), jnp.float32) / 4
    w_down = jax.random.normal(ks[3], (n_exp, m, d), jnp.float32) / 6
    args = (x, router, w_gate, w_up, w_down)

    def loss(axis, tp_axis):
        def f(x, *w):
            y, _ = moe_layer(x, *w, top_k=2, axis=axis, tp_axis=tp_axis,
                             dtype=jnp.float32)
            total = jnp.sum(y * jnp.cos(y))
            return (jax.lax.psum(total, axis) if axis else total), y
        return _checkpointed(f) if axis and remat == "full" else f

    grad = lambda *axes: jax.value_and_grad(  # noqa: E731
        loss(*axes), argnums=(0, 1, 2, 3, 4), has_aux=True)
    (want_loss, want), want_grads = jax.jit(grad(None, None))(*args)
    specs = (P("ep"), P(), P("ep", None, "tp"), P("ep", None, "tp"),
             P("ep", "tp", None))
    (got_loss, got), got_grads = jax.jit(jax.shard_map(
        grad("ep", "tp"), mesh=hvd.mesh(), in_specs=specs,
        out_specs=((P(), P("ep")), specs)))(*args)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


def test_pipeline_matches_sequential(make_runtime):
    make_runtime(mesh_shape={"pp": 4}, devices=jax.devices()[:4])
    n_stages, M, mb, d = 4, 6, 3, 8
    rng = jax.random.PRNGKey(1)
    W = jax.random.normal(rng, (n_stages, d, d), jnp.float32) / float(np.sqrt(d))
    x = jax.random.normal(jax.random.PRNGKey(2), (M, mb, d), jnp.float32)

    def stage(w, h):
        return h + jnp.tanh(h @ w)

    expected = x
    for s in range(n_stages):
        expected = stage(W[s], expected)

    got = jax.jit(jax.shard_map(
        lambda w, x: pipeline_apply(stage, w, x, axis="pp"),
        mesh=hvd.mesh(), in_specs=(P("pp"), P()), out_specs=P()))(W, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_gradients_match_sequential(make_runtime):
    make_runtime(mesh_shape={"pp": 2}, devices=jax.devices()[:2])
    n_stages, M, mb, d = 2, 4, 2, 6
    W = jax.random.normal(jax.random.PRNGKey(3), (n_stages, d, d),
                          jnp.float32) / float(np.sqrt(d))
    x = jax.random.normal(jax.random.PRNGKey(4), (M, mb, d), jnp.float32)

    def stage(w, h):
        return h + jnp.tanh(h @ w)

    def ref_loss(W):
        h = x
        for s in range(n_stages):
            h = stage(W[s], h)
        return jnp.sum(h ** 2)

    expected = jax.jit(jax.grad(ref_loss))(W)

    def pp_loss(W):
        out = pipeline_apply(stage, W, x, axis="pp")
        return jnp.sum(out ** 2)

    def body(W):
        g = jax.grad(pp_loss)(W)
        return g

    got = jax.jit(jax.shard_map(body, mesh=hvd.mesh(), in_specs=(P("pp"),),
                        out_specs=P("pp")))(W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-4, atol=1e-4)


def test_pipeline_remat_gradients_match(make_runtime):
    """remat=True recomputes each tick in backward (bounding the scan's
    stored intermediates); gradients must match the stored-activation
    pipeline."""
    make_runtime(mesh_shape={"pp": 2}, devices=jax.devices()[:2])
    n_stages, M, mb, d = 2, 4, 2, 6
    W = jax.random.normal(jax.random.PRNGKey(3), (n_stages, d, d),
                          jnp.float32) / float(np.sqrt(d))
    x = jax.random.normal(jax.random.PRNGKey(4), (M, mb, d), jnp.float32)

    def stage(w, h):
        return h + jnp.tanh(h @ w)

    def grad_of(remat):
        def loss(W):
            out = pipeline_apply(stage, W, x, axis="pp", remat=remat)
            return jnp.sum(out ** 2)

        return jax.jit(jax.shard_map(jax.grad(loss), mesh=hvd.mesh(),
                             in_specs=(P("pp"),), out_specs=P("pp")))(W)

    np.testing.assert_allclose(np.asarray(grad_of(True)),
                               np.asarray(grad_of(False)),
                               rtol=1e-5, atol=1e-6)


def test_stage_partition():
    assert stage_partition(8, 4) == [(0, 2), (2, 2), (4, 2), (6, 2)]
    assert stage_partition(8, 4, rank=3) == (6, 2)
    with pytest.raises(ValueError):
        stage_partition(7, 2)


def _reference(cfg):
    """``cfg`` for an unsharded run that gives an expected value: the dense
    reference by name (unsharded, "ring" and "ulysses" are the flash kernel,
    which is what some of these tests hold to the reference)."""
    return dataclasses.replace(cfg, attention="dense", sp_axis=None)


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
def test_gpt_tp_sp_dp_forward_parity(make_runtime, attention):
    """dp=2 x tp=2 x sp=2 sharded forward == single-device forward."""
    make_runtime(mesh_shape={"dp": 2, "tp": 2, "sp": 2})
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=2, num_heads=4,
                        head_dim=8, embed_dim=32, mlp_dim=64,
                        dtype=jnp.float32, attention=attention)
    params = gpt.init_params(jax.random.PRNGKey(5), cfg)
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0, 64)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    expected = jax.jit(
        lambda p: gpt.forward(p, tokens, positions, _reference(cfg)))(params)

    step = hvd.run_step(
        lambda p, t, pos: gpt.forward(p, t, pos, cfg),
        in_specs=(gpt.param_specs(cfg), P("dp", "sp"), P("dp", "sp")),
        out_specs=P("dp", "sp"))
    got = step(params, tokens, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_gpt_flash_attention_matches_dense(make_runtime):
    """attention='flash' (fused Pallas kernel, interpret mode on CPU) ==
    attention='dense' through the full GPT forward and loss gradient."""
    make_runtime()
    base = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                embed_dim=32, mlp_dim=64, dtype=jnp.float32, tp_axis=None,
                sp_axis=None)
    cfg_dense = gpt.GPTConfig(attention="dense", **base)
    cfg_flash = gpt.GPTConfig(attention="flash", **base)
    params = gpt.init_params(jax.random.PRNGKey(5), cfg_dense)
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0, 64)
    targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def loss_grads(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tokens, targets, positions, cfg)))(
                params)

    l_d, g_d = loss_grads(cfg_dense)
    l_f, g_f = loss_grads(cfg_flash)
    np.testing.assert_allclose(float(l_f), float(l_d), rtol=1e-5)
    for gd, gf in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_f)):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=5e-4, atol=5e-5)
    # sp-bound mesh must reject local flash attention with a clear error.
    make_runtime(mesh_shape={"dp": 4, "sp": 2})
    cfg_sp = gpt.GPTConfig(attention="flash", **{**base, "sp_axis": "sp"})
    tokens4 = jax.random.randint(jax.random.PRNGKey(8), (4, S), 0, 64)
    positions4 = jnp.broadcast_to(jnp.arange(S), (4, S))
    with pytest.raises(ValueError, match="ring.*ulysses|local"):
        step = hvd.run_step(
            lambda p, t, pos: gpt.forward(p, t, pos, cfg_sp),
            in_specs=(gpt.param_specs(cfg_sp), P("dp", "sp"),
                      P("dp", "sp")),
            out_specs=P("dp", "sp"))
        step(params, tokens4, positions4)


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_gpt_gqa_dense_matches_flash(make_runtime, kv_heads):
    """Grouped-query attention on the dense path: ``_attention`` tiles the
    key and value heads up before ``default_attention`` (which takes equal
    head counts), as flash does itself; both agree on loss and gradient."""
    make_runtime()
    base = dict(vocab_size=64, num_layers=2, num_heads=4,
                num_kv_heads=kv_heads, head_dim=8, embed_dim=32, mlp_dim=64,
                dtype=jnp.float32, tp_axis=None, sp_axis=None)
    cfg_dense = gpt.GPTConfig(attention="dense", **base)
    cfg_flash = gpt.GPTConfig(attention="flash", **base)
    params = gpt.init_params(jax.random.PRNGKey(5), cfg_dense)
    assert params["layers"][0]["wk"].shape == (32, kv_heads, 8)
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0, 64)
    targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def loss_grads(cfg):
        return jax.jit(jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tokens, targets, positions, cfg)))(
                params)

    l_d, g_d = loss_grads(cfg_dense)
    l_f, g_f = loss_grads(cfg_flash)
    np.testing.assert_allclose(float(l_f), float(l_d), rtol=1e-5)
    for gd, gf in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_f)):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("top_k", [1, 2])
def test_gpt_moe_ep_parity(make_runtime, top_k):
    """dp=2 x ep=2 x sp=2 sparse GPT == single-device: the logits, and the
    loss with its auxiliary terms where they are the same estimate (the
    unsharded run routes all 64 tokens together; a dp x sp shard routes its
    ep group's 16, so the two auxiliary terms are compared at ep alone)."""
    make_runtime(mesh_shape={"dp": 2, "ep": 2, "sp": 2})
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=2, num_heads=4,
                        head_dim=8, embed_dim=32, mlp_dim=64,
                        dtype=jnp.float32, tp_axis=None, attention="ring",
                        ep_axis="ep", moe_every=2, num_experts=4,
                        experts_per_token=top_k, load_balance_coef=0.01,
                        router_z_coef=0.001)
    params = gpt.init_params(jax.random.PRNGKey(7), cfg)
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.PRNGKey(8), (B, S), 0, 64)
    targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    expected = jax.jit(
        lambda p: gpt.forward(p, tokens, positions, _reference(cfg)))(params)

    data = P(("dp", "ep"), "sp")
    step = hvd.run_step(
        lambda p, t, pos: gpt.forward(p, t, pos, cfg),
        in_specs=(gpt.param_specs(cfg), data, data), out_specs=data)
    got = step(params, tokens, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)

    # Experts over ep alone: every rank's group is the whole batch, so the
    # loss, both auxiliary terms and the counts are the unsharded ones, and
    # the gradients (the experts' are ep shards) too.
    make_runtime(mesh_shape={"ep": 4}, devices=jax.devices()[:4])
    cfg = _reference(cfg)
    value_and_grad = jax.value_and_grad(
        lambda p, *d: gpt.loss_and_aux(p, *d, cfg), has_aux=True)
    (want, want_aux), want_grads = jax.jit(value_and_grad)(
        params, tokens, targets, positions)
    specs = gpt.param_specs(cfg)
    (loss, aux), grads = hvd.run_step(
        value_and_grad, in_specs=(specs, P("ep"), P("ep"), P("ep")),
        out_specs=((P(), P()), specs))(params, tokens, targets, positions)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    np.testing.assert_array_equal(aux["counts"], want_aux["counts"])
    for key in ("cross_entropy", "load_balance", "router_z"):
        np.testing.assert_allclose(aux[key], want_aux[key], rtol=1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("remat", ["full"])
def test_gpt_remat_gradients_match(make_runtime, remat):
    """Rematerialization (jax.checkpoint per block — the TPU FLOPs-for-HBM
    lever, SURVEY build brief) must leave loss AND gradients numerically equivalent
    with the stored-activation path, including with ring attention + sp
    (backward replays the ppermute chain)."""
    make_runtime(mesh_shape={"dp": 2, "tp": 2, "sp": 2})
    base = dict(vocab_size=64, num_layers=2, num_heads=4, head_dim=8,
                embed_dim=32, mlp_dim=64, dtype=jnp.float32,
                attention="ring")
    cfg0 = gpt.GPTConfig(**base)
    cfg1 = gpt.GPTConfig(**base, remat=remat)
    params = gpt.init_params(jax.random.PRNGKey(5), cfg0)
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0, 64)
    targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def make_step(cfg):
        def body(p, t, tg, pos):
            loss, grads = jax.value_and_grad(
                lambda q: gpt.loss_fn(q, t, tg, pos, cfg))(p)
            # loss_fn reduces over sp/ep only; dp is the optimizer's job.
            return hvd.allreduce_p(loss, op=hvd.ReduceOp.AVERAGE,
                                   axis="dp"), grads

        return hvd.run_step(
            body,
            in_specs=(gpt.param_specs(cfg), P("dp", "sp"), P("dp", "sp"),
                      P("dp", "sp")),
            out_specs=(hvd.REPLICATED, gpt.param_specs(cfg)))

    loss0, grads0 = make_step(cfg0)(params, tokens, targets, positions)
    loss1, grads1 = make_step(cfg1)(params, tokens, targets, positions)
    np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-6)
    for g0, g1 in zip(jax.tree.leaves(grads0), jax.tree.leaves(grads1)):
        np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                                   rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="remat"):
        gpt.forward(params, tokens, positions,
                    gpt.GPTConfig(**base, remat="bogus"))


def test_gpt_loss_and_grads_replicated(make_runtime):
    """Training semantics: loss is the global mean on every rank; grads of
    replicated params come out dp/sp-reduced (check_vma autodiff)."""
    make_runtime(mesh_shape={"dp": 2, "tp": 2, "sp": 2})
    cfg = gpt.GPTConfig(vocab_size=32, num_layers=1, num_heads=4,
                        head_dim=4, embed_dim=16, mlp_dim=32,
                        dtype=jnp.float32)
    params = gpt.init_params(jax.random.PRNGKey(9), cfg)
    B, S = 4, 8
    tokens = jax.random.randint(jax.random.PRNGKey(10), (B, S), 0, 32)
    targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    expected_loss, expected_grads = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_fn(p, tokens, targets, positions,
                              _reference(cfg))))(params)

    def body(p, t, tg, pos):
        # Per-dp-shard loss; average over dp to the global mean.
        loss = gpt.loss_fn(p, t, tg, pos, cfg)
        loss = hvd.allreduce_p(loss, op=hvd.Sum, axis="dp") / 2.0
        grads = jax.grad(
            lambda p: gpt.loss_fn(p, t, tg, pos, cfg))(p)
        grads = hvd.allreduce_gradients(grads, op=hvd.Average)
        return loss, grads

    step = hvd.run_step(
        body,
        in_specs=(gpt.param_specs(cfg), P("dp", "sp"), P("dp", "sp"),
                  P("dp", "sp")),
        out_specs=(hvd.REPLICATED, gpt.param_specs(cfg)))
    loss, grads = step(params, tokens, targets, positions)
    np.testing.assert_allclose(float(loss), float(expected_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grads["embed"]), np.asarray(expected_grads["embed"]),
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grads["layers"][0]["wq"]),
        np.asarray(expected_grads["layers"][0]["wq"]),
        rtol=1e-4, atol=1e-5)

"""Tensor/expert/pipeline parallelism + the explicitly-parallel GPT model:
parity against single-device (unsharded) execution of the same math.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.parallel.moe import switch_moe
from horovod_tpu.parallel.pipeline import pipeline_apply, stage_partition


def test_switch_moe_expert_parallel_matches_local(make_runtime):
    make_runtime(mesh_shape={"ep": 4}, devices=jax.devices()[:4])
    d, m, n_exp = 16, 32, 4
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 4)
    x = jax.random.normal(ks[0], (4, 8, d), jnp.float32)
    gate = jax.random.normal(ks[1], (d, n_exp), jnp.float32)
    w_up = jax.random.normal(ks[2], (n_exp, d, m), jnp.float32) / 4
    w_down = jax.random.normal(ks[3], (m, d), jnp.float32) / 6
    w_down = jnp.broadcast_to(w_down, (n_exp, m, d))
    # capacity_factor = n_exp guarantees no token drops, so local and
    # expert-parallel routing compute identical math.
    kw = dict(capacity_factor=float(n_exp), dtype=jnp.float32)

    expected, aux = switch_moe(x, gate, w_up, w_down, axis=None, **kw)
    assert float(aux["dropped_fraction"]) == 0.0

    def body(x, gate, w_up, w_down):
        out, aux = switch_moe(x, gate, w_up, w_down, axis="ep", **kw)
        return out

    got = jax.shard_map(
        body, mesh=hvd.mesh(),
        in_specs=(P("ep"), P(), P("ep"), P("ep")),
        out_specs=P("ep"))(x, gate, w_up, w_down)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


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

    got = jax.shard_map(
        lambda w, x: pipeline_apply(stage, w, x, axis="pp"),
        mesh=hvd.mesh(), in_specs=(P("pp"), P()), out_specs=P())(W, x)
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

    expected = jax.grad(ref_loss)(W)

    def pp_loss(W):
        out = pipeline_apply(stage, W, x, axis="pp")
        return jnp.sum(out ** 2)

    def body(W):
        g = jax.grad(pp_loss)(W)
        return g

    got = jax.shard_map(body, mesh=hvd.mesh(), in_specs=(P("pp"),),
                        out_specs=P("pp"))(W)
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

        return jax.shard_map(jax.grad(loss), mesh=hvd.mesh(),
                             in_specs=(P("pp"),), out_specs=P("pp"))(W)

    np.testing.assert_allclose(np.asarray(grad_of(True)),
                               np.asarray(grad_of(False)),
                               rtol=1e-5, atol=1e-6)


def test_stage_partition():
    assert stage_partition(8, 4) == [(0, 2), (2, 2), (4, 2), (6, 2)]
    assert stage_partition(8, 4, rank=3) == (6, 2)
    with pytest.raises(ValueError):
        stage_partition(7, 2)


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

    expected = gpt.forward(params, tokens, positions, cfg)  # unsharded

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
        return jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tokens, targets, positions, cfg))(
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
        return jax.value_and_grad(
            lambda p: gpt.loss_fn(p, tokens, targets, positions, cfg))(
                params)

    l_d, g_d = loss_grads(cfg_dense)
    l_f, g_f = loss_grads(cfg_flash)
    np.testing.assert_allclose(float(l_f), float(l_d), rtol=1e-5)
    for gd, gf in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_f)):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=5e-4, atol=5e-5)


def test_gpt_moe_ep_forward_parity(make_runtime):
    """dp=2 x ep=2 x sp=2 MoE-GPT == single-device forward (no drops)."""
    make_runtime(mesh_shape={"dp": 2, "ep": 2, "sp": 2})
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=2, num_heads=4,
                        head_dim=8, embed_dim=32, mlp_dim=64,
                        dtype=jnp.float32, tp_axis=None, attention="ring",
                        moe_every=2, num_experts=4, capacity_factor=4.0)
    params = gpt.init_params(jax.random.PRNGKey(7), cfg)
    B, S = 4, 16
    tokens = jax.random.randint(jax.random.PRNGKey(8), (B, S), 0, 64)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    expected = gpt.forward(params, tokens, positions, cfg)

    step = hvd.run_step(
        lambda p, t, pos: gpt.forward(p, t, pos, cfg),
        in_specs=(gpt.param_specs(cfg), P(("dp", "ep"), "sp"),
                  P(("dp", "ep"), "sp")),
        out_specs=P(("dp", "ep"), "sp"))
    got = step(params, tokens, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("remat", ["full", "dots"])
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

    def ref():
        return gpt.loss_fn(params, tokens, targets, positions, cfg)

    expected_loss = ref()
    expected_grads = jax.grad(
        lambda p: gpt.loss_fn(p, tokens, targets, positions, cfg))(params)

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

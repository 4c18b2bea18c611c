"""Sequence/context parallelism: ring attention + Ulysses vs. single-device
reference attention (exactness tests, the framework's long-context mechanisms).

Test shapes follow the reference's op-test pattern (SURVEY.md §4): correctness
vs. a local model of the computation, plus gradient correctness.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import gpt
from horovod_tpu.ops.attention import default_attention


def _qkv(rng, batch=2, seq=32, heads=4, kv_heads=None, dim=8,
         dtype=jnp.float32):
    kq, kk, kv = jax.random.split(rng, 3)
    kv_heads = kv_heads or heads
    q = jax.random.normal(kq, (batch, seq, heads, dim), dtype)
    k = jax.random.normal(kk, (batch, seq, kv_heads, dim), dtype)
    v = jax.random.normal(kv, (batch, seq, kv_heads, dim), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(make_runtime, causal):
    make_runtime(mesh_shape={"sp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(0))
    expected = default_attention(q, k, v, causal=causal)
    got = jax.jit(lambda *a: hvd.ring_attention(*a, causal=causal,
                                                axis="sp"))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_gqa(make_runtime):
    make_runtime(mesh_shape={"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _qkv(jax.random.PRNGKey(1), heads=4, kv_heads=2)
    kr = jnp.repeat(k, 2, axis=2)
    vr = jnp.repeat(v, 2, axis=2)
    expected = default_attention(q, kr, vr, causal=True)
    got = jax.jit(lambda *a: hvd.ring_attention(*a, causal=True,
                                                axis="sp"))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_gradients(make_runtime):
    make_runtime(mesh_shape={"sp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(2), seq=16, heads=2)

    def ref_loss(q, k, v):
        return jnp.sum(default_attention(q, k, v, causal=True) ** 2)

    def ring_loss(q, k, v):
        return jnp.sum(hvd.ring_attention_p(q, k, v, causal=True,
                                            axis="sp") ** 2)

    expected = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(q, k, v)

    spec = P(None, "sp")

    def body(q, k, v):
        g = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
        return g

    got = jax.jit(jax.shard_map(body, mesh=hvd.mesh(), in_specs=(spec,) * 3,
                                out_specs=(spec,) * 3))(q, k, v)
    for g, e in zip(got, expected):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(make_runtime, causal):
    make_runtime(mesh_shape={"sp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(3), heads=8)
    expected = default_attention(q, k, v, causal=causal)
    got = hvd.ulysses_attention(q, k, v, causal=causal, axis="sp",
                                attn_fn=default_attention)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_gqa(make_runtime):
    make_runtime(mesh_shape={"sp": 4}, devices=jax.devices()[:4])
    q, k, v = _qkv(jax.random.PRNGKey(7), heads=8, kv_heads=2)
    kr = jnp.repeat(k, 4, axis=2)
    vr = jnp.repeat(v, 4, axis=2)
    expected = default_attention(q, kr, vr, causal=True)
    got = hvd.ulysses_attention(q, k, v, causal=True, axis="sp",
                                attn_fn=default_attention)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_requires_sp_axis(make_runtime):
    """No silent fallback to the data-parallel axis (would ring over batch)."""
    make_runtime(mesh_shape={"dp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(8))
    with pytest.raises(ValueError, match="sequence-parallel"):
        hvd.ring_attention(q, k, v)


def test_ulysses_head_divisibility_error(make_runtime):
    make_runtime(mesh_shape={"sp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(4), heads=4)  # 4 heads, 8 devices
    with pytest.raises(Exception, match="divisible|Ulysses"):
        hvd.ulysses_attention(q, k, v, axis="sp")


def _tiny_gpt(attention, seq, batch=2):
    cfg = gpt.GPTConfig(vocab_size=64, num_layers=2, num_heads=8,
                        head_dim=8, embed_dim=32, mlp_dim=64,
                        dtype=jnp.float32, tp_axis=None, sp_axis="sp",
                        attention=attention)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1).at[:, -1].set(-1)
    positions = jnp.broadcast_to(jnp.arange(seq), tokens.shape)
    return cfg, params, (tokens, targets, positions)


def _reference(cfg):
    """The same model on one device, through the dense reference by name."""
    return dataclasses.replace(cfg, attention="dense", sp_axis=None)


@pytest.mark.parametrize("attention", ["ring", "ulysses"])
def test_gpt_sequence_parallel_forward(make_runtime, attention):
    """Full model under sequence sharding == unsharded: the logits, and the
    loss with its gradient (Ulysses runs the flash kernel on each device)."""
    make_runtime(mesh_shape={"sp": 8})
    cfg, params, (tokens, targets, positions) = _tiny_gpt(attention, seq=32)
    ref = _reference(cfg)
    expected = jax.jit(lambda p: gpt.forward(p, tokens, positions, ref))(
        params)
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p: gpt.loss_fn(p, tokens, targets, positions, ref)))(params)

    seq = P(None, "sp")
    logits = hvd.run_step(
        lambda p, t, pos: gpt.forward(p, t, pos, cfg),
        in_specs=(hvd.REPLICATED, seq, seq), out_specs=seq)(
            params, tokens, positions)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)
    loss, grads = hvd.run_step(
        jax.value_and_grad(lambda p, *d: gpt.loss_fn(p, *d, cfg)),
        in_specs=(hvd.REPLICATED, seq, seq, seq),
        out_specs=hvd.REPLICATED)(params, tokens, targets, positions)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-5)


def test_ulysses_with_flash_inner_matches_reference(make_runtime):
    """Ulysses' default per-device attention is the flash kernel (what
    attention="ulysses" runs in GPT): values must match dense attention
    (interpret mode here; Mosaic-compiled on TPU)."""
    make_runtime(mesh_shape={"sp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(11), heads=8)
    expected = default_attention(q, k, v, causal=True)
    got = hvd.ulysses_attention(q, k, v, causal=True, axis="sp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-3, atol=2e-3)


def test_gpt_ulysses_flash_matches_dense(make_runtime):
    """GPT loss parity: attention="ulysses" under a bound sp axis (the flash
    kernel inside) equals the dense single-device computation."""
    make_runtime(mesh_shape={"sp": 8})
    cfg, params, data = _tiny_gpt("ulysses", seq=16)
    seq = P(None, "sp")
    loss_sp = jax.jit(jax.shard_map(
        lambda p, *d: gpt.loss_fn(p, *d, cfg), mesh=hvd.mesh(),
        in_specs=(P(), seq, seq, seq), out_specs=P()))(params, *data)
    loss_dense = jax.jit(
        lambda p: gpt.loss_fn(p, *data, _reference(cfg)))(params)
    np.testing.assert_allclose(float(loss_sp), float(loss_dense),
                               rtol=2e-3, atol=2e-3)

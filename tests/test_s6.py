"""``ops/s6.py``: the selective scan's kernels (interpret mode off the TPU)
against ``s6_sequential``, one token a step, values and every gradient, at
lengths that are and are not whole chunks and channel counts that are and are
not whole lane tiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import s6


def operands(batch, tokens, channels, state, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (batch, tokens, channels)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1],
                                              (batch, tokens, channels)) - 1),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (channels, state))),
            jax.random.normal(ks[3], (batch, tokens, state)).astype(dtype),
            jax.random.normal(ks[4], (batch, tokens, state)).astype(dtype),
            jax.random.normal(ks[5], (channels,)))


@pytest.mark.parametrize("tokens,channels,state,chunk", [
    (32, 20, 8, 16),      # whole chunks, channels carried to a lane tile
    (40, 130, 16, 16),    # a length the chunk does not divide, two tiles
])
def test_the_kernels_are_the_sequential_scan(tokens, channels, state, chunk):
    args = operands(2, tokens, channels, state)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def both(scan):
        """``(y, the gradients of <y, weight>)`` of a scan."""
        def fn(*a):
            y = scan(*a)
            return jnp.sum(y * weight), y
        grads, y = jax.jit(jax.grad(fn, argnums=range(6), has_aux=True))(
            *args)
        return y, grads

    y, got = both(lambda *a: s6.selective_scan(*a, chunk=chunk))
    want, ref = both(s6.s6_sequential)
    assert y.shape == want.shape and y.dtype == args[0].dtype
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    for name, g, r in zip(("u", "dt", "A", "B", "C", "D"), got, ref):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        np.testing.assert_allclose(
            g, r, rtol=1e-4, atol=1e-5 * float(jnp.max(jnp.abs(r))),
            err_msg=name)


def test_operands_in_the_compute_dtype_sums_in_float32():
    args = operands(1, 32, 16, 8, jnp.bfloat16)
    def fn(u):
        y = s6.selective_scan(u, *args[1:], chunk=16)
        return jnp.sum(y.astype(jnp.float32)), y

    du, y = jax.jit(jax.grad(fn, has_aux=True))(args[0])
    assert y.dtype == du.dtype == jnp.bfloat16
    want = s6.s6_sequential(*args)
    # One rounding of y, none of the state.
    np.testing.assert_allclose(y.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2 * float(jnp.max(jnp.abs(want))))


def test_a_chunk_is_whole_groups_of_tokens():
    with pytest.raises(ValueError, match="multiple of 16"):
        s6.selective_scan(*operands(1, 32, 16, 8), chunk=24)

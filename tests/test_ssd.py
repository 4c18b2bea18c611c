"""The chunked state-space scan (``ops/ssd.py::ssd_chunked``) held to the
plain recurrence (``ssd_sequential``, one token a step), values and the
gradient with respect to every input, and the causal depthwise convolution
to a loop over taps and tokens.

Tolerances. In float32 the two differ only by the order of sums and by
``exp(cum_i - cum_j)`` against a product of per-token decays: a few float32
eps (1.2e-7) times the chunk's length, so 2e-5 of the largest value. With
bfloat16 MXU operands (the benchmark's compute type) each of the four
products rounds its operands to 2**-8: 3e-2 of the largest value holds, and
the float32 decays are what keep it there; the last test shows that a scan
whose running sums are rounded to bfloat16 would not pass.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import ssd

CHUNK = 16


def _inputs(seed, batch=2, seq=64, heads=4, width=8, groups=2, state=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (batch, seq, heads, width))
    # Steps from 0.02 to 0.6 and A from -1 to -15: a token's decay runs from
    # 0.98 down to 1e-4, so states both persist across chunks and die in one.
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, seq, heads)) - 2)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0., maxval=2.7))
    b_in = jax.random.normal(ks[3], (batch, seq, groups, state))
    c_in = jax.random.normal(ks[4], (batch, seq, groups, state))
    d = jax.random.normal(ks[5], (heads,))
    start = jax.random.normal(ks[6], (batch, heads, width, state))
    return (x, dt, a, b_in, c_in, d), start


def _rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


# 64: four whole chunks. 70 and 37: the chunk does not divide the length
# (more than two chunks, then padded). 9: shorter than one chunk.
@pytest.mark.parametrize("seq", [64, 70, 37, 9])
@pytest.mark.parametrize("groups", [1, 2])
def test_chunked_scan_matches_the_recurrence(seq, groups):
    args, start = _inputs(seq, seq=seq, groups=groups)
    y, final = ssd.ssd_chunked(*args, chunk=CHUNK, dtype=jnp.float32,
                               initial_state=start)
    want_y, want_final = ssd.ssd_sequential(*args, initial_state=start)
    assert y.shape == want_y.shape == args[0].shape
    assert _rel(y, want_y) < 2e-5
    # Padded rows (dt = 0) leave the state alone: the state after the last
    # real token is the recurrence's.
    assert _rel(final, want_final) < 2e-5


@pytest.mark.parametrize("seq", [64, 70])
def test_chunked_scan_gradients_match_the_recurrence(seq):
    args, start = _inputs(3, seq=seq)

    def scalar(fn):
        def loss(*inputs):
            y, final = fn(*inputs[:-1], initial_state=inputs[-1])
            return jnp.sum(jnp.sin(y)) + jnp.sum(final * final)
        return loss

    def chunked(*a, **kw):
        return ssd.ssd_chunked(*a, chunk=CHUNK, dtype=jnp.float32, **kw)

    got = jax.grad(scalar(chunked), argnums=range(7))(*args, start)
    want = jax.grad(scalar(ssd.ssd_sequential), argnums=range(7))(
        *args, start)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D", "initial state"),
                          got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _rel(g, w) < 2e-5, name


def test_bfloat16_operands_stay_close_and_bfloat16_decays_would_not():
    # Steps around 0.015 a token: a chunk's running sum reaches -1 to -4,
    # where bfloat16 keeps two decimal digits of it.
    args, _ = _inputs(5, seq=4 * 64, groups=1)
    x, dt, a, b_in, c_in, d = args
    dt = 0.05 * dt
    want, _ = ssd.ssd_sequential(x, dt, a, b_in, c_in, d)
    got, _ = ssd.ssd_chunked(x.astype(jnp.bfloat16), dt, a, b_in, c_in, d,
                             chunk=64, dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert _rel(got.astype(jnp.float32), want) < 3e-2
    # The same scan with dt A rounded to bfloat16 before the running sum
    # (what computing the decays in the compute type would do).
    rounded = (dt * a).astype(jnp.bfloat16).astype(jnp.float32) / a
    worse, _ = ssd.ssd_chunked(x, rounded, a, b_in, c_in, d, chunk=64,
                               dtype=jnp.float32)
    exact, _ = ssd.ssd_chunked(x, dt, a, b_in, c_in, d, chunk=64,
                               dtype=jnp.float32)
    # Holding dt itself fixed in the input term would hide it; the decay
    # alone moves y by more than the float32 path's whole error.
    assert _rel(worse, want) > 20 * _rel(exact, want)


def test_groups_must_divide_heads():
    args, _ = _inputs(0, heads=4, groups=3)
    with pytest.raises(ValueError, match="not a multiple of groups"):
        ssd.ssd_chunked(*args, chunk=CHUNK)


def test_causal_conv_is_a_loop_over_taps():
    rng = np.random.default_rng(0)
    u = rng.standard_normal((2, 11, 6)).astype(np.float32)
    w = rng.standard_normal((4, 6)).astype(np.float32)
    b = rng.standard_normal((6,)).astype(np.float32)
    want = np.zeros_like(u)
    for t in range(11):
        want[:, t] = b
        for k in range(4):
            src = t - 3 + k
            if src >= 0:
                want[:, t] += w[k] * u[:, src]
    got = ssd.causal_conv1d(jnp.asarray(u), jnp.asarray(w), jnp.asarray(b))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # Causal: a later token moves no earlier output.
    u2 = u.copy()
    u2[:, 7:] += 1.0
    got2 = ssd.causal_conv1d(jnp.asarray(u2), jnp.asarray(w), jnp.asarray(b))
    np.testing.assert_array_equal(got[:, :7], got2[:, :7])

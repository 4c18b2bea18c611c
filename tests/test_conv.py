"""The causal depthwise convolution with its SiLU in one pass a direction
(``ops/conv.py::causal_conv_silu``: the kernels ``hvd_conv_fwd`` and
``hvd_conv_bwd``, in interpret mode here) held to the plain lines,
``silu(causal_conv1d(u, w, b)).astype(u.dtype)`` under autodiff, and
``causal_conv1d`` itself to a loop over taps and tokens.
``tests/test_flash_mosaic_compile.py`` compiles the kernels for a v5e.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import conv


def _rel(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


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
    got = conv.causal_conv1d(jnp.asarray(u), jnp.asarray(w), jnp.asarray(b))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # Causal: a later token moves no earlier output.
    u2 = u.copy()
    u2[:, 7:] += 1.0
    got2 = conv.causal_conv1d(jnp.asarray(u2), jnp.asarray(w), jnp.asarray(b))
    np.testing.assert_array_equal(got[:, :7], got2[:, :7])


# ---- the convolution with its SiLU in one pass (hvd_conv_fwd, hvd_conv_bwd) --

def _plain_conv_silu(u, w, b):
    return jax.nn.silu(conv.causal_conv1d(u, w, b)).astype(u.dtype)


@pytest.fixture
def small_cut(monkeypatch):
    """A cut that gives a few tokens several grid cells and a cell several
    passes of its loop, on both layouts (the calls are jitted inline: what
    was traced under another cut is dropped, before and after)."""
    jax.clear_caches()
    monkeypatch.setattr(conv, "_CONV_CUT", {False: (0, 16, 16, 128, 1),
                                            True: (1, 128, 128, 16, 1)})
    monkeypatch.setattr(conv, "_CONV_BLOCK", 1 << 12)
    yield
    jax.clear_caches()


def _conv_case(dtype, bias, channels, seq, batch, taps, minor):
    ks = jax.random.split(jax.random.PRNGKey(seq + channels), 4)
    u = jax.random.normal(ks[0], (batch, seq, channels)).astype(dtype)
    w = 0.5 * jax.random.normal(ks[1], (taps, channels))
    b = jax.random.normal(ks[2], (channels,)) if bias else None
    dy = jax.random.normal(ks[3], u.shape).astype(dtype)

    def both(fn):
        def run(u, w, b):
            y, vjp = jax.vjp(fn, u, w, b)
            return (y,) + tuple(g for g in vjp(dy) if g is not None)
        return jax.jit(run)(u, w, b)

    got = both(lambda u, w, b: conv.causal_conv_silu(u, w, b, minor=minor))
    want = both(_plain_conv_silu)
    assert len(got) == len(want) == (4 if bias else 3)
    for g, t in zip(got, want):
        assert g.shape == t.shape and g.dtype == t.dtype
    return [_rel(g.astype(jnp.float32), t.astype(jnp.float32))
            for g, t in zip(got, want)]


# dtype, bias, channels, length, batch, taps. 6 channels ride one lane tile
# with zeros; 4352 is the hybrid cell's count (34 lane tiles, 272 sublane
# tiles); 37, 70 and 300 are lengths no tile divides.
CONV_CASES = [
    (jnp.float32, True, 6, 37, 2, 4),
    (jnp.float32, False, 6, 70, 1, 3),
    (jnp.float32, False, 128, 300, 2, 4),
    (jnp.float32, True, 128, 64, 1, 3),
    (jnp.bfloat16, True, 6, 300, 2, 4),
    (jnp.bfloat16, False, 128, 37, 1, 4),
    (jnp.bfloat16, True, 128, 300, 1, 3),
    (jnp.float32, True, 4352, 37, 1, 4),
    (jnp.bfloat16, False, 4352, 48, 2, 4),
]


@pytest.mark.parametrize("minor", ["channels", "tokens"])
@pytest.mark.parametrize(
    "dtype, bias, channels, seq, batch, taps", CONV_CASES,
    ids=[f"{jnp.dtype(c[0]).name}-{'bias' if c[1] else 'nobias'}-c{c[2]}-"
         f"s{c[3]}-b{c[4]}-k{c[5]}" for c in CONV_CASES])
def test_conv_silu_matches_the_plain_lines(dtype, bias, channels, seq, batch,
                                           taps, minor):
    """Values, ``du``, ``dw`` and ``db`` against ``silu(causal_conv1d(...))``
    cast to the input's dtype, under autodiff. The arithmetic is the same in
    the same precisions (float32 taps, bias and SiLU, one rounding): float32
    differs by the order of the cotangents' sums, bfloat16 by a last bit of
    the output where the two logistics differ in theirs."""
    off = _conv_case(dtype, bias, channels, seq, batch, taps, minor)
    assert max(off) < (1e-5 if dtype == jnp.float32 else 1e-2), off


@pytest.mark.parametrize("minor", ["channels", "tokens"])
@pytest.mark.parametrize("dtype, bias, channels, seq, batch, taps", [
    (jnp.float32, True, 200, 500, 2, 4),
    (jnp.bfloat16, False, 256, 440, 1, 3)], ids=["f32", "bf16"])
def test_conv_silu_across_grid_cells_and_passes(small_cut, dtype, bias,
                                                channels, seq, batch, taps,
                                                minor):
    """The same with the tensor cut small: the halo a piece reads comes from
    the piece before it in the cell, from the cell before it and, at a
    sequence's start and end, is zeros; channels span two cells."""
    plan = conv._conv_plan("probe", seq, channels, dtype, taps, bias,
                          minor == "tokens")
    assert plan.seq // plan.tokens > 1 and plan.tokens // plan.sub > 1
    assert plan.width // plan.channels > 1
    off = _conv_case(dtype, bias, channels, seq, batch, taps, minor)
    assert max(off) < (1e-5 if dtype == jnp.float32 else 1e-2), off


@pytest.mark.parametrize("minor", ["channels", "tokens"])
def test_conv_silu_is_causal_both_ways(small_cut, minor):
    """A later token moves no earlier output; an earlier ``dy`` moves no
    later ``du`` (``du_t`` reads ``dy`` from ``t`` to ``t + K - 1``)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    u = jax.random.normal(ks[0], (2, 500, 24))
    w = jax.random.normal(ks[1], (4, 24))
    dy = jax.random.normal(ks[2], u.shape)

    def convolve(u):
        return conv.causal_conv_silu(u, w, None, minor=minor)

    y, vjp = jax.vjp(convolve, u)
    y2 = convolve(u.at[:, 131:].add(1.0))
    np.testing.assert_array_equal(y[:, :131], y2[:, :131])
    assert float(jnp.abs(y[:, 131:135] - y2[:, 131:135]).min()) > 0
    du, du2 = vjp(dy)[0], vjp(dy.at[:, :131].add(1.0))[0]
    np.testing.assert_array_equal(du[:, 131:], du2[:, 131:])
    assert float(jnp.abs(du[:, 127:131] - du2[:, 127:131]).min()) > 0


def test_checkpointed_conv_runs_forward_twice_and_backward_once():
    """Under ``jax.checkpoint`` with the policy ``remat="full"`` uses, the
    rule keeps its inputs alone: where what follows needs the output (a
    scan's backward pass needs ``q``, ``k``, ``v``) the forward kernel runs in
    the forward pass and again in the recomputed copy, the backward kernel
    once; where nothing needs it, the recomputed copy drops it."""
    from horovod_tpu.models import gpt
    u = jnp.ones((1, 64, 128), jnp.bfloat16)
    w = jnp.ones((4, 128))

    def kernels(after):
        @functools.partial(jax.checkpoint, policy=gpt._full_policy)
        def block(u, w):
            return after(conv.causal_conv_silu(u, w, None))

        return re.findall(r"\bname=(hvd_conv_\w+)", str(jax.make_jaxpr(
            jax.grad(lambda u, w: jnp.cos(block(u, w).astype(jnp.float32))
                     .sum(), argnums=(0, 1)))(u, w)))

    assert kernels(jnp.sin) == ["hvd_conv_fwd", "hvd_conv_fwd",
                                "hvd_conv_bwd"]
    assert kernels(lambda y: 2 * y) == ["hvd_conv_fwd", "hvd_conv_bwd"]


def test_conv_silu_refuses_an_unknown_layout():
    with pytest.raises(ValueError, match="'channels' or 'tokens'"):
        conv.causal_conv_silu(jnp.ones((1, 8, 8)), jnp.ones((4, 8)), None,
                             minor="rows")


@pytest.mark.parametrize("minor", ["channels", "tokens"])
@pytest.mark.parametrize("first, channels, wide, seq", [
    (256, 128, 512, 64),   # read in place: the cut divides all three
    (0, 128, 200, 64),     # in place from the first channel on
    (40, 24, 100, 37),     # a slice carried with zeros: nothing divides
], ids=["in_place", "in_place_first", "sliced"])
def test_conv_silu_reads_its_channels_out_of_a_wider_tensor(
        first, channels, wide, seq, minor):
    """``first``: the convolution over channels ``first`` to ``first + C`` of
    a projection's whole output, and ``du`` zero on the channels beside
    them."""
    ks = jax.random.split(jax.random.PRNGKey(first + wide), 4)
    u = jax.random.normal(ks[0], (2, seq, wide))
    w = 0.5 * jax.random.normal(ks[1], (4, channels))
    b = jax.random.normal(ks[2], (channels,))
    dy = jax.random.normal(ks[3], (2, seq, channels))

    def both(fn):
        def run(u, w, b):
            y, vjp = jax.vjp(fn, u, w, b)
            return (y,) + vjp(dy)
        return jax.jit(run)(u, w, b)

    got = both(lambda u, w, b: conv.causal_conv_silu(
        u, w, b, first=first, minor=minor))
    want = both(lambda u, w, b: _plain_conv_silu(
        u[..., first:first + channels], w, b))
    for g, t in zip(got, want):
        assert g.shape == t.shape and _rel(g, t) < 1e-5
    beside = jnp.concatenate([got[1][..., :first],
                              got[1][..., first + channels:]], axis=-1)
    assert not beside.any()
    with pytest.raises(ValueError, match="channels 90 to 114 of 100"):
        conv.causal_conv_silu(u[..., :100], w[:, :24], None, first=90)

"""The chunked state-space scan (``ops/ssd.py::ssd_chunked``) held to the
plain recurrence (``ssd_sequential``, one token a step), values and the
gradient with respect to every input.

Tolerances. In float32 the two differ only by the order of sums and by
``exp(cum_i - cum_j)`` against a product of per-token decays: a few float32
eps (1.2e-7) times the chunk's length, so 2e-5 of the largest value. With
bfloat16 MXU operands (the benchmark's compute type) each of the four
products rounds its operands to 2**-8: 3e-2 of the largest value holds, and
the float32 decays are what keep it there; one test shows that a scan
whose running sums are rounded to bfloat16 would not pass, and another that
the kernels themselves read the running sums in float32.

The within-chunk term is two Pallas kernels (``hvd_ssd_fwd``,
``hvd_ssd_bwd``), in interpret mode here: every test of ``ssd_chunked`` runs
them. ``tests/test_flash_mosaic_compile.py`` compiles them for a v5e.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import pallas_util, ssd

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
    y, final = jax.jit(lambda *a: ssd.ssd_chunked(
        *a[:-1], chunk=CHUNK, dtype=jnp.float32, initial_state=a[-1]))(
            *args, start)
    want_y, want_final = jax.jit(lambda *a: ssd.ssd_sequential(
        *a[:-1], initial_state=a[-1]))(*args, start)
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

    got = jax.jit(jax.grad(scalar(chunked), argnums=range(7)))(*args, start)
    want = jax.jit(jax.grad(scalar(ssd.ssd_sequential), argnums=range(7)))(
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


NAMES = ("x", "dt", "A", "B", "C", "D", "initial state")


def _loss(fn):
    def loss(*inputs):
        y, final = fn(*inputs[:-1], initial_state=inputs[-1])
        return jnp.sum(jnp.sin(y.astype(jnp.float32))) \
            + jnp.sum(final * final)
    return loss


# Values and the gradient of every input through the kernels. Heads a grid
# cell: 2 of a group's 4 (groups=1: the group's d(C B^T) is summed over two
# grid cells and over the heads inside each) or a group's 2 (groups=2: the
# cell's group changes along the grid). 70: a length the chunk does not
# divide; 9: shorter than one chunk.
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("seq", [70, 9])
@pytest.mark.parametrize("groups", [1, 2])
def test_kernels_match_the_recurrence_in_values_and_every_gradient(
        monkeypatch, groups, seq, dtype, tol):
    monkeypatch.setattr(ssd, "_MAX_HEADS", 2)
    args, start = _inputs(11 + seq, seq=seq, groups=groups)

    def chunked(x, *rest, **kw):
        return ssd.ssd_chunked(x.astype(dtype), *rest, chunk=CHUNK,
                               dtype=dtype, **kw)

    (y, final), (want_y, want_final) = (
        f(*args, initial_state=start) for f in (chunked, ssd.ssd_sequential))
    assert y.dtype == dtype
    assert _rel(y.astype(jnp.float32), want_y) < tol
    assert _rel(final, want_final) < tol
    got = jax.jit(jax.grad(_loss(chunked), argnums=range(7)))(*args, start)
    want = jax.jit(jax.grad(_loss(ssd.ssd_sequential), argnums=range(7)))(
        *args, start)
    for name, g, w in zip(NAMES, got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        assert _rel(g, w) < tol, name


# bfloat16: y's own rounding under the sine decides (x's gradient is off by
# 7.1e-2, as the plain expression the kernels replaced was, digit for digit).
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 5e-5),
                                        (jnp.bfloat16, 1e-1)])
def test_kernels_at_the_cells_own_tile(dtype, tol):
    """Chunk 256 in 128 x 128 tiles (the one above the diagonal skipped),
    heads of 64 two to a lane tile, state 128: the shapes the
    ``granite-4.0-h-micro_s4096`` cell runs, with two heads and a chunk and
    a half of tokens."""
    args, start = _inputs(21, batch=1, seq=384, heads=2, width=64, groups=1,
                          state=128)
    x, dt, a, b_in, c_in, d = args
    # Steps of the size the model's are: a chunk's running sum reaches -3.
    args = (x, 0.1 * dt, a, b_in / 128 ** 0.5, c_in, d)

    def chunked(x, *rest, **kw):
        return ssd.ssd_chunked(x.astype(dtype), *rest, chunk=256,
                               dtype=dtype, **kw)

    (y, final), (want_y, want_final) = (
        f(*args, initial_state=start) for f in (chunked, ssd.ssd_sequential))
    assert _rel(y.astype(jnp.float32), want_y) < tol
    assert _rel(final, want_final) < tol
    got = jax.jit(jax.grad(_loss(chunked), argnums=range(7)))(*args, start)
    want = jax.jit(jax.grad(_loss(ssd.ssd_sequential), argnums=range(7)))(
        *args, start)
    for name, g, w in zip(NAMES, got, want):
        assert _rel(g, w) < tol, name


def _kernel_inputs(seed, dtype):
    """What ``ssd_chunked`` hands the kernels: ``x`` ``[B, S, H, P]``, ``dt``
    and the float32 running sums ``[B, S, H]``, ``B`` and ``C`` ``[B, S, G,
    N]``, the entering state's part ``[B, c, H, P, Q]`` and ``D``; in chunks
    of 64."""
    (x, dt, a, b_in, c_in, d), _ = _inputs(seed, seq=4 * 64, groups=1)
    dt = 0.05 * dt
    cum = jnp.cumsum((dt * a).reshape(dt.shape[0], 4, 64, -1),
                     axis=2).reshape(dt.shape)
    through = jax.random.normal(jax.random.PRNGKey(seed),
                                (x.shape[0], 4) + x.shape[2:] + (64,))
    f32 = jnp.float32
    return (x.astype(dtype), dt.astype(f32), b_in.astype(dtype),
            c_in.astype(dtype), cum.astype(f32), through.astype(f32),
            d.astype(f32))


def _plain_scan_output(x, dt, b_in, c_in, cum, through, d):
    """The kernels' sum as plain ``jax.numpy``, float32, one group."""
    n_chunks, chunk = through.shape[1], through.shape[-1]

    def chunked(t):
        return t.astype(jnp.float32).reshape(
            t.shape[:1] + (n_chunks, chunk) + t.shape[2:])

    x, cum = x.astype(jnp.float32), chunked(cum).swapaxes(2, 3)
    keep = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(keep, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                    # [B, c, H, i, j]
    cb = jnp.einsum("bcign,bcjgn->bcij", chunked(c_in), chunked(b_in),
                    precision="highest")
    y = jnp.einsum("bchij,bcjhp->bcihp", decay * cb[:, :, None],
                   chunked(x * dt[..., None]), precision="highest")
    y = y + jnp.moveaxis(through, 4, 2) * jnp.exp(cum).swapaxes(2, 3)[..., None]
    return y.reshape(x.shape) + d[:, None] * x


def test_the_kernels_read_the_running_sums_in_float32():
    """The kernels make every decay from float32 sums, in float32: handed
    sums rounded to bfloat16 (what decays in the compute type would be made
    from) their output moves by far more than their own whole error; and
    with bfloat16 operands the gradient of the sums stays at the products'
    rounding."""
    inputs = _kernel_inputs(5, jnp.float32)
    want = _plain_scan_output(*inputs)
    exact = _rel(ssd._scan_output(*inputs), want)
    assert exact < 2e-5
    rounded = inputs[4].astype(jnp.bfloat16).astype(jnp.float32)
    worse = ssd._scan_output(*inputs[:4], rounded, *inputs[5:])
    assert _rel(worse, want) > 100 * exact

    def d_cum(f, inputs):
        return jax.jit(jax.grad(lambda cum: jnp.sum(jnp.sin(f(
            *inputs[:4], cum, *inputs[5:]).astype(jnp.float32)))))(inputs[4])

    inputs = _kernel_inputs(5, jnp.bfloat16)
    assert _rel(d_cum(ssd._scan_output, inputs),
                d_cum(_plain_scan_output, inputs)) < 2e-2


def _hbm_shapes(fn, *args):
    """The shape of every float tensor in ``fn``'s lowered program."""
    text = jax.jit(fn).lower(*args).as_text()
    return {tuple(int(n) for n in dims.split("x"))
            for dims in re.findall(r"tensor<(\d+(?:x\d+)*)xb?f\d+>", text)}


def test_no_tensor_with_two_chunk_axes_a_head_reaches_the_program():
    """Forward and backward, the lowered program of the scan holds no value
    with a heads axis and two chunk-length axes (the decay tile, the
    weights, their cotangents): they live inside the kernels. ``d(C B^T)``
    has two chunk axes a GROUP, and is the one such value."""
    heads, chunk = 6, 24
    args, start = _inputs(2, batch=2, seq=3 * chunk, heads=heads, width=5,
                          groups=1, state=7)

    def per_head(shape):
        # Four axes or more: a batch's or a chunk's worth. (The interpreter
        # shows the kernels' VMEM scratch as a value too: the heads of one
        # grid cell's running sums as lane-replicated columns, three axes.)
        return shape.count(chunk) >= 2 and heads in shape and len(shape) > 3

    def scan(*inputs, **kw):
        return ssd.ssd_chunked(*inputs, chunk=chunk, **kw)

    shapes = _hbm_shapes(jax.grad(_loss(scan), argnums=range(7)), *args,
                         start)
    assert not [s for s in shapes if per_head(s)]
    assert any(s.count(chunk) >= 2 for s in shapes)     # d(C B^T), a group
    # The plain expression, by the same reading, is caught.
    ones = jnp.ones
    kernel_inputs = (ones((2, 3 * chunk, heads, 5)), ones((2, 3 * chunk, heads)),
                     ones((2, 3 * chunk, 1, 7)), ones((2, 3 * chunk, 1, 7)),
                     ones((2, 3 * chunk, heads)),
                     ones((2, 3, heads, 5, chunk)), ones((heads,)))
    assert [s for s in _hbm_shapes(_plain_scan_output, *kernel_inputs)
            if per_head(s)]


def test_a_shape_the_kernels_do_not_tile_raises_on_the_tpu(monkeypatch):
    monkeypatch.setattr(pallas_util, "on_tpu", lambda: True)
    args, _ = _inputs(0)
    with pytest.raises(ValueError, match="hvd_ssd_fwd does not tile chunk=16"):
        jax.eval_shape(lambda *a: ssd.ssd_chunked(*a, chunk=CHUNK), *args)
    assert [ssd.heads_per_block(n) for n in (64, 12, 2, 7)] == [16, 12, 2, 7]


def test_groups_must_divide_heads():
    args, _ = _inputs(0, heads=4, groups=3)
    with pytest.raises(ValueError, match="not a multiple of groups"):
        ssd.ssd_chunked(*args, chunk=CHUNK)

"""The chunked state-space scan (``ops/ssd.py::ssd_chunked``) held to the
plain recurrence (``ssd_sequential``, one token a step), values and the
gradient with respect to every input, and the causal depthwise convolution
to a loop over taps and tokens.

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

import functools
import re

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
    got = jax.grad(_loss(chunked), argnums=range(7))(*args, start)
    want = jax.grad(_loss(ssd.ssd_sequential), argnums=range(7))(*args, start)
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
    got = jax.grad(_loss(chunked), argnums=range(7))(*args, start)
    want = jax.grad(_loss(ssd.ssd_sequential), argnums=range(7))(*args, start)
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
        return jax.grad(lambda cum: jnp.sum(jnp.sin(f(
            *inputs[:4], cum, *inputs[5:]).astype(jnp.float32))))(inputs[4])

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
    from horovod_tpu.compression import quantize
    monkeypatch.setattr(quantize, "_pallas_backend_enabled", lambda *_: True)
    args, _ = _inputs(0)
    with pytest.raises(ValueError, match="hvd_ssd_fwd does not tile chunk=16"):
        jax.eval_shape(lambda *a: ssd.ssd_chunked(*a, chunk=CHUNK), *args)
    assert [ssd.heads_per_block(n) for n in (64, 12, 2, 7)] == [16, 12, 2, 7]


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


# ---- the convolution with its SiLU in one pass (hvd_conv_fwd, hvd_conv_bwd) --

def _plain_conv_silu(u, w, b):
    return jax.nn.silu(ssd.causal_conv1d(u, w, b)).astype(u.dtype)


@pytest.fixture
def small_cut(monkeypatch):
    """A cut that gives a few tokens several grid cells and a cell several
    passes of its loop, on both layouts (the calls are jitted inline: what
    was traced under another cut is dropped, before and after)."""
    jax.clear_caches()
    monkeypatch.setattr(ssd, "_CONV_CUT", {False: (0, 16, 16, 128, 1),
                                           True: (1, 128, 128, 16, 1)})
    monkeypatch.setattr(ssd, "_CONV_BLOCK", 1 << 12)
    yield
    jax.clear_caches()


def _conv_case(dtype, bias, channels, seq, batch, taps, minor):
    ks = jax.random.split(jax.random.PRNGKey(seq + channels), 4)
    u = jax.random.normal(ks[0], (batch, seq, channels)).astype(dtype)
    w = 0.5 * jax.random.normal(ks[1], (taps, channels))
    b = jax.random.normal(ks[2], (channels,)) if bias else None
    dy = jax.random.normal(ks[3], u.shape).astype(dtype)

    def both(fn):
        y, vjp = jax.vjp(fn, u, w, b)
        return (y,) + tuple(g for g in vjp(dy) if g is not None)

    got = both(lambda u, w, b: ssd.causal_conv_silu(u, w, b, minor=minor))
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
    plan = ssd._conv_plan("probe", seq, channels, dtype, taps, bias,
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

    def conv(u):
        return ssd.causal_conv_silu(u, w, None, minor=minor)

    y, vjp = jax.vjp(conv, u)
    y2 = conv(u.at[:, 131:].add(1.0))
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
            return after(ssd.causal_conv_silu(u, w, None))

        return re.findall(r"\bname=(hvd_conv_\w+)", str(jax.make_jaxpr(
            jax.grad(lambda u, w: jnp.cos(block(u, w).astype(jnp.float32))
                     .sum(), argnums=(0, 1)))(u, w)))

    assert kernels(jnp.sin) == ["hvd_conv_fwd", "hvd_conv_fwd",
                                "hvd_conv_bwd"]
    assert kernels(lambda y: 2 * y) == ["hvd_conv_fwd", "hvd_conv_bwd"]


def test_conv_silu_refuses_an_unknown_layout():
    with pytest.raises(ValueError, match="'channels' or 'tokens'"):
        ssd.causal_conv_silu(jnp.ones((1, 8, 8)), jnp.ones((4, 8)), None,
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
        y, vjp = jax.vjp(fn, u, w, b)
        return (y,) + vjp(dy)

    got = both(lambda u, w, b: ssd.causal_conv_silu(
        u, w, b, first=first, minor=minor))
    want = both(lambda u, w, b: _plain_conv_silu(
        u[..., first:first + channels], w, b))
    for g, t in zip(got, want):
        assert g.shape == t.shape and _rel(g, t) < 1e-5
    beside = jnp.concatenate([got[1][..., :first],
                              got[1][..., first + channels:]], axis=-1)
    assert not beside.any()
    with pytest.raises(ValueError, match="channels 90 to 114 of 100"):
        ssd.causal_conv_silu(u[..., :100], w[:, :24], None, first=90)

"""``ops/cca.py``: a CCA mixer's mix as two Pallas kernels under one
``custom_vjp`` (``hvd_cca_fwd``, ``hvd_cca_bwd``; interpret mode here), held
to the plain ``jax.numpy`` lines they replaced (``cca_mix_reference``) in
``q``, ``k`` and all six gradients, element by element.

In float32 nothing is rounded on either side, so what is left is the order
of sums: 2e-5 of an output's largest element. In bfloat16 both sides compute
in float32 and round where the module's docstring says, so an element of
``q`` or ``k`` is the plain lines' or, where the float32 values straddle a
rounding boundary, the representable value next to it: that, and at most one
element in a hundred, is the tolerance a mean or a norm rounded to bfloat16
fails (the control the benchmark cell's own check lacks).
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.ops import cca

OUTPUTS = ("q", "k", "du", "dconv0_w", "dconv0_b", "dconv1_w", "dconv1_b",
           "dtemp")

# name -> (B, S, Hq, Hk, D, taps, rotary_dim (0: no rotary embedding; None:
# the whole head), the cut: tokens a grid cell and a piece at most)
CASES = {
    "tiny-twin": (2, 32, 4, 2, 16, (2, 2), 8, {}),
    "lane-multiple": (1, 32, 2, 1, 128, (2, 2), 64, {}),
    "two-lane-tiles-a-head": (1, 16, 1, 1, 256, (2, 2), 64, {}),
    "no-rotary": (1, 32, 2, 2, 16, (2, 2), 0, {}),
    "whole-head-rotary": (1, 32, 2, 1, 16, (2, 2), None, {}),
    "whole-head-rotary-128": (1, 16, 1, 1, 128, (2, 2), None, {}),
    # Two grid cells of two pieces a sequence: every boundary inside it.
    "blocks-and-pieces": (2, 64, 2, 1, 16, (2, 2), 8,
                          dict(tokens=32, rows=16)),
    "one-key-head": (1, 32, 3, 1, 16, (2, 2), 8, {}),
    "groups-of-one": (1, 32, 2, 2, 16, (2, 2), 8, {}),
    # A length no tile divides rides zeros to the next one.
    "ragged-length": (1, 40, 2, 1, 16, (2, 2), 8, dict(tokens=32, rows=16)),
    "three-and-one-taps": (1, 32, 1, 1, 16, (3, 1), 8, dict(rows=16)),
    "one-and-three-taps": (1, 32, 1, 1, 16, (1, 3), 8, dict(rows=16)),
}


@contextlib.contextmanager
def cut(tokens=None, rows=None):
    """The kernels' cut (a grid cell's tokens, a piece's) bounded lower than
    the package's constants, so that a few tokens hold several blocks."""
    was = cca._TOKENS, cca._ROWS
    cca._TOKENS, cca._ROWS = tokens or was[0], rows or was[1]
    try:
        yield
    finally:
        cca._TOKENS, cca._ROWS = was


def inputs(case: str, dtype):
    batch, seq, heads, kv_heads, dim, taps, rotary, bounds = CASES[case]
    groups = heads + kv_heads
    wide = groups * dim
    keys = jax.random.split(jax.random.PRNGKey(sum(map(ord, case))), 8)
    args = (
        jax.random.normal(keys[0], (batch, seq, wide)).astype(dtype),
        0.7 * jax.random.normal(keys[1], (taps[0], wide)),
        0.5 * jax.random.normal(keys[2], (wide,)),
        jax.random.normal(keys[3], (taps[1], groups, dim, dim))
        / np.sqrt(taps[1] * dim),
        0.1 * jax.random.normal(keys[4], (wide,)),
        0.3 * jax.random.normal(keys[5], (kv_heads,)))
    # Positions that differ by sequence, as packed documents give them.
    positions = None if rotary == 0 else \
        jnp.arange(seq)[None] + 7 * jnp.arange(batch)[:, None]
    weights = (jax.random.normal(keys[6], (batch, seq, heads, dim)),
               jax.random.normal(keys[7], (batch, seq, kv_heads, dim)))
    model = dict(heads=heads, kv_heads=kv_heads, rope_theta=5e6,
                 rotary_dim=rotary or None)
    return args, positions, weights, model, bounds


def outputs(fn, args, positions, weights, model):
    """``q``, ``k`` and the gradients of ``sum(q wq) + sum(k wk)`` by the
    six inputs, in ``OUTPUTS``' order."""
    def loss(*args):
        q, k = fn(*args, positions, **model)
        f32 = jnp.float32
        return jnp.sum(q.astype(f32) * weights[0]) \
            + jnp.sum(k.astype(f32) * weights[1]), (q, k)

    (_, (q, k)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))(*args)
    return dict(zip(OUTPUTS, (q, k) + grads, strict=True))


@functools.lru_cache(maxsize=None)
def both(case: str, dtype=jnp.float32):
    args, positions, weights, model, bounds = inputs(case, dtype)
    with cut(**bounds):
        got = outputs(cca.cca_mix, args, positions, weights, model)
    return got, outputs(cca.cca_mix_reference, args, positions, weights,
                        model)


@pytest.mark.parametrize("output", OUTPUTS)
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernels_are_the_plain_lines_in_float32(case, output):
    got, want = (side[output] for side in both(case))
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))
    assert np.any(np.asarray(want))


BF16_CASES = ("tiny-twin", "lane-multiple", "blocks-and-pieces")


def next_to(got, want):
    """Where ``got`` is ``want`` or the bfloat16 value next to it."""
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    return np.abs(got - want) <= ulp * 1.001


@pytest.mark.parametrize("case", BF16_CASES)
def test_bfloat16_values_are_the_plain_lines_rounded_once(case):
    got, want = both(case, jnp.bfloat16)
    for name in ("q", "k"):
        assert got[name].dtype == jnp.bfloat16
        assert next_to(got[name], want[name]).all(), name
        assert np.mean(np.asarray(got[name] != want[name])) < 0.01, name


@pytest.mark.parametrize("output", OUTPUTS[2:])
@pytest.mark.parametrize("case", BF16_CASES)
def test_bfloat16_gradients_are_the_plain_lines(case, output):
    """The kernels hand ``dx`` to the MXU in bfloat16 (as XLA's default
    precision does with the plain lines' float32 on the chip) and keep
    ``dc1`` float32 where the plain lines' autodiff rounds it: an element
    is within 1% of the largest."""
    got, want = (side[output] for side in both(case, jnp.bfloat16))
    assert got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0,
        atol=1e-2 * float(jnp.abs(want.astype(jnp.float32)).max()))


class Rounded:
    """A ``jax.numpy`` whose ``float32`` is ``bfloat16``."""
    float32 = jnp.bfloat16

    def __getattr__(self, name):
        return getattr(jnp, name)


@pytest.mark.parametrize("case", BF16_CASES)
def test_a_mean_or_a_norm_in_bfloat16_fails_that_tolerance(case, monkeypatch):
    """The control: the plain lines with their means, norms, temperature
    and rotary embedding in bfloat16 are no neighbour of themselves in
    float32."""
    args, positions, weights, model, _ = inputs(case, jnp.bfloat16)
    want = both(case, jnp.bfloat16)[1]
    monkeypatch.setattr(cca, "jnp", Rounded())
    rounded = outputs(cca.cca_mix_reference, args, positions, weights, model)
    for name in ("q", "k"):
        assert not next_to(rounded[name], want[name]).all(), name
        assert np.mean(np.asarray(rounded[name] != want[name])) > 0.1, name


@functools.lru_cache(maxsize=None)
def jitted(case: str):
    _, positions, _, model, _ = inputs(case, jnp.float32)
    return jax.jit(lambda *a: cca.cca_mix(*a, positions, **model))


def mix(case: str, u=None):
    args, _, _, _, bounds = inputs(case, jnp.float32)
    if u is not None:
        args = (u,) + args[1:]
    with cut(**bounds):
        return jitted(case)(*args)


def test_a_sequence_starts_from_zeros_in_both_stages():
    """Token 0 reads zeros before it, not the sequence before it in the
    batch nor the first stage's bias: what a sequence gives alone, it gives
    behind another."""
    u = inputs("blocks-and-pieces", jnp.float32)[0][0]
    together = mix("blocks-and-pieces")
    alone = mix("blocks-and-pieces", u.at[0].set(3.0))
    for got, want in zip(together, alone):
        np.testing.assert_array_equal(got[1], want[1])
        assert np.any(np.asarray(got[0] != want[0]))
    # And tokens 0 and 1 are the plain lines', whose pads are zeros.
    want = both("blocks-and-pieces")[1]
    for got, name in zip(together, ("q", "k")):
        np.testing.assert_allclose(got[:, :2], want[name][:, :2], rtol=2e-5,
                                   atol=2e-6)


@pytest.mark.parametrize("token", [15, 16, 17, 31, 32, 33, 47, 48, 61])
def test_the_mix_is_causal_across_pieces_and_blocks(token):
    """A token moved at a piece's or a block's edge: nothing before it
    moves, the token and the two after it (a tap of each stage) do, and no
    later one."""
    u = inputs("blocks-and-pieces", jnp.float32)[0][0]
    was = mix("blocks-and-pieces")
    now = mix("blocks-and-pieces", u.at[:, token].add(1.0))
    for a, b in zip(was, now):
        moved = np.asarray(jnp.any(a != b, axis=(0, 2, 3)))
        assert list(np.nonzero(moved)[0]) == [token, token + 1, token + 2]


def test_the_gradient_crosses_a_block_boundary():
    """``du`` of the last token of a block reads the cotangents of the two
    tokens after it, in the next block: a loss on those alone reaches it."""
    args, positions, _, model, bounds = inputs("blocks-and-pieces",
                                               jnp.float32)

    def loss(u):
        q, k = cca.cca_mix(u, *args[1:], positions, **model)
        return jnp.sum(q[:, 33] ** 2) + jnp.sum(k[:, 32])

    with cut(**bounds):
        du = jax.jit(jax.grad(loss))(args[0])
    reached = np.asarray(jnp.any(du != 0, axis=(0, 2)))
    assert list(np.nonzero(reached)[0]) == [30, 31, 32, 33]


def test_the_rule_keeps_its_inputs_alone():
    """No ``[B, S, .]`` float32 tensor crosses from the forward to the
    backward pass: the residuals are ``u``, the parameters and the
    positions."""
    args, positions, _, model, _ = inputs("lane-multiple", jnp.bfloat16)
    _, vjp = jax.vjp(lambda *a: cca.cca_mix(*a, positions, **model), *args)
    kept = [leaf for leaf in jax.tree.leaves(vjp)
            if hasattr(leaf, "shape") and leaf.ndim >= 3
            and leaf.dtype == jnp.float32
            and leaf.shape[:2] == args[0].shape[:2]]
    assert not kept, [leaf.shape for leaf in kept]


def test_the_kernels_count_their_traces(spmd8):
    args, positions, weights, model, _ = inputs("tiny-twin", jnp.float32)
    jax.clear_caches()  # the calls are jitted inline: traced once a shape
    outputs(cca.cca_mix, args, positions, weights, model)
    family = hvd.metrics()["hvdtpu_spmd_cca_kernel_traces_total"]
    seen = {labels["kernel"]: labels for _, labels, _ in family["samples"]}
    assert set(seen) == {"hvd_cca_fwd", "hvd_cca_bwd"}
    assert {k: v for k, v in seen["hvd_cca_bwd"].items() if k != "kernel"} \
        == dict(tokens="32", rows="32", heads="4", kv_heads="2",
                head_lanes="128", rotary_dim="8", operand_dtype="float32")


@pytest.mark.parametrize("what,change", [
    ("key heads", dict(heads=3, kv_heads=2)),
    ("rotary_dim", dict(rotary_dim=5)),
    ("rotary_dim", dict(rotary_dim=32)),
])
def test_a_shape_the_mix_has_no_meaning_for_is_refused(what, change):
    args, positions, _, model, _ = inputs("tiny-twin", jnp.float32)
    if "heads" in change:
        args = (args[0][..., :80],) + args[1:]
    with pytest.raises(ValueError, match=what):
        cca.cca_mix(*args, positions, **{**model, **change})


def test_taps_beyond_a_blocks_halo_are_refused():
    with pytest.raises(ValueError, match="a block carries 16"):
        cca._plan(64, 4, 2, 16, 10, 9, 0)


def test_the_cut_comes_from_the_shapes():
    """The zaya1-8b_s4096 cell's; a short sequence; a length no tile
    divides; a head that is no lane multiple."""
    assert cca._plan(4096, 8, 2, 128, 2, 2, 64)[3:] == (128, 2, 2, 64, 4096,
                                                        512, 256)
    assert cca._plan(32, 4, 2, 16, 2, 2, 8)[3:] == (128, 2, 2, 8, 32, 32, 32)
    assert cca._plan(1000, 4, 2, 96, 2, 2, 0)[3:] == (128, 2, 2, 0, 1024,
                                                      512, 256)
    assert cca._plan(4096, 8, 2, 192, 2, 2, 64).wide == 10 * 256

"""``ops/gated_delta.py``: the chunked (WY) form of the gated delta rule held
to the recurrence one token a step, forward and gradients of every input.

Tolerances. In float32 the two differ by the order of sums (a chunk's tokens
are summed through ``T`` and two products, the recurrence adds one at a
time): 2e-5 of the largest element covers it (seen: 3e-6 at 70 tokens). With
bfloat16 MXU operands every product's inputs are rounded to 8 bits (eps
2**-8 = 3.9e-3) and ``T``, made in float32, is rounded once before it is
applied: 2e-2 of the largest output (seen: 6e-3). The gradient of the log
decays is then off by 2e-3 of its norm, and by ten times that with the
chunk's running sums of them made in bfloat16 (the last test). A ``T`` made
in bfloat16 reads as the shipped program does while keys are nearly
orthogonal (``A`` is small and ``T`` is rounded before it is applied
anyway): what holds it to float32 is the equal-keys test.

Since PR 34 everything of the chunked form with two chunk-length axes (``K
K^T``, the decays, ``A``, ``T`` and ``T``'s two products) is two Pallas
kernels, which run here in interpret mode: every test of
``gated_delta_chunked`` above and below goes through them. The ``cell``
cases repeat the comparison at the benchmark cell's head layout, and the
kernels' own inverse (substitution inside diagonal blocks, then products) is
held to ``unit_lower_inverse`` directly.

Since PR 36 the recurrence over chunks is two more kernels
(``hvd_gdn_rec_fwd``, ``hvd_gdn_rec_bwd``: the state in a scratch from a
sequence's first chunk to its last), so every test here runs them too; the
``recurrence`` cases take a grid of two blocks of value heads and, at chunks
of 32, two blocks of chunks, an initial state and a cotangent on the final
one.

Since PR 37 a head has any size: ``gated_delta_chunked`` carries it inside at
the next multiple of the lane width, with zeros (the same code here and on
the chip), and ``beta`` may lie in (0, 2). The ``LAYOUTS`` cases of the
recurrence test run heads that are no lane multiple and not square (96 by
192, one value head a key head, six heads: a count eight does not divide),
heads smaller than a tile, thirty heads, and 128 chunks in a sequence, all
with ``beta`` drawn in (0, 2); the padded lanes come back exactly zero.

Since PR 50 the chunk-local kernels norm the rows of ``q`` and ``k``
themselves under ``norm_qk`` (what a model's mixer asks for): the ``NORMED``
cases feed raw rows, of any length, and hold the outputs and the gradient of
every input, **of the raw rows**, to the recurrence fed rows normed outside
by the plain line (``unit_rows``), at heads of 128 by 128 and of 96 by 192
carried on lanes, in float32 and with bfloat16 operands; then the rows the
chunk's padding adds, a zero row inside a chunk, and the scale of ``q``,
which is the true head's and not the lanes'.

Since PR 68 the forward chunk-local kernel writes the float32 ``T`` it holds
and the backward kernel reads it and makes no inverse: the ``T_CASES`` hold
what reaches HBM, through the kernels' own packing, to ``unit_lower_inverse``
of the plain ``A`` (nearly equal keys among them), and a checkpointed
gradient is the plain one bit for bit with the names kept and with nothing
kept, at both head layouts.
"""

import collections
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.ops import gated_delta
from horovod_tpu.ops.gated_delta import (
    gated_delta_chunked, gated_delta_sequential, unit_lower_inverse,
    unit_rows)
from horovod_tpu.ops.pallas_util import (
    largest_divisor, to_lanes, unit_lower_inverse_in_vmem, unpack_t,
    use_interpret)

B, HK, HV, K, V = 2, 2, 4, 16, 8


def _inputs(seed, seq, decay=0.3, key_heads=HK, batch=B, heads=HV,
            key_dim=K, width=V, beta_max=1.0):
    rng = np.random.default_rng(seed)

    def unit(t):
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((batch, seq, key_heads, key_dim))) \
        / np.sqrt(key_dim)
    k = unit(rng.standard_normal((batch, seq, key_heads, key_dim)))
    v = rng.standard_normal((batch, seq, heads, width))
    g = -decay * np.exp(rng.standard_normal((batch, seq, heads)))
    beta = beta_max / (1 + np.exp(-rng.standard_normal((batch, seq, heads))))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))


# The benchmark cell's head layout: two value heads a key head, key and
# value heads of 128, chunks of 64, a length the chunk does not divide
# (three chunks: the kernels' grid cell takes all three).
CELL = dict(batch=1, key_heads=1, heads=2, key_dim=128, width=128)
CELL_SEQ = 150


def _close(got, want, tol):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=0,
        atol=tol * float(jnp.abs(want).max()) + 1e-12)


@functools.cache
def _gradients(inputs_seed, seq, seed, chunk, **shape):
    """The gradients of every input of a random linear form of the output
    and the final state, ``(chunked, sequential)``: one run of each side,
    shared by the cases that each hold one input's."""
    args = _inputs(inputs_seed, seq, **shape)
    rng = np.random.default_rng(seed)
    like_o, like_s = jax.eval_shape(gated_delta_sequential, *args)
    co = jnp.asarray(rng.standard_normal(like_o.shape), jnp.float32)
    cs = jnp.asarray(rng.standard_normal(like_s.shape), jnp.float32)

    def scalar(fn):
        def f(*a):
            o, s = fn(*a)
            return jnp.sum(o * co) + jnp.sum(s * cs)
        return jax.grad(f, argnums=tuple(range(5)))(*args)

    return (scalar(lambda *a: gated_delta_chunked(*a, chunk=chunk,
                                                  dtype=jnp.float32)),
            scalar(gated_delta_sequential))


def _gradient_matches(wrt, *case, **shape):
    """The gradient of input ``wrt``, chunked against sequential."""
    got, want = _gradients(*case, **shape)
    _close(got[wrt], want[wrt], 5e-5)


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("seq", [64, 70, 9])
def test_chunked_matches_sequential(chunk, seq):
    """Two chunk sizes; lengths the chunk divides, does not divide (padded
    with tokens that neither decay nor write), and shorter than a chunk."""
    args = _inputs(0, seq)
    want_o, want_s = gated_delta_sequential(*args)
    o, s = gated_delta_chunked(*args, chunk=chunk, dtype=jnp.float32)
    assert o.shape == want_o.shape and s.shape == (B, HV, K, V)
    _close(o, want_o, 2e-5)
    _close(s, want_s, 2e-5)


def test_the_kernels_are_the_path():
    """No flag chooses: off the TPU the chunk-local part runs as the two
    kernels in interpret mode, forward and backward."""
    assert use_interpret()
    args = _inputs(0, 32)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: gated_delta_chunked(
        *a, chunk=16, dtype=jnp.float32)[0].sum(), argnums=(0, 3)))(*args))
    for kernel in ("fwd", "bwd", "rec_fwd", "rec_bwd"):
        assert f"name=hvd_gdn_{kernel}" in text
    assert "lax.scan" not in inspect.getsource(gated_delta_chunked)


# name -> (what ``_inputs`` takes, tokens, chunk, value heads a grid cell of
# the recurrence, chunks a cell). ``recurrence``: sixteen value heads, two a
# key head: two of the recurrence's blocks of eight; 170 tokens are six
# chunks of 32 (two blocks of three) or three of 64 (one block), the last one
# padded either way. The others draw ``beta`` in (0, 2) and are carried at
# padded sizes: ``96 by 192`` is the ``olmo-hybrid-7b_s8192`` cell's head
# (no lane multiple, not square, one value head a key head) at six heads,
# which eight does not divide, and a length the chunk does not divide;
# ``under a tile`` heads of 24 by 40, two value heads a key head; ``thirty
# heads`` the cell's head count (blocks of six); ``128 chunks`` the cell's
# chunks a sequence (32 blocks of four on the recurrence's ordered axis).
LAYOUTS = {
    "recurrence-32": (dict(batch=1, key_heads=8, heads=16, key_dim=16,
                           width=8), 170, 32, 8, 3),
    "recurrence-64": (dict(batch=1, key_heads=8, heads=16, key_dim=16,
                           width=8), 170, 64, 8, 3),
    "96 by 192": (dict(batch=1, key_heads=6, heads=6, key_dim=96, width=192,
                       beta_max=2.0), 150, 64, 6, 3),
    "under a tile": (dict(batch=2, key_heads=3, heads=6, key_dim=24,
                          width=40, beta_max=2.0), 37, 16, 6, 3),
    "thirty heads": (dict(batch=1, key_heads=30, heads=30, key_dim=8,
                          width=16, beta_max=2.0), 48, 16, 6, 3),
    "128 chunks": (dict(batch=1, key_heads=1, heads=2, key_dim=8, width=8,
                        beta_max=2.0), 2048, 16, 2, 4),
}


@functools.cache
def _recurrence_case(layout):
    """A layout's six inputs, the initial state among them, and both sides'
    gradients of every one of them of a linear form of both outputs: one
    run of each side, shared by the layout's seven cases."""
    shape, seq, chunk, _, _ = LAYOUTS[layout]
    rng = np.random.default_rng(18)
    q, k, v, g, beta = _inputs(19, seq, **shape)
    state_shape = (v.shape[0], v.shape[2], k.shape[3], v.shape[3])
    args = (q, k, v, g, beta, jnp.asarray(
        0.5 * rng.standard_normal(state_shape), jnp.float32))

    def chunked(*a):
        return gated_delta_chunked(*a[:5], chunk=chunk, dtype=jnp.float32,
                                   initial_state=a[5])

    def sequential(*a):
        return gated_delta_sequential(*a[:5], initial_state=a[5])

    co, cs = (jnp.asarray(rng.standard_normal(t.shape), jnp.float32)
              for t in jax.eval_shape(sequential, *args))

    def gradients(fn):
        def f(*a):
            o, s = fn(*a)
            return jnp.sum(o * co) + jnp.sum(s * cs)
        return jax.grad(f, argnums=tuple(range(6)))(*args)

    return args, chunked, sequential, gradients(chunked), gradients(sequential)


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("what", ["o and final", "q", "k", "v", "g", "beta",
                                  "initial_state"])
def test_recurrence_kernels_match_sequential(monkeypatch, layout, what):
    """``o``, the final state and the gradient of every input, the initial
    state's too, of a linear form of both outputs, element by element; the
    outputs carry the sizes given, and what the kernels carried beyond them
    comes back exactly zero."""
    shape, seq, chunk, heads_per_block, chunks_per_block = LAYOUTS[layout]
    args, chunked, sequential, got_grads, want_grads = _recurrence_case(layout)
    q, k, v, g, beta, _ = args
    state_shape = (v.shape[0], v.shape[2], k.shape[3], v.shape[3])
    n_chunks = -(-seq // chunk)
    lanes = [-(-n // 128) * 128 for n in state_shape[2:]]
    assert gated_delta._rec_heads(v.shape[2], chunks_per_block, chunk,
                                  *lanes, 4) == heads_per_block
    assert largest_divisor(n_chunks, gated_delta._REC_CHUNKS) \
        == chunks_per_block

    if what == "o and final":
        carried = []
        real = gated_delta._recurrence
        monkeypatch.setattr(gated_delta, "_recurrence", lambda *a: (
            carried.append(real(*a)) or carried[-1]))
        got, want = chunked(*args), sequential(*args)
        for have, ref in zip(got, want, strict=True):
            assert have.shape == ref.shape
            _close(have, ref, 2e-5)
        # What the kernels carried: whole lane tiles a head, zeros beyond
        # the sizes given.
        (o, final), = carried
        o = o.reshape(o.shape[:2] + (v.shape[2], -1))
        assert o.shape[-1] == lanes[1] and final.shape[2:] == tuple(lanes)
        assert not np.any(np.asarray(o[..., v.shape[3]:]))
        assert not np.any(np.asarray(final[:, :, k.shape[3]:]))
        assert not np.any(np.asarray(final[..., v.shape[3]:]))
        return
    wrt = ["q", "k", "v", "g", "beta", "initial_state"].index(what)
    _close(got_grads[wrt], want_grads[wrt], 5e-5)


def test_cell_layout_matches_sequential():
    args = _inputs(11, CELL_SEQ, **CELL)
    want_o, want_s = gated_delta_sequential(*args)
    o, s = gated_delta_chunked(*args, chunk=64, dtype=jnp.float32)
    _close(o, want_o, 2e-5)
    _close(s, want_s, 2e-5)


@pytest.mark.parametrize("wrt", range(5), ids=["q", "k", "v", "g", "beta"])
def test_cell_layout_gradients_of_every_input(wrt):
    _gradient_matches(wrt, 12, CELL_SEQ, 13, 64, **CELL)


def _names_in(jaxpr, out=None):
    """``checkpoint_name``'s equations by name in a jaxpr and every jaxpr
    under it."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            out[eqn.params["name"]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _names_in(sub, out)
    return out


@pytest.mark.parametrize("heads", ["whole", "padded"])
def test_the_names_a_checkpoint_may_keep_change_nothing_outside_one(heads):
    """Outside a checkpoint a name is an identity: the value and every
    input's gradient are the sequential rule's, where the entering states
    are named (a head of whole lane tiles) and where they are not (a head
    carried with zeros); the chunk-local kernel's six outputs are named
    either way."""
    # (The shapes of the cases above, whose compiled kernels these calls
    # find again.)
    args = _inputs(31, CELL_SEQ, **CELL) if heads == "whole" \
        else _inputs(31, 70)
    rng = np.random.default_rng(32)
    like_o, like_s = jax.eval_shape(gated_delta_sequential, *args)
    co = jnp.asarray(rng.standard_normal(like_o.shape), jnp.float32)
    cs = jnp.asarray(rng.standard_normal(like_s.shape), jnp.float32)

    def value_and_grads(fn):
        def f(*a):
            o, s = fn(*a)
            return jnp.sum(o * co) + jnp.sum(s * cs)
        return jax.value_and_grad(f, argnums=tuple(range(5)))

    chunked = value_and_grads(lambda *a: gated_delta_chunked(
        *a, chunk=64, dtype=jnp.float32))
    value, grads = chunked(*args)
    want_value, want = value_and_grads(gated_delta_sequential)(*args)
    np.testing.assert_allclose(value, want_value, rtol=2e-5)
    for got, w in zip(grads, want):
        _close(got, w, 5e-5)
    # (The chunk-local kernel's sixth output, ``T``, is the chunk-local
    # rule's residual under the five's name: PR 68.)
    assert _names_in(jax.make_jaxpr(chunked)(*args).jaxpr) == {
        "gdn_scan_operands": 6,
        **({"gdn_scan_entering": 1} if heads == "whole" else {})}


@pytest.mark.parametrize("kept", ["the_names", "nothing"])
@pytest.mark.parametrize("heads", ["whole", "padded"])
def test_a_checkpoints_gradient_is_the_plain_one_to_the_last_bit(
        heads, kept, equations_of):
    """The backward kernels read the tensors the forward kernels wrote,
    ``T`` among them, where the checkpoint keeps them by name and a second
    run's identical copies where it keeps nothing: every input's gradient
    is the un-checkpointed one's either way, bit for bit, at a head of
    whole lane tiles and at one carried with zeros. With the names kept
    (the module's and the mixer's for the output, ``gdn_scan_out``) the
    recomputed copy holds no ``hvd_gdn_fwd``."""
    args = _inputs(33, CELL_SEQ, **CELL) if heads == "whole" \
        else _inputs(33, 70)

    def loss(*a):
        o, final = gated_delta_chunked(*a, chunk=64)
        o = checkpoint_name(o, "gdn_scan_out")
        return jnp.sum(jnp.sin(o.astype(jnp.float32))) + jnp.sum(final)

    policy = jax.checkpoint_policies.save_only_these_names(
        *((*gated_delta.SAVED_NAMES, "gdn_scan_out")
          if kept == "the_names" else ()))
    argnums = tuple(range(5))
    plain = jax.jit(jax.grad(loss, argnums))
    checkpointed = jax.jit(jax.grad(jax.checkpoint(loss, policy=policy),
                                    argnums))
    for name, a, b in zip(INPUT_NAMES, checkpointed(*args), plain(*args)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    assert sum(
        eqn.primitive.name == "pallas_call"
        and eqn.params["name"] == gated_delta.KERNEL_FWD
        for eqn, _ in equations_of(jax.make_jaxpr(checkpointed)(
            *args).jaxpr)) == (1 if kept == "the_names" else 2)


# name -> (``_inputs``' keywords, tokens, chunk, the kept ``T``'s last two
# axes): the Qwen cell's grid cell (two value heads a key head, four chunks),
# the Olmo cell's (one value head a key head, four chunks), and a chunk of
# 16, whose tile is still under the lanes' width.
T_CASES = {
    "two_value_heads_a_key_head": (CELL, 256, 64, (32, 128)),
    "one_value_head_a_key_head": (
        dict(batch=2, key_heads=2, heads=2, key_dim=16, width=8,
             beta_max=2.0), 256, 64, (32, 128)),
    "nearly_equal_keys": (CELL, 128, 64, (32, 128)),
    "chunk_of_16": (dict(batch=1, key_heads=1, heads=2, key_dim=16, width=8),
                    64, 16, (8, 32)),
}


@pytest.mark.parametrize("case", list(T_CASES))
def test_the_t_the_forward_kernel_writes_is_unit_lower_inverse_of_a(case):
    """``hvd_gdn_fwd``'s sixth output, read back through the kernels' own
    packing (a ``[64, 64]`` float32 tile kept as ``[32, 128]``: no lane of
    it padding), against the plain inverse of the plain ``A``; keys a
    twentieth apart at ``alpha = beta = 1`` make ``A``'s entries 0.99...
    and ``T`` nearly bidiagonal, which an inverse with its arithmetic in
    bfloat16 misses by 1.7e-2."""
    shape, seq, chunk, kept_shape = T_CASES[case]
    q, k, v, g, beta = _inputs(34, seq, **shape)
    if case == "nearly_equal_keys":
        rng = np.random.default_rng(35)
        k = rng.standard_normal(128) \
            + 0.05 * rng.standard_normal((1, seq, 1, 128))
        k = jnp.asarray(k / np.linalg.norm(k, axis=-1, keepdims=True),
                        jnp.float32)
        g, beta = jnp.zeros_like(g), jnp.ones_like(beta)
    batch, heads = v.shape[0], v.shape[2]
    rep = heads // k.shape[2]
    by_chunk = (batch, seq // chunk, chunk, heads)
    cum = jnp.cumsum(g.reshape(by_chunk), axis=2)
    kept = gated_delta._fwd_call(q, k, v, cum, beta.reshape(by_chunk))[5]
    assert kept.dtype == jnp.float32
    assert kept.shape == (seq // chunk, batch, heads) + kept_shape
    # The plain A, [c, B, Hv, Q, Q]: beta_t (G_t / G_j) <k_t, k_j>, j < t.
    keys = jnp.repeat(k, rep, axis=2).reshape(by_chunk + k.shape[3:])
    kk = jnp.einsum("bcihk,bcjhk->cbhij", keys, keys,
                    precision=jax.lax.Precision.HIGHEST)
    rows = jnp.moveaxis(cum, (1, 3), (0, 2))                # [c, B, Hv, Q]
    a = jnp.tril(kk * jnp.exp(rows[..., :, None] - rows[..., None, :])
                 * jnp.moveaxis(beta.reshape(by_chunk), (1, 3),
                                (0, 2))[..., None], -1)
    _close(unpack_t(kept, chunk), unit_lower_inverse(a), 1e-5)


@pytest.mark.parametrize("what", ["beta zero", "alpha one"])
def test_cell_layout_beta_zero_and_alpha_one(what):
    """The two degenerate cases at the cell's layout: nothing written (the
    entering state only decays), and the ungated delta rule (a unit key reads
    back its value)."""
    q, k, v, g, beta = _inputs(14, 128, **CELL)
    if what == "beta zero":
        start = jnp.asarray(np.random.default_rng(15).standard_normal(
            (1, 2, 128, 128)), jnp.float32)
        o, s = gated_delta_chunked(q, k, v, g, jnp.zeros_like(g), chunk=64,
                                   dtype=jnp.float32, initial_state=start)
        total = jnp.exp(jnp.cumsum(g, axis=1))
        _close(s, total[:, -1][..., None, None] * start, 1e-5)
        _close(o, jnp.einsum("bshk,bhkv->bshv", jnp.repeat(q, 2, axis=2),
                             start) * total[..., None], 1e-5)
    else:
        k2 = jnp.repeat(k, 2, axis=2)
        o, _ = gated_delta_chunked(k2, k2, v, jnp.zeros_like(g),
                                   jnp.ones_like(beta), chunk=64,
                                   dtype=jnp.float32)
        _close(o, v, 1e-5)


@pytest.mark.parametrize("key_heads", [HV, HK, 1])
def test_value_heads_share_key_heads(key_heads):
    """Value head h reads key head h // (Hv / Hk): equal to repeating the
    key heads by hand."""
    q, k, v, g, beta = _inputs(1, 40, key_heads=key_heads)
    rep = HV // key_heads
    want, _ = gated_delta_sequential(
        jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2), v, g, beta)
    o, _ = gated_delta_chunked(q, k, v, g, beta, chunk=16, dtype=jnp.float32)
    _close(o, want, 2e-5)


@pytest.mark.parametrize("chunk,seq", [(16, 64), (64, 70), (16, 37)])
@pytest.mark.parametrize("wrt", range(5), ids=["q", "k", "v", "g", "beta"])
def test_gradients_of_every_input(chunk, seq, wrt):
    _gradient_matches(wrt, 2, seq, 3, chunk)


@pytest.mark.parametrize("decay,what", [(1e-3, "near one"), (8.0, "near zero")])
def test_decays_near_one_and_near_zero(decay, what):
    """alpha = exp(g) near 1 (the state hardly forgets: the correction does
    all the work) and near 0 (exp(-8) and far less: ratios of running
    products underflow unless the exponent is masked before the exp)."""
    args = _inputs(4, 48, decay=decay)
    want_o, want_s = gated_delta_sequential(*args)
    o, s = gated_delta_chunked(*args, chunk=16, dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(o)))
    _close(o, want_o, 2e-5)
    _close(s, want_s, 2e-5)
    grads = jax.grad(lambda *a: jnp.sum(gated_delta_chunked(
        *a, chunk=16, dtype=jnp.float32)[0] ** 2), argnums=(0, 1, 2, 3, 4))(
            *args)
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in grads)


def test_beta_zero_is_plain_decay():
    """Nothing is written: an entering state only decays, and each token
    reads it through q."""
    q, k, v, g, _ = _inputs(5, 32)
    start = jnp.asarray(np.random.default_rng(6).standard_normal(
        (B, HV, K, V)), jnp.float32)
    o, s = gated_delta_chunked(q, k, v, g, jnp.zeros_like(g), chunk=16,
                               dtype=jnp.float32, initial_state=start)
    total = jnp.exp(jnp.cumsum(g, axis=1))                      # [B, S, Hv]
    _close(s, total[:, -1][..., None, None] * start, 1e-5)
    want = jnp.einsum("bshk,bhkv->bshv", jnp.repeat(q, HV // HK, axis=2),
                      start) * total[..., None]
    _close(o, want, 1e-5)


def test_alpha_one_beta_one_is_the_ungated_delta_rule():
    """S_t = S_{t-1} + k_t (v_t - S_{t-1}^T k_t)^T: after writing, a unit key
    reads back exactly its value."""
    q, k, v, g, beta = _inputs(7, 32, key_heads=HV)
    o, _ = gated_delta_chunked(k, k, v, jnp.zeros_like(g),
                               jnp.ones_like(beta), chunk=16,
                               dtype=jnp.float32)
    _close(o, v, 1e-5)
    state = np.zeros((B, HV, K, V), np.float32)
    kn, vn = np.asarray(k), np.asarray(v)
    for t in range(32):
        seen = np.einsum("bhkv,bhk->bhv", state, kn[:, t])
        state += np.einsum("bhk,bhv->bhkv", kn[:, t], vn[:, t] - seen)
    _, s = gated_delta_chunked(q, k, v, jnp.zeros_like(g),
                               jnp.ones_like(beta), chunk=16,
                               dtype=jnp.float32)
    _close(s, state, 1e-5)


@pytest.mark.parametrize("size", [1, 2, 16, 64])
def test_unit_lower_inverse_and_its_gradient(size):
    rng = np.random.default_rng(8)
    a = jnp.asarray(np.tril(rng.standard_normal((3, size, size)), -1),
                    jnp.float32)
    want = jnp.linalg.inv(jnp.eye(size) + a)
    _close(unit_lower_inverse(a), want, 1e-5)
    if size > 1:
        co = jnp.asarray(rng.standard_normal((3, size, size)), jnp.float32)
        got = jax.jit(jax.grad(
            lambda t: jnp.sum(unit_lower_inverse(t) * co)))(a)
        ref = jax.jit(jax.grad(lambda t: jnp.sum(
            jnp.linalg.inv(jnp.eye(size) + jnp.tril(t, -1)) * co)))(a)
        _close(jnp.tril(got, -1), ref, 1e-4)


def test_equal_keys_do_not_blow_the_inverse_up():
    """Every key the same at alpha = beta = 1: ``A`` is all ones below the
    diagonal, whose inverse is bidiagonal but whose powers hold binomials up
    to 1e17; the inverse by blocks stays exact."""
    a = jnp.tril(jnp.ones((64, 64), jnp.float32), -1)
    want = np.eye(64, dtype=np.float32) - np.eye(64, k=-1, dtype=np.float32)
    np.testing.assert_allclose(unit_lower_inverse(a), want, atol=1e-6)


@pytest.mark.parametrize("substitute", [1, 8, 32, 64])
@pytest.mark.parametrize("size", [16, 64])
@pytest.mark.parametrize("beta", [1.0, 2.0])
def test_the_kernels_inverse_is_unit_lower_inverse(size, substitute, beta):
    """The inverse as the kernels make it (diagonal blocks of ``substitute``
    rows by forward substitution, then ``_inverse``'s rounds; 32 ships)
    against the plain form, on a random matrix and on the equal-keys one, at
    ``beta = 1`` and at ``beta = 2`` (``A``'s entries doubled: all twos
    below the diagonal, whose inverse alternates ``-2, 2, -2`` down each
    column)."""
    rng = np.random.default_rng(16)
    a = beta * jnp.asarray(np.tril(rng.standard_normal((size, size)), -1),
                           jnp.float32)
    _close(unit_lower_inverse_in_vmem(a, substitute), unit_lower_inverse(a),
           1e-5)
    equal = beta * jnp.tril(jnp.ones((size, size), jnp.float32), -1)
    rows, cols = np.indices((size, size))
    want = np.where(rows == cols, 1.0, np.where(
        rows > cols, -beta * (1.0 - beta) ** np.maximum(rows - cols - 1, 0),
        0.0))
    np.testing.assert_allclose(unit_lower_inverse(equal), want, atol=1e-6)
    np.testing.assert_allclose(unit_lower_inverse_in_vmem(equal, substitute),
                               want, atol=1e-6)


@pytest.mark.parametrize("noise, dtype, tol, beta", [
    (0.0, jnp.float32, 1e-5, 1.0), (0.05, jnp.float32, 1e-4, 1.0),
    (0.0, jnp.bfloat16, 5e-2, 1.0), (0.0, jnp.float32, 1e-4, 2.0),
    (0.05, jnp.float32, 2e-4, 2.0)],
    ids=["equal", "nearly equal", "bfloat16", "equal, beta 2",
         "nearly equal, beta 2"])
def test_equal_keys_through_the_kernels(noise, dtype, tol, beta):
    """Every key the same at alpha = beta = 1, through the kernel path:
    ``A`` is all ones below the diagonal, each token unwrites the one before
    and the output is ``<q, k> v_t``; an inverse by powers is off by orders
    of magnitude. Keys a twentieth apart make ``A``'s entries 0.99...: the
    same inverse with its arithmetic in bfloat16 reads 1.7e-2 here, the
    float32 one 1e-6 (all ones are exact in any type, so the first case
    cannot see that). With bfloat16 operands the keys' own rounding shows
    (3.7e-2 seen). At ``beta = 2`` a token's transition is the reflection
    ``I - 2 k k^T`` (eigenvalue -1 along the key): ``A`` is all twos, each
    token overwrites the one before with the opposite sign, and ``T``'s
    entries alternate ``-2, 2`` without growing; the outputs are alternating
    sums of all earlier values (up to 68 here for 4 at ``beta = 1``), which
    float32 holds to 1.4e-5 and 3.7e-5 of the largest. Nothing decays under
    a reflection, so bfloat16 keys' rounding adds up over the 128 tokens
    (0.4 of the largest, the sequential form in bfloat16 alike): no case."""
    seq = 128
    rng = np.random.default_rng(17)
    k = rng.standard_normal(128) \
        + noise * rng.standard_normal((1, seq, 1, 128))
    k = jnp.asarray(k / np.linalg.norm(k, axis=-1, keepdims=True),
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, seq, 2, 128)), jnp.float32)
    zeros, ones = jnp.zeros((1, seq, 2)), beta * jnp.ones((1, seq, 2))
    want, want_s = gated_delta_sequential(k, k, v, zeros, ones)
    if not noise and beta == 1.0:
        _close(want, v, 1e-5)
    o, s = gated_delta_chunked(k, k, v, zeros, ones, chunk=64, dtype=dtype)
    _close(o, want, tol)
    _close(s, want_s, tol)


def test_bad_shapes_and_chunks_raise_by_name():
    q, k, v, g, beta = _inputs(9, 16)
    with pytest.raises(ValueError, match="power of two"):
        gated_delta_chunked(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="gated delta rule"):
        gated_delta_chunked(q, k, v[:, :, :3], g, beta)


def test_bfloat16_products_hold_and_bfloat16_running_sums_would_not(
        monkeypatch):
    """The shipped precision (bfloat16 MXU operands, float32 decays, sums and
    ``T``) against the float32 recurrence, and the fault the benchmark's
    fifth check row exists for, on the gradient of the log decays: the
    chunk's running sums of them made in bfloat16."""
    args = _inputs(10, 256, decay=0.05)

    def g_grad(fn):
        return jax.grad(lambda g: jnp.sum(fn(
            args[0], args[1], args[2], g, args[4])[0].astype(jnp.float32)
            ** 2))(args[3])

    def miss(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    want_o, _ = gated_delta_sequential(*args)
    want = g_grad(gated_delta_sequential)
    o, _ = gated_delta_chunked(*args, chunk=64)
    assert o.dtype == jnp.bfloat16
    _close(o, want_o, 2e-2)
    shipped = miss(g_grad(lambda *a: gated_delta_chunked(*a, chunk=64)), want)
    real = jnp.cumsum
    monkeypatch.setattr(jnp, "cumsum", lambda x, axis: real(
        x.astype(jnp.bfloat16), axis=axis).astype(jnp.float32))
    faulty = miss(g_grad(lambda *a: gated_delta_chunked(*a, chunk=64)), want)
    assert shipped < 1e-2 < faulty, (shipped, faulty)


# name -> (what ``_inputs`` takes, tokens): the Qwen cell's head (two value
# heads a key head) and the Olmo cell's (no lane multiple: the kernels norm
# a row over 128 lanes, 32 of them zeros), ``beta`` in (0, 2) there; 150
# tokens are two chunks and 22 tokens of a third, the rest padding.
NORMED = {
    "128 by 128": (dict(batch=1, key_heads=1, heads=2, key_dim=128,
                        width=128), 150),
    "96 by 192": (dict(batch=1, key_heads=2, heads=2, key_dim=96, width=192,
                       beta_max=2.0), 150),
}
INPUT_NAMES = ("q", "k", "v", "g", "beta")
# Of the largest element: outputs, gradients. float32: the file's own (seen:
# 1.1e-6 at most). bfloat16 operands: the shipped-precision test's 2e-2 for
# both (seen: outputs 7.3e-3, gradients 6.8e-3 at most, the raw rows' 4.6e-3).
NORMED_TOL = {"float32": (2e-5, 5e-5), "bfloat16": (2e-2, 2e-2)}


def _raw_inputs(seed, seq, dtype, **shape):
    """``_inputs`` with ``q`` and ``k`` as a convolution leaves them: each
    row of another length (a lognormal factor around the root of the head's
    size), rounded to ``dtype`` so that both sides read the same rows."""
    q, k, *rest = _inputs(seed, seq, **shape)
    rng = np.random.default_rng(seed + 1)

    def raw(t, mean):
        length = mean * np.exp(0.5 * rng.standard_normal(t.shape[:3] + (1,)))
        return (t * jnp.asarray(length, jnp.float32)).astype(dtype).astype(
            jnp.float32)

    return (raw(q, q.shape[-1]), raw(k, q.shape[-1] ** 0.5), *rest)


def _normed_outside(q, k, *rest):
    """The parent's formula: the plain line on each, then the recurrence."""
    return gated_delta_sequential(unit_rows(q, q.shape[-1] ** -0.5),
                                  unit_rows(k), *rest)


def _outputs_and_gradients(fn, args, seed=21):
    """``(o, final, dq, dk, dv, dg, dbeta)`` of a random linear form of both
    outputs, float32."""
    rng = np.random.default_rng(seed)
    co, cs = (jnp.asarray(rng.standard_normal(t.shape), jnp.float32)
              for t in jax.eval_shape(fn, *args))

    def form(*a):
        o, s = fn(*a)
        o = o.astype(jnp.float32)
        return jnp.sum(o * co) + jnp.sum(s * cs), (o, s)

    (_, outs), grads = jax.value_and_grad(
        form, argnums=tuple(range(5)), has_aux=True)(*args)
    return outs + grads


@functools.lru_cache(maxsize=None)
def _normed_case(layout, dtype):
    """One run of each side a case, shared by its seven comparisons."""
    shape, seq = NORMED[layout]
    args = _raw_inputs(22, seq, jnp.dtype(dtype), **shape)
    return (_outputs_and_gradients(functools.partial(
        gated_delta_chunked, chunk=64, dtype=jnp.dtype(dtype), norm_qk=True),
        args), _outputs_and_gradients(_normed_outside, args), args)


@pytest.mark.parametrize("dtype", list(NORMED_TOL))
@pytest.mark.parametrize("layout", list(NORMED))
@pytest.mark.parametrize("what", ("o and final",) + INPUT_NAMES)
def test_kernels_norm_matches_rows_normed_outside(layout, dtype, what):
    """The chunked rule with the kernels' norm against the recurrence fed
    rows normed outside: the outputs, and the gradient of every input, those
    of ``q`` and ``k`` with respect to the raw rows (through the norm: the
    kernels' own ``dt = r (dn - n <dn, n>)`` against autodiff of the plain
    line)."""
    got, want, args = _normed_case(layout, dtype)
    tol_out, tol_grad = NORMED_TOL[dtype]
    if what == "o and final":
        _close(got[0], want[0], tol_out)
        _close(got[1], want[1], tol_out)
        # The recurrence's own ``norm_qk`` is the plain line before it.
        for have, ref in zip(gated_delta_sequential(*args, norm_qk=True),
                             want[:2], strict=True):
            _close(have, ref, 1e-6)
        return
    at = 2 + INPUT_NAMES.index(what)
    assert got[at].shape == args[at - 2].shape
    _close(got[at], want[at], tol_grad)


@pytest.mark.parametrize("dtype", list(NORMED_TOL))
def test_kernels_norm_leaves_padding_rows_zero(dtype):
    """A length the chunk does not divide: the rows the padding adds are
    zero going in and the kernels' norm leaves them zero (``0 *
    rsqrt(eps)``): the rows of ``w``, ``q G`` and ``k G_last / G`` and the
    rows and columns of ``attn`` they own are exactly zero, and every
    cotangent the backward kernel returns is finite, theirs too."""
    shape, seq = NORMED["96 by 192"]
    dtype = jnp.dtype(dtype)
    q, k, v, g, beta = _raw_inputs(23, seq, dtype, **shape)
    chunk, pad = 64, -seq % 64
    assert pad

    def padded(t, lanes=False):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return to_lanes(t.astype(dtype)) if lanes else t

    def chunked(t):
        return padded(t).reshape(1, -1, chunk, t.shape[-1])

    args = (padded(q, True), padded(k, True), padded(v, True),
            jnp.cumsum(chunked(g), axis=2), chunked(beta))
    q_scale = q.shape[-1] ** -0.5
    outs = gated_delta._fwd_call(*args, q_scale=q_scale)
    rows = slice(seq % chunk, None)
    for name, t in zip(("u_own", "w", "attn", "q_in", "k_out"), outs):
        assert not np.any(np.asarray(t[-1, :, :, rows], np.float32)), name
    assert not np.any(np.asarray(outs[2][-1, ..., rows], np.float32))
    rng = np.random.default_rng(24)
    cts = tuple(jnp.asarray(rng.standard_normal(t.shape), jnp.float32)
                .astype(t.dtype) for t in outs)
    grads = gated_delta._bwd_call(*args, *cts, q_scale=q_scale)
    for name, t in zip(INPUT_NAMES, grads):
        assert bool(jnp.all(jnp.isfinite(t.astype(jnp.float32)))), name
    # The whole rule drops them: every gradient has its input's shape.
    whole = _outputs_and_gradients(functools.partial(
        gated_delta_chunked, chunk=chunk, dtype=dtype, norm_qk=True),
        (q, k, v, g, beta))
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in whole)


@pytest.mark.parametrize("zeroed", ["q", "k", "q and k"])
def test_a_zero_row_inside_a_chunk_stays_zero_under_the_kernels_norm(zeroed):
    """A raw row of zeros (a token the convolution silenced) norms to zero
    under ``eps``, not to NaN: the outputs and every gradient are finite and
    the reference's, the zero row's own gradient (``rsqrt(eps)`` times its
    cotangent) included."""
    shape, seq = NORMED["128 by 128"]
    q, k, *rest = _raw_inputs(25, seq, jnp.float32, **shape)
    if "q" in zeroed:
        q = q.at[:, 70].set(0.0)
    if "k" in zeroed:
        k = k.at[:, 9].set(0.0).at[:, 70].set(0.0)
    args = (q, k, *rest)
    got = _outputs_and_gradients(functools.partial(
        gated_delta_chunked, chunk=64, dtype=jnp.float32, norm_qk=True), args)
    want = _outputs_and_gradients(_normed_outside, args)
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in got)
    for have, ref, tol in zip(got, want, (2e-5,) * 2 + (5e-5,) * 5,
                              strict=True):
        _close(have, ref, tol)


@pytest.mark.parametrize("scaled_by", ["the head", "the lanes"])
def test_q_scale_is_the_true_heads_not_the_lanes(monkeypatch, scaled_by):
    """A head of 96 rides 128 lanes; ``q``'s scale under the kernels' norm
    is ``96^-0.5``. The planted fault, the scale read from what the kernels
    carry (``128^-0.5``, 13% low), must miss the reference: this test sees
    it."""
    shape, seq = NORMED["96 by 192"]
    args = _raw_inputs(26, seq, jnp.float32, **shape)
    if scaled_by == "the lanes":
        real = gated_delta._chunk_local
        monkeypatch.setattr(
            gated_delta, "_chunk_local", lambda scale, q, *rest: real(
                q.shape[-1] ** -0.5, q, *rest))
    o, _ = gated_delta_chunked(*args, chunk=64, dtype=jnp.float32,
                               norm_qk=True)
    want, _ = _normed_outside(*args)
    miss = float(jnp.abs(o - want).max() / jnp.abs(want).max())
    if scaled_by == "the head":
        assert miss < 2e-5, miss
    else:
        assert miss > 5e-2, miss

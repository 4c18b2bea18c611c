"""``ops/kda.py``: the chunked form of Kimi delta attention (a delta-rule
state that decays a key channel), its four Pallas kernels in interpret mode,
held to the recurrence one token a step, forward and gradients of every
input.

Tolerances, as ``tests/test_gated_delta.py`` sets them: in float32 the two
differ by the order of sums, 2e-5 of the largest element (seen: 3e-6); with
bfloat16 MXU operands every product's inputs are rounded to 8 bits and ``T``
is rounded once before it is applied, 3e-2 of the largest output (seen:
7e-3) and 2e-2 of a gradient's norm (seen: 7e-3).

The cases take two blocks of chunks and two blocks of heads in the
recurrence's grid, an initial state and a cotangent on the final one, a
length the chunk does not divide, rows normed by the kernels (``norm_qk``)
and by the caller, a gate that sits at its lower bound for a whole chunk
(every exponent the sub-blocks were made for), heads of 128 (the cell's) and
a head that is no lane multiple (interpreted here; compiled it raises by
name). Since PR 68 ``hvd_kda_fwd`` writes the float32 ``T`` and ``hvd_kda_bwd``
reads it: the ``T_CASES`` hold what reaches HBM to ``unit_lower_inverse`` of
the plain ``A``.
"""

import collections
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from horovod_tpu.ops import gated_delta, kda, pallas_util
from horovod_tpu.ops.gated_delta import gated_delta_chunked, unit_rows
from horovod_tpu.ops.kda import kda_chunked, kda_sequential


def _inputs(seed, batch=1, seq=64, heads=2, key_dim=16, width=16,
            lower_bound=-5.0, spread=2.0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((batch, seq, heads, key_dim))
    k = rng.standard_normal((batch, seq, heads, key_dim))
    v = rng.standard_normal((batch, seq, heads, width))
    g = lower_bound / (1 + np.exp(
        -spread * rng.standard_normal((batch, seq, heads, key_dim))))
    beta = 1 / (1 + np.exp(-rng.standard_normal((batch, seq, heads))))
    return tuple(jnp.asarray(t, jnp.float32) for t in (q, k, v, g, beta))


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want)), \
        np.max(np.abs(got - want)) / np.max(np.abs(want))


def _grads(fn, args, weights, **kw):
    def loss(*a):
        o, final = fn(*a, **kw)
        return jnp.sum(o.astype(jnp.float32) * weights[0]) \
            + jnp.sum(final * weights[1])

    return jax.jit(jax.grad(loss, argnums=tuple(range(len(args)))))(*args)


CASES = {
    # (inputs' keywords, chunked's keywords, output tolerance, gradients')
    "float32": (dict(), dict(chunk=32, sub_chunk=8, dtype=jnp.float32),
                2e-5, 2e-5),
    "bfloat16": (dict(), dict(chunk=32, sub_chunk=8), 3e-2, 2e-2),
    "two_blocks_of_chunks_and_heads": (
        dict(seq=160, heads=16, key_dim=8, width=8),
        dict(chunk=16, sub_chunk=8, dtype=jnp.float32), 2e-5, 4e-5),
    "ragged_length": (dict(seq=50), dict(chunk=32, sub_chunk=16,
                                         dtype=jnp.float32), 2e-5, 2e-5),
    "cell_heads_of_128": (dict(seq=64, heads=1, key_dim=128, width=128),
                          dict(chunk=64, sub_chunk=16), 3e-2, 2e-2),
    "no_lane_multiple": (dict(seq=32, heads=3, key_dim=24, width=40),
                         dict(chunk=32, sub_chunk=16, dtype=jnp.float32),
                         2e-5, 2e-5),
    # The Ling cell's 128 chunks a sequence, under decays slow enough that
    # the last chunk still reads the first one's writes: what a fault in the
    # carried state or the running decays would grow with.
    "as_many_chunks_as_the_cell": (
        dict(seq=2048, heads=1, key_dim=8, width=8, lower_bound=-0.02),
        dict(chunk=16, sub_chunk=8, dtype=jnp.float32), 2e-5, 4e-5),
}


@pytest.mark.parametrize("norm_qk", [True, False],
                         ids=["kernel_norm", "caller_norm"])
@pytest.mark.parametrize("case", list(CASES))
def test_chunked_matches_the_recurrence(case, norm_qk):
    """Values, the final state and the gradient of every input (of the raw
    rows under ``norm_qk``), an initial state and its gradient included."""
    shape, how, tol, grad_tol = CASES[case]
    q, k, v, g, beta = _inputs(3, **shape)
    if not norm_qk:
        q, k = unit_rows(q, q.shape[-1] ** -0.5), unit_rows(k)
    rng = np.random.default_rng(11)
    start = jnp.asarray(0.1 * rng.standard_normal(
        k.shape[:1] + k.shape[2:] + v.shape[3:]), jnp.float32)
    want = kda_sequential(q, k, v, g, beta, initial_state=start,
                          norm_qk=norm_qk)
    got = kda_chunked(q, k, v, g, beta, initial_state=start, norm_qk=norm_qk,
                      **how)
    _close(got[0], want[0], tol)
    _close(got[1], want[1], tol)
    weights = [jnp.asarray(rng.standard_normal(t.shape), jnp.float32)
               for t in want]

    def with_start(fn):
        return lambda q, k, v, g, beta, start, **kw: fn(
            q, k, v, g, beta, initial_state=start, **kw)

    args = (q, k, v, g, beta, start)
    d_got = _grads(with_start(kda_chunked), args, weights, norm_qk=norm_qk,
                   **how)
    d_want = _grads(with_start(kda_sequential), args, weights,
                    norm_qk=norm_qk)
    for name, a, b in zip(("q", "k", "v", "g", "beta", "start"), d_got,
                          d_want):
        off = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert off <= grad_tol, (name, off)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_a_gate_at_its_lower_bound_for_a_whole_chunk_stays_finite(dtype):
    """Every ``g`` at -5 over chunks of 64 by sub-blocks of 16: the running
    sum reaches -320 (``exp(320)`` is no float32) and a sub-block's
    exponents 75. Values and gradients are finite and the recurrence's."""
    q, k, v, g, beta = _inputs(5, seq=128, heads=1)
    g = jnp.full_like(g, -5.0)
    o, final = kda_chunked(q, k, v, g, beta, chunk=64, sub_chunk=16,
                           dtype=dtype, norm_qk=True)
    want = kda_sequential(q, k, v, g, beta, norm_qk=True)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(
        jnp.all(jnp.isfinite(final)))
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    _close(o, want[0], tol)
    weights = [jnp.ones_like(t) for t in want]
    for name, a, b in zip(
            "qkvgb",
            _grads(kda_chunked, (q, k, v, g, beta), weights, chunk=64,
                   sub_chunk=16, dtype=dtype, norm_qk=True),
            _grads(kda_sequential, (q, k, v, g, beta), weights,
                   norm_qk=True)):
        assert bool(jnp.all(jnp.isfinite(a))), name
        # A state that forgets all but exp(-5) a token hardly hangs on its
        # decay: that gradient is the difference of a row's and a column's
        # share, and with bfloat16 operands it is their rounding.
        if name != "g" or dtype == jnp.float32:
            _close(a, b, 10 * tol)


def test_one_decay_a_head_is_the_gated_delta_rule():
    """With every channel of a head decaying alike the rule is
    ``ops/gated_delta.py``'s: both chunked forms and both recurrences
    agree."""
    q, k, v, g, beta = _inputs(7, seq=64, heads=2)
    a_head = jnp.mean(g, axis=-1)
    g = jnp.broadcast_to(a_head[..., None], g.shape)
    o, final = kda_chunked(q, k, v, g, beta, chunk=32, sub_chunk=8,
                           dtype=jnp.float32, norm_qk=True)
    o_gdn, final_gdn = gated_delta_chunked(q, k, v, a_head, beta, chunk=32,
                                           dtype=jnp.float32, norm_qk=True)
    _close(o, o_gdn, 2e-5)
    _close(final, final_gdn, 2e-5)


def test_a_bound_the_sub_block_cannot_hold_raises_by_name():
    args = _inputs(0, seq=32)
    with pytest.raises(ValueError, match="sub_chunk or a tighter bound"):
        kda_chunked(*args, chunk=32, sub_chunk=32, lower_bound=-5.0)
    with pytest.raises(ValueError, match="power of two"):
        kda_chunked(*args, chunk=48)
    with pytest.raises(ValueError, match="Kimi delta attention: q, k and g"):
        kda_chunked(args[0], args[1], args[2], args[4], args[4])


def test_compiled_a_head_that_is_no_lane_multiple_raises_by_name(monkeypatch):
    """Interpreted, a head of any size works (the case above); through
    Mosaic the kernels' blocks are whole lane tiles and such a head is
    refused by name before anything is lowered."""
    monkeypatch.setattr(pallas_util, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="hvd_kda_fwd does not tile"):
        jax.eval_shape(lambda *a: kda_chunked(*a, chunk=32), *_inputs(
            0, seq=32, heads=3, key_dim=24, width=40))


def test_the_kernels_names_and_the_trace_record(make_runtime):
    from horovod_tpu.observability import sample_value
    hvd = make_runtime(devices=jax.devices()[:1])
    q, k, v, g, beta = _inputs(1, seq=64, heads=2)
    text = jax.jit(jax.grad(lambda q: jnp.sum(kda_chunked(
        q, k, v, g, beta, chunk=32, sub_chunk=8,
        norm_qk=True)[0].astype(jnp.float32)))).lower(q).as_text(
        debug_info=True)
    for name in (kda.KERNEL_FWD, kda.KERNEL_BWD, kda.KERNEL_REC_FWD,
                 kda.KERNEL_REC_BWD):
        assert name in text, name
    assert sample_value(
        hvd.metrics(), "hvdtpu_spmd_kda_traces_total", heads="2",
        key_dim="16", value_dim="16", chunk="32", sub_chunk="8",
        lower_bound="-5.0") >= 1
    assert kda.SAVED_NAMES == ("kda_scan_operands", "kda_scan_entering")


def _scan_loss(q, k, v, g, beta):
    """The mixer's use of the scan: the output under ``kda_scan_out``."""
    o, final = kda_chunked(q, k, v, g, beta, chunk=32, sub_chunk=8,
                           norm_qk=True)
    o = checkpoint_name(o, "kda_scan_out")
    return jnp.sum(jnp.sin(o.astype(jnp.float32))) + jnp.sum(final)


def test_the_rules_residuals_carry_the_names_a_checkpoint_keeps(equations_of):
    """The five operands, ``T`` under their name (the chunk-local
    kernel's sixth output, PR 68) and the entering states are named inside
    the rule's forward, once each, whether or not a gradient is asked for;
    the output's name is the caller's."""
    def names_in(fn):
        return collections.Counter(
            eqn.params["name"]
            for eqn, _ in equations_of(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "name")

    args = _inputs(2, seq=64, heads=2)
    names = {"kda_scan_operands": 6, "kda_scan_entering": 1}
    assert names_in(lambda *a: kda_chunked(*a, chunk=32,
                                           sub_chunk=8)) == names
    assert names_in(jax.grad(_scan_loss, argnums=tuple(range(5)))) == {
        **names, "kda_scan_out": 1}


@pytest.mark.parametrize("kept", ["the_three_names", "nothing"])
def test_a_checkpoints_gradient_is_the_plain_one_to_the_last_bit(
        kept, equations_of):
    """The backward kernels read the tensors the forward kernels wrote,
    ``T`` among them, where
    the checkpoint keeps them by name and a second run's identical copies
    where it keeps nothing: every input's gradient is the un-checkpointed
    one's either way, bit for bit. With the names kept the recomputed copy
    holds no ``hvd_kda_fwd``."""
    args = _inputs(4, seq=96, heads=2)
    policy = jax.checkpoint_policies.save_only_these_names(
        *((*kda.SAVED_NAMES, "kda_scan_out")
          if kept == "the_three_names" else ()))
    argnums = tuple(range(5))
    want = jax.jit(jax.grad(_scan_loss, argnums))(*args)
    checkpointed = jax.jit(jax.grad(
        jax.checkpoint(_scan_loss, policy=policy), argnums))
    for name, a, b in zip("qkvgb", checkpointed(*args), want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)
    assert sum(
        eqn.primitive.name == "pallas_call"
        and eqn.params["name"] == kda.KERNEL_FWD
        for eqn, _ in equations_of(jax.make_jaxpr(checkpointed)(
            *args).jaxpr)) == (1 if kept == "the_three_names" else 2)


# name -> (``_inputs``' keywords, ``_fwd_call``'s, the kept ``T``'s last two
# axes): the Ling cell's chunk and sub-block at two heads and four chunks a
# grid cell, keys a twentieth apart that hardly decay, and a chunk of 32,
# whose tile is still under the lanes' width.
T_CASES = {
    "cell_chunk": (dict(seq=256), dict(chunk=64, sub=16), (32, 128)),
    "nearly_equal_keys": (dict(seq=128, lower_bound=-1e-3),
                          dict(chunk=64, sub=16), (32, 128)),
    "chunk_of_32": (dict(seq=64), dict(chunk=32, sub=8), (8, 128)),
}


@pytest.mark.parametrize("case", list(T_CASES))
def test_the_t_the_forward_kernel_writes_is_unit_lower_inverse_of_a(case):
    """``hvd_kda_fwd``'s sixth output, read back through the kernels' own
    packing (a ``[64, 64]`` float32 tile kept as ``[32, 128]``: no lane of
    it padding), against the plain inverse of ``A`` made in float64 from
    the same rows (``beta_t <k_t Gamma_t, k_j / Gamma_j>``, no sub-blocks);
    nearly equal keys at ``beta = 1`` make ``A``'s entries 0.99... and
    ``T`` nearly bidiagonal."""
    shape, how, kept_shape = T_CASES[case]
    q, k, v, g, beta = _inputs(5, **shape)
    k = unit_rows(k)
    if case == "nearly_equal_keys":
        rng = np.random.default_rng(6)
        k = unit_rows(jnp.asarray(rng.standard_normal(k.shape[-1]) + 0.05
                                  * rng.standard_normal(k.shape), jnp.float32))
        beta = jnp.ones_like(beta)
    kept = kda._fwd_call(q, k, v, g, beta, q_scale=None, **how)[5]
    batch, seq, heads, _ = k.shape
    chunk = how["chunk"]
    assert kept.dtype == jnp.float32
    assert kept.shape == (seq // chunk, batch, heads) + kept_shape
    by_chunk = (batch, seq // chunk, chunk, heads, -1)
    keys = np.asarray(k, np.float64).reshape(by_chunk)
    cum = np.cumsum(np.asarray(g, np.float64).reshape(by_chunk), axis=2)
    a = np.einsum("bcihk,bcjhk->cbhij", keys * np.exp(cum),
                  keys * np.exp(-cum)) * np.moveaxis(
        np.asarray(beta, np.float64).reshape(by_chunk[:4]), (1, 3),
        (0, 2))[..., None]
    _close(pallas_util.unpack_t(kept, chunk),
           gated_delta.unit_lower_inverse(
               jnp.asarray(np.tril(a, -1), jnp.float32)), 1e-5)


# sha256 of the StableHLO text (no source locations) the gated delta rule's
# gradient lowers to at the two cells' head layouts (Qwen3-Next's whole lane
# tiles, Olmo's 96 x 192 carried on lanes), interpreted kernels included.
# First pinned as the parent of PR 63 lowered it (that PR moved the inverse in
# VMEM to ``pallas_util`` for ``ops/kda.py`` to share, and the scalar form
# stayed the program it was); pinned anew by PR 68, which meant to alter it:
# ``hvd_gdn_fwd`` writes ``T`` and ``hvd_gdn_bwd`` reads it and holds no
# inverse. A change that means to alter it pins these anew.
GDN_LOWERED = {
    (1, 2, 128, 128): "ddab0b16d9e16405188df2183c242fd6"
                      "9d725d7f26164135b40d84830099b5c0",
    (2, 2, 96, 192): "a71f4e99a3bad62f8236d07663e352d7"
                     "90d69b26aa8c42e6782ed07b1261405e",
}


@pytest.mark.parametrize("case", list(GDN_LOWERED),
                         ids=lambda c: "-".join(map(str, c)))
def test_the_scalar_form_lowers_to_the_program_it_lowered_to(case):
    key_heads, heads, key_dim, width = case
    q = jax.ShapeDtypeStruct((1, 128, key_heads, key_dim), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 128, heads, width), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, 128, heads), jnp.float32)

    def loss(q, k, v, g, b):
        o, s = gated_delta_chunked(q, k, v, g, b, norm_qk=True)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(s)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, v, g, g).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == GDN_LOWERED[case]

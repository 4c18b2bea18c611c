"""Flash attention Pallas kernels vs the plain softmax reference.

Runs under interpret mode on the CPU mesh (pallas_call(interpret=True)):
values AND gradients must match ops.attention.default_attention, which
is itself validated against hand math elsewhere. NOTE interpret mode does
not validate Mosaic lowering — on-chip validation happens via the bench
kernel microbench (same policy as the quantize kernels).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.ops.attention import default_attention, repeat_kv_heads
from horovod_tpu.observability import sample_value
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.flash_attention import flash_attention


def _qkv(b, s, h, d, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, dtype) * 0.5 for k in ks)


@pytest.mark.parametrize("s", [128, 256, 384])
def test_matches_dense_forward(s):
    q, k, v = _qkv(2, s, 2, 64)
    out = flash_attention(q, k, v, causal=True)
    ref = default_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_unaligned_seq_pads():
    # 200 is not a multiple of the 128-row padding: causal masking makes the
    # tail padding free.
    q, k, v = _qkv(1, 200, 2, 64, seed=3)
    out = flash_attention(q, k, v, causal=True)
    ref = default_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gradients_match_dense():
    q, k, v = _qkv(1, 256, 2, 64, seed=7)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=True) * w)

    g_flash = jax.jit(jax.grad(lambda *a: loss(flash_attention, *a),
                       argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(lambda *a: loss(default_attention, *a),
                     argnums=(0, 1, 2)))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=5e-4, atol=5e-5,
            err_msg=f"d{name} mismatch")


def test_gradients_match_unaligned():
    q, k, v = _qkv(1, 200, 1, 64, seed=11)

    def s_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v) ** 2)

    def s_ref(q, k, v):
        return jnp.sum(default_attention(q, k, v) ** 2)

    g_flash = jax.jit(jax.grad(s_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(s_ref, argnums=(0, 1, 2)))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-4, atol=5e-5)


def test_bf16_runs():
    q, k, v = _qkv(1, 128, 2, 64, dtype=jnp.bfloat16, seed=13)
    out = flash_attention(q, k, v)
    ref = default_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def _value_and_grads(fn, q, k, v, w, **kw):
    """Output and dQ, dK, dV of ``sum(fn(q, k, v) * w)``, as float32."""
    def loss(q, k, v):
        out = fn(q, k, v, **kw)
        return jnp.sum(out.astype(jnp.float32) * w), out

    (_, out), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True))(q, k, v)
    return [np.asarray(x, np.float32) for x in (out, *grads)]


def _dense_on_repeated_heads(q, k, v, causal):
    h = q.shape[2]
    return default_attention(q, repeat_kv_heads(k, h),
                             repeat_kv_heads(v, h), causal=causal)


# Forced tiles: unequal blocks put the diagonal through a tile's interior,
# make the list of kept tiles one of rectangles, and (256-wide over S=200 ->
# 256) put the padding inside the only block.
TILINGS = [(128, 128), (256, 128), (128, 256), (256, 256)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s", [512, 200])
@pytest.mark.parametrize("blocks", TILINGS, ids=lambda b: f"{b[0]}x{b[1]}")
def test_forced_tilings_match_dense(blocks, s, causal):
    q, k, v = _qkv(1, s, 2, 32, seed=s + blocks[0])
    w = jax.random.normal(jax.random.PRNGKey(21), q.shape) * 0.1
    got = _value_and_grads(flash_attention, q, k, v, w, causal=causal,
                           _blocks=blocks)
    want = _value_and_grads(default_attention, q, k, v, w, causal=causal)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5,
                               err_msg="forward")
    for g, r, name in zip(got[1:], want[1:], "qkv"):
        np.testing.assert_allclose(g, r, rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name}")


def test_forced_tiling_must_divide_the_padded_length():
    q, k, v = _qkv(1, 384, 1, 16)
    with pytest.raises(ValueError, match="divide the padded length"):
        flash_attention(q, k, v, _blocks=(256, 128))


@pytest.mark.parametrize("causal", [True, False])
def test_gqa_gradients_at_kv_heads(causal):
    """H=8 over Hkv=2: dK and dV come back at 2 heads, each the sum over
    its group of four, equal to dense attention on repeated heads (whose
    repeat's transpose makes that sum)."""
    q, _, _ = _qkv(1, 256, 8, 32, seed=23)
    _, k, v = _qkv(1, 256, 2, 32, seed=29)
    w = jax.random.normal(jax.random.PRNGKey(31), q.shape) * 0.1

    got = _value_and_grads(flash_attention, q, k, v, w, causal=causal,
                           _blocks=(128, 128))
    want = _value_and_grads(_dense_on_repeated_heads, q, k, v, w,
                            causal=causal)
    assert got[2].shape == k.shape and got[3].shape == v.shape
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for g, r, name in zip(got[1:], want[1:], "qkv"):
        np.testing.assert_allclose(g, r, rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name}")


def test_gqa_rejects_indivisible_heads():
    q, _, _ = _qkv(1, 128, 4, 16)
    _, k, v = _qkv(1, 128, 3, 16)
    with pytest.raises(ValueError, match="not a multiple"):
        flash_attention(q, k, v)


# bfloat16 keeps 8 bits of mantissa: a value rounded to it is off by at most
# 2**-8 of its size. Flash and dense both round q·k's operands, p (ds) and
# each result once, in different places, and both accumulate in float32, so
# an element may differ by a few roundings of the largest element.
BF16_EPS = 2.0 ** -8


@pytest.mark.parametrize("causal", [True, False])
def test_bf16_matches_dense_in_bf16(causal):
    q, k, v = _qkv(1, 256, 4, 64, dtype=jnp.bfloat16, seed=37)
    k, v = k[:, :, :2], v[:, :, :2]
    w = jax.random.normal(jax.random.PRNGKey(41), q.shape) * 0.1

    got = _value_and_grads(flash_attention, q, k, v, w, causal=causal)
    want = _value_and_grads(_dense_on_repeated_heads, q, k, v, w,
                            causal=causal)
    exact = _value_and_grads(
        _dense_on_repeated_heads,
        *(x.astype(jnp.float32) for x in (q, k, v)), w, causal=causal)
    for g, r, e, name in zip(got, want, exact, ("out", "dq", "dk", "dv")):
        tol = 4 * BF16_EPS * np.abs(e).max()
        np.testing.assert_allclose(g, r, rtol=0, atol=tol, err_msg=name)
        # ... and no further from the float32 answer than dense bf16 is,
        # within the same allowance.
        assert np.abs(g - e).max() <= np.abs(r - e).max() + tol, name


# ---- the entry a call takes (PR 70) ------------------------------------------

def _layouts_traced():
    """``{(layout, head_dim, batch)}`` of the calls traced so far."""
    fam = hvd.metrics().get("hvdtpu_spmd_flash_layout_traces_total",
                            {"samples": []})
    return {(labels["layout"], int(labels["head_dim"]), int(labels["batch"]))
            for _, labels, _ in fam["samples"]}


# batch, H, Hkv, D, Dv, window, how the caller asks for the operands
# (``heads_major``: rank4, ``[B, H, S, D]``; the default: rank3, merged to
# ``[B*H, S, D]``). The kernels take either at any batch, grouping and width:
# the benchmark cells' 32:4, 28:4, 16:16 and 24:2 heads of 128, 16:2 of 256,
# and the widths no cell takes at rank 4 yet (64, 192 beside 128, 64 beside
# 128), at batches of one, two and four.
ENTRY_CASES = [
    (1, 32, 4, 128, 128, None, "rank3"),
    (1, 32, 4, 128, 128, None, "rank4"),
    (2, 32, 4, 128, 128, None, "rank4"),
    (4, 28, 4, 128, 128, None, "rank4"),
    (2, 32, 4, 128, 128, 100, "rank4"),
    (2, 16, 16, 128, 128, None, "rank4"),
    (2, 24, 2, 128, 128, None, "rank3"),
    (2, 24, 2, 128, 128, None, "rank4"),
    (1, 4, 4, 256, 256, None, "rank3"),
    (2, 8, 4, 256, 256, 150, "rank4"),
    (4, 16, 2, 256, 256, None, "rank3"),
    (4, 16, 2, 256, 256, None, "rank4"),
    (2, 4, 4, 256, 128, None, "rank4"),
    (1, 8, 2, 64, 64, None, "rank3"),
    (2, 8, 2, 64, 64, 100, "rank3"),
    (2, 8, 2, 64, 64, 100, "rank4"),
    (4, 4, 4, 64, 64, None, "rank3"),
    (2, 2, 2, 192, 128, None, "rank3"),
    (2, 2, 2, 192, 128, None, "rank4"),
    (1, 2, 1, 192, 128, 100, "rank3"),
    (2, 4, 2, 64, 128, None, "rank3"),
    (2, 2, 1, 128, 64, None, "rank4"),
]


@pytest.mark.parametrize(
    "batch, h, hkv, dk, dv, window, layout", ENTRY_CASES,
    ids=lambda x: str(x))
def test_either_entry_matches_dense(make_runtime, batch, h, hkv, dk, dv,
                                    window, layout):
    """The output and all three gradients against ``default_attention`` on
    repeated heads through the entry the caller asks for, at batches of
    one, two and four (200 rows: padded to 256, two tiles a side), and the
    counter says which entry it was."""
    make_runtime(devices=jax.devices()[:1])
    s = 200
    ks = jax.random.split(jax.random.PRNGKey(batch + h + dk), 4)
    q = jax.random.normal(ks[0], (batch, s, h, dk)) * 0.5
    k = jax.random.normal(ks[1], (batch, s, hkv, dk)) * 0.5
    v = jax.random.normal(ks[2], (batch, s, hkv, dv)) * 0.5
    w = jax.random.normal(ks[3], (batch, s, h, dv)) * 0.1

    def dense(q, k, v):
        return default_attention(q, repeat_kv_heads(k, h),
                                 repeat_kv_heads(v, h), causal=True,
                                 window=window)

    got = _value_and_grads(flash_attention, q, k, v, w, window=window,
                           heads_major=layout == "rank4", _blocks=(128, 128))
    want = _value_and_grads(dense, q, k, v, w)
    assert _layouts_traced() == {(layout, dk, batch)}
    assert [g.shape for g in got] == [w.shape, q.shape, k.shape, v.shape]
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for g, r, name in zip(got[1:], want[1:], "qkv"):
        np.testing.assert_allclose(g, r, rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("batch", [1, 2, 4])
def test_the_pair_of_backward_kernels_through_either_entry(monkeypatch, batch):
    """A sequence too long for the one backward kernel keeps the pair, and
    the pair takes rank-4 operands (a batch of two and of four) as the one
    kernel does: dK and dV bit for bit, dQ to float32 rounding, at 12:4
    heads of 128 under a band."""
    q, _, _ = _qkv(batch, 384, 12, 128, seed=71)
    _, k, v = _qkv(batch, 384, 4, 128, seed=73)
    w = jax.random.normal(jax.random.PRNGKey(79), q.shape) * 0.1
    kw = dict(window=150, heads_major=batch > 1, _blocks=(128, 128))
    dq, dk, dv = _grads(q, k, v, w, **kw)
    monkeypatch.setattr(fa, "backward_is_fused", lambda *a: False)
    dq_pair, dk_pair, dv_pair = _grads(q, k, v, w, **kw)
    np.testing.assert_array_equal(dk, dk_pair)
    np.testing.assert_array_equal(dv, dv_pair)
    np.testing.assert_allclose(dq, dq_pair, rtol=1e-5,
                               atol=1e-6 * np.abs(dq_pair).max())


def test_the_entry_holds_a_transpose_and_no_merge_where_asked():
    """What ``heads_major`` is for: the traced call holds no reshape of an
    operand, at any width (XLA's layout assignment crosses a transpose and
    not a merge of two axes: PERF.md, Findings, PR 70); by default the
    operands are merged as before."""
    def reshapes(d, heads_major):
        q = jax.ShapeDtypeStruct((2, 256, 4, d), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(lambda q: jax.grad(lambda q: jnp.sum(
            flash_attention(q, q, q, heads_major=heads_major).astype(
                jnp.float32)))(q))(q)
        return [eqn for eqn in jaxpr.eqns if eqn.primitive.name == "reshape"
                and eqn.invars[0].aval.dtype == jnp.bfloat16]

    for d in (64, 128, 256):
        assert not reshapes(d, True) and len(reshapes(d, False)) >= 4


# ---- the block table (a pure function) --------------------------------------

KERNELS = (fa.KERNEL_FWD, fa.KERNEL_DKDV, fa.KERNEL_DQ)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("d", [16, 64, 128])
@pytest.mark.parametrize("s", [128, 200, 384, 512, 4096])
def test_block_table_divides_and_fits(s, d, dtype):
    s_pad = s + (-s) % 128
    for kernel in KERNELS:
        for causal in (True, False):
            bq, bk = fa.block_sizes(kernel, s_pad, d, dtype, causal)
            assert bq % 128 == 0 and bk % 128 == 0
            assert s_pad % bq == 0 and s_pad % bk == 0, (kernel, bq, bk)
            assert fa.vmem_estimate(kernel, bq, bk, d,
                                    jnp.dtype(dtype).itemsize) \
                <= fa.VMEM_LIMIT_BYTES
            assert (bq, bk) == fa.block_sizes(kernel, s_pad, d, dtype,
                                              causal)


def test_block_table_follows_the_sequence():
    """The two benchmark cells get different tiles: the long one the
    largest candidate, the short one its whole sequence; a length only 128
    divides keeps 128."""
    for kernel in KERNELS:
        long = fa.block_sizes(kernel, 4096, 128, jnp.bfloat16, True)
        short = fa.block_sizes(kernel, 512, 128, jnp.bfloat16, True)
        assert long == (1024, 1024) and short == (512, 512), (kernel, long,
                                                              short)
        assert fa.block_sizes(kernel, 384, 64, jnp.float32, False) \
            == (128, 128)


def test_block_table_shrinks_to_the_vmem_limit(monkeypatch):
    monkeypatch.setattr(fa, "VMEM_LIMIT_BYTES", 4 * 1024 * 1024)
    for kernel in KERNELS:
        bq, bk = fa.block_sizes(kernel, 4096, 128, jnp.float32, True)
        assert fa.vmem_estimate(kernel, bq, bk, 128, 4) <= 4 * 1024 * 1024
        assert 4096 % bq == 0 and 4096 % bk == 0


def test_metrics_name_the_tiling(make_runtime):
    make_runtime(devices=jax.devices()[:1])
    q, _, _ = _qkv(1, 256, 4, 16, dtype=jnp.bfloat16)
    _, k, v = _qkv(1, 256, 2, 16, dtype=jnp.bfloat16)
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, _blocks=(128, 256)).astype(jnp.float32)))(q)
    fams = hvd.metrics()
    fam = fams["hvdtpu_spmd_flash_kernel_traces_total"]
    assert fam["type"] == "counter"
    # The backward pass is one kernel under the dKdV kernel's name, and the
    # entry says that it made dQ too.
    for kernel, dq in ((fa.KERNEL_FWD, "none"), (fa.KERNEL_DKDV, "fused")):
        labels = {"kernel": kernel, "block_q": "128", "block_k": "256",
                  "operand_dtype": "bfloat16", "kv_group": "2", "dq": dq}
        assert sample_value(
            fams, "hvdtpu_spmd_flash_kernel_traces_total", **labels) >= 1, \
            (kernel, fam["samples"])
    assert sample_value(fams, "hvdtpu_spmd_flash_kernel_traces_total",
                        kernel=fa.KERNEL_DQ) is None, fam["samples"]


# ---- the backward pass as one kernel ----------------------------------------

def _grads(q, k, v, w, **kw):
    """dQ, dK, dV of ``sum(flash_attention(q, k, v) * w)``, as float32."""
    return _value_and_grads(flash_attention, q, k, v, w, **kw)[1:]


# causal, window, the sequence (200 pads to 256: the bidirectional call's
# padded key columns are masked, and its padded query rows are real rows of
# the dKdV sums that must add nothing).
MODES = {"causal": (True, None, 256), "window": (True, 100, 256),
         "bidirectional_padded": (False, None, 200)}


F32, BF16 = jnp.float32, jnp.bfloat16
# Query heads a K/V head, the key and value widths, the mask, the dtype:
# every group, pair of widths and mask in both dtypes, and each group under
# each mask.
FUSED_CASES = [
    (1, 32, 32, "causal", F32), (1, 192, 128, "causal", F32),
    (1, 192, 128, "window", BF16), (1, 32, 32, "bidirectional_padded", BF16),
    (4, 192, 128, "causal", BF16), (4, 32, 32, "window", F32),
    (4, 192, 128, "bidirectional_padded", F32),
    (12, 32, 32, "causal", BF16), (12, 192, 128, "window", F32),
    (12, 192, 128, "bidirectional_padded", BF16),
    # 28:4 heads of 128: a group that is no power of two.
    (7, 128, 128, "causal", BF16), (7, 128, 128, "window", F32),
]


@pytest.mark.parametrize(
    "group, dk, dv, mode, dtype", FUSED_CASES,
    ids=lambda x: getattr(x, "__name__", None) or str(x))
def test_fused_backward_matches_dense(group, dk, dv, mode, dtype):
    """dQ, dK and dV of the one backward kernel against the dense
    reference's on repeated heads, at tiles that are not square (the
    diagonal through a tile's interior, a K/V head's sums indexed by a k
    block that is not a q block), over a whole group of query heads."""
    causal, window, s = MODES[mode]
    blocks = (128, 256) if group == 4 else (256, 128)
    q, k, v = _qkv_two_widths(s, group, 1, dk, dv, seed=group + dk,
                              dtype=dtype)
    w = jax.random.normal(jax.random.PRNGKey(47), (1, s, group, dv)) * 0.1

    def dense(q, k, v):
        return default_attention(q, repeat_kv_heads(k, group),
                                 repeat_kv_heads(v, group), causal=causal,
                                 window=window)

    assert fa.backward_is_fused(*blocks, 256, dk, dtype, dv)
    got = _grads(q, k, v, w, causal=causal, window=window, _blocks=blocks)
    want = _value_and_grads(dense, q, k, v, w)[1:]
    if dtype == jnp.float32:
        for g, r, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(g, r, rtol=5e-4, atol=5e-5,
                                       err_msg=f"d{name}")
        return
    exact = _value_and_grads(
        dense, *(x.astype(jnp.float32) for x in (q, k, v)), w)[1:]
    for g, r, e, name in zip(got, want, exact, "qkv"):
        tol = 4 * BF16_EPS * np.abs(e).max()
        np.testing.assert_allclose(g, r, rtol=0, atol=tol,
                                   err_msg=f"d{name}")
        assert np.abs(g - e).max() <= np.abs(r - e).max() + tol, name


@pytest.mark.parametrize("mode", ["causal", "window"])
def test_a_group_of_seven_matches_dense_forward(mode):
    """28:4 heads of 128 (seven query heads a K/V head) under the causal
    mask and under a band, two sequences: the forward kernel's outputs."""
    causal, window, s = MODES[mode]
    q, k, v = _qkv_two_widths(s, 28, 4, 128, 128, seed=7)
    q, k, v = (jnp.concatenate([x, 0.5 * x[:, ::-1]]) for x in (q, k, v))
    got = flash_attention(q, k, v, causal=causal, window=window,
                          _blocks=(128, 128))
    want = default_attention(q, repeat_kv_heads(k, 28),
                             repeat_kv_heads(v, 28), causal=causal,
                             window=window)
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("mode, blocks, dtype", [
    *((mode, blocks, (F32, BF16)[(i + j) % 2])
      for i, mode in enumerate(MODES)
      for j, blocks in enumerate([(128, 256), (256, 128)])),
    ("causal", None, BF16)],
    ids=lambda x: getattr(x, "__name__", None) or str(x))
def test_fused_backward_equals_the_pair_at_equal_tiles(monkeypatch, mode,
                                                       blocks, dtype):
    """One score tile where the pair made two: dK and dV are the same
    float32 sums in the same order, bit for bit; dQ's product contracts the
    tile's other axis and agrees to float32 rounding (in bfloat16 that may
    turn the one rounding of the result: a last place of its own). Tiles not
    square either way under each mask, and the table's."""
    causal, window, s = MODES[mode]
    s = s + 256                  # two k blocks a row at the widest tile
    q, k, v = _qkv_two_widths(s, 4, 2, 64, 32, seed=s, dtype=dtype)
    w = jax.random.normal(jax.random.PRNGKey(53), (1, s, 4, 32)) * 0.1
    kw = dict(causal=causal, window=window, _blocks=blocks)
    dq, dk, dv = _grads(q, k, v, w, **kw)
    # The backward pass as a dKdV and a dQ kernel whatever the shape.
    monkeypatch.setattr(fa, "backward_is_fused", lambda *a: False)
    dq_pair, dk_pair, dv_pair = _grads(q, k, v, w, **kw)
    np.testing.assert_array_equal(dk, dk_pair)
    np.testing.assert_array_equal(dv, dv_pair)
    np.testing.assert_allclose(
        dq, dq_pair, atol=1e-6 * np.abs(dq_pair).max(),
        rtol=1e-5 if dtype == jnp.float32 else 2 * BF16_EPS)


# The mask, the sequence, the tile: four tiles a side or more, so that a
# grid's one axis runs over rows of several tiles whose first and last steps
# differ, over tiles the mask drops (six above the diagonal; under the band
# of 150 three below it, whole), and over the whole rectangle of a
# bidirectional call whose last tile holds padding.
KEPT_TILE_GRIDS = {
    "causal": (True, None, 512, (128, 128)),
    "band_drops_whole_tiles": (True, 150, 512, (128, 128)),
    "band_not_square": (True, 150, 1024, (256, 128)),
    "bidirectional_padded": (False, None, 500, (128, 128)),
}


@pytest.mark.parametrize("backward", ["fused", "pair"])
@pytest.mark.parametrize("mode", list(KEPT_TILE_GRIDS))
def test_kept_tile_grids_match_dense(monkeypatch, mode, backward):
    """The forward output and all three gradients against
    ``default_attention`` on repeated heads where the grid walks a list of
    kept tiles several rows long, for the one backward kernel and (forced)
    the pair of dKdV, whose list runs column by column under each query
    head of a group of two, and dQ."""
    causal, window, s, blocks = KEPT_TILE_GRIDS[mode]
    if backward == "pair":
        monkeypatch.setattr(fa, "backward_is_fused", lambda *a: False)
    q, k, v = _qkv_two_widths(s, 4, 2, 32, 32, seed=len(mode))
    w = jax.random.normal(jax.random.PRNGKey(67), q.shape) * 0.1

    def dense(q, k, v):
        return default_attention(q, repeat_kv_heads(k, 4),
                                 repeat_kv_heads(v, 4), causal=causal,
                                 window=window)

    got = _value_and_grads(flash_attention, q, k, v, w, causal=causal,
                           window=window, _blocks=blocks)
    want = _value_and_grads(dense, q, k, v, w)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5,
                               err_msg="forward")
    for g, r, name in zip(got[1:], want[1:], "qkv"):
        np.testing.assert_allclose(g, r, rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name}")


def test_backward_over_the_vmem_limit_takes_the_pair_and_says_so(
        monkeypatch, make_runtime):
    """A sequence whose dK and dV sums do not fit the VMEM a call asks for
    beside the tile keeps a kernel each for dKdV and dQ (here the limit is
    brought down to the sequence), the counter says which, and the
    gradients are the dense reference's either way."""
    make_runtime(devices=jax.devices()[:1])
    s, limit = 512, 3 * 1024 * 1024
    monkeypatch.setattr(fa, "VMEM_LIMIT_BYTES", limit)
    tile = fa.block_sizes(fa.KERNEL_DKDV, s, 32, jnp.float32, True)
    assert tile == fa.block_sizes(fa.KERNEL_DKDV, 256, 32, jnp.float32, True)
    assert fa.vmem_estimate(fa.KERNEL_DKDV, *tile, 32, 4) <= limit
    assert not fa.backward_is_fused(*tile, s, 32, jnp.float32)
    assert fa.backward_is_fused(*tile, 256, 32, jnp.float32)

    def traced():
        return {(labels["kernel"], labels["dq"]) for _, labels, _ in
                hvd.metrics()["hvdtpu_spmd_flash_kernel_traces_total"]
                ["samples"]}

    for length, backward in (
            (s, {(fa.KERNEL_DKDV, "own"), (fa.KERNEL_DQ, "own")}),
            (256, {(fa.KERNEL_DKDV, "fused")})):
        q, k, v = _qkv(1, length, 2, 32, seed=59)
        k, v = k[:, :, :1], v[:, :, :1]
        w = jax.random.normal(jax.random.PRNGKey(61), q.shape) * 0.1
        before = traced()
        got = _grads(q, k, v, w)
        assert traced() - before - {(fa.KERNEL_FWD, "none")} == backward
        want = _value_and_grads(_dense_on_repeated_heads, q, k, v, w,
                                causal=True)[1:]
        for g, r, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(g, r, rtol=5e-4, atol=5e-5,
                                       err_msg=f"d{name}")


@pytest.mark.parametrize("s, d, dv, fused", [
    (512, 128, 128, True), (4096, 128, 128, True), (4096, 64, 64, True),
    (4096, 256, 256, True), (8192, 128, 128, True), (8192, 192, 128, True),
    (16384, 128, 128, True), (32768, 128, 128, False),
    (16384, 256, 256, False)])
def test_which_lengths_take_the_fused_backward(s, d, dv, fused):
    """Every benchmark cell's shape does (the longest at the widest head:
    8192 rows of 192 beside 128); past 16k rows at heads of 128 a head's dK
    and dV no longer fit, and the estimate counts them, the float32 sums
    and, twice, the blocks they leave through, on top of at most what a dQ
    kernel's step holds."""
    tile = fa.block_sizes(fa.KERNEL_DKDV, s, d, jnp.bfloat16, True, dv)
    assert fa.backward_is_fused(*tile, s, d, jnp.bfloat16, dv) == fused
    whole = s * (-(-d // 128) + -(-dv // 128)) * 128 * (4 + 2 * 2)
    estimate = fa.vmem_estimate(fa.KERNEL_DKDV, *tile, d, 2, dv,
                                fused_rows=s)
    assert whole <= estimate \
        <= whole + fa.vmem_estimate(fa.KERNEL_DQ, *tile, d, 2, dv)


def test_grouped_query_attention():
    """Hkv < H (GQA): K/V heads tile up to the query head count, matching
    dense attention on the explicitly repeated heads."""
    q, _, _ = _qkv(1, 128, 4, 64, seed=17)
    kk = jax.random.split(jax.random.PRNGKey(19), 2)
    k = jax.random.normal(kk[0], (1, 128, 2, 64)) * 0.5
    v = jax.random.normal(kk[1], (1, 128, 2, 64)) * 0.5
    out = flash_attention(q, k, v, causal=True)
    kr = jnp.repeat(k, 2, axis=2)
    vr = jnp.repeat(v, 2, axis=2)
    ref = default_attention(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s", [128, 256])
def test_non_causal_matches_dense_forward(s):
    q, k, v = _qkv(2, s, 2, 64, seed=5)
    out = flash_attention(q, k, v, causal=False)
    ref = default_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_non_causal_unaligned_seq_masks_padding():
    # 200 pads to 256: without the key-axis padding mask every query would
    # attend the zero-filled tail (zero logits still win softmax weight).
    q, k, v = _qkv(1, 200, 2, 64, seed=6)
    out = flash_attention(q, k, v, causal=False)
    ref = default_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("s", [256, 200])  # 200: padded rows in the bwd too
def test_non_causal_gradients_match_dense(s):
    q, k, v = _qkv(1, s, 2, 32, seed=7)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape) * 0.1

    def loss(fn):
        def inner(a, b, c):
            return jnp.sum(fn(a, b, c, causal=False) * w)
        return jax.jit(jax.grad(inner, argnums=(0, 1, 2)))(q, k, v)

    g_flash = loss(flash_attention)
    g_ref = loss(default_attention)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)


# ---- a value head of another width than a query/key head --------------------

# (dk, dv, H, Hkv, window, causal): latent attention's 192 beside 128 (one
# and a half lane tiles), heads smaller than a tile, grouped-query heads and
# a band among them.
TWO_WIDTHS = [
    (192, 128, 1, 1, None, True),
    (192, 128, 2, 1, 96, True),
    (64, 32, 2, 1, None, False),
    (32, 64, 1, 1, 100, True),
]


def _qkv_two_widths(s, h, hkv, dk, dv, seed, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, s, h, dk), dtype) * 0.5,
            jax.random.normal(ks[1], (1, s, hkv, dk), dtype) * 0.5,
            jax.random.normal(ks[2], (1, s, hkv, dv), dtype) * 0.5)


@pytest.mark.parametrize("dk, dv, h, hkv, window, causal", TWO_WIDTHS)
def test_two_widths_match_dense(dk, dv, h, hkv, window, causal):
    """Scores over ``dk`` dimensions (scaled by one over its root), values of
    ``dv``: the output and dV are ``dv`` wide, dQ and dK ``dk``, all equal
    to the dense reference on repeated heads."""
    s = 200          # padded to 256: two tiles a side, the last one short
    q, k, v = _qkv_two_widths(s, h, hkv, dk, dv, seed=dk + dv)
    w = jax.random.normal(jax.random.PRNGKey(43), (1, s, h, dv)) * 0.1

    def dense(q, k, v):
        return default_attention(q, repeat_kv_heads(k, h),
                                 repeat_kv_heads(v, h), causal=causal,
                                 window=window)

    got = _value_and_grads(flash_attention, q, k, v, w, causal=causal,
                           window=window, _blocks=(128, 128))
    want = _value_and_grads(dense, q, k, v, w)
    assert got[0].shape == (1, s, h, dv)
    assert [g.shape for g in got[1:]] == [q.shape, k.shape, v.shape]
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)
    for g, r, name in zip(got[1:], want[1:], "qkv"):
        np.testing.assert_allclose(g, r, rtol=5e-4, atol=5e-5,
                                   err_msg=f"d{name}")


def test_dense_reference_takes_a_value_head_of_another_width():
    """``default_attention`` scales by one over the root of the query's
    width and multiplies by a ``v`` of any width: by hand on two tokens."""
    q = jnp.asarray([[[[1.0, 0.0, 2.0]], [[0.0, 3.0, 0.0]]]])    # [1,2,1,3]
    k = jnp.asarray([[[[1.0, 1.0, 0.0]], [[0.0, 1.0, 1.0]]]])
    v = jnp.asarray([[[[1.0, 2.0]], [[3.0, 5.0]]]])              # [1,2,1,2]
    out = np.asarray(default_attention(q, k, v, causal=True))
    p = np.exp(np.asarray([3.0, 3.0]) / np.sqrt(3.0))
    p = p / p.sum()
    np.testing.assert_allclose(out[0, 0, 0], [1.0, 2.0], rtol=1e-6)
    np.testing.assert_allclose(out[0, 1, 0],
                               p[0] * np.asarray([1.0, 2.0])
                               + p[1] * np.asarray([3.0, 5.0]), rtol=1e-6)


def test_a_key_head_must_be_as_wide_as_a_query_head():
    q, k, v = _qkv_two_widths(128, 2, 2, 64, 32, seed=3)
    with pytest.raises(ValueError, match="as wide as a query head"):
        flash_attention(q, v, v)


@pytest.mark.parametrize("kernel", KERNELS)
def test_block_table_counts_both_widths(kernel):
    """Equal widths said twice are the one-width table; a wider value head
    costs VMEM as a wider head does, each width as whole lane tiles."""
    for d in (64, 128, 256):
        assert fa.vmem_estimate(kernel, 512, 512, d, 2, d) \
            == fa.vmem_estimate(kernel, 512, 512, d, 2)
        assert fa.block_sizes(kernel, 4096, d, jnp.bfloat16, True, d) \
            == fa.block_sizes(kernel, 4096, d, jnp.bfloat16, True)
    both = fa.vmem_estimate(kernel, 512, 512, 192, 2, 128)
    assert fa.vmem_estimate(kernel, 512, 512, 128, 2) < both \
        < fa.vmem_estimate(kernel, 512, 512, 256, 2)
    assert both == fa.vmem_estimate(kernel, 512, 512, 256, 2, 100)
    assert fa.block_sizes(kernel, 8192, 192, jnp.bfloat16, True, 128) \
        == (1024, 1024)


# sha256 of the StableHLO text (no source locations) that a call with one
# head width lowers to, interpreted kernels included, by (batch, H, Hkv, D,
# window, dtype). Pinned anew by PR 61, which meant to change what such a
# call traces to: every kernel's grid is the mask's kept tiles, read from a
# scalar-prefetch table, where it was the whole rectangle of blocks. (Before
# it ``forward`` had stood since before a value head could have a width of
# its own, PR 49: 36607613...842723 and d40641ff...07d496; ``gradient`` since
# the backward pass became one kernel, PR 52: 130bc696...8fe30c and
# e92ada63...55bcbf.) **PR 70 gave the entry a second form, which a caller
# asks for** (``heads_major``: the operands reach the kernels as
# ``[B, H, S, D]`` and are not merged to ``[B*H, S, D]``), **and meant to
# change nothing else**. So the last case, the one with a seventh field, is
# PR 70's own, and the six before it (one sequence at heads of 64 and of 128
# as PR 61 pinned them; two at heads of 64, of 192, at 8:2 and 8:4 heads of
# 128, new here) are what the parent commit lowers them to, computed there:
# a call that does not ask lowers to the parent's program to the hash. A
# change that means to alter what such a call traces to pins these anew.
LOWERED = {
    (1, 4, 2, 64, None, "bfloat16"): dict(
        forward="3c95cf8de0618e21e09d957f0a74a8ed"
                "cb6e4e5cbb3a34ca5dc5f0ef1a39d2cc",
        gradient="bff3e06a472781c110c44f20367b1887"
                 "88c29c879518817a185856f3162be42e"),
    (1, 2, 2, 128, 96, "float32"): dict(
        forward="e3e550643e36aa04303183f911c13827"
                "2587636e355acd0f3d9e9221723ecde1",
        gradient="8ad34bdc9babdc7b98e7189e39c9cc9c"
                 "b4fadc89b8e4e965aa42892f0de3a0b9"),
    (2, 8, 2, 64, None, "bfloat16"): dict(
        forward="08b80b81a5665560e5cb1f0da685dcb7"
                "fd96d406aa116225b0da6209431ab0e9",
        gradient="f184472aeaac19760e808507dd2dec1b"
                 "27ba4d17eb6a55776f1aec39f9489611"),
    (2, 2, 2, 192, 100, "bfloat16"): dict(
        forward="4923aa1d3ab8e7db9be409a44f1db587"
                "877dce7dda07f98b12296e20da8ed00a",
        gradient="8bac6d0637c13ae7e44b00d86db64310"
                 "8ef9b3f4c3152455ce3d4939d6c0fc0c"),
    (2, 8, 2, 128, None, "bfloat16"): dict(
        forward="dc6a22c023683fa3301fd21e68e01008"
                "b997e0aa5cfe943b64f22c76bc9d443b",
        gradient="7f75fbc8f2513a550615f49c4b7f650f"
                 "af9c808240eebaf55a600b873285a1c2"),
    (2, 8, 4, 128, None, "bfloat16"): dict(
        forward="08bb41aaf2e2f21b8a9e18387fd65897"
                "cea72f1ac543a344148347408fbf8469",
        gradient="546db422382acf56bc8d24c6ee2d18d6"
                 "5d3c97e87a2b1eaf249e7becba8f6cf8"),
    (2, 8, 4, 128, None, "bfloat16", "heads_major"): dict(
        forward="218a23855a6e712c13402bd85b53a6f9"
                "707b6c7bf276eec447542bbdba8ef749",
        gradient="2edbe3703ca74f8187c5ded4aed0c589"
                 "a4816851578dc9f3a5079548c2013843"),
}


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("case", list(LOWERED),
                         ids=lambda c: "-".join(map(str, c)))
def test_equal_widths_lower_to_the_program_they_lowered_to(case, what):
    import hashlib
    b, h, hkv, d, window, dtype, *asked = case
    q = jax.ShapeDtypeStruct((b, 256, h, d), jnp.dtype(dtype))
    k = jax.ShapeDtypeStruct((b, 256, hkv, d), jnp.dtype(dtype))

    def out(q, k, v):
        return flash_attention(q, k, v, window=window,
                               heads_major=bool(asked))

    def loss(q, k, v):
        return jnp.sum(out(q, k, v).astype(jnp.float32))

    fn = out if what == "forward" else jax.grad(loss, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(q, k, k).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[case][what]

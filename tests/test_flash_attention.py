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

    g_flash = jax.grad(lambda *a: loss(flash_attention, *a),
                       argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: loss(default_attention, *a),
                     argnums=(0, 1, 2))(q, k, v)
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

    g_flash = jax.grad(s_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(s_ref, argnums=(0, 1, 2))(q, k, v)
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

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return [np.asarray(x, np.float32) for x in (out, *grads)]


def _dense_on_repeated_heads(q, k, v, causal):
    h = q.shape[2]
    return default_attention(q, repeat_kv_heads(k, h),
                             repeat_kv_heads(v, h), causal=causal)


# Forced tiles: unequal blocks put the diagonal through a tile's interior,
# make the causal skip and the clamped index maps work on rectangles, and
# (256-wide over S=200 -> 256) put the padding inside the only block.
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
    for kernel in KERNELS:
        labels = {"kernel": kernel, "block_q": "128", "block_k": "256",
                  "operand_dtype": "bfloat16", "kv_group": "2"}
        assert sample_value(
            fams, "hvdtpu_spmd_flash_kernel_traces_total", **labels) >= 1, \
            (kernel, fam["samples"])


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
        return jax.grad(inner, argnums=(0, 1, 2))(q, k, v)

    g_flash = loss(flash_attention)
    g_ref = loss(default_attention)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)

"""The flash kernels, the state-space scan's, the gated delta rule's and Kimi
delta attention's, through the real Mosaic compiler, without a chip.

Interpret mode (every other flash test) says nothing about Mosaic lowering:
block shapes, VMEM, layouts. The TPU compiler is installed in the sandbox and
compiles for a chip that is described and not attached, so the kernels of the
benchmark's two GPT cells (and the small shapes ``chip_smoke.py`` runs) are
compiled here at their real sizes with the tiles the block table gives them,
their grids one axis over the mask's kept tiles, read from a scalar-prefetch
table (since PR 61). Nothing runs; a compile that passes is not a chip run.

All such tests live in this one file: the worker that gets it loads libtpu
and holds its lock until it exits.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops import cca, conv, gated_delta, kda, pallas_util, s6, ssd


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Compile as a program on the chip does: through Mosaic, and without
    the 64-bit types tests/conftest.py turns on (Mosaic has no float64, and
    under them every Python constant in a kernel becomes one)."""
    monkeypatch.setattr(pallas_util, "on_tpu", lambda: True)
    with jax.enable_x64(False):
        yield


# (B*H, B*Hkv, S, D or (D, Dv), dtype, causal[, the rest of ``Mask``]): the
# two cells; chip_smoke's legs.
SHAPES = {
    "starcoder2-3b_s4096": (48, 4, 4096, 128, jnp.bfloat16, True),
    "starcoder2-3b_s512": (384, 32, 512, 128, jnp.bfloat16, True),
    "smoke_d64": (16, 16, 1024, 64, jnp.bfloat16, True),
    # 4 sequences at 16:2 heads of 256: the widest head a cell runs.
    "qwen3-next-80b-a3b_s4096": (64, 8, 4096, 256, jnp.bfloat16, True),
    "encoder_f32_s384": (8, 8, 384, 64, jnp.float32, False),
    # 2 sequences at 16:16 latent-attention heads: scores over 192
    # dimensions (one and a half lane tiles), values of 128.
    "moonlight-16b-a3b_s8192": (32, 32, 8192, (192, 128), jnp.bfloat16,
                                True),
}
# The backward pass as one kernel: besides those, 2 sequences at 32:4 heads
# with and without the window of 2048, 4 sequences at 8:2 heads, and one
# sequence of 16,384 tokens at 28:4 heads (a group of 7) with and without the
# window of 4096: the deepest shape ``backward_is_fused`` admits at heads of
# 128 (the whole sequence's dQ in VMEM).
LONGEST = {
    "smallthinker-21b-a3b_s16384": (28, 4, 16384, 128, jnp.bfloat16, True),
    "smallthinker-21b-a3b_s16384_window": (28, 4, 16384, 128, jnp.bfloat16,
                                           True, 4096),
    # One map of a differential attention layer: 20:10 pairs of heads, keys
    # of 64 beside values of 128 (a pair's two value heads side by side),
    # whole and under a band of 512, narrower than the 1024-wide tile.
    "phi-4-mini-flash-reasoning_s16384": (20, 10, 16384, (64, 128),
                                          jnp.bfloat16, True),
    "phi-4-mini-flash-reasoning_s16384_window": (20, 10, 16384, (64, 128),
                                                 jnp.bfloat16, True, 512),
    # The noised and the clean copy of 8,192 positions at 32:4 heads under
    # the block-diffusion mask in blocks of 4 (no window; the mask's block
    # and half): 80 of 256 tiles, the noised queries' over the clean keys
    # above the diagonal.
    "sdar-30b-a3b-chat_s8192": (32, 4, 16384, 128, jnp.bfloat16, True, None,
                                4, 8192),
}
FUSED_SHAPES = {
    **SHAPES,
    "trinity-mini_s8192": (64, 8, 8192, 128, jnp.bfloat16, True),
    "trinity-mini_s8192_window": (64, 8, 8192, 128, jnp.bfloat16, True,
                                  2048),
    "zaya1-8b_s4096": (32, 8, 4096, 128, jnp.bfloat16, True),
    # Two sequences at 16:16 heads: the looped cell's.
    "ouro-2.6b_s4096": (32, 32, 4096, 128, jnp.bfloat16, True),
    **LONGEST,
}


def _compile_flash(one_chip, kernel, bh, bkv, s, d, dtype, causal, *rest,
                   batch=None):
    """One of the four calls compiled at ``[B*H, S, D]`` operands or, with
    ``batch``, at the same heads at rank 4, ``[B, H, S, D]`` (what the entry
    hands the calls where its caller asks since PR 70)."""
    d, dv = d if isinstance(d, tuple) else (d, d)
    mask = fa.Mask(causal, *rest)

    def sds(n, rows, width, dt=dtype):
        dims = (n, rows, width) if batch is None \
            else (batch, n // batch, rows, width)
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    def stat(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    q, k, v, do = sds(bh, s, d), sds(bkv, s, d), sds(bkv, s, dv), \
        sds(bh, s, dv)
    scale = d ** -0.5
    if kernel == "fwd":
        f = lambda q, k, v: fa._fwd_call(q, k, v, scale, mask, s)
        args = (q, k, v)
    elif kernel in ("dkdv", "fused"):
        # The one kernel that makes dQ too carries the dKdV kernel's name.
        call = fa._dkdv_call if kernel == "dkdv" else fa._bwd_call
        f = lambda *a: call(*a, scale, mask, s)
        rows = stat(bh, 1, s)
        args = (q, k, v, do, rows, rows)
        if kernel == "fused":
            tile = fa.block_sizes(fa.KERNEL_DKDV, s, d, dtype, causal, dv)
            assert fa.backward_is_fused(*tile, s, d, dtype, dv)
    else:
        f = lambda *a: fa._dq_call(*a, scale, mask, s)
        cols = stat(bh, s, 128)
        args = (q, k, v, do, cols, cols)
    lowered = jax.jit(f).lower(*args)
    text = lowered.compile().as_text()
    name = "dkdv" if kernel == "fused" else kernel
    assert "tpu_custom_call" in text and f"hvd_flash_{name}" in text
    return lowered


@pytest.mark.parametrize("shape, kernel", [
    *((shape, kernel) for shape in SHAPES
      for kernel in ("fwd", "dkdv", "dq")),
    # The pair under a band: the dKdV kernel's table runs column by column
    # under each of eight query heads, the dQ kernel's row by row.
    *(("trinity-mini_s8192_window", kernel) for kernel in ("dkdv", "dq")),
    *((shape, "fwd") for shape in LONGEST),
    *((shape, "fused") for shape in FUSED_SHAPES)])
def test_kernel_compiles_for_v5e(one_chip, mosaic, shape, kernel):
    _compile_flash(one_chip, kernel, *FUSED_SHAPES[shape])


# The four calls at rank 4, ``[B, H, S, D]`` (a block ``(squeezed, 1, rows,
# D)`` at ``(b, h, tile, 0)``, a grouped-query key at ``h // group``; the
# whole dK and dV of a K/V head a block ``(squeezed, 1, S, D)`` at ``(b, hkv,
# 0, 0)``), which since PR 70 the entry hands them where its caller asks
# (``heads_major``): a row of ``FUSED_SHAPES`` and its batch. What the
# attention mixer asks for: two sequences at 32:4 heads, whole and under the
# band, two at 16:16, two at 24:2 of 4096 rows and sixteen of 512; what the
# latent-attention mixer asks for since PR 72: two at 16:16 heads of 192
# beside 128; and, which no mixer asks for yet, four at 16:2 heads of 256,
# the block-diffusion cell's one row of 16,384, one of 16,384 at a group of
# seven, and two sequences at heads of 64.
RANK_4 = {
    "trinity-mini_s8192": 2, "trinity-mini_s8192_window": 2,
    "ouro-2.6b_s4096": 2, "sdar-30b-a3b-chat_s8192": 1,
    "qwen3-next-80b-a3b_s4096": 4, "smallthinker-21b-a3b_s16384_window": 1,
    "starcoder2-3b_s4096": 2, "starcoder2-3b_s512": 16, "smoke_d64": 2,
    "moonlight-16b-a3b_s8192": 2,
}


@pytest.mark.parametrize("kernel", ["fwd", "fused", "dkdv", "dq"])
@pytest.mark.parametrize("shape", list(RANK_4))
def test_kernel_compiles_for_v5e_at_rank_4(one_chip, mosaic, shape, kernel):
    """The forward, the one backward kernel and the pair through the
    rank-4 index maps: a block Mosaic refuses fails here and not on the
    chip. The program holds no merged operand."""
    batch = RANK_4[shape]
    bh, _, s, d, *_ = FUSED_SHAPES[shape]
    d = d[0] if isinstance(d, tuple) else d     # q is a key head wide
    lowered = _compile_flash(one_chip, kernel, *FUSED_SHAPES[shape],
                             batch=batch)
    text = lowered.as_text()
    assert f"tensor<{batch}x{bh // batch}x{s}x{d}xbf16>" in text
    assert f"tensor<{bh}x{s}x{d}xbf16>" not in text


# (B, S, H, P, N, G, Q, dtype): the granite-4.0-h-micro_s4096 cell's scan;
# a small one with two groups, four heads a group and a one-tile chunk.
SSD_SHAPES = {
    "granite-4.0-h-micro_s4096": (2, 4096, 64, 64, 128, 1, 256, jnp.bfloat16),
    "small_two_groups": (1, 512, 8, 32, 128, 2, 128, jnp.bfloat16),
}


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", list(SSD_SHAPES))
def test_ssd_kernel_compiles_for_v5e(one_chip, mosaic, shape, kernel):
    batch, seq, heads, width, state, groups, chunk, dtype = SSD_SHAPES[shape]

    def sds(*dims, dt=dtype):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    x, b_in = sds(batch, seq, heads, width), sds(batch, seq, groups, state)
    row = sds(batch, seq, heads, dt=jnp.float32)
    args = (x, row, b_in, b_in, row,
            sds(batch, seq // chunk, heads, width, chunk, dt=jnp.float32),
            sds(heads, dt=jnp.float32))
    if kernel == "fwd":
        f = ssd._fwd_call
    else:
        f, args = ssd._bwd_call, args + (x,)
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and f"hvd_ssd_{kernel}" in text


# (B, S, Hk, Hv, K, V, Q, dtype, the key head's true size): the
# qwen3-next-80b-a3b_s4096 cell's scan; a small one with a value head a key
# head and two chunks a sequence; the olmo-hybrid-7b_s8192 cell's as the
# kernels carry it (heads of 96 by 192 at 128 by 256 lanes, not square,
# thirty of them, 128 chunks a sequence).
GDN_SHAPES = {
    "qwen3-next-80b-a3b_s4096": (4, 4096, 16, 32, 128, 128, 64, jnp.bfloat16,
                                 128),
    "small_one_head_a_key": (1, 128, 2, 2, 128, 128, 64, jnp.bfloat16, 128),
    "olmo-hybrid-7b_s8192_carried": (1, 8192, 30, 30, 128, 256, 64,
                                     jnp.bfloat16, 96),
}


@pytest.mark.parametrize("kernel", ["fwd", "bwd", "rec_fwd", "rec_bwd",
                                    "fwd_caller_norms", "bwd_caller_norms"])
@pytest.mark.parametrize("shape", list(GDN_SHAPES))
def test_gdn_kernel_compiles_for_v5e(one_chip, mosaic, shape, kernel):
    """``fwd`` and ``bwd`` as a model's step runs them, the rows of ``q``
    and ``k`` normed in the kernels (``q_scale`` from the head's true size);
    ``*_caller_norms`` the same kernels on keys that come normed. The
    forward kernel's sixth output and the backward's last input is the
    float32 ``T``, a chunk's ``[64, 64]`` kept as ``[32, 128]`` (PR 68)."""
    batch, seq, key_heads, heads, key_dim, width, chunk, dtype, true_dim = \
        GDN_SHAPES[shape]
    kernel, _, caller_norms = kernel.partition("_caller_norms")
    q_scale = None if caller_norms or kernel.startswith("rec") \
        else true_dim ** -0.5

    def sds(*dims, dt=dtype):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    def like(f, *args, **kw):
        return tuple(sds(*t.shape, dt=t.dtype)
                     for t in jax.eval_shape(functools.partial(f, **kw),
                                             *args))

    q = sds(batch, seq, key_heads, key_dim)
    row = sds(batch, seq // chunk, chunk, heads, dt=jnp.float32)
    args = (q, q, sds(batch, seq, heads, width), row, row)
    scan = like(gated_delta._fwd_call, *args)
    if kernel == "fwd":
        f = functools.partial(gated_delta._fwd_call, q_scale=q_scale)
    elif kernel == "bwd":
        f = functools.partial(gated_delta._bwd_call, q_scale=q_scale)
        args += scan
    else:
        # The recurrence over chunks reads the chunk-local kernel's outputs,
        # a chunk's last decay a head and the state a sequence starts from.
        state = sds(batch, heads, key_dim, width, dt=jnp.float32)
        args = scan[:5] + (sds(batch, seq // chunk, heads, dt=jnp.float32),
                           state)
        f = functools.partial(gated_delta._rec_fwd_call, keep=True)
        if kernel == "rec_bwd":
            o, _, entering = like(gated_delta._rec_fwd_call, *args,
                                  keep=True)
            f, args = gated_delta._rec_bwd_call, args[:6] + (entering, o,
                                                             state)
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and f"hvd_gdn_{kernel}" in text
    kept_t = f"f32[{seq // chunk},{batch},{heads},{chunk // 2},{2 * chunk}]"
    assert (kept_t in text) == (kernel in ("fwd", "bwd"))
    assert f"f32[{seq // chunk},{batch},{heads},{chunk},{chunk}]" not in text


@pytest.mark.parametrize("norm_qk", [True, False],
                         ids=["kernels norm", "caller norms"])
@pytest.mark.parametrize("key_dim, width", [(96, 192), (24, 40)])
def test_gdn_scan_compiles_for_v5e_at_heads_of_any_size(one_chip, mosaic,
                                                        key_dim, width,
                                                        norm_qk):
    """``gated_delta_chunked`` whole, forward and backward, at heads that are
    no lane multiple (the olmo-hybrid-7b_s8192 cell's, and ones smaller than
    a tile): the four kernels at padded sizes, the published sizes out; with
    the keys' norm in the chunk-local kernels, as a mixer asks, and
    without."""
    batch, seq, heads = 1, 1024, 30

    def sds(*dims, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    def loss(q, k, v, g, beta):
        o, final = gated_delta.gated_delta_chunked(
            q, k, v, g, beta, beta_max=2, norm_qk=norm_qk)
        assert o.shape == v.shape
        assert final.shape == (batch, heads, key_dim, width)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(final)

    q = sds(batch, seq, heads, key_dim)
    row = sds(batch, seq, heads, dt=jnp.float32)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, sds(batch, seq, heads, width), row, row).compile().as_text()
    for kernel in ("fwd", "bwd", "rec_fwd", "rec_bwd"):
        assert f"hvd_gdn_{kernel}" in text


# (B, S, C, bias, the axis the mixer asks for on the lanes): the convolution
# in front of the scan in the three recurrent cells, and a tensor no tile
# divides (a float32 one: the halo of 16 tokens is two of its tiles).
CONV_SHAPES = {
    "qwen3-next-80b-a3b_s4096": (4, 4096, 8192, False, "channels",
                                 jnp.bfloat16),
    "olmo-hybrid-7b_s8192": (1, 8192, 11520, False, "channels", jnp.bfloat16),
    "olmo-hybrid-7b_s8192_tokens": (1, 8192, 11520, False, "tokens",
                                    jnp.bfloat16),
    "granite-4.0-h-micro_s4096": (2, 4096, 4352, True, "tokens",
                                  jnp.bfloat16),
    "granite-4.0-h-micro_s4096_channels": (2, 4096, 4352, True, "channels",
                                           jnp.bfloat16),
    "ragged_float32": (2, 1000, 200, True, "channels", jnp.float32),
    "ragged_float32_tokens": (2, 1000, 200, True, "tokens", jnp.float32),
}


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", list(CONV_SHAPES))
def test_conv_kernel_compiles_for_v5e(one_chip, mosaic, shape, kernel):
    batch, seq, channels, bias, minor, dtype = CONV_SHAPES[shape]

    def sds(*dims, dt=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    u = sds(batch, seq, channels, dt=dtype)
    args = (u, sds(4, channels), sds(channels) if bias else None)
    if kernel == "fwd":
        f = conv._conv_fwd_call
    else:
        f, args = conv._conv_bwd_call, args + (u,)
    text = jax.jit(functools.partial(
        f, first=0, tokens_minor=minor == "tokens")).lower(*args).compile() \
        .as_text()
    assert "tpu_custom_call" in text and f"hvd_conv_{kernel}" in text


# (B, S, channels, states, dtype): Mamba-1's selective scan at the
# phi-4-mini-flash-reasoning_s16384 cell's shape (40 lane tiles of channels,
# the 16 states on the sublanes, 128 blocks of 128 tokens), and a small one
# whose channels are carried to a lane tile.
S6_SHAPES = {
    "phi-4-mini-flash-reasoning_s16384": (1, 16384, 5120, 16, jnp.bfloat16),
    "small_float32": (2, 200, 72, 8, jnp.float32),
}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("shape", list(S6_SHAPES))
def test_s6_kernel_compiles_for_v5e(one_chip, mosaic, shape, grad):
    batch, seq, channels, state, dtype = S6_SHAPES[shape]

    def sds(*dims, dt=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    args = (sds(batch, seq, channels, dt=dtype), sds(batch, seq, channels),
            sds(channels, state), sds(batch, seq, state, dt=dtype),
            sds(batch, seq, state, dt=dtype), sds(channels))

    def scan(*a):
        return s6.selective_scan(*a).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(scan, argnums=tuple(range(6))) if grad
                   else scan).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and s6.KERNEL_FWD in text
    assert (s6.KERNEL_BWD in text) == grad
    # The states of a sequence, [T, C, N] float32, are made nowhere.
    assert f"f32[{batch},{seq},{channels},{state}]" not in text
    assert f"f32[{batch},{seq},{state},{channels}]" not in text


# (B, S, heads, key and value size of a head): Kimi delta attention at the
# ling-3.0-flash_s8192 cell's shape, 128 chunks of 64 by sub-blocks of 16,
# and a short sequence of two heads.
KDA_SHAPES = {
    "ling-3.0-flash_s8192": (1, 8192, 32, 128),
    "small": (2, 256, 2, 128),
}


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("shape", list(KDA_SHAPES))
def test_kda_scan_compiles_for_v5e(one_chip, mosaic, shape, grad):
    batch, seq, heads, dim = KDA_SHAPES[shape]

    def sds(*dims, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    def scan(q, k, v, g, beta):
        o, final = kda.kda_chunked(q, k, v, g, beta, norm_qk=True)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(final)

    q = sds(batch, seq, heads, dim)
    text = jax.jit(jax.grad(scan, argnums=(0, 1, 2, 3, 4)) if grad
                   else scan).lower(
        q, q, q, sds(batch, seq, heads, dim, dt=jnp.float32),
        sds(batch, seq, heads, dt=jnp.float32)).compile().as_text()
    assert "tpu_custom_call" in text
    for kernel in (kda.KERNEL_FWD, kda.KERNEL_REC_FWD):
        assert kernel in text
    for kernel in (kda.KERNEL_BWD, kda.KERNEL_REC_BWD):
        assert (kernel in text) == grad
    # Of a chunk's float32 [Q, Q] tiles T alone reaches HBM, with the
    # backward pass to read it and no lane of it padding (PR 68); a state a
    # token does not.
    chunks = seq // 64
    assert f"f32[{batch},{chunks},{heads},64,64]" not in text
    assert f"f32[{chunks},{batch},{heads},64,64]" not in text
    assert f"f32[{chunks},{batch},{heads},32,128]" in text
    assert f"f32[{batch},{seq},{heads},{dim},{dim}]" not in text


# (B, S, query heads, key heads, head size, rotary dimensions): a CCA
# mixer's mix at the zaya1-8b_s4096 cell's shape (taps (2, 2)), as the cell
# runs it, with the rotary embedding on the whole head and without one.
CCA_SHAPES = {
    "zaya1-8b_s4096": (4, 4096, 8, 2, 128, 64),
    "whole_head_rotary": (4, 4096, 8, 2, 128, 128),
    "no_rotary": (4, 4096, 8, 2, 128, 0),
}


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", list(CCA_SHAPES))
def test_cca_kernel_compiles_for_v5e(one_chip, mosaic, shape, kernel):
    batch, seq, heads, kv_heads, dim, rotary = CCA_SHAPES[shape]
    plan = cca._plan(seq, heads, kv_heads, dim, 2, 2, rotary)
    assert (plan.lanes, plan.seq) == (dim, seq)

    def sds(*dims, dt=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)

    groups = heads + kv_heads
    u = sds(batch, seq, plan.wide, dt=jnp.bfloat16)
    args = (u, (sds(2, plan.wide), sds(plan.wide),
                sds(2, groups, dim, dim), sds(plan.wide), sds(kv_heads)),
            sds(batch, seq, 2 * dim) if rotary else None)
    if kernel == "fwd":
        f = cca._fwd_call
    else:
        f = cca._bwd_call
        args += (sds(batch, seq, heads * dim, dt=jnp.bfloat16),
                 sds(batch, seq, kv_heads * dim, dt=jnp.bfloat16))
    text = jax.jit(functools.partial(f, plan=plan)).lower(*args).compile() \
        .as_text()
    assert "tpu_custom_call" in text and f"hvd_cca_{kernel}" in text
